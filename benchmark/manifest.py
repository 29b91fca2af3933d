"""BENCHMARK.json and the data files it names, resolved by name.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own:

  benchmark/configs/<config>.json        sizes, source, reduced, assumed
  benchmark/configs/<reference>.py       the plain reference the file names
  benchmark/traffic/<traffic>.json       kind + that kind's parameters
  benchmark/drivers/<kind>.py            one driver per kind of job
  benchmark/layer_metrics/<metric>.json  reader + arguments
  benchmark/readers/<reader>.py          one reader each

so a later PR adds files and entries and edits nothing. Stdlib only: a
driver whose chip-holding work runs in a child must stay off JAX here.
"""

import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def _load(*parts):
    path = os.path.join(BENCH_DIR, *parts)
    if not os.path.isfile(path):
        raise ManifestError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def load_config(name):
    return _load("configs", f"{name}.json")


def load_traffic(name):
    return _load("traffic", f"{name}.json")


def load_layer_metric(name):
    return _load("layer_metrics", f"{name}.json")


def load_peaks(device_kind):
    peaks = _load("peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise ManifestError(f"no published peaks for device kind "
                            f"{device_kind!r} in benchmark/peaks.json")
    return peaks[device_kind]


def load_driver(kind):
    if not NAME_RE.match(kind):
        raise ManifestError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"benchmark.drivers.{kind}")


def load_reference(name):
    """The plain reference beside a configuration (imports JAX)."""
    if not NAME_RE.match(name):
        raise ManifestError(f"bad reference name {name!r}")
    return importlib.import_module(f"benchmark.configs.{name}")


def load_reader(name):
    if not NAME_RE.match(name):
        raise ManifestError(f"bad reader name {name!r}")
    return importlib.import_module(f"benchmark.readers.{name}")


def metric_in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of `workloads` with everything it names resolved."""

    def __init__(self, bench, name):
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if not entries:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (have: "
                f"{', '.join(w['name'] for w in bench['workloads'])})")
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        self.config = load_config(self.config_name)
        self.traffic = load_traffic(self.traffic_name)
        self.kind = self.traffic["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if metric_in_cell(m, name)]
        self.per_layer = [m for m in bench["per_layer"]
                          if metric_in_cell(m, name)]

    @property
    def reference(self):
        return load_reference(self.config["reference"])


def validate(bench=None):
    """Every name resolves and keeps to the contract's character sets;
    every cell reports setup_s, another end-to-end metric and a per-layer
    metric; a per-layer metric's `moves` is reported wherever it is.
    Returns the list of problems (empty = sound)."""
    bench = bench or load_benchmark()
    bad = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        bad += [f"{group}: bad name {n!r}" for n in names
                if not NAME_RE.match(n)]
        bad += [f"{group}: duplicate name {n!r}" for n in set(names)
                if names.count(n) > 1]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source={m['source']!r}")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: end-to-end source {m['source']!r}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    cfgs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        try:
            body = load_config(c["name"])
        except (ManifestError, ValueError) as e:
            bad.append(str(e))
            continue
        if c["file"] != f"benchmark/configs/{c['name']}.json":
            bad.append(f"config {c['name']}: file {c['file']!r}")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        if w["config"] not in cfgs:
            bad.append(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips={w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
            bad.append(f"{w['name']}: why has {len(w['why'])} characters")
        try:
            cell = Cell(bench, w["name"])
            load_driver(cell.kind)
        except (ManifestError, ImportError, ValueError, KeyError) as e:
            bad.append(f"{w['name']}: {e}")
            continue
        reported = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2:
            bad.append(f"{w['name']}: reports {sorted(reported)}")
        if not cell.per_layer:
            bad.append(f"{w['name']}: no per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in reported:
                bad.append(f"{w['name']}: {m['name']} moves "
                           f"{m['moves']!r}, which the cell does not report")
    for m in bench["end_to_end"] + bench["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown workload {w!r}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
        try:
            spec = load_layer_metric(m["name"])
            load_reader(spec["reader"])
        except (ManifestError, ImportError, ValueError, KeyError) as e:
            bad.append(f"{m['name']}: {e}")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    return bad
