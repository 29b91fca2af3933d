"""BENCHMARK.json and the data files it names, resolved by name.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own:

  benchmark/configs/<config>.json        the configuration as it is run
  benchmark/configs/<reference>.py       the plain reference the file names
  benchmark/configs/<costs>.py           cost functions of its own, if any
  benchmark/traffic/<traffic>.json       kind + that kind's parameters
  benchmark/drivers/<kind>.py            one driver per kind of job
  benchmark/layer_metrics/<metric>.json  reader + arguments
  benchmark/readers/<reader>.py          one reader each
  benchmark/kernel_costs.py              the cost functions cells share

so a later PR adds files and entries and edits nothing. Every loader
takes a `root`: a directory laid out like benchmark/ that is looked in
first (the harness's tests keep a cell there that is in no benchmark);
what it lacks is found under benchmark/.

A configuration's file holds the sizes under the names its reference and
its cost functions read, and beside them:

  source, reference     where the sizes are published; the module name of
                        the plain reference in configs/
  task_flags            the program's flags that build this model
  reduced               the keys cut from the source, equal to the
                        `configs` entry's list; each is a key of the file
  published             {key: the source's value} for every key in reduced
  deployment            where a layer is cut to one chip's share:
                        {"chips": how many share a layer, "how": by what}
  assumed               what the source leaves open

and what the train driver reads (its CONFIG_KEYS):

  streams               2: a parallel corpus, two vocabularies; 1: one
                        file, one vocabulary, the lines as documents
  kernels               the kernel FAMILIES of the step (`flash_attention`,
                        not `flash_attention_dq`): the run notes which the
                        compiled step holds, the traced run totals each
                        one's device time; none is demanded for `correct`
  train_flops           the cost function of one step's model FLOPs
  built                 {key of the file: what the program built}, compared
                        before any run; a value is a field of the model's
                        cfg or `vocab`, or `a/b` for a whole quotient
  rehearse              --rehearse only: {"dims": overrides of the file's
                        sizes, "flags": the program's flags that set them}

A cost function is named `function` (in kernel_costs.py) or
`module:function`, the module a dotted path under the root
(`configs.<costs>`): in a configuration's `train_flops` and in a metric
file's `args.cost`.

Adding, with no edit to a file that is there:
  a configuration   configs/<name>.json (+ its reference, + its costs) and
                    an entry in `configs`
  a cell            traffic/<mix>.json where the mix is new, an entry in
                    `workloads`, and its name in the `workloads` list of
                    each metric it reports
  a per-layer metric  layer_metrics/<metric>.json (+ readers/<reader>.py
                    where no reader fits) and an entry in `per_layer`
  a cost function   a module under configs/, named `module:function`

Stdlib only: a driver whose chip-holding work runs in a child must stay
off JAX here.
"""

import importlib
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MODULE_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*(\.[A-Za-z_][A-Za-z0-9_]*)*$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def _find(root, *parts):
    """The file under `root`, else under benchmark/."""
    for base in dict.fromkeys((root or BENCH_DIR, BENCH_DIR)):
        path = os.path.join(base, *parts)
        if os.path.isfile(path):
            return path
    raise ManifestError("missing benchmark file " + os.path.relpath(
        os.path.join(root or BENCH_DIR, *parts), ROOT))


def _load(root, *parts):
    with open(_find(root, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def _module(dotted, root):
    """The module at <root>/<dotted as a path>.py, else benchmark's."""
    if not MODULE_RE.match(dotted):
        raise ManifestError(f"bad module name {dotted!r}")
    parts = dotted.split(".")
    path = _find(root, *parts[:-1], parts[-1] + ".py")
    if path.startswith(BENCH_DIR + os.sep):
        return importlib.import_module(f"benchmark.{dotted}")
    name = "benchmark_root_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[name] = module
    return sys.modules[name]


def load_benchmark(root=None):
    """BENCHMARK.json at the repo's root, or a root's own."""
    with open(os.path.join(root or ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def load_config(name, root=None):
    return _load(root, "configs", f"{name}.json")


def load_traffic(name, root=None):
    return _load(root, "traffic", f"{name}.json")


def load_layer_metric(name, root=None):
    return _load(root, "layer_metrics", f"{name}.json")


def load_peaks(device_kind):
    peaks = _load(None, "peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise ManifestError(f"no published peaks for device kind "
                            f"{device_kind!r} in benchmark/peaks.json")
    return peaks[device_kind]


def load_driver(kind, root=None):
    return _module(f"drivers.{kind}", root)


def load_reference(name, root=None):
    """The plain reference beside a configuration (imports JAX)."""
    return _module(f"configs.{name}", root)


def load_reader(name, root=None):
    return _module(f"readers.{name}", root)


def load_cost(spec, root=None):
    """The cost function `function` of kernel_costs.py, or
    `module:function` of a module under the root."""
    module, _, name = str(spec).rpartition(":")
    fn = getattr(_module(module or "kernel_costs", root), name, None)
    if not callable(fn):
        raise ManifestError(f"no cost function {spec!r}")
    return fn


def metric_in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of `workloads` with everything it names resolved."""

    def __init__(self, bench, name, root=None):
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if not entries:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (have: "
                f"{', '.join(w['name'] for w in bench['workloads'])})")
        self.entry = entries[0]
        self.name = name
        self.root = root
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        self.config = load_config(self.config_name, root)
        self.traffic = load_traffic(self.traffic_name, root)
        self.kind = self.traffic["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if metric_in_cell(m, name)]
        self.per_layer = [m for m in bench["per_layer"]
                          if metric_in_cell(m, name)]

    @property
    def reference(self):
        return load_reference(self.config["reference"], self.root)


def _config_problems(entry, root):
    """What a `configs` entry and its file must agree on, and what a cut
    configuration owes: every key in `reduced` is a key of the file with
    its published value beside it."""
    name = entry["name"]
    try:
        path = _find(root, "configs", f"{name}.json")
        with open(path, encoding="utf-8") as fh:
            body = json.load(fh)
    except (ManifestError, ValueError) as e:
        return [str(e)]
    bad = []
    if entry["file"] != os.path.relpath(path, ROOT):
        bad.append(f"config {name}: file {entry['file']!r}")
    if sorted(body.get("reduced", [])) != sorted(entry["reduced"]):
        bad.append(f"config {name}: reduced differs from its file")
    published = body.get("published", {})
    for key in body.get("reduced", []):
        if key not in body:
            bad.append(f"config {name}: cuts {key!r}, which is no key of "
                       f"its file")
        elif key not in published:
            bad.append(f"config {name}: cuts {key!r} without its "
                       f"published value")
        elif published[key] == body[key]:
            bad.append(f"config {name}: lists {key!r} as cut and runs the "
                       f"published value")
    dep = body.get("deployment")
    if dep is not None and not (
            isinstance(dep, dict) and isinstance(dep.get("chips"), int)
            and dep["chips"] >= 1 and isinstance(dep.get("how"), str)
            and dep["how"].strip()):
        bad.append(f"config {name}: deployment wants chips (how many share "
                   f"a layer) and how")
    return bad


def validate(bench=None, root=None):
    """Every name resolves and keeps to the contract's character sets;
    every cell reports setup_s, another end-to-end metric and a per-layer
    metric; a per-layer metric's `moves` is reported wherever it is; a
    configuration holds what its cells' driver reads; every cost function
    named is there. Returns the list of problems (empty = sound)."""
    bench = bench or load_benchmark(root)
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
        bad += _config_problems(c, root)
    cells = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    for w in bench["workloads"]:
        if w["config"] not in cfgs:
            bad.append(f"{w['name']}: unknown config {w['config']!r}")
        if pairs.count((w["config"], w["traffic"])) > 1:
            bad.append(f"{w['name']}: its configuration and traffic make "
                       f"another cell too")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips={w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
            bad.append(f"{w['name']}: why has {len(w['why'])} characters")
        try:
            cell = Cell(bench, w["name"], root)
            driver = load_driver(cell.kind, root)
            lacks = [k for k in getattr(driver, "CONFIG_KEYS", ())
                     if k not in cell.config]
            if lacks:
                raise ManifestError(f"config {cell.config_name} lacks "
                                    f"{lacks}, which drivers/{cell.kind}.py "
                                    f"reads")
            if "train_flops" in cell.config:
                load_cost(cell.config["train_flops"], root)
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
            spec = load_layer_metric(m["name"], root)
            load_reader(spec["reader"], root)
            if "cost" in spec["args"]:
                load_cost(spec["args"]["cost"], root)
        except (ManifestError, ImportError, ValueError, KeyError) as e:
            bad.append(f"{m['name']}: {e}")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    return bad
