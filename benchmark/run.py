#!/usr/bin/env python3
"""One cell, once:

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

makes data and weights from --seed, warms the cell's own shapes (set-up),
measures for --seconds, checks the outputs, and prints as the LAST line of
stdout one JSON object: correct, attempted, failed, metrics, device (and
breakdown with --trace 1). --trace 0 reports the cell's end-to-end
metrics, --trace 1 its per-layer metrics. Without the cell's TPU chips it
exits non-zero and prints no result; --rehearse runs the same control flow
at tiny size on any platform and prints `rehearsal_*` numbers on earlier
lines only, never a result.

The cell's configuration, traffic mix, driver and per-layer metric readers
are files found by the names in BENCHMARK.json (benchmark/manifest.py).
"""

import time

T_START = time.perf_counter()          # set-up counts from process start

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

# --rehearse only: control flow on any platform, never a result
TINY = {"dim_emb": 64, "dim_ffn": 128, "heads": 4, "dim_head": 16,
        "enc_depth": 2, "dec_depth": 2, "vocab": 512}
TINY_FLAGS = [x for flag, key in (
    ("--dim-emb", "dim_emb"), ("--transformer-dim-ffn", "dim_ffn"),
    ("--transformer-heads", "heads"), ("--enc-depth", "enc_depth"),
    ("--dec-depth", "dec_depth")) for x in (flag, str(TINY[key]))]


class Context:
    """What a driver is given, and what it reports back through."""

    def __init__(self, cell, args):
        self.cell = cell
        self.seed = args.seed
        # the program's --seed is a positive int32
        self.program_seed = args.seed % (2 ** 31 - 1) or 1
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.dims = dict(cell.config, **TINY) if args.rehearse \
            else cell.config
        self.tiny_flags = TINY_FLAGS if args.rehearse else []
        # the traced span: a few seconds inside the window
        self.trace_after_s = min(2.0, self.seconds / 4)
        self.trace_for_s = min(3.0, self.seconds / 2)
        self.setup_s = None

    def window_opens(self):
        self.setup_s = time.perf_counter() - T_START

    def note(self, msg):
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def peaks(self, device_kind):
        """The device's published peaks; None only in a rehearsal."""
        if self.rehearse:
            return None
        return manifest.load_peaks(device_kind)

    def check_dims(self, cfg, vocab):
        """The model the program built is the configuration file's."""
        d = self.dims
        got = {"dim_emb": cfg.dim_emb, "dim_ffn": cfg.dim_ffn,
               "heads": cfg.heads, "enc_depth": cfg.enc_depth,
               "dec_depth": cfg.dec_depth, "vocab": vocab,
               "dim_head": cfg.dim_emb // cfg.heads}
        diff = {k: (v, d[k]) for k, v in got.items() if d[k] != v}
        if diff:
            raise SystemExit(f"benchmark: the program built {diff} "
                             f"(built, configuration file)")


def layer_metrics(cell, obs):
    out = {}
    for m in cell.per_layer:
        spec = manifest.load_layer_metric(m["name"])
        value = manifest.load_reader(spec["reader"]).read(obs, spec["args"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell, traced, result, e2e, layers, trace):
    """The contract's last line: --trace 0 carries the cell's end-to-end
    metrics, --trace 1 its per-layer metrics, busy/window seconds and the
    breakdown."""
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    missing = [n for n in units if n not in e2e]
    if missing:
        raise SystemExit(f"benchmark: the driver reported no {missing}")
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": layers if traced else {
                n: {"value": e2e[n], "unit": u} for n, u in units.items()},
            "device": dict(result["device"])}
    if traced:
        if not trace or trace["busy_s"] <= 0:
            raise SystemExit("benchmark: the trace holds no device op")
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any platform; never prints a result")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "marian_tpu")):
        print("benchmark: no marian_tpu package beside benchmark/ — no "
              "result", file=sys.stderr)
        return 4
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, args.workload)
    ctx = Context(cell, args)
    result = manifest.load_driver(cell.kind).run(ctx)
    if ctx.setup_s is None:
        raise SystemExit("benchmark: the driver never opened its window")
    for p in result.get("problems", []):
        ctx.note(f"NOT CORRECT: {p}")
    e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
    obs = dict(result.get("obs", {}), dims=ctx.dims,
               peaks=ctx.peaks(result["device"]["kind"]))
    layers = layer_metrics(cell, obs) if ctx.trace else {}
    if ctx.rehearse or result["device"]["platform"] != "tpu":
        # a CPU number is never written under a metric's name
        for k, v in e2e.items():
            print(f"rehearsal_{k} = {v}")
        for k, v in layers.items():
            print(f"rehearsal_{k} = {v['value']}")
        print(f"rehearsal complete: correct={result['correct']} "
              f"attempted={result['attempted']} failed={result['failed']}; "
              f"not the real size on a TPU, no result")
        return 0 if result["correct"] else 1
    line = result_line(cell, ctx.trace, result, e2e, layers,
                       obs.get("trace"))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
