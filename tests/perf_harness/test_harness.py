"""The benchmark's own harness, CPU only: the manifest resolves by name, the
traffic is the same work in another order for every seed, the trace
reducer gives the busy/idle/kernel seconds of a recorded trace, the readers
read, the reference check tells a faulty cost from a sound one, and the
last line has the contract's shape. Starts no JAX at import time.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import corpus, kernel_costs, manifest  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
HIST = {"mu": 3.2, "sigma": 0.45, "min_words": 4, "max_words": 63}


# -- manifest ----------------------------------------------------------------

def test_manifest_is_sound():
    assert manifest.validate(BENCH) == []


def test_an_unsound_manifest_is_told():
    bad = json.loads(json.dumps(BENCH))
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["workloads"][0]["traffic"] = "no-such-mix"
    problems = manifest.validate(bad)
    assert any("no_such_metric" in p for p in problems)
    assert any("no-such-mix" in p for p in problems)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_resolve_by_name(cell_name):
    cell = manifest.Cell(BENCH, cell_name)
    assert hasattr(manifest.load_driver(cell.kind), "run")
    assert cell.config["reduced"] == []
    for m in cell.per_layer:
        spec = manifest.load_layer_metric(m["name"])
        assert hasattr(manifest.load_reader(spec["reader"]), "read")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2


def test_names_units_and_paths_keep_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert manifest.NAME_RE.match(m["name"]) and len(m["unit"]) <= 16
        assert manifest.UNIT_RE.match(m["unit"]), m
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for arg in BENCH["command"]:
        assert not arg.startswith("/") and ".." not in arg
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_an_unlisted_device_has_no_peaks():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError):
        manifest.load_peaks("TPU v9")


# -- traffic -----------------------------------------------------------------

def test_every_seed_draws_the_same_lengths_in_another_order():
    a = corpus.sentence_lengths(HIST, 500, 1)
    b = corpus.sentence_lengths(HIST, 500, 3000000019)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 4 and a.max() <= 63 and 24 < a.mean() < 32


def test_lines_are_seeded_and_cover_the_vocabulary():
    lines, lens = corpus.make_lines(HIST, 200, 32000, 7)
    again, _ = corpus.make_lines(HIST, 200, 32000, 7)
    other, _ = corpus.make_lines(HIST, 200, 32000, 8)
    assert lines == again and lines != other
    assert [len(l.split()) for l in lines] == list(lens)
    ids = {int(w[1:]) for l in lines for w in l.split()}
    assert max(ids) < 32000 - 2 and len(ids) > 1000
    assert corpus.width_of(7, (8, 16, 24, 32, 48, 64)) == 8
    assert corpus.width_of(63, (8, 16, 24, 32, 48, 64)) == 64


# -- trace reduction -----------------------------------------------------------

def test_union_and_self_time_arithmetic():
    secs, merged = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45)])
    assert secs == 35e-9 and merged == [[0, 20], [30, 45]]
    selfs = dict(trace_reduce.self_times(
        [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]))
    assert selfs == {"while": 30, "a": 20, "b": 40, "c": 10}


def test_reducer_on_the_synthetic_trace():
    """A hand-written trace: one chip, ops at [0,4) [4,6) ms and [8,10) ms
    inside a 12 ms window; the kernel is the 2 ms op at 4 ms."""
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "synthetic_trace.textproto")) as fh:
        raw = ProfileData.text_proto_to_serialized_xspace(fh.read())
    path = os.path.join(HERE, ".synthetic.xplane.pb")
    with open(path, "wb") as fh:
        fh.write(raw)
    try:
        t = trace_reduce.reduce_trace(path, ("my_kernel",))
    finally:
        os.remove(path)
    assert t["window_from"] == "host span"
    assert t["window_s"] == pytest.approx(12e-3)
    assert t["busy_s"] == pytest.approx(8e-3)
    assert t["kernel_s"]["my_kernel"] == pytest.approx(2e-3)
    assert dict(map(tuple, t["idle_gaps"])) == pytest.approx(
        {"bench.data": 2e-3, "bench.window": 2e-3})
    assert t["device_ops"][0] == ["fusion", pytest.approx(4e-3)]


def test_reducer_on_the_recorded_tpu_trace():
    """fixture.xplane.pb: three steps of a tiny jitted program with one
    named Pallas kernel, 30 ms sleeps between, recorded on a TPU v5e by
    record_fixture.py."""
    t = trace_reduce.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"),
                                  ("fixture_kernel",))
    assert t is not None and t["n_devices"] == 1
    assert t["window_from"] == "host span"
    assert 0.09 < t["window_s"] < 0.5            # 3 x 30 ms of sleep at least
    assert 0 < t["busy_s"] < 0.5 * t["window_s"]
    assert 0 < t["kernel_s"]["fixture_kernel"] < t["busy_s"]
    assert t["idle_gaps"][0][0] == "bench.sleep"
    assert t["idle_gaps"][0][1] > 0.08


# -- readers and cost functions ----------------------------------------------

def test_readers_read_and_return_nothing_when_there_is_nothing():
    obs = {"values": {"data_wait_s": 1.0, "window_s": 20.0,
                      "real_tokens": 90, "padded_tokens": 100, "n": 3},
           "trace": {"window_s": 2.0, "busy_s": 1.5, "kernel_s": {"k": 0.5}},
           "traced_work": [{"rows": 128, "src_width": 32, "trg_width": 32}],
           "dims": manifest.load_config("transformer-big"),
           "peaks": manifest.load_peaks("TPU v5 lite")}

    def read(reader, args, o=obs):
        return importlib.import_module(
            f"benchmark.readers.{reader}").read(o, args)
    assert read("counter", {"name": "n", "scale": 2.0}) == 6.0
    assert read("host_span_share", {"span": "data_wait_s"}) == 5.0
    assert read("host_span_share", {"span": "real_tokens",
                                    "of": "padded_tokens",
                                    "complement": True}) == \
        pytest.approx(10.0)
    assert read("trace_idle_share", {}) == 25.0
    roofline = {"kernels": ["k"], "cost": "packed_attention_train"}
    flops, nbytes = kernel_costs.packed_attention_train(
        obs["traced_work"], obs["dims"])
    assert read("trace_kernel_roofline", roofline) == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 0.5)
    for reader, args in (("counter", {"name": "nope"}),
                         ("host_span_share", {"span": "nope"}),
                         ("trace_idle_share", {}),
                         ("trace_kernel_roofline", roofline)):
        assert read(reader, args, {}) is None


def test_cost_functions_scale_with_the_shapes():
    dims = manifest.load_config("transformer-big")
    one = kernel_costs.packed_attention_train(
        [{"rows": 128, "src_width": 32, "trg_width": 32}], dims)
    two = kernel_costs.packed_attention_train(
        [{"rows": 256, "src_width": 32, "trg_width": 32}], dims)
    assert two[0] == 2 * one[0] and two[1] == 2 * one[1]
    # 18 attention blocks, fwd 4 + bwd 10 flops per (row, head, tq, tk, dh)
    assert one[0] == 18 * 14.0 * 128 * 16 * 32 * 32 * 64
    # 6ND within the attention and tied-logits terms: 210M parameters
    flops = kernel_costs.train_step_flops(dims, 1000, 1000, 32, 32)
    assert 0.8 < flops / (6 * 210e6 * 1000) < 1.3
    least, bound = kernel_costs.roofline_seconds(
        197e12, 819e9, manifest.load_peaks("TPU v5 lite"))
    assert least == 1.0 and bound == "compute"


# -- the reference check -------------------------------------------------------

class _Model:
    """Stands in for the program: the reference's own per-token costs,
    summed under the weights, with a fault switched in."""

    def __init__(self, ref, dims, fault):
        self.ref, self.dims, self.fault = ref, dims, fault

    def loss(self, params, batch, key, train):
        import jax.numpy as jnp
        assert key is None and train is False
        dims = dict(self.dims)
        if self.fault == "no smoothing":
            dims["label_smoothing"] = 0.0
        if self.fault == "a layer left out":
            dims["dec_depth"] -= 1
        ce = self.ref.token_costs(params, dims, batch["src_ids"],
                                  batch["src_mask"], batch["trg_ids"],
                                  batch["trg_mask"])
        if self.fault == "coarse arithmetic":   # each token 8 % off
            ce = ce + 0.08 * (ce - ce.mean()) * jnp.sign(
                jnp.sin(1e3 * ce))
        return jnp.sum(ce * batch["trg_mask"] * batch["data_weights"]), {}


@pytest.mark.parametrize("fault", [None, "no smoothing", "a layer left out",
                                   "coarse arithmetic"])
def test_reference_check_tells_a_faulty_cost(fault):
    import types
    import jax
    run = importlib.import_module("benchmark.run")
    train = manifest.load_driver("train")
    cell = manifest.Cell(BENCH, "big.train")
    dims = dict(cell.config, **run.TINY)
    d, v, rs = dims["dim_emb"], dims["vocab"], np.random.RandomState(0)
    names = ["Wemb"] + [
        f"{side}_l{l}_{blk}_{w}"
        for side, blocks, depth in (
            ("encoder", ("self", "ffn"), dims["enc_depth"]),
            ("decoder", ("self", "context", "ffn"), dims["dec_depth"]))
        for l in range(1, depth + 1) for blk in blocks
        for w in (("W1", "b1", "W2", "b2", "ffn_ln_scale", "ffn_ln_bias")
                  if blk == "ffn" else
                  ("Wq", "bq", "Wk", "bk", "Wv", "bv", "Wo", "bo",
                   "Wo_ln_scale", "Wo_ln_bias"))]
    shape = {"Wemb": (v, d), "W1": (d, dims["dim_ffn"]),
             "b1": (dims["dim_ffn"],), "W2": (dims["dim_ffn"], d)}
    params = {}
    for n in names:
        leaf = n.rsplit("_", 1)[-1] if n != "Wemb" else n
        shp = shape.get(leaf, (d, d) if leaf.startswith("W") else (d,))
        params[n] = (np.ones(shp, np.float32) if leaf == "scale" else
                     rs.normal(0, 0.3, shp).astype(np.float32))
    rows, width = 12, 8
    lens = rs.randint(3, width + 1, rows)
    mask = (np.arange(width)[None] < lens[:, None]).astype(np.float32)
    ids = (rs.randint(2, v, (rows, width)) * mask).astype(np.int32)
    side = types.SimpleNamespace(ids=ids, mask=mask)
    notes = []
    ctx = types.SimpleNamespace(cell=cell, dims=dims, seed=3000000019,
                                note=notes.append)
    chk = cell.traffic["reference_check"]
    with jax.default_matmul_precision("highest"):
        problems = train.compare_with_reference(
            ctx, chk, _Model(cell.reference, dims, fault), params,
            types.SimpleNamespace(src=side, trg=side))
    assert (problems == []) == (fault is None), (problems, notes)


# -- the last line -------------------------------------------------------------

@pytest.mark.parametrize("cell_name", CELLS)
def test_last_line_shape(cell_name):
    run = importlib.import_module("benchmark.run")
    cell = manifest.Cell(BENCH, cell_name)
    device = {"platform": "tpu", "kind": "TPU v5 lite",
              "count": cell.chips, "memory_peak_bytes": 5 * 2 ** 30}
    result = {"correct": True, "attempted": 10, "failed": 0,
              "device": device}
    e2e = {m["name"]: 1.0 for m in cell.end_to_end}
    line = run.result_line(cell, False, result, e2e, {}, None)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == set(e2e)
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    trace = {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["a", 1.0]],
             "idle_gaps": [["b", 1.0]]}
    layers = {cell.per_layer[0]["name"]: {"value": 1.0, "unit": "%"}}
    line = run.result_line(cell, True, result, e2e, layers, trace)
    assert line["device"]["busy_s"] == 1.0 and "breakdown" in line
    assert set(line["metrics"]) == set(layers)
    with pytest.raises(SystemExit):
        run.result_line(cell, True, result, e2e, layers, None)
    json.dumps(line)


def _rehearse(cell_name, seconds):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell_name, "--seed", "3000000019",
         "--seconds", str(seconds), "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_runs_end_to_end_and_prints_no_result():
    r = _rehearse("big.train", 1)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert any(l.startswith("rehearsal_setup_s = ") for l in lines)
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
