"""A kernel's NAME is no part of `correct` (PR 51): the step's kernels are
reported by FAMILY, not demanded, and a roofline charges a family the same
work whatever kernels implement it. Held here on planted step dumps and
planted traces, CPU only; `names_oracle.py` keeps the parent's reduction
by exact name beside the new one.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import jaxside, kernel_costs, manifest  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
import names_oracle  # noqa: E402  (pytest puts this directory first)

BENCH = manifest.load_benchmark()
PEAKS = manifest.load_peaks("TPU v5 lite")
ROOFLINES = {m["name"]: manifest.load_layer_metric(m["name"])["args"]
             for m in BENCH["per_layer"] if m["name"].endswith("_roofline")}
# (cell, a family that one of its rooflines totals)
CELL_FAMILIES = [
    (w["name"], family) for w in BENCH["workloads"]
    for family in dict.fromkeys(
        f for m in manifest.Cell(BENCH, w["name"]).per_layer
        for f in ROOFLINES.get(m["name"], {}).get("kernels", ()))]
WORK = [{"rows": 2, "src_width": 256, "trg_width": 256}]


def plant_dump(tmp_path, kernels):
    """A JAX_DUMP_IR_TO directory whose programs name these kernels
    (jaxside.kernels_dumped drops it once read)."""
    d = tmp_path / "ir"
    d.mkdir()
    for i, k in enumerate(kernels):
        (d / f"jit_one_update.{i}.mlir").write_text(
            f'%0 = stablehlo.custom_call @tpu_custom_call(%arg0) '
            f'{{kernel_name = "{k}"}} : tensor<8x128xbf16>\n')
    return str(d)


def plant_trace(tmp_path, ops, name="planted"):
    """A one-chip trace: `ops` [(instruction name, ns)] one after another,
    500 ns apart, inside one `bench.window`."""
    events, meta, at = [], [], 1000
    for i, (op, ns) in enumerate(ops, 1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {at * 1000} "
                      f"duration_ps: {ns * 1000} }}")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"%{op}.{i} = bf16[8,128]{{1,0}} custom-call()" }} }}')
        at += ns + 500
    text = (
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" '
        "timestamp_ns: 0 " + " ".join(events) + " } " + " ".join(meta) + " } "
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "main" '
        f"timestamp_ns: 0 events {{ metadata_id: 1 offset_ps: 0 "
        f"duration_ps: {(at + 1000) * 1000} }} }} "
        'event_metadata { key: 1 value { id: 1 name: "bench.window" } } }')
    from jax.profiler import ProfileData
    path = str(tmp_path / f"{name}.xplane.pb")
    with open(path, "wb") as fh:
        fh.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def step_ops(families, but=()):
    """A few updates' ops: every name that stood for these families until
    PR 51 (less `but`), some under a `jvp_` prefix, between fusions, at
    durations no two of which are alike."""
    names = [n for n in names_oracle.names_of(families) if n not in but]
    ops = []
    for update in range(3):
        for i, n in enumerate(names):
            ops.append(("fusion", 40_000 + 13 * i))
            ops.append((("jvp_" if (i + update) % 2 else "") + n + "_",
                        100_003 + 7_919 * i + 104_729 * update))
    return ops


@pytest.fixture
def tracer():
    """The program's totals, counters and gauges as a traced window
    leaves them, for the readers that take them from TRACER."""
    from marian_tpu import obs
    obs.TRACER.reset()
    obs.TRACER._totals = {n: [3, 0.2, 0.1, "MainThread"] for n in (
        "train.sync", "train.h2d", "train.dispatch", "train.bookkeep",
        "data.wait")}
    obs.TRACER._counters = {
        "moe.assignments": 4000.0, "moe.assignments_held": 125.0,
        "moe.load_max": 30.0, "moe.load_mean": 20.0, "moe.dropped": 0.0,
        "attn.pairs_seen": 3.0, "attn.pairs_tiled": 4.0}
    obs.TRACER._gauges = {"hbm.limit": [16e9, 16e9, 16e9, 1],
                          "hbm.headroom": [15e8, 5e8, 25e8, 5],
                          "hbm.peak": [10e9, 10e9, 10e9, 1],
                          "hbm.programs_code": [4e8, 4e8, 4e8, 5]}
    yield obs.TRACER
    obs.TRACER.reset()


def observed(cell, trace):
    """What run.py hands the readers after a traced run."""
    return {"values": {"data_wait_s": 0.1, "dispatch_s": 0.2, "host_s": 0.1,
                       "window_s": 20.0, "updates": 30, "real_tokens": 900,
                       "padded_tokens": 1000, "window_compiles": 0,
                       "mfu_pct": 25.0},
            "trace": trace, "traced_work": WORK, "dims": cell.config,
            "root": None, "peaks": PEAKS}


# -- (a) reported, not demanded ---------------------------------------------------

@pytest.mark.parametrize("cell_name, family", CELL_FAMILIES)
def test_a_step_without_a_family_is_correct_and_says_so(
        tmp_path, tracer, cell_name, family):
    run = importlib.import_module("benchmark.run")
    train = manifest.load_driver("train")
    cell = manifest.Cell(BENCH, cell_name)
    families = cell.config["kernels"]
    gone = names_oracle.NAMES_UNTIL_PR51[family]
    dumped = jaxside.kernels_dumped(plant_dump(
        tmp_path, [n for n in names_oracle.names_of(families)
                   if n not in gone] + ["kda_prep_fwd"]))
    note, problems = train.kernels_in_step(cell, dumped)
    assert problems == []
    assert f"ABSENT of the configuration's families: {family} " in note
    for f in families:
        if f != family:
            assert f"{f}: " + ", ".join(sorted(
                names_oracle.NAMES_UNTIL_PR51[f])) in note
    assert "kda_prep (not the configuration's): kda_prep_fwd" in note
    # the traced run of such a step: the family's roofline is left out of
    # the line, every other metric of the cell reads
    trace = trace_reduce.reduce_trace(
        plant_trace(tmp_path, step_ops(families, but=gone)), families)
    assert trace["kernel_s"][family] == 0.0
    metrics = run.layer_metrics(cell, observed(cell, trace))
    silent = {m["name"] for m in cell.per_layer
              if family in ROOFLINES.get(m["name"], {}).get("kernels", ())}
    assert silent and set(metrics) == {m["name"] for m in cell.per_layer} \
        - silent
    assert all(v["value"] is not None for v in metrics.values())
    assert metrics["mfu.train"]["value"] == 25.0
    # and with the family there, nothing is absent and its roofline reads
    dumped = jaxside.kernels_dumped(plant_dump(
        tmp_path, names_oracle.names_of(families)))
    note, problems = train.kernels_in_step(cell, dumped)
    assert problems == [] and "ABSENT" not in note
    trace = trace_reduce.reduce_trace(
        plant_trace(tmp_path, step_ops(families)), families)
    assert set(run.layer_metrics(cell, observed(cell, trace))) \
        == {m["name"] for m in cell.per_layer}


def without_a_step_share(how):
    """The benchmark with its cells' `mfu` metric gone, or moving another
    end-to-end metric than the rooflines do."""
    bench = json.loads(json.dumps(BENCH))
    if how == "no mfu metric":
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] != "mfu.train"]
    else:
        for m in bench["per_layer"]:
            if m["name"] == "mfu.train":
                m["moves"] = "setup_s"
    return bench


@pytest.mark.parametrize("how", ["no mfu metric", "mfu moves another metric"])
@pytest.mark.parametrize("cell_name, family", CELL_FAMILIES)
def test_without_a_share_of_the_whole_step_the_absence_is_a_problem(
        tmp_path, how, cell_name, family):
    train = manifest.load_driver("train")
    cell = manifest.Cell(without_a_step_share(how), cell_name)
    families = cell.config["kernels"]
    gone = names_oracle.NAMES_UNTIL_PR51[family]
    dumped = jaxside.kernels_dumped(plant_dump(
        tmp_path, [n for n in names_oracle.names_of(families)
                   if n not in gone]))
    note, problems = train.kernels_in_step(cell, dumped)
    assert len(problems) == 1 and problems[0].startswith(
        f"kernels missing from the compiled step: ['{family}']")
    assert f"ABSENT of the configuration's families: {family} " in note
    # a family that no roofline of the cell totals is only ever noted
    dumped = jaxside.kernels_dumped(plant_dump(
        tmp_path, [n for n in names_oracle.names_of(families)
                          if "fused_ce" not in n]))
    note, problems = train.kernels_in_step(cell, dumped)
    assert problems == [] and "families: fused_ce " in note


def test_which_metric_is_a_share_of_the_whole_step(tmp_path):
    """`mfu` as a part of the name of its own: `step_mfu` and `mfu.train`
    are, `flops_util` and `mfuture` are not."""
    train = manifest.load_driver("train")
    dumped = {"fused_ce_fwd"}
    for name, bounded in (("mfu.train", True), ("step_mfu", True),
                          ("train-mfu", True), ("flops_util.train", False),
                          ("mfuture.train", False)):
        bench = json.loads(json.dumps(BENCH))
        for m in bench["per_layer"]:
            if m["name"] == "mfu.train":
                m["name"] = name
        # the renamed metric's file, in a root that is looked in first
        os.makedirs(tmp_path / "layer_metrics", exist_ok=True)
        with open(tmp_path / "layer_metrics" / f"{name}.json", "w") as fh:
            json.dump(manifest.load_layer_metric("mfu.train"), fh)
        cell = manifest.Cell(bench, "big.train", str(tmp_path))
        _, problems = train.kernels_in_step(cell, dumped)
        assert (problems == []) == bounded, (name, problems)


# -- (b) a family is charged the same work whatever implements it -----------------

def test_one_backward_kernel_in_place_of_two_is_charged_the_same(tmp_path):
    cell = manifest.Cell(BENCH, "trinity-mini.train-docs16k")
    args = ROOFLINES["window_flash_roofline"]
    assert args["kernels"] == ["flash_attention"]
    read = manifest.load_reader("trace_kernel_roofline").read
    three = [("flash_attention_fwd", 300_000), ("fusion", 50_000),
             ("flash_attention_dq", 400_000), ("flash_attention_dkv", 500_000)]
    two = [("flash_attention_fwd", 300_000), ("fusion", 50_000),
           ("flash_attention_bwd", 900_000)]
    faster = [("flash_attention_fwd", 300_000), ("fusion", 50_000),
              ("flash_attention_bwd", 600_000)]
    got = {}
    for name, ops in (("three", three), ("two", two), ("faster", faster)):
        trace = trace_reduce.reduce_trace(
            plant_trace(tmp_path, ops, name), cell.config["kernels"])
        got[name] = (trace["kernel_s"]["flash_attention"],
                     read(observed(cell, trace), args))
    assert got["three"][0] == got["two"][0] == 1.2e-3
    flops, nbytes = manifest.load_cost(args["cost"])(WORK, cell.config)
    least, _ = kernel_costs.roofline_seconds(flops, nbytes, PEAKS)
    # the cost is the metric file's, unchanged: the same least time over
    # the family's time, so the same reading, and a higher one only where
    # the family takes less time
    assert got["three"][1] == got["two"][1] == 100.0 * least / 1.2e-3
    assert got["faster"][1] == 100.0 * least / 0.9e-3 > got["two"][1]
    assert got["faster"][1] < 100.0


# -- (c) the longest family wins --------------------------------------------------

@pytest.mark.parametrize("name, family", [
    ("paged_decode_attention", "paged_decode_attention"),
    ("decode_attention", "decode_attention"),
    ("jvp_kda_prep_fwd", "kda_prep"), ("kda_prep_bwd", "kda_prep"),
    ("kda_chunk_bwd", "kda_chunk"),
    ("flash_attention_dkv", "flash_attention"),
    ("flash_attention_bwd", "flash_attention"),
    ("jvp_packed_attention_fwd", "packed_attention"),
    ("fused_ce_dw", "fused_ce"), ("fused_ce_bwd", "fused_ce"),
    ("fa_bwd", None), ("multiply_add_fusion", None), ("fusion", None),
])
def test_an_op_belongs_to_the_longest_family_its_name_holds(name, family):
    assert trace_reduce.kernel_of(name, trace_reduce.KNOWN_KERNELS) == family


def test_a_family_never_claims_a_longer_family_s_ops(tmp_path):
    ops = [("paged_decode_attention", 700_000), ("decode_attention", 110_000),
           ("kda_prep_fwd", 300_000), ("jvp_kda_prep_bwd_", 500_000),
           ("kda_chunk_fwd", 130_000), ("kda_chunk_bwd", 170_000)]
    path = plant_trace(tmp_path, ops)
    t = trace_reduce.reduce_trace(path, ("kda_chunk", "decode_attention"))
    assert t["kernel_s"] == {"kda_chunk": 300_000 / 1e9,
                             "decode_attention": 110_000 / 1e9}
    t = trace_reduce.reduce_trace(
        path, ("kda_prep", "paged_decode_attention", "flash_attention"))
    assert t["kernel_s"] == {"kda_prep": 800_000 / 1e9,
                             "paged_decode_attention": 700_000 / 1e9,
                             "flash_attention": 0.0}
    # a family of a later PR's own is taken beside the known ones
    t = trace_reduce.reduce_trace(path, ("kda", "my_kernel"))
    assert t["kernel_s"] == {"kda": 0.0, "my_kernel": 0.0}


# -- (d) the numbers do not move --------------------------------------------------

def test_all_ten_names_reduce_to_what_they_reduced_to(tmp_path):
    """One trace that holds all ten of the names the accepted tree's steps
    hold, reduced by names as the parent reduced it (the oracle) and by
    families. A family's total is the sum of its names' nanoseconds,
    exactly; the parent added SECONDS event by event and then name by
    name, and float addition keeps no order, so its readings and the new
    ones agree to the last bit or two (1e-14 here), not bit for bit."""
    families = tuple(names_oracle.NAMES_UNTIL_PR51)
    names = names_oracle.names_of(families)
    assert len(names) == 10
    path = plant_trace(tmp_path, step_ops(families))
    by_family = trace_reduce.reduce_trace(path, families)
    by_name = trace_reduce.reduce_trace(path, names)
    parent = names_oracle.kernel_s_by_name(path, names)
    assert set(by_family["kernel_s"]) == set(families)
    assert set(by_name["kernel_s"]) == set(parent) == set(names)
    ns = {k: round(v * 1e9) for k, v in by_name["kernel_s"].items()}
    for f in families:
        mine = names_oracle.NAMES_UNTIL_PR51[f]
        assert all(ns[n] > 0 for n in mine)
        assert round(by_family["kernel_s"][f] * 1e9) == sum(
            ns[n] for n in mine)
        assert by_family["kernel_s"][f] == sum(ns[n] for n in mine) / 1e9
        assert by_family["kernel_s"][f] == pytest.approx(
            sum(parent[n] for n in mine), rel=1e-14, abs=0)
    for n in names:
        assert by_name["kernel_s"][n] == pytest.approx(parent[n], rel=1e-14,
                                                       abs=0)
    # everything else of the reduction is the parent's, to the bit
    for key in ("window_s", "busy_s", "device_ops", "idle_gaps"):
        assert by_family[key] == by_name[key]
    # the five roofline files: by names over the parent's seconds, by
    # families over the new ones
    read = manifest.load_reader("trace_kernel_roofline").read
    assert sorted(ROOFLINES) == [
        "block_diffusion_flash_roofline", "kda_roofline",
        "mla_flash_roofline", "packed_attention_roofline",
        "window_flash_roofline"]
    for name, args in ROOFLINES.items():
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        cell = manifest.Cell(BENCH, entry["workloads"][0])
        new = read(observed(cell, by_family), args)
        old = names_oracle.roofline_by_name(
            observed(cell, {"kernel_s": parent}), args)
        by_names = read(observed(cell, by_name),
                        dict(args, kernels=names_oracle.names_of(
                            args["kernels"])))
        assert 0 < new < 100
        assert new == pytest.approx(by_names, rel=1e-14, abs=0)
        assert new == pytest.approx(old, rel=1e-14, abs=0)


def test_the_files_name_families_not_kernels():
    """configs/*.json::kernels and the rooflines' files name families of
    trace_reduce.KNOWN_KERNELS, and each name that stood there until PR 51
    holds the family that stands there now. (Nothing here reads the
    program's source: which kernels it has is the program's to change.)"""
    for c in BENCH["configs"]:
        body = manifest.load_config(c["name"])
        assert body["kernels"] and set(body["kernels"]) <= set(
            trace_reduce.KNOWN_KERNELS) & set(names_oracle.NAMES_UNTIL_PR51)
    for name, args in ROOFLINES.items():
        assert len(args["kernels"]) == 1 \
            and args["kernels"][0] in names_oracle.NAMES_UNTIL_PR51, name
    for family, names in names_oracle.NAMES_UNTIL_PR51.items():
        for n in names:
            assert trace_reduce.kernel_of(
                n, trace_reduce.KNOWN_KERNELS) == family
