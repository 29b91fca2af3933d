"""The benchmark's fifth configuration, `trinity-mini`, and its cell
`trinity-mini.train-docs16k`: the manifest is sound with them; the
configuration's file keeps every number of its source and declares its cuts
and what it assumed; its entries come after the fourth configuration's in
every list; its cost functions agree with a brute-force count of the
reference's own masks; the new metrics read nothing where there is no
trace; and the UNCHANGED train driver rehearses the configuration to
`correct=True`.

The rehearsal uses the benchmark's own configuration file under a traffic
mix of short documents kept here (`trinity_cell/`, found through `--root`),
as `test_sdar_cell.py` does for the fourth: the cell's own documents of
2k-16k tokens are the chip's.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.configs import trinity_mini_costs as costs  # noqa: E402
from test_harness import BENCH, _rehearse  # noqa: E402

CONFIG, CELL = "trinity-mini", "trinity-mini.train-docs16k"
ROOFLINE, SHARE = "window_flash_roofline", "attention_pairs_seen_share.train"
BEFORE_CONFIG, BEFORE_CELL = "sdar-30b-a3b", "sdar.train-docs8k"
SHORT_ROOT = os.path.join(ROOT, "tests", "perf_harness", "trinity_cell")
HBM = {"hbm_free_min_share.train", "hbm_peak_share.train",
       "hbm_step_programs_share.train"}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row of the source (model-configs guide), its `config`
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "layer_types": PERIOD * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_the_manifest_is_sound_with_the_fifth_configuration_and_cell():
    assert manifest.validate(BENCH) == []
    assert manifest.validate(root=SHORT_ROOT) == []
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    cell = manifest.Cell(BENCH, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.kind) \
        == (1, CONFIG, "train-docs16k", "train")
    # what the three plan cells all report, and two metrics of its own: the
    # work its flash kernels are charged differs (a rule a layer). NOT the
    # three `hbm_*` metrics: test_program_gauges.py pins their lists to the
    # first four cells, and a PR that adds a cell may not edit it (PERF.md
    # 7); the trainer's HBM line and `memory_peak_bytes` say what is held
    reported = {m["name"] for m in cell.per_layer}
    sdar = {m["name"] for m in manifest.Cell(BENCH, BEFORE_CELL).per_layer}
    assert reported == (sdar - {"block_diffusion_flash_roofline"} - HBM) \
        | {ROOFLINE, SHARE}
    assert {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "train_tok_s_chip"}
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"].endswith("/config.json") \
        and len(entry["source"]) < 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    # appended: AFTER the fourth configuration's, in every list (an ORDER,
    # not "last": the next cell comes after this one)
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == names.index(BEFORE_CELL) + 1
    names = [c["name"] for c in BENCH["configs"]]
    assert names.index(CONFIG) == names.index(BEFORE_CONFIG) + 1
    order = [m["name"] for m in BENCH["per_layer"]]
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if m["name"] in (ROOFLINE, SHARE)}
    assert mine == {
        ROOFLINE: {"name": ROOFLINE, "unit": "%", "better": "higher",
                   "source": "device_trace", "layer": "kernels",
                   "moves": "train_tok_s_chip", "workloads": [CELL]},
        SHARE: {"name": SHARE, "unit": "%", "better": "higher",
                "source": "program_counter", "layer": "kernels",
                "moves": "train_tok_s_chip", "workloads": [CELL]}}
    for earlier in ("block_diffusion_flash_roofline",
                    "hbm_step_programs_share.train"):      # the last there
        assert order.index(earlier) < order.index(ROOFLINE) \
            < order.index(SHARE)
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed and BEFORE_CELL in listed:
            assert listed.index(CELL) == listed.index(BEFORE_CELL) + 1
    assert CELL not in [m for m in BENCH["end_to_end"]
                        if m["name"] == "train_tok_s_chip.dense"][0][
                            "workloads"]


def test_the_file_keeps_the_source_and_declares_its_cuts():
    body = manifest.load_config(CONFIG)
    cut = {"num_hidden_layers": 5, "num_experts": 8}
    for key, value in PUBLISHED.items():
        assert body[key] == cut.get(key, value), key
    assert sorted(body["reduced"]) == ["num_experts", "num_hidden_layers",
                                       "vocab"]
    assert body["published"] == {"num_hidden_layers": 32,
                                 "num_experts": 128, "vocab": 200192}
    assert body["vocab"] == 25024 == body["vocab_size"] // 8
    assert body["deployment"]["chips"] == 16 \
        == body["published"]["num_experts"] // body["num_experts"]
    assert body["router_width"] == 128            # no width is cut
    # the floors: a whole period and four layers after the dense one, 8
    # routed experts, an eighth of the vocabulary
    built = body["layers_built"]
    assert len(built) == body["num_hidden_layers"]
    assert [body["layer_types"][l] for l in built] \
        == ["sliding_attention"] + PERIOD
    assert [l < body["num_dense_layers"] for l in built] \
        == [True] + [False] * 4
    assert body["layer_plan"] == ["swa:dense"] + ["swa:experts"] * 3 \
        + ["gqa:experts"]
    assert body["streams"] == 1 and body["kernels"] == [
        "flash_attention", "fused_ce"]
    flags = body["task_flags"]
    assert "--gradient-checkpointing" in flags \
        and "--plan-gqa-gate" in flags and "--plan-post-norms" in flags

    def flag(name, n=1):
        i = flags.index(name)
        return flags[i + 1:i + 1 + n]
    assert flag("--precision", 2) == ["bfloat16", "float32"]
    assert flag("--transformer-layer-plan", 5) == body["layer_plan"]
    assert flag("--plan-gqa-kv-heads") == ["4"]
    assert flag("--plan-gqa-dim-head") == ["128"]
    assert float(flag("--plan-swa-rope-theta")[0]) == body["rope_theta"]
    assert float(flag("--plan-gqa-rope-theta")[0]) == 0 \
        == body["full_attention_rope_theta"]
    assert flag("--plan-swa-window") == [str(body["sliding_window"])]
    assert flag("--plan-experts-score") == [body["score_func"]]
    assert float(flag("--plan-experts-scale")[0]) == body["route_scale"]
    assert flag("--plan-experts-shared") == ["1"]
    assert flag("--plan-experts-held", 2) == ["0", "8"]
    assert [int(w) for w in flag("--length-buckets", 6)] \
        == body["assumed"]["width_buckets"] \
        == [4096, 6144, 8192, 10240, 12288, 16384]
    for key in body["rehearse"]["dims"]:
        assert key in body
    for key in ("gate", "qk_norm", "rotation", "window", "norms",
                "route_norm", "expert_bias", "router", "num_dense_layers",
                "positions", "placement", "weights", "width_buckets", "rows",
                "experts_pool", "precompile", "parameters"):
        assert key in body["assumed"]
    traffic = manifest.load_traffic("train-docs16k")
    assert traffic["lengths"] == {"dist": "lognormal-quantiles", "mu": 8.6,
                                  "sigma": 0.6, "min_words": 2048,
                                  "max_words": 16383}
    assert (traffic["mini_batch_words_per_chip"], traffic["sync_every"]) \
        == (16384, 5)
    assert traffic["trainer_flags"] == [
        "--max-length", "16384", "--mini-batch-fit", "false", "--cost-type",
        "ce-mean-words"]
    check = traffic["reference_check"]
    assert (check["chunk_tokens"], check["projections"],
            check["cost_rtol"]) == (4096, 8, 0.001)


def test_the_parameters_counted_by_hand():
    d, h, hk, dh = 2048, 32, 4, 128
    attn = 3 * d * h * dh + 2 * d * hk * dh          # q, o, gate; k, v
    assert attn == 27_262_976
    expert, dense, table = 3 * d * 1024, 3 * d * 6144, 25024 * d
    assert (expert, dense, table) == (6_291_456, 37_748_736, 51_249_152)
    norms = 4 * d + 2 * dh
    dense_layer = attn + dense + norms
    expert_layer = attn + d * 128 + 9 * expert + norms
    assert 65.0e6 < dense_layer < 65.1e6 and 84.1e6 < expert_layer < 84.2e6
    assert dense_layer + 4 * expert_layer + 2 * table + d == 504_147_200
    # the published whole by the same count: 26.1 B
    whole = 2 * dense_layer + 30 * (attn + d * 128 + 129 * expert + norms) \
        + 2 * 200192 * d + d
    assert 26.0e9 < whole < 26.2e9


@pytest.mark.parametrize("width", [5, 64, 150])
def test_cost_functions_against_a_brute_force_count_of_pairs(width):
    """The pairs each layer's rule admits, counted off the reference's own
    masks at the rehearsal's window of 8 (a row under it, rows over it),
    against the cost functions' closed forms."""
    whole = manifest.load_config(CONFIG)
    dims = dict(whole, **whole["rehearse"]["dims"])
    ref = manifest.load_reference(whole["reference"])
    h, dh = dims["num_attention_heads"], dims["head_dim"]
    seen = []
    for kind, _ in ref.layer_kinds(dims):
        mask = ref.visibility(width, kind, dims["sliding_window"])
        assert int(mask.sum()) == costs.pairs(width, kind,
                                              dims["sliding_window"])
        seen.append(int(mask.sum()))
    assert len(seen) == 5 and seen[4] == width * (width + 1) // 2
    assert seen[0] == seen[1] == seen[2] == seen[3] <= seen[4]
    work = [{"rows": 3, "src_width": width, "trg_width": width}]
    flops, nbytes = costs.window_attention_train(work, dims)
    assert flops == 3 * h * sum(seen) * 14 * dh
    assert nbytes == 5 * 2 * 3 * width * dh * (5 * h + 6 * 2)
    # model FLOPs: every weight a token meets, and its share of the pairs
    d = float(dims["hidden_size"])
    attn_w = 3 * d * h * dh + 2 * d * 2 * dh
    one = 3 * d * dims["moe_intermediate_size"]
    met = 5 * attn_w + 3 * d * dims["intermediate_size"] \
        + 4 * (d * dims["router_width"] + (4 * 8 / 32 + 1) * one) \
        + d * dims["vocab"]
    per_token = 2 * met + h * 4 * dh * sum(seen) / width
    assert costs.train_step_flops(dims, 0, 100, 0, width) \
        == pytest.approx(3.0 * 100 * per_token, rel=1e-12)


def test_the_costs_at_the_published_widths():
    whole = manifest.load_config(CONFIG)
    # a [1, 16384] row: 7.7 TFLOP the global layer, 1.8 each window layer
    # through the three kernels (14 dh a pair), ISSUE 44's arithmetic
    t, h, dh = 16384, 32, 128
    full = costs.pairs(t, "full_attention", 2048)
    window = costs.pairs(t, "sliding_attention", 2048)
    assert (full, window) == (t * (t + 1) // 2, t * 2048 - 2048 * 2047 // 2)
    assert 7.6e12 < h * 14 * dh * full < 7.8e12
    assert 1.7e12 < h * 14 * dh * window < 1.9e12
    # a window layer computes 75 %, 44 % and 23 % of a causal layer's pairs
    for width, share in ((4096, 0.75), (8192, 0.44), (16384, 0.23)):
        assert abs(costs.pairs(width, "sliding_attention", 2048)
                   / costs.pairs(width, "full_attention", 2048) - share) < 0.01
    work = [{"rows": 1, "src_width": t, "trg_width": t}]
    flops, nbytes = costs.window_attention_train(work, whole)
    assert flops == h * (4 * window + full) * 14 * dh
    assert nbytes == 5 * 2 * t * dh * (5 * h + 6 * 4)
    for name, want in ((ROOFLINE, {"reader": "trace_kernel_roofline", "args": {
            "kernels": ["flash_attention"],
            "cost": "configs.trinity_mini_costs:window_attention_train"}}),
            (SHARE, {"reader": "program_counters", "args": {
                "num": "attn.pairs_seen", "den": "attn.pairs_tiled",
                "scale": 100.0}})):
        spec = manifest.load_layer_metric(name)
        assert spec == want
        # nothing to read without a trace: no number, no error
        assert manifest.load_reader(spec["reader"]).read(
            {}, spec["args"]) is None


def test_the_unchanged_driver_rehearses_the_configuration():
    r = _rehearse("trinity-mini.train-docs-short", 1, trace=1,
                  root=SHORT_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
    assert "placed decoder_l5_experts_router" in r.stderr
    short = manifest.load_traffic("train-docs-short", SHORT_ROOT)
    full = manifest.load_traffic("train-docs16k")
    for key in ("kind", "mini_batch_words_per_chip", "sync_every"):
        assert short[key] == full[key]
    for key in ("chunk_tokens", "cost_rtol", "token_rtol"):
        assert short["reference_check"][key] == full["reference_check"][key]
    # every document passes the rehearsal's window
    assert short["lengths"]["min_words"] \
        > manifest.load_config(CONFIG)["rehearse"]["dims"]["sliding_window"]
    with open(os.path.join(SHORT_ROOT, "BENCHMARK.json")) as fh:
        assert json.load(fh)["configs"][0]["file"] \
            == "benchmark/configs/trinity-mini.json"
