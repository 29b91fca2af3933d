"""The benchmark's sixth configuration, `lfm2-24b-a2b`, and its cell
`lfm2.train-docs8k`: the manifest is sound with them; the configuration's
file keeps every number of its source and declares its cuts and what it
assumed; its entries come after the fifth configuration's in every list;
its cost functions agree with a count by hand; the new roofline reads
nothing where there is no trace; and the UNCHANGED train driver rehearses
the configuration to `correct=True`.

The rehearsal uses the benchmark's own configuration file under a traffic
mix of short documents kept here (`lfm2_cell/`, found through `--root`), as
`test_trinity_cell.py` does for the fifth: the cell's own documents of
256-8191 tokens are the chip's.

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
from benchmark.configs import lfm2_costs as costs  # noqa: E402
from test_harness import BENCH, _rehearse  # noqa: E402

CONFIG, CELL = "lfm2-24b-a2b", "lfm2.train-docs8k"
ROOFLINE = "gqa64_flash_roofline.train"
BEFORE_CONFIG, BEFORE_CELL = "trinity-mini", "trinity-mini.train-docs16k"
SHORT_ROOT = os.path.join(ROOT, "tests", "perf_harness", "lfm2_cell")
PERIOD = ["conv", "conv", "conv", "full_attention"]
# the catalog row of the source (model-configs guide), its `config`
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention"] + PERIOD * 9 + ["conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_the_manifest_is_sound_with_the_sixth_configuration_and_cell():
    assert manifest.validate(BENCH) == []
    assert manifest.validate(root=SHORT_ROOT) == []
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    cell = manifest.Cell(BENCH, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.kind) \
        == (1, CONFIG, "train-docs8k-lfm2", "train")
    # what the fifth cell reports less its own two metrics, and a roofline
    # of its own: the flash family at heads of 64. NOT the three `hbm_*`
    # metrics: test_program_gauges.py pins their lists to the first four
    # cells, and a PR that adds a cell may not edit it (PERF.md 7); the
    # trainer's HBM line and `memory_peak_bytes` say what is held
    reported = {m["name"] for m in cell.per_layer}
    fifth = {m["name"] for m in manifest.Cell(BENCH, BEFORE_CELL).per_layer}
    assert reported == (fifth - {"window_flash_roofline",
                                 "attention_pairs_seen_share.train"}) \
        | {ROOFLINE}
    assert "mfu.train" in reported
    assert {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "train_tok_s_chip"}
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab"]
    # appended: AFTER the fifth configuration's, in every list
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == names.index(BEFORE_CELL) + 1
    names = [c["name"] for c in BENCH["configs"]]
    assert names.index(CONFIG) == names.index(BEFORE_CONFIG) + 1
    order = [m["name"] for m in BENCH["per_layer"]]
    mine = [m for m in BENCH["per_layer"] if m["name"] == ROOFLINE]
    assert mine == [{"name": ROOFLINE, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "train_tok_s_chip", "workloads": [CELL]}]
    assert order.index("attention_pairs_seen_share.train") \
        < order.index(ROOFLINE)
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
    assert body["reduced"] == ["num_hidden_layers", "num_experts", "vocab"]
    assert body["published"] == {"num_hidden_layers": 40, "num_experts": 64,
                                 "vocab": 65536}
    assert body["vocab"] == 8192 == body["vocab_size"] // 8
    assert body["deployment"]["chips"] == 8 \
        == body["published"]["num_experts"] // body["num_experts"]
    # no width is cut; the two the catalog's row leaves open
    assert body["router_width"] == 64
    assert body["head_dim"] == 64 \
        == body["hidden_size"] // body["num_attention_heads"]
    assert body["rope_theta"] == body["rope_parameters"]["rope_theta"]
    assert body["tie_embedding"] is True
    types = body["layer_types"]
    assert (types.count("conv"), types.count("full_attention")) == (30, 10)
    assert [i for i, t in enumerate(types) if t == "full_attention"] \
        == list(range(2, 40, 4))
    # the floors: the dense layer once, then a whole period and four
    # layers after the dense ones, 8 routed experts, an eighth of the
    # vocabulary
    built = body["layers_built"]
    assert built == [1, 2, 3, 4, 5] and len(built) == body["num_hidden_layers"]
    assert [types[l] for l in built] == ["conv", "full_attention"] \
        + ["conv"] * 3
    assert sorted(types[l] for l in built[1:]) == sorted(PERIOD)
    assert [l < body["num_dense_layers"] for l in built] \
        == [True] + [False] * 4
    assert body["layer_plan"] == ["conv:dense", "gqa:experts"] \
        + ["conv:experts"] * 3
    assert body["streams"] == 1 and body["kernels"] == [
        "flash_attention", "fused_ce"]
    flags = body["task_flags"]
    assert "--gradient-checkpointing" in flags \
        and "--tied-embeddings" in flags \
        and "--plan-gqa-gate" not in flags and "--plan-post-norms" not in flags

    def flag(name, n=1):
        i = flags.index(name)
        return flags[i + 1:i + 1 + n]
    assert flag("--precision", 2) == ["bfloat16", "float32"]
    assert flag("--transformer-layer-plan", 5) == body["layer_plan"]
    assert flag("--dim-emb") == ["2048"]
    assert flag("--transformer-heads") == ["32"]
    assert flag("--transformer-dim-ffn") == ["11776"]
    assert flag("--plan-gqa-kv-heads") == ["8"]
    assert flag("--plan-gqa-dim-head") == ["64"]
    assert float(flag("--plan-gqa-rope-theta")[0]) == body["rope_theta"]
    assert flag("--plan-conv-taps") == [str(body["conv_L_cache"])]
    assert float(flag("--plan-norm-eps")[0]) == body["norm_eps"]
    assert flag("--plan-experts") == ["64"]
    assert flag("--plan-experts-held", 2) == ["0", "8"]
    assert flag("--plan-experts-top-k") == ["4"]
    assert flag("--plan-experts-dim-ffn") == ["1536"]
    assert flag("--plan-experts-shared") == ["0"]
    assert flag("--plan-experts-score") == [body["scoring_func"]] \
        == ["sigmoid"]
    assert float(flag("--plan-experts-scale")[0]) \
        == body["routed_scaling_factor"]
    assert [int(w) for w in flag("--length-buckets", 6)] \
        == body["assumed"]["width_buckets"] \
        == [1024, 2048, 3072, 4096, 6144, 8192]
    assert flag("--precompile-buckets") == ["3"]
    for key in body["rehearse"]["dims"]:
        assert key in body
    # the router's geometry is small as published: a rehearsal keeps it
    assert not {"router_width", "num_experts", "num_experts_per_tok"} \
        & set(body["rehearse"]["dims"])
    for key in ("vocab", "router_width", "num_dense_layers", "block", "tied",
                "conv", "head_dim", "qk_norm", "rotation", "attention",
                "route_norm", "expert_bias", "router", "positions",
                "weights", "parameters", "width_buckets", "rows",
                "experts_pool", "remat", "precompile", "placement"):
        assert key in body["assumed"], key
    for key in ("block", "tied", "conv", "qk_norm"):
        assert "which no file here could be fetched to check against" \
            in body["assumed"][key]
    traffic = manifest.load_traffic("train-docs8k-lfm2")
    assert traffic["lengths"] == manifest.load_traffic(
        "train-docs8k")["lengths"] == {
            "dist": "lognormal-quantiles", "mu": 7.6, "sigma": 0.8,
            "min_words": 256, "max_words": 8191}
    assert (traffic["kind"], traffic["mini_batch_words_per_chip"],
            traffic["sync_every"]) == ("train", 16384, 5)
    assert traffic["trainer_flags"] == [
        "--max-length", "8192", "--mini-batch-fit", "false", "--cost-type",
        "ce-mean-words"]
    check = traffic["reference_check"]
    assert (check["chunk_tokens"], check["projections"],
            check["cost_rtol"]) == (1024, 8, 0.001)
    assert 0 < check["token_rtol"] < 1


def test_the_parameters_counted_by_hand():
    d, h, hk, dh = 2048, 32, 8, 64
    conv = d * 3 * d + 3 * d + d * d
    attn = 2 * d * h * dh + 2 * d * hk * dh + 2 * dh
    assert (conv, attn) == (16_783_360, 10_485_888)
    expert, dense, table = 3 * d * 1536, 3 * d * 11776, 8192 * d
    assert (expert, dense, table) == (9_437_184, 72_351_744, 16_777_216)
    norms, router = 2 * d, d * 64 + 64        # with its selection biases
    dense_layer = conv + dense + norms
    attn_layer = attn + router + 8 * expert + norms
    conv_layer = conv + router + 8 * expert + norms
    assert (dense_layer, attn_layer, conv_layer) \
        == (89_139_200, 86_118_592, 92_416_064)
    built = dense_layer + attn_layer + 3 * conv_layer + table + d
    assert built == 469_285_248
    assert 5.62e9 < built * 12 < 5.64e9 and 7.50e9 < built * 16 < 7.52e9
    # the published whole by the same count: 23.8 B
    whole = 2 * dense_layer + 10 * (attn + router + 64 * expert + norms) \
        + 28 * (conv + router + 64 * expert + norms) + 65536 * d + d
    assert 23.8e9 < whole < 23.9e9
    # two periods, or 16 held experts, would not fit beside six step
    # programs and a step's temporaries (the issue's arithmetic)
    assert 0.83e9 < built + attn_layer + 3 * conv_layer < 0.84e9
    assert 0.77e9 < built + 4 * 8 * expert < 0.78e9


@pytest.mark.parametrize("width", [5, 64, 150])
def test_cost_functions_against_a_count_by_hand(width):
    whole = manifest.load_config(CONFIG)
    dims = dict(whole, **whole["rehearse"]["dims"])
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    seen = sum(q + 1 for q in range(width))      # query q sees keys 0..q
    assert costs.pairs(width) == seen
    work = [{"rows": 3, "src_width": width, "trg_width": width}]
    flops, nbytes = costs.causal_attention_train(work, dims)
    assert flops == 3 * h * seen * 14 * dh       # ONE attention layer of 5
    assert nbytes == 2 * 3 * width * dh * (5 * h + 6 * hk)
    # model FLOPs: every weight a token meets, the core, its share of pairs
    d = float(dims["hidden_size"])
    conv = 4 * d * d
    attn_w = 2 * d * h * dh + 2 * d * hk * dh
    one = 3 * d * dims["moe_intermediate_size"]
    routed = d * 64 + 4 * 8 / 64 * one
    met = 4 * conv + attn_w + 3 * d * dims["intermediate_size"] \
        + 4 * routed + d * dims["vocab"]         # the tied table ONCE
    per_token = 2 * met + 4 * 8 * d + h * 4 * dh * seen / width
    assert costs.train_step_flops(dims, 0, 100, 0, width) \
        == pytest.approx(3.0 * 100 * per_token, rel=1e-12)


def test_the_costs_at_the_published_widths():
    whole = manifest.load_config(CONFIG)
    # the issue's reckoning, MFLOP a token forward at the median row of
    # 2000: the dense layer 178, three conv expert layers 130, the
    # attention layer 31 + ~10 of pairs, the table 34; 1.15 GFLOP trained
    forward = costs.train_step_flops(whole, 0, 1, 0, 2000) / 3.0
    assert 3.8e8 < forward < 3.9e8
    d = 2048.0
    conv = 8 * d * d + 8 * d
    routed = 2 * d * 64 + 0.5 * 6 * d * 1536
    assert 1.77e8 < conv + 6 * d * 11776 < 1.79e8
    assert 1.29e8 < 3 * (conv + routed) < 1.31e8
    assert 3.0e7 < 2 * (2 * d * d + 2 * d * 512) + routed < 3.1e7
    assert 3.3e7 < 2 * d * 8192 < 3.4e7
    # a [2, 8192] batch through the three kernels: 14 dh a pair
    t, h, hk, dh = 8192, 32, 8, 64
    work = [{"rows": 2, "src_width": t, "trg_width": t}]
    flops, nbytes = costs.causal_attention_train(work, whole)
    assert flops == 2 * h * (t * (t + 1) // 2) * 896
    assert nbytes == 2 * 2 * t * dh * (5 * h + 6 * hk)
    spec = manifest.load_layer_metric(ROOFLINE)
    assert spec == {"reader": "trace_kernel_roofline", "args": {
        "kernels": ["flash_attention"],
        "cost": "configs.lfm2_costs:causal_attention_train"}}
    # nothing to read without a trace: no number, no error
    assert manifest.load_reader(spec["reader"]).read({}, spec["args"]) is None


def test_the_unchanged_driver_rehearses_the_configuration():
    r = _rehearse("lfm2.train-docs-short", 1, trace=1, root=SHORT_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
    for layer in (2, 3, 4, 5):
        assert f"placed decoder_l{layer}_experts_router" in r.stderr
    assert "placed decoder_l1_" not in r.stderr
    short = manifest.load_traffic("train-docs-short", SHORT_ROOT)
    full = manifest.load_traffic("train-docs8k-lfm2")
    for key in ("kind", "mini_batch_words_per_chip", "sync_every"):
        assert short[key] == full[key]
    for key in ("chunk_tokens", "cost_rtol", "token_rtol"):
        assert short["reference_check"][key] == full["reference_check"][key]
    with open(os.path.join(SHORT_ROOT, "BENCHMARK.json")) as fh:
        own = json.load(fh)
    assert own["configs"][0]["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert {m["name"] for m in own["per_layer"]} \
        == {m["name"] for m in manifest.Cell(BENCH, CELL).per_layer}
