"""The benchmark's fourth configuration, `sdar-30b-a3b`, and its cell
`sdar.train-docs8k`: the manifest is sound with them; the configuration's
file keeps every number of its source and declares its cuts and what it
assumed; its two cost functions give hand-worked numbers; the new roofline
metric reads nothing where there is no trace; and the UNCHANGED train
driver rehearses the configuration to `correct=True` through the reference
check's evaluation rule.

The rehearsal uses the benchmark's own configuration file under a traffic
mix of short documents kept here (`sdar_cell/`, found through `--root`), as
`test_joyai_cell.py` does for the third.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.configs import sdar_costs as costs  # noqa: E402
from test_harness import BENCH, _rehearse  # noqa: E402

CONFIG, CELL = "sdar-30b-a3b", "sdar.train-docs8k"
METRIC = "block_diffusion_flash_roofline"
SHORT_ROOT = os.path.join(ROOT, "tests", "perf_harness", "sdar_cell")
# the catalog row of the source (model-configs guide), its `config`
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_the_manifest_is_sound_with_the_fourth_configuration_and_cell():
    assert manifest.validate(BENCH) == []
    assert manifest.validate(root=SHORT_ROOT) == []
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    cell = manifest.Cell(BENCH, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.kind) \
        == (1, CONFIG, "train-docs8k-sdar", "train")
    reported = {m["name"] for m in cell.per_layer}
    # the work its flash kernels are charged differs (pairs of the block
    # rule, shared key heads): a metric of its own, and none of the others'
    assert not {"kda_roofline", "mla_flash_roofline",
                "packed_attention_roofline"} & reported
    joyai = {m["name"] for m in manifest.Cell(
        BENCH, "joyai-flash.train-docs8k").per_layer}
    assert reported == (joyai - {"mla_flash_roofline"}) | {METRIC}
    assert {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "train_tok_s_chip"}
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"].endswith("/config.json") \
        and len(entry["source"]) < 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    # appended: after the cell that was the last before it, in every list
    # (an ORDER, not "last": the next cell comes after this one)
    before = "joyai-flash.train-docs8k"
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == names.index(before) + 1
    names = [c["name"] for c in BENCH["configs"]]
    assert names.index(CONFIG) == names.index("joyai-llm-flash") + 1
    mine = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    assert mine == [{
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tok_s_chip", "workloads": [CELL]}]
    # and its metric after the metrics that were there when it came
    # (later PRs append theirs after it, and may list both cells)
    order = [m["name"] for m in BENCH["per_layer"]]
    for earlier in ("kda_roofline", "mla_flash_roofline",
                    "routed_here_share.train", "moe_dropped.train"):
        assert order.index(earlier) < order.index(METRIC)
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed and before in listed:
            assert listed.index(CELL) == listed.index(before) + 1


def test_the_file_keeps_the_source_and_declares_its_cuts():
    body = manifest.load_config(CONFIG)
    cut = {"num_hidden_layers": 4, "num_experts": 16}
    for key, value in PUBLISHED.items():
        assert body[key] == cut.get(key, value), key
    assert sorted(body["reduced"]) == ["num_experts", "num_hidden_layers",
                                       "vocab"]
    assert body["published"] == {"num_hidden_layers": 48,
                                 "num_experts": 128, "vocab": 151936}
    assert body["vocab"] == 18992 == body["vocab_size"] // 8
    assert body["deployment"]["chips"] == 8 \
        == body["published"]["num_experts"] // body["num_experts"]
    assert body["router_width"] == 128            # no width is cut
    assert body["layer_plan"] == ["gqa:experts"] * 4
    assert body["streams"] == 1 and body["kernels"] == [
        "flash_attention", "fused_ce"]
    assert (body["block_length"], body["noise_eps"], body["mask_token_id"]) \
        == (4, 1e-3, 1)
    flags = body["task_flags"]
    assert "--gradient-checkpointing" in flags

    def flag(name, n=1):
        i = flags.index(name)
        return flags[i + 1:i + 1 + n]
    assert flag("--precision", 2) == ["bfloat16", "float32"]
    assert flag("--transformer-layer-plan", 4) == body["layer_plan"]
    assert flag("--plan-gqa-kv-heads") == ["4"]
    assert flag("--plan-gqa-dim-head") == ["128"]
    assert float(flag("--plan-gqa-rope-theta")[0]) == body["rope_theta"]
    assert flag("--plan-diffusion-block") == [str(body["block_length"])]
    assert flag("--plan-experts-score") == [body["scoring_func"]]
    assert flag("--plan-experts-shared") == ["0"]
    assert flag("--plan-experts-held", 2) == ["0", "16"]
    # the program's constants are the file's
    from marian_tpu.models import layer_plan as P
    assert (P.DIFFUSION_EPS, P.MASK_TOKEN) \
        == (body["noise_eps"], body["mask_token_id"])
    # the thirteen widths of the other plans' cells: the same documents
    other = manifest.load_config("kimi-linear-48b-a3b")
    assert body["assumed"]["width_buckets"] \
        == other["assumed"]["width_buckets"]
    for key in body["rehearse"]["dims"]:
        assert key in body
    for key in ("block_length", "noise", "mask_token_id", "evaluation",
                "attention", "router", "intermediate_size"):
        assert key in body["assumed"]


def test_cost_functions_against_hand_worked_cases():
    whole = manifest.load_config(CONFIG)
    d, h = 2048, 32
    attn_w = d * 4096 + 2 * d * 512 + 4096 * d
    assert attn_w + d * 128 == 19_136_512          # ISSUE 34's 19.14 M
    expert = 3 * d * 768
    assert expert == 4_718_592
    # what a layer holds here and the whole cut: ISSUE 34's 456.4 M
    # (2 * 128 norm scales a layer and 2 * d + d of norms beside them)
    layer = attn_w + d * 128 + 16 * expert + 2 * 128 + 2 * d
    assert 4 * layer + 2 * 18992 * d + d == 456_346_624
    # a position meets the attention weights, the router and 8 * 16 / 128
    # = 1 expert: 47.7 MFLOP forward
    met = attn_w + d * 128 + expert
    assert 47.7e6 < 2 * met < 47.8e6
    per_token = 4 * (2 * 2 * met + h * 512 * (1024 + 4)) + 2 * d * 18992
    assert costs.train_step_flops(whole, 0, 1000, 0, 1024) \
        == 3.0 * 1000 * per_token
    # ISSUE 34's 22.6 TFLOP of weights an update of 16384 tokens
    weights = 3 * 16384 * (4 * 2 * 2 * met + 2 * d * 18992)
    assert 22.5e12 < weights < 22.7e12
    # kernels: one row of 128 positions, 256 indices; T^2 + 4 T pairs
    work = [{"rows": 1, "src_width": 128, "trg_width": 128}]
    flops, nbytes = costs.block_diffusion_attention_train(work, whole)
    assert flops == 4 * h * (128 * 128 + 4 * 128) * (512 + 1280)
    assert nbytes == 4 * 2 * 256 * 128 * (5 * h + 6 * 4)
    # the pairs are the rule's own: count them off the reference's mask
    ref = manifest.load_reference(whole["reference"])
    assert int(ref.visibility(128, 4).sum()) == 128 * 128 + 4 * 128
    # 7.7 TFLOP a layer at [2, 8192]
    big = [{"rows": 2, "src_width": 8192, "trg_width": 8192}]
    assert 7.6e12 < costs.block_diffusion_attention_train(
        big, whole)[0] / 4 < 7.8e12
    spec = manifest.load_layer_metric(METRIC)
    assert spec == {"reader": "trace_kernel_roofline", "args": {
        "kernels": ["flash_attention"],
        "cost": "configs.sdar_costs:block_diffusion_attention_train"}}
    # nothing to read without a trace: no number, no error
    assert manifest.load_reader(spec["reader"]).read({}, spec["args"]) is None


def test_the_unchanged_driver_rehearses_the_configuration():
    r = _rehearse("sdar.train-docs-short", 1, trace=1, root=SHORT_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
    short = manifest.load_traffic("train-docs-short", SHORT_ROOT)
    full = manifest.load_traffic("train-docs8k-sdar")
    docs = manifest.load_traffic("train-docs8k")
    for key in ("kind", "mini_batch_words_per_chip", "sync_every"):
        assert short[key] == full[key]
    # the documents and the batch are the other plans' cells'
    for key in ("kind", "lengths", "mini_batch_words_per_chip",
                "trainer_flags", "sync_every"):
        assert full[key] == docs[key], key
    for key in ("cost_rtol", "token_rtol"):
        assert short["reference_check"][key] \
            == full["reference_check"][key]
    with open(os.path.join(SHORT_ROOT, "BENCHMARK.json")) as fh:
        assert json.load(fh)["configs"][0]["file"] \
            == "benchmark/configs/sdar-30b-a3b.json"
