"""The benchmark's third configuration, `joyai-llm-flash`, and its cell
`joyai-flash.train-docs8k`: the manifest is sound with them; the
configuration's file keeps every number of its source and declares its
cuts; its model-FLOPs function and the accepted flash-attention cost
function on its dims give hand-worked numbers; the counter reader reads
the prediction module's counters, or nothing where the program keeps
none; and the UNCHANGED train driver rehearses the configuration to
`correct=True`, module cost and all.

The rehearsal uses the benchmark's own configuration file under a traffic
mix of short documents kept here (`joyai_cell/`, found through `--root`),
as `test_kimi_cell.py` does for the other plan.

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
from benchmark.configs import joyai_llm_flash_costs as costs  # noqa: E402
from benchmark.configs import kimi_linear_costs as shared  # noqa: E402
from test_harness import BENCH, _rehearse  # noqa: E402

CONFIG, CELL = "joyai-llm-flash", "joyai-flash.train-docs8k"
SHORT_ROOT = os.path.join(ROOT, "tests", "perf_harness", "joyai_cell")
# the catalog row of the source (model-configs guide), its `config`
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_the_manifest_is_sound_with_the_third_configuration_and_cell():
    assert manifest.validate(BENCH) == []
    assert manifest.validate(root=SHORT_ROOT) == []
    cell = manifest.Cell(BENCH, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.kind) \
        == (1, CONFIG, "train-docs8k-joyai", "train")
    reported = {m["name"] for m in cell.per_layer}
    # its flash kernels are read by the ACCEPTED metric: one kernel family,
    # one roofline share, on two plans
    assert not {"kda_roofline", "packed_attention_roofline"} & reported
    assert {"mla_flash_roofline", "mfu.train", "moe_dropped.train", "routed_here_share.train",
            "expert_load_max_over_mean.train", "device_idle_share.train",
            "window_compiles.train"} <= reported
    assert {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "train_tok_s_chip"}
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"].endswith("/config.json") \
        and len(entry["source"]) < 200
    assert len(cell.entry["why"]) <= 200
    # the cell brings no metric of its own: whatever it reports, a cell
    # that was there reports too
    older = set()
    for other in ("big.train", "kimi-linear.train-docs8k"):
        older |= {m["name"] for m in manifest.Cell(BENCH, other).per_layer}
    assert reported <= older
    # appended: right after the entries that were the last before it, in
    # every list (an ORDER, not "last": later cells come after this one)
    before = "kimi-linear.train-docs8k"
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == names.index(before) + 1
    names = [c["name"] for c in BENCH["configs"]]
    assert names.index(CONFIG) == names.index("kimi-linear-48b-a3b") + 1
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed and before in listed:
            assert listed.index(CELL) == listed.index(before) + 1


def test_the_file_keeps_the_source_and_declares_its_cuts():
    body = manifest.load_config(CONFIG)
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16}
    for key, value in PUBLISHED.items():
        assert body[key] == cut.get(key, value), key
    assert sorted(body["reduced"]) == ["n_routed_experts",
                                       "num_hidden_layers", "vocab"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "n_routed_experts": 256, "vocab": 129280}
    assert body["vocab"] == 16160 == body["vocab_size"] // 8
    assert body["deployment"]["chips"] == 16 \
        == body["published"]["n_routed_experts"] // body["n_routed_experts"]
    assert body["router_width"] == 256            # no width is cut
    plan = body["layer_plan"]
    # the leading dense layer, four expert layers, then the module's block
    assert len(plan) == body["num_hidden_layers"] \
        + body["num_nextn_predict_layers"] == 6
    assert plan[0] == "mla:dense" and plan[1:] == ["mla:experts"] * 5
    assert body["streams"] == 1 and body["kernels"] == [
        "flash_attention", "fused_ce"]
    flags = body["task_flags"]
    assert "--gradient-checkpointing" in flags

    def flag(name, n=1):
        i = flags.index(name)
        return flags[i + 1:i + 1 + n]
    assert flag("--precision", 2) == ["bfloat16", "float32"]
    assert flag("--transformer-layer-plan", 6) == plan
    assert flag("--plan-mla-q-rank") == ["1536"]
    assert float(flag("--plan-mla-rope-theta")[0]) == body["rope_theta"]
    assert flag("--plan-mtp-modules") == ["1"]
    assert float(flag("--plan-mtp-weight")[0]) == body["mtp_loss_weight"]
    assert flag("--plan-experts-held", 2) == ["0", "16"]
    # six of the other plan's thirteen widths, the narrowest and the
    # widest among them: the same documents, every step program loaded
    # (assumed.width_buckets_why); the flags are the list
    other = manifest.load_config("kimi-linear-48b-a3b")
    widths = body["assumed"]["width_buckets"]
    assert len(widths) == 6 and widths == sorted(widths)
    assert set(widths) < set(other["assumed"]["width_buckets"])
    assert (widths[0], widths[-1]) == (
        other["assumed"]["width_buckets"][0],
        other["assumed"]["width_buckets"][-1])
    assert flag("--length-buckets", 6) == [str(w) for w in widths]
    assert flag("--length-buckets", 7)[-1] == "--precompile-buckets"
    for key in body["rehearse"]["dims"]:
        assert key in body
    for key in ("mtp", "mtp_loss_weight", "rope", "positions", "router"):
        assert key in body["assumed"]


def test_cost_functions_against_hand_worked_cases():
    whole = manifest.load_config(CONFIG)
    d, h = 2048, 32
    mla_w = d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256 \
        + h * 128 * d
    assert mla_w == 26_345_472                    # ISSUE 32's 26.35 M
    mla = 2 * mla_w + h * (2 * 192 + 2 * 128) * 1024 / 2
    experts = 2 * d * 256 + (8 * 16 / 256 + 1) * 6 * d * 768
    table = 2 * d * 16160
    # one dense block, one expert block, one module
    dims = dict(whole, num_hidden_layers=2)
    per_token = (mla + 6 * d * 7168) + (mla + experts) + table \
        + (2 * 2 * d * d + mla + experts + table)
    assert costs.train_step_flops(dims, 0, 1000, 0, 1024) \
        == 3.0 * 1000 * per_token
    # no module: the stack and one pass over the table
    bare = dict(dims, num_nextn_predict_layers=0)
    assert costs.train_step_flops(bare, 0, 1000, 0, 1024) \
        == 3.0 * 1000 * ((mla + 6 * d * 7168) + (mla + experts) + table)
    # the whole cut at width 1024: the matmul weights a token meets (the
    # six attention layers, the dense layer, 5 x 1.5 experts and routers,
    # the join, the table twice) plus attention over half the width
    met = 6 * mla_w + 3 * d * 7168 + 5 * (d * 256 + 1.5 * 3 * d * 768) \
        + 2 * d * d + 2 * d * 16160
    forward = costs.train_step_flops(whole, 0, 1, 0, 1024) / 3
    assert forward == 2 * met + 6 * h * 640 * 512
    assert 314e6 < met < 315e6                    # 314.7 M a token
    # kernels, by the accepted metric's cost function on THESE dims: one
    # row of 128 positions, the plan's six `mla` entries (the module's too)
    spec = manifest.load_layer_metric("mla_flash_roofline")
    assert spec["args"]["cost"] \
        == "configs.kimi_linear_costs:mla_attention_train"
    work = [{"rows": 1, "src_width": 128, "trg_width": 128}]
    flops, nbytes = shared.mla_attention_train(work, whole)
    assert flops == 6 * h * 128 * 129 / 2 * (8 * 192 + 6 * 128)
    assert nbytes == 6 * 2 * h * 128 * (6 * 192 + 5 * 128)
    stack = dict(whole, layer_plan=whole["layer_plan"][:5])
    assert shared.mla_attention_train(work, stack)[0] * 6 == flops * 5


def test_the_counter_reader_reads_the_modules_cost_or_nothing():
    from marian_tpu.obs import TRACER
    reader = manifest.load_reader("program_counters")
    # no metric file: a cost is no layer's speed. An operator's pair
    args = {"num": "mtp.ce_sum", "den": "mtp.labels"}
    traced = {"trace": {"window_s": 1.0}}
    TRACER.reset()
    assert reader.read(traced, args) is None         # nothing counted
    assert reader.read({}, args) is None             # not a traced run
    with TRACER._lock:
        # a program without the module keeps no such counters
        TRACER._counters = {"moe.assignments": 4000.0, "moe.dropped": 0.0}
    try:
        assert reader.read(traced, args) is None
        with TRACER._lock:
            TRACER._counters.update({"mtp.ce_sum": 9690.0,
                                     "mtp.labels": 1000.0})
        assert reader.read(traced, args) == 9.69
    finally:
        TRACER.reset()
    spec = manifest.load_layer_metric("mla_flash_roofline")
    assert spec["reader"] == "trace_kernel_roofline"
    # nothing to read without a trace: no number, no error
    roofline = manifest.load_reader(spec["reader"])
    assert roofline.read({}, spec["args"]) is None


def test_the_unchanged_driver_rehearses_the_configuration():
    r = _rehearse("joyai-flash.train-docs-short", 1, trace=1,
                  root=SHORT_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
    short = manifest.load_traffic("train-docs-short", SHORT_ROOT)
    full = manifest.load_traffic("train-docs8k-joyai")
    docs = manifest.load_traffic("train-docs8k")
    for key in ("kind", "mini_batch_words_per_chip", "sync_every"):
        assert short[key] == full[key]
    # the documents, the batch and the window are the other plan's cell's
    for key in ("kind", "lengths", "mini_batch_words_per_chip",
                "trainer_flags", "sync_every", "lines_per_second"):
        assert full[key] == docs[key], key
    for key in ("cost_rtol", "token_rtol"):
        assert short["reference_check"][key] \
            == full["reference_check"][key]
    with open(os.path.join(SHORT_ROOT, "BENCHMARK.json")) as fh:
        assert json.load(fh)["configs"][0]["file"] \
            == "benchmark/configs/joyai-llm-flash.json"
