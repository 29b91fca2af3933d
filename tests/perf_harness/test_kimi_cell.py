"""The benchmark's second configuration, `kimi-linear-48b-a3b`, and its cell
`kimi-linear.train-docs8k`: the manifest is sound with them; the
configuration's file keeps every number of its source and declares its
cuts; the cost functions give hand-worked numbers; the counter reader reads
what the program keeps and nothing where it keeps none; and the UNCHANGED
train driver rehearses the configuration to `correct=True`.

The rehearsal uses the benchmark's own configuration file under a traffic
mix of short documents kept here (`kimi_cell/`, found through `--root`):
documents of up to 8191 tokens take a quarter of an hour on the CPU (PR 28
ran that once: PERF.md).

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
from benchmark.configs import kimi_linear_costs as costs  # noqa: E402
from test_harness import BENCH, _rehearse  # noqa: E402

CONFIG, CELL = "kimi-linear-48b-a3b", "kimi-linear.train-docs8k"
SHORT_ROOT = os.path.join(ROOT, "tests", "perf_harness", "kimi_cell")
OWN_METRICS = ("kda_roofline", "mla_flash_roofline",
               "expert_load_max_over_mean.train", "routed_here_share.train",
               "moe_dropped.train")
# the catalog row of the source (model-configs guide), its `config`
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "model_max_length": 1048576, "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "routed_scaling_factor": 2.446, "topk_group": 1,
    "v_head_dim": 128, "vocab_size": 163840}


def test_the_manifest_is_sound_with_the_new_configuration_and_cell():
    assert manifest.validate(BENCH) == []
    assert manifest.validate(root=SHORT_ROOT) == []
    cell = manifest.Cell(BENCH, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.kind) \
        == (1, CONFIG, "train-docs8k", "train")
    reported = {m["name"] for m in cell.per_layer}
    assert set(OWN_METRICS) <= reported
    assert "packed_attention_roofline" not in reported
    assert {m["name"] for m in cell.end_to_end} \
        == {"setup_s", "train_tok_s_chip"}
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"].endswith("/config.json") \
        and len(entry["source"]) < 200
    big = manifest.Cell(BENCH, "big.train")       # the old cell as it was
    assert not set(OWN_METRICS) & {m["name"] for m in big.per_layer}


def test_the_file_keeps_the_source_and_declares_its_cuts():
    body = manifest.load_config(CONFIG)
    cut = {"num_hidden_layers": 5, "num_experts": 8}
    for key, value in PUBLISHED.items():
        assert body[key] == cut.get(key, value), key
    assert sorted(body["reduced"]) == ["num_experts", "num_hidden_layers",
                                       "vocab"]
    assert body["published"] == {"num_hidden_layers": 27,
                                 "num_experts": 256, "vocab": 163840}
    assert body["vocab"] == 20480 == body["vocab_size"] // 8
    assert body["deployment"]["chips"] == 32
    assert body["router_width"] == 256            # no width is cut
    plan = body["layer_plan"]
    assert len(plan) == body["num_hidden_layers"]
    assert plan[0] == "kda:dense" and plan.count("mla:experts") == 1 \
        and plan.count("kda:experts") == 3        # one whole 3 : 1 period
    assert body["streams"] == 1 and body["kernels"] == [
        "kda_chunk", "flash_attention", "fused_ce"]
    flags = body["task_flags"]
    assert "--gradient-checkpointing" in flags
    assert flags[flags.index("--precision") + 1:][:2] == ["bfloat16",
                                                          "float32"]
    assert not any("smooth" in f and flags[i + 1] != "0"
                   for i, f in enumerate(flags[:-1]))
    for key in body["rehearse"]["dims"]:
        assert key in body


def test_cost_functions_against_hand_worked_cases():
    dims = dict(manifest.load_config(CONFIG),
                layer_plan=["kda:dense", "mla:experts"])
    # one KDA layer, one MLA layer, one dense and one expert feed-forward
    d, h = 2304, 32
    kda_w = 4 * d * h * 128 + 2 * (d * 128 + 128 * h * 128) + d * h \
        + 3 * 4 * h * 128
    kda = 2 * kda_w + h * (2 * 64 * 128 + 64 * 256 + 6 * 128 * 128
                           + 64 * 128)
    mla_w = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    mla = 2 * mla_w + h * (2 * 192 + 2 * 128) * 1024 / 2
    experts = 2 * d * 256 + (8 * 8 / 256 + 1) * 6 * d * 1024
    per_token = kda + mla + 6 * d * 9216 + experts + 2 * d * 20480
    assert costs.train_step_flops(dims, 0, 1000, 0, 1024) \
        == 3.0 * 1000 * per_token
    # the whole cut: about 336 M matmul parameters a token (ISSUE 28)
    whole = manifest.load_config(CONFIG)
    forward = costs.train_step_flops(whole, 0, 1, 0, 1024) / 3
    assert 2 * 336e6 < forward < 2 * 336e6 + 4 * 4.5e6 + 11e6 + 5e6
    # kernels: one row of 128 positions = 2 chunks a head
    work = [{"rows": 1, "src_width": 128, "trg_width": 128}]
    flops, nbytes = costs.kda_train(work, dims)
    chunk = 20 * 64 * 128 * 128 + 3 * 64 * 64 * 128
    terms = 3 * 64 * 128 + 64 * 128 + 128 + 64 * 64
    assert flops == 2 * h * chunk
    assert nbytes == 2 * h * 2 * (3 * terms + 2 * 64 * 128)
    flops, nbytes = costs.mla_attention_train(work, dims)
    assert flops == h * 128 * 129 / 2 * (8 * 192 + 6 * 128)
    assert nbytes == 2 * h * 128 * (6 * 192 + 5 * 128)
    assert costs.kda_train(work, whole)[0] == 4 * 2 * h * chunk


def test_the_counter_reader_reads_the_program_or_nothing():
    from marian_tpu.obs import TRACER
    reader = manifest.load_reader("program_counters")
    share = manifest.load_layer_metric("routed_here_share.train")["args"]
    ratio = manifest.load_layer_metric(
        "expert_load_max_over_mean.train")["args"]
    dropped = manifest.load_layer_metric("moe_dropped.train")["args"]
    traced = {"trace": {"window_s": 1.0}}
    TRACER.reset()
    assert reader.read(traced, share) is None        # nothing counted
    assert reader.read({}, share) is None            # not a traced run
    with TRACER._lock:
        TRACER._counters = {"moe.assignments": 4000.0,
                            "moe.assignments_held": 125.0,
                            "moe.load_max": 30.0, "moe.load_mean": 20.0,
                            "moe.dropped": 0.0}
    try:
        assert reader.read(traced, share) == 3.125
        assert reader.read(traced, ratio) == 1.5
        assert reader.read(traced, dropped) == 0.0
    finally:
        TRACER.reset()


def test_the_unchanged_driver_rehearses_the_configuration():
    r = _rehearse("kimi-linear.train-docs-short", 1, trace=1,
                  root=SHORT_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert "reference check on a" in r.stderr
    short = manifest.load_traffic("train-docs-short", SHORT_ROOT)
    full = manifest.load_traffic("train-docs8k")
    for key in ("kind", "mini_batch_words_per_chip", "sync_every"):
        assert short[key] == full[key]
    with open(os.path.join(SHORT_ROOT, "BENCHMARK.json")) as fh:
        assert json.load(fh)["configs"][0]["file"] \
            == "benchmark/configs/kimi-linear-48b-a3b.json"
