"""cli/profile_summary.py reads what the profiler writes (*.xplane.pb):
the recorded TPU trace the repo holds, and a CPU trace of the trainer's
loop taken here. The scope of an op is found IN the trace: on a TPU as the
op metadata's `tf_op`, on the CPU through the HLO protos of the
/host:metadata plane."""

import os
import subprocess
import sys

import jax
import pytest

from marian_tpu.cli import profile_summary as ps

from tests.test_trainer_spans import build_loop, run_epoch
from tests.time_limit import time_limit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "perf_harness", "fixture.xplane.pb")


@time_limit(60)
def test_tpu_fixture_prints_ops_and_busy_share(tmp_path):
    """The operator's command, on the TPU trace PR 23 recorded (three
    steps of a matmul chain with one Pallas kernel, 30 ms sleeps)."""
    r = subprocess.run(
        [sys.executable, "-m", "marian_tpu.cli.profile_summary",
         os.path.dirname(FIXTURE), "5"],
        capture_output=True, text=True, timeout=50, cwd=ROOT,
        env=dict(os.environ, PYTHONPATH=ROOT))
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "device busy" in out and "-> idle 99." in out
    assert "top device ops by self time" in out
    for op in ("fusion", "convolution_tanh_fusion", "fixture_kernel"):
        assert op in out
    assert "device time by scope" in out
    # the host was asleep while the device idled, and the summary says so
    gaps = out.split("idle gaps of device 0")[1]
    assert "bench.sleep" in gaps.splitlines()[1]
    empty = subprocess.run(
        [sys.executable, "-m", "marian_tpu.cli.profile_summary",
         str(tmp_path)], capture_output=True, text=True, timeout=50,
        cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    assert empty.returncode == 1 and "no *.xplane.pb" in empty.stderr


def test_tpu_fixture_metadata_carries_the_name_stack():
    planes = ps.read_xspace(FIXTURE)
    (ops,) = ps.device_ops(planes)
    stacks = {op: stack for _s, _e, (op, stack) in ops}
    assert stacks["fixture_kernel"] == "jit(step)/fixture_kernel/pallas_call:"
    assert stacks["fusion"] == "jit(step)/dot_general:"


@pytest.mark.parametrize("stack, want", [
    ("jit(one_update)/grads/transpose(jvp(decoder))/ffn/dot_general:",
     ("grads bwd", "decoder/ffn")),
    ("jit(one_update)/grads/jvp(encoder)/self_attn/"
     "packed_attention_fwd/pallas_call", ("grads fwd", "encoder/self_attn")),
    ("transpose(jvp(decoder))/pre_post/reduce_sum",      # shard_map body
     ("grads bwd", "decoder/pre_post")),
    ("jit(one_update)/grads/jvp(loss)/fused_ce_fwd/pallas_call",
     ("grads fwd", "loss")),
    ("jit(one_update)/grads/jvp(cast)/convert_element_type",
     ("grads fwd", "cast")),
    ("jit(one_update)/optimizer/adam/mul", ("optimizer", "adam")),
    ("jit(one_update)/optimizer/clip/jit(global_norm)/reduce_sum",
     ("optimizer", "clip")),
    ("collectives/reduce_scatter", ("collectives", "-")),
    ("jit(one_update)/expand_batch/convert_element_type",
     ("expand_batch", "-")),
    ("jit(one_update)/grads/transpose(grads)/jvp(decoder)/mul",
     ("grads bwd", "decoder")),
    ("jit(step)/dot_general:", ("other", "-")),
    ("", ("other", "-")),
    # a layer plan's window and global attention, and the gate in either
    ("jit(one_update)/grads/jvp(checkpoint)/swa/flash_attention_fwd/"
     "pallas_call", ("grads fwd", "swa")),
    ("jit(one_update)/grads/transpose(jvp(checkpoint))/swa/swa.rope/mul",
     ("grads bwd", "swa.rope")),
    ("jit(one_update)/grads/jvp(checkpoint)/gqa/attn.gate/dot_general",
     ("grads fwd", "attn.gate")),
])
def test_scope_of_a_name_stack(stack, want):
    assert ps.scope_of(stack) == want


@pytest.fixture
def empty_compile_cache(tmp_path, monkeypatch):
    """On the CPU an executable loaded from the persistent compile cache
    carries no HLO metadata, so every op of it reads `other`: whatever an
    earlier test of this process enabled, the step traced below compiles
    against a cache directory of its own, which is empty."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    empty = str(tmp_path / "xla_cache")
    os.makedirs(empty)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", empty)
    jax.config.update("jax_compilation_cache_dir", empty)
    cc.reset_cache()
    yield empty
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


@time_limit(240)
def test_cpu_trace_of_the_trainer_prints_by_scope(tmp_corpus, tmp_path,
                                                  capsys,
                                                  empty_compile_cache):
    parts = build_loop(tmp_corpus, tmp_path)
    run_epoch(*parts)
    trace_dir = str(tmp_path / "prof")
    popts = jax.profiler.ProfileOptions()
    popts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=popts)
    try:
        run_epoch(*parts)
    finally:
        jax.profiler.stop_trace()
    assert ps.summarize(trace_dir, 40) == 0
    out = capsys.readouterr().out
    scope = out.split("device time by scope")[1].split("host spans")[0]
    for first in ("grads fwd", "grads bwd", "optimizer"):
        assert first in scope, scope
    for second in ("encoder/ffn", "decoder/cross_attn", "adam", "loss"):
        assert second in scope, scope
    other = [l for l in scope.splitlines() if l.endswith("  other")]
    assert not other or float(other[0].split("ms")[1].split("%")[0]) < 15
    host = out.split("host spans")[1].split("idle gaps")[0]
    for span in ("train.dispatch", "train.bookkeep", "train.sync",
                 "train.h2d", "data.wait"):
        assert span in host
    assert "by innermost program span" in out
