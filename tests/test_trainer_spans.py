"""The trainer's spans live in its layer objects and on the profiler's
clock (ISSUE 24): whoever drives BatchGenerator -> batch_to_arrays ->
GraphGroup.update -> Scheduler.update gets them, train.py's loop, the
benchmark's driver or a test. CPU, tiny sizes; every case has a time
limit of its own."""

import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from marian_tpu import obs
from marian_tpu.common import Options
from marian_tpu.data import Corpus, DefaultVocab
from marian_tpu.data.batch_generator import BatchGenerator
from marian_tpu.models.encoder_decoder import batch_to_arrays, create_model
from marian_tpu.training import GraphGroup, TrainingState
from marian_tpu.training.scheduler import Scheduler

from tests.test_obs import _RaisingLock
from tests.time_limit import time_limit
from tests.test_training import train_options

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAINER_SPANS = ("data.wait", "train.h2d", "train.dispatch",
                 "train.bookkeep", "train.sync")


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.TRACER.reset()
    yield
    obs.TRACER.reset()


def build_loop(tmp_corpus, tmp_path, **over):
    """The trainer's loop objects as train.py wires them, tiny."""
    src, tgt, _ = tmp_corpus
    opts = train_options(tmp_path, src, tgt, **{
        "disp-freq": "2u", "mini-batch": 2, "maxi-batch": 4,
        "shuffle": "none", **over})
    vs = DefaultVocab.build(open(src).read().splitlines())
    vt = DefaultVocab.build(open(tgt).read().splitlines())
    corpus = Corpus([src, tgt], [vs, vt], opts)
    model = create_model(opts, len(vs), len(vt))
    gg = GraphGroup(model, opts)
    gg.initialize(jax.random.key(0))
    state = TrainingState()
    return corpus, opts, gg, Scheduler(opts, state), state


def run_epoch(corpus, opts, gg, scheduler, state, rng=None):
    rng = rng if rng is not None else jax.random.key(9)
    n = 0
    for batch in BatchGenerator(corpus, opts):
        out = gg.update(batch_to_arrays(batch), state.batches + 1, rng)
        scheduler.update(out.loss_sum, batch.words, batch.size,
                         src_words=batch.src_words, skipped=out.skipped)
        n += 1
    jax.block_until_ready(gg.params)
    return n


def host_events(trace_dir):
    """[(line index, name, start_ns, end_ns, stats)] of the /host:CPU
    plane (a line is a thread; every Python thread's is named alike)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    pd = ProfileData.from_file(paths[0])
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((i, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@time_limit(180)
def test_spans_reach_the_profilers_host_plane(tmp_corpus, tmp_path):
    """No --trace, no edit to the caller: a profiler session alone turns
    the spans on, as TraceMe events beside the device ops."""
    parts = build_loop(tmp_corpus, tmp_path)
    run_epoch(*parts)                      # compile outside the session
    assert obs.TRACER.totals() == {}       # nothing was live
    trace_dir = str(tmp_path / "prof")
    popts = jax.profiler.ProfileOptions()
    popts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=popts)
    try:
        n = run_epoch(*parts)
    finally:
        jax.profiler.stop_trace()
    assert not obs.enabled() and obs.TRACER._ring is None   # ring: --trace
    events = host_events(trace_dir)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[1], []).append(ev)
    for name in TRAINER_SPANS + ("data.epoch_prepare", "data.make_batch"):
        assert name in by_name, (name, sorted(by_name)[:40])
    # the update number rides on the spans of one update
    steps = sorted(ev[4]["step"] for ev in by_name["train.dispatch"])
    assert len(steps) == n and steps == list(range(steps[0], steps[0] + n))
    assert all(ev[4]["retraced"] == 0 for ev in by_name["train.dispatch"])
    assert all(ev[4]["bytes"] > 0 for ev in by_name["train.h2d"])
    # train.sync lies inside a train.bookkeep, on the same thread
    for line, _name, s, e, _st in by_name["train.sync"]:
        assert any(l2 == line and s2 <= s and e <= e2
                   for l2, _n, s2, e2, _ in by_name["train.bookkeep"])
    # loader spans are the prefetch thread's, trainer spans the caller's
    trainer = {ev[0] for name in TRAINER_SPANS for ev in by_name[name]}
    loader = {ev[0] for ev in by_name["data.make_batch"]}
    assert len(trainer) == 1 and not trainer & loader

    totals = obs.TRACER.totals()
    assert totals["train.dispatch"]["calls"] == n
    assert totals["train.bookkeep"]["calls"] == n
    assert totals["train.sync"]["calls"] == n // 2       # --disp-freq 2u
    # self seconds add up to the parents' durations: bookkeep's self time
    # is its duration less the sync inside it; a leaf's is its duration
    bk, sy = totals["train.bookkeep"], totals["train.sync"]
    assert bk["self_seconds"] == pytest.approx(
        bk["seconds"] - sy["seconds"], abs=1e-6)
    assert sy["self_seconds"] == pytest.approx(sy["seconds"], abs=1e-9)
    ep, mb = totals["data.epoch_prepare"], totals["data.make_batch"]
    assert ep["self_seconds"] == pytest.approx(
        ep["seconds"] - mb["seconds"], abs=1e-6)
    assert ep["thread"] == "batchgen-prefetch" != bk["thread"]


@time_limit(120)
def test_no_session_no_tracer_costs_two_flag_reads(tmp_corpus, tmp_path):
    """The zero-overhead guard on the trainer's path: no totals, no ring,
    Tracer._lock never taken."""
    parts = build_loop(tmp_corpus, tmp_path)
    saved = obs.TRACER._lock
    obs.TRACER._lock = _RaisingLock()
    try:
        assert run_epoch(*parts) > 0
        with obs.span("x", a=1) as sp:
            assert sp is obs.NOOP_SPAN
    finally:
        obs.TRACER._lock = saved
    assert obs.TRACER._ring is None and obs.TRACER._events is None
    assert obs.TRACER._totals is None and obs.TRACER.totals() == {}


@time_limit(120)
def test_tracer_alone_fills_ring_and_totals(tmp_corpus, tmp_path):
    """--trace without a profiler session: the ring (for /tracez) and the
    totals, no TraceMe."""
    parts = build_loop(tmp_corpus, tmp_path)
    obs.TRACER.enable()
    n = run_epoch(*parts)
    spans, _ = obs.TRACER.snapshot()
    names = {s.name for s in spans}
    assert set(TRAINER_SPANS) <= names
    assert all(s._ann is None for s in spans)
    sync = [s for s in spans if s.name == "train.sync"][0]
    parent = [s for s in spans if s.span_id == sync.parent_id][0]
    assert parent.name == "train.bookkeep"
    assert obs.TRACER.totals()["train.dispatch"]["calls"] == n
    first = [s for s in spans if s.name == "train.dispatch"][0]
    assert first.attrs["retraced"] == 1 and first.attrs["step"] == 1
    obs.TRACER.reset()
    assert obs.TRACER.totals() == {}


@time_limit(60)
def test_trace_module_never_imports_jax():
    """obs/trace.py (and the lockdep it builds its lock with) is stdlib
    only, and a live span in a process without jax imports none. The
    package __init__s are kept out of the way: marian_tpu.common's pulls
    in jax through common/prng.py, which is not this module's doing."""
    code = textwrap.dedent(f"""
        import sys, types
        for name, sub in (("marian_tpu", ""), ("marian_tpu.common", "common"),
                          ("marian_tpu.obs", "obs")):
            mod = types.ModuleType(name)
            mod.__path__ = [{os.path.join(ROOT, "marian_tpu")!r} + "/" + sub]
            sys.modules[name] = mod
        from marian_tpu.obs import trace
        assert not trace.profiler_collecting()
        with trace.span("off") as sp:
            assert sp is trace.NOOP_SPAN
        trace.TRACER.enable()
        with trace.span("on", k=1) as sp:
            assert sp and sp._ann is None
        assert trace.TRACER.totals()["on"]["calls"] == 1
        bad = [m for m in sys.modules if m == "jax" or m.startswith("jax.")]
        assert not bad, bad
        print("ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=50)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr


SCOPES = ("optimizer", "grads", "encoder", "decoder", "ffn", "loss",
          "self_attn", "cross_attn", "embed", "expand_batch", "adam")


def _one_step(tmp_corpus, tmp_path):
    """(lowered text, cost, gradient norm) of one update on a fixed
    seeded batch, from freshly built step functions."""
    corpus, opts, gg, _sch, _st = build_loop(tmp_corpus, tmp_path)
    batch = next(iter(BatchGenerator(corpus, opts, prefetch=False)))
    from marian_tpu.parallel import mesh as M
    arrays = M.shard_batch(batch_to_arrays(batch, compact=True), gg.mesh)
    rng = jax.random.key(9)
    text = gg._fused.lower(gg.params, gg.opt_state, arrays, np.int32(1),
                           rng).as_text(debug_info=True)
    out = gg.update(arrays, 1, rng)
    return text, np.asarray(out.loss_sum), np.asarray(out.grad_norm)


@time_limit(240)
def test_step_scopes_are_metadata_only(tmp_corpus, tmp_path, monkeypatch):
    """The lowered step names its parts, and cost and gradient norm equal,
    bit for bit, those of the step built with jax.named_scope a no-op."""
    text, cost, gnorm = _one_step(tmp_corpus, tmp_path)
    # a scope is a component of an op's name stack, bare or as autodiff
    # wraps it: grads/jvp(encoder)/ffn/.. forward,
    # grads/transpose(jvp(encoder))/ffn/.. backward (on a mesh of
    # several devices the shard_map body's stack starts at jvp(..))
    for scope in SCOPES:
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    assert "jvp(encoder)/" in text and "transpose(jvp(encoder))/" in text
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    plain_text, plain_cost, plain_gnorm = _one_step(tmp_corpus, tmp_path)
    assert not re.search(r'[/("](optimizer|grads|ffn)[/)"]', plain_text)
    assert cost.tobytes() == plain_cost.tobytes()
    assert gnorm.tobytes() == plain_gnorm.tobytes()


@time_limit(60)
def test_profile_window_holds_spans_and_no_python_calls(tmp_path):
    """marian-train --profile: the window's trace carries the program's
    spans; the Python tracer stays off (an event per call would nest in
    every span and eat its self time)."""
    from marian_tpu.obs.profiling import TraceWindow
    trace_dir = str(tmp_path / "prof")
    win = TraceWindow(Options({"profile": trace_dir, "profile-start": 3,
                               "profile-updates": 2}))
    for update in range(1, 7):
        win.tick(update)
        with obs.span("train.dispatch", step=update):
            sum(range(1000))
    win.close()
    events = host_events(trace_dir)
    steps = sorted(ev[4]["step"] for ev in events
                   if ev[1] == "train.dispatch")
    assert steps == [3, 4]
    assert not [ev[1] for ev in events if ev[1].startswith("$")]
    assert obs.TRACER.totals()["train.dispatch"]["calls"] == 2
