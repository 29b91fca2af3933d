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


# -- the memory ledger (ISSUE 38) ---------------------------------------------

FIXED_MEMORY = {"limit": 16_000, "in_use": 10_000, "peak": 12_000,
                "reserved": 5_000, "largest_free": 4_000}


@pytest.fixture
def memory(monkeypatch):
    """The allocator's word as a test gives it; counts the calls."""
    from marian_tpu.training import hbm
    box = {"answer": dict(FIXED_MEMORY), "calls": 0}

    def device_memory():
        box["calls"] += 1
        if isinstance(box["answer"], Exception):
            raise box["answer"]
        return box["answer"]
    monkeypatch.setattr(hbm, "device_memory", device_memory)
    return box


@pytest.fixture
def log_lines():
    """(level, message) of every line the program logs."""
    import logging
    from marian_tpu.common import logging as mlog
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append((record.levelname, record.getMessage()))
    logger, handler = mlog._get("general"), Keep()
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


@time_limit(120)
def test_live_spans_carry_the_allocators_word(tmp_corpus, tmp_path, memory):
    """Spans on: every dispatch says which program it runs and what was
    free before it, the gauges keep the least, and the display's sync
    leaves the drained set."""
    parts = build_loop(tmp_corpus, tmp_path)
    obs.TRACER.enable()
    n = run_epoch(*parts)
    spans, _ = obs.TRACER.snapshot()
    dispatches = [s for s in spans if s.name == "train.dispatch"]
    assert len(dispatches) == n
    for s in dispatches:
        assert re.fullmatch(r"\d+x\d+(\+\d+x\d+)*", s.attrs["program"])
        assert s.attrs["free_before"] == 6_000
        assert "retraced" in s.attrs and "step" in s.attrs
    g = obs.TRACER.gauges()
    assert g["hbm.free"] == {"last": 6_000, "min": 6_000, "max": 6_000,
                             "n": n}
    assert g["hbm.largest_free"]["last"] == 4_000
    assert g["hbm.reserved"]["last"] == 5_000
    for name, want in (("hbm.in_use_drained", 10_000), ("hbm.peak", 12_000)):
        assert g[name]["last"] == want and g[name]["n"] == n // 2
    # the limit: once by the first dispatch of the stretch, and at every
    # display's sync
    assert g["hbm.limit"] == {"last": 16_000, "min": 16_000, "max": 16_000,
                              "n": 1 + n // 2}
    gg = parts[2]
    # what ONE device holds: a leaf sharded over the mesh costs a shard
    state = sum(int(a.addressable_shards[0].data.nbytes)
                for a in jax.tree_util.tree_leaves((gg.params,
                                                    gg.opt_state)))
    assert g["hbm.state"]["last"] == gg._state_bytes == state > 0
    assert g["hbm.state"]["n"] == 1         # what does not change: once
    # the jitted step without --precompile-buckets holds no executable,
    # so no temporaries to hold against what is free
    assert not {"hbm.programs_code", "hbm.step_temp_max",
                "hbm.headroom"} & set(g)
    assert memory["calls"] == n + n // 2


@time_limit(120)
def test_the_ledger_is_written_once_a_stretch(tmp_corpus, tmp_path, memory):
    """The sums that do not change between dispatches are gauged by the
    first dispatch after the gauges were cleared, with no display tick
    needed for the divisor, and again when the ledger has changed."""
    parts = build_loop(tmp_corpus, tmp_path)
    gg = parts[2]
    gg._programs = {"k": {"name": "4x16", "code": 300, "temp": 5_500}}
    arrays = batch_to_arrays(next(iter(BatchGenerator(*parts[:2]))))
    obs.TRACER.enable()
    for step in (1, 2, 3):
        gg.update(arrays, step, jax.random.key(0))
    g = obs.TRACER.gauges()
    assert g["hbm.free"]["n"] == g["hbm.headroom"]["n"] == 3
    assert g["hbm.headroom"]["min"] == 6_000 - 5_500
    for name, want in (("hbm.limit", 16_000), ("hbm.state", gg._state_bytes),
                       ("hbm.programs_code", 300),
                       ("hbm.step_temp_max", 5_500)):
        assert g[name] == {"last": want, "min": want, "max": want, "n": 1}
    gg._programs["l"] = {"name": "2x32", "code": 200, "temp": 5_900}
    memory["answer"] = dict(FIXED_MEMORY, in_use=10_050)
    gg.update(arrays, 4, jax.random.key(0))
    g = obs.TRACER.gauges()
    assert g["hbm.programs_code"] == {"last": 500, "min": 300, "max": 500,
                                      "n": 2}
    assert g["hbm.headroom"]["min"] == 5_950 - 5_900
    obs.TRACER.disable()                    # clears the gauges
    obs.TRACER.enable()
    gg.update(arrays, 5, jax.random.key(0))
    g = obs.TRACER.gauges()
    assert g["hbm.state"]["n"] == g["hbm.limit"]["n"] == g["hbm.free"]["n"] == 1
    assert g["hbm.step_temp_max"]["last"] == 5_900


@time_limit(120)
def test_the_least_free_is_kept(tmp_corpus, tmp_path, memory):
    parts = build_loop(tmp_corpus, tmp_path)
    obs.TRACER.enable()
    run_epoch(*parts)
    memory["answer"] = dict(FIXED_MEMORY, in_use=15_000)
    run_epoch(*parts)
    memory["answer"] = dict(FIXED_MEMORY)
    run_epoch(*parts)
    free = obs.TRACER.gauges()["hbm.free"]
    assert (free["min"], free["max"], free["last"]) == (1_000, 6_000, 6_000)


@time_limit(120)
def test_no_statistics_no_attribute_no_gauge(tmp_corpus, tmp_path, memory):
    """The CPU's own answer: nothing is recorded and nothing fails."""
    memory["answer"] = None
    parts = build_loop(tmp_corpus, tmp_path)
    obs.TRACER.enable()
    n = run_epoch(*parts)
    spans, _ = obs.TRACER.snapshot()
    dispatches = [s for s in spans if s.name == "train.dispatch"]
    assert len(dispatches) == n
    assert all(set(s.attrs) == {"step", "retraced"} for s in dispatches)
    assert obs.TRACER.gauges() == {}
    assert memory["calls"] == n + n // 2


@time_limit(120)
def test_a_partial_answer_keeps_what_it_holds(tmp_corpus, tmp_path, memory):
    """A runtime that gives no limit: no free bytes, the rest stays."""
    memory["answer"] = {"in_use": 5, "peak": 7}
    parts = build_loop(tmp_corpus, tmp_path)
    obs.TRACER.enable()
    run_epoch(*parts)
    spans, _ = obs.TRACER.snapshot()
    first = [s for s in spans if s.name == "train.dispatch"][0]
    assert "program" in first.attrs and "free_before" not in first.attrs
    g = obs.TRACER.gauges()
    assert sorted(g) == ["hbm.in_use_drained", "hbm.peak", "hbm.state"]


@time_limit(120)
def test_spans_off_never_ask_the_allocator(tmp_corpus, tmp_path, memory):
    """With spans off _dispatch and _display make no call they did not
    make: the helper is called zero times."""
    parts = build_loop(tmp_corpus, tmp_path)
    assert run_epoch(*parts) > 0
    assert memory["calls"] == 0
    assert obs.TRACER.gauges() == {}


@time_limit(180)
def test_programs_compiled_ahead_enter_the_ledger(memory, log_lines):
    """--precompile-buckets: one entry a compiled shape, the compiler's
    integers; one line when the last compile is done, tracing off; with
    spans on the sums are gauges."""
    from tests.test_compile_ahead import _bucket_updates
    gg, _ = _bucket_updates(2)
    assert sorted(gg._programs) == sorted(gg._ahead)
    assert sorted(p["name"] for p in gg._programs.values()) == [
        "1x48", "2x32", "4x16"]
    for p in gg._programs.values():
        assert all(isinstance(p[f], int) and p[f] >= 0
                   for f in ("code", "temp", "args", "out", "alias"))
        assert p["args"] > 0 and p["temp"] > 0
    lines = [m for level, m in log_lines if m.startswith("HBM ")]
    assert len(lines) == 1 and [lv for lv, m in log_lines
                                if m.startswith("HBM ")] == ["INFO"]
    assert re.fullmatch(
        r"HBM 0\.0 MB: state \d+\.\d MB, 3 step programs \d+\.\d MB "
        r"\(largest \d+\.\d MB, \d+x\d+\), widest step's temporaries "
        r"\d+\.\d MB \(\d+x\d+\), in use now 0\.0 MB, reserved 0\.0 MB, "
        r"largest free block 0\.0 MB, headroom \(free less those "
        r"temporaries\) -?\d+\.\d MB", lines[0]), lines[0]
    assert obs.TRACER.gauges() == {}            # spans were off
    obs.TRACER.enable()
    batch = {"src_tok": jax.numpy.zeros((4, 16), "uint16"),
             "src_len": jax.numpy.full((4,), 9, "int32"),
             "trg_tok": jax.numpy.zeros((4, 16), "uint16"),
             "trg_len": jax.numpy.full((4,), 9, "int32")}
    gg.update(batch, 9, jax.random.key(1))
    g = obs.TRACER.gauges()
    held = list(gg._programs.values())
    assert g["hbm.programs_code"]["last"] == sum(p["code"] for p in held)
    assert g["hbm.step_temp_max"]["last"] == max(p["temp"] for p in held)
    assert g["hbm.headroom"]["min"] == 6_000 - max(p["temp"] for p in held)
    spans, _ = obs.TRACER.snapshot()
    assert [s.attrs["program"] for s in spans
            if s.name == "train.dispatch"] == ["4x16"]


@time_limit(60)
def test_the_ledgers_line_without_statistics(tmp_corpus, tmp_path):
    """On the CPU the allocator's part reads `?`; the jitted path says
    that it holds no executable."""
    gg = build_loop(tmp_corpus, tmp_path)[2]
    line = gg._memory_line(None)
    assert re.fullmatch(
        r"HBM \?: state \d+\.\d MB, no step program compiled ahead, in use "
        r"now \?, reserved \?, largest free block \?", line), line
    gg._programs = {
        "a": {"name": "16x1024", "code": 30_000_000, "temp": 900_000_000},
        "b": {"name": "2x8192", "code": 10_000_000, "temp": 2_000_000_000}}
    line = gg._memory_line({"limit": 16_000_000_000, "in_use": 12_345_678})
    assert ("HBM 16000.0 MB: " in line and "2 step programs 40.0 MB "
            "(largest 30.0 MB, 16x1024), widest step's temporaries 2000.0 "
            "MB (2x8192), in use now 12.3 MB, reserved ?, largest free block "
            "?, headroom (free less those temporaries) 13987.7 MB" in line)


@pytest.mark.parametrize("live", [False, True], ids=["spans_off", "spans_on"])
@pytest.mark.parametrize("text, reported", [
    ("RESOURCE_EXHAUSTED: Error loading program 'jit_one_update': "
     "Attempting to allocate 341.28M. That was not possible. There are "
     "329.74M free.; (0x0x0_HBM0)", True),
    ("some other failure of the step", False)],
    ids=["exhausted", "another_error"])
@time_limit(60)
def test_a_step_that_does_not_fit_is_reported_once(
        tmp_corpus, tmp_path, memory, log_lines, live, text, reported):
    """RESOURCE_EXHAUSTED: exactly one `error` line naming the program,
    the ledger and the programs held, and the SAME exception raised;
    any other exception passes unlogged."""
    gg = build_loop(tmp_corpus, tmp_path)[2]
    gg._programs = {"k": {"name": "10x1536", "code": 341_000_000,
                          "temp": 1, "args": 1, "out": 1, "alias": 0}}
    if live:
        obs.TRACER.enable()
    boom = ValueError(text)

    def step(*_args):
        raise boom
    batch = {"trg_tok": np.zeros((10, 1536), np.uint16),
             "trg_len": np.zeros((10,), np.int32)}
    del log_lines[:]
    with pytest.raises(ValueError) as caught:
        gg._dispatch(step, 52, batch, batch=batch)
    assert caught.value is boom
    errors = [m for level, m in log_lines if level == "ERROR"]
    assert len(errors) == (1 if reported else 0)
    assert not [m for level, m in log_lines if level != "ERROR"]
    if reported:
        assert "\n" not in errors[0]
        assert "Update 52: step program 10x1536 does not fit" in errors[0]
        assert "HBM 0.0 MB: state " in errors[0]
        assert "in use now 0.0 MB" in errors[0]
        assert "headroom (free less those temporaries) 0.0 MB; " in errors[0]
        assert errors[0].endswith("step programs held: 10x1536 341.0 MB")
    if live:
        spans, _ = obs.TRACER.snapshot()
        assert spans[-1].name == "train.dispatch"
        assert "error" in spans[-1].attrs


@pytest.mark.parametrize("broken", ["allocator", "batch"])
@time_limit(60)
def test_a_report_that_fails_leaves_the_exception_alone(
        tmp_corpus, tmp_path, memory, log_lines, broken):
    """The report asks a client that has just failed an allocation: what
    it raises is logged in its place, and the caller still gets the
    exception the step raised, not the report's."""
    gg = build_loop(tmp_corpus, tmp_path)[2]
    batch = {"trg_tok": np.zeros((10, 1536), np.uint16)}
    if broken == "allocator":
        memory["answer"] = RuntimeError("the client is gone")
    else:
        batch = {"trg_tok": object()}           # no `.ndim` to name it by
    boom = ValueError("RESOURCE_EXHAUSTED: Error loading program")

    def step(*_args):
        raise boom
    del log_lines[:]
    with pytest.raises(ValueError) as caught:
        gg._dispatch(step, 7, batch, batch=batch)
    assert caught.value is boom and boom.__context__ is None
    assert [level for level, _ in log_lines] == ["ERROR"]
    assert log_lines[0][1].startswith(
        "Update 7: a step program does not fit the device (no memory "
        "report: ")


@time_limit(120)
def test_a_cancelled_compile_never_reports_on_the_update_path(
        monkeypatch, memory, log_lines):
    """`_step_for` cancels a compile still queued when its batch comes:
    that future is done on the UPDATE path. It is counted, and where it
    is the last the ledger's line, with its call to the allocator, is
    left out and not made there."""
    from concurrent.futures import Future
    from marian_tpu.training import graph_group
    from tests.test_compile_ahead import _bucket_updates

    class NeverStarts:
        """A pool whose every compile is still queued."""
        def __init__(self, *_args, **_kwargs):
            pass

        def submit(self, *_args):
            return Future()

        def shutdown(self, wait=True):
            pass
    monkeypatch.setattr(graph_group, "ThreadPoolExecutor", NeverStarts)
    gg, costs = _bucket_updates(2, widths=(16, 32, 48))
    assert len(costs) == 3 and gg._ahead == {} and gg._programs == {}
    assert any(m.startswith("Compiling the train step ahead for 3 shapes")
               for _, m in log_lines)
    assert not [m for _, m in log_lines if m.startswith("HBM ")]
    assert memory["calls"] == 0
