"""Observability layer (marian_tpu/obs/ — ISSUE 8): span tracer, event
timeline, /tracez export, flight recorder, reply-metadata protocol,
histogram exemplars, StepTimer honesty. Everything runs under
JAX_PLATFORMS=cpu with stub translate functions.

The acceptance-critical properties covered tier-1:
- span-tree integrity through a REAL scheduler batch (parent/child
  edges + model_version tags);
- /tracez round-trips into a Perfetto-valid Chrome trace JSON document;
- an injected MARIAN_FAULTS watchdog trip and a canary auto-rollback
  each produce a flight-recorder dump holding the victim's full
  ingest→dispatch→failure span tree;
- tracer off ⇒ no ring allocation and no lock acquisition on the
  scheduler's per-batch hot path (the zero-overhead contract).
"""

import asyncio
import json
import os
import threading
import time
import urllib.request

import pytest

from marian_tpu import obs
from marian_tpu.common import Options
from marian_tpu.common import faultpoints as fp
from marian_tpu.obs.trace import NOOP_SPAN, Tracer
from marian_tpu.serving import metrics as msm
from marian_tpu.serving.lifecycle import SwapController
from marian_tpu.serving.scheduler import ContinuousScheduler, DispatchStalled
from marian_tpu.server.server import ServingApp, split_trace_header
from marian_tpu.training import bundle as bdl


@pytest.fixture(scope="module", autouse=True)
def _lockdep_witness(lockdep_witness):
    """The tracer adds Tracer._lock / FlightRecorder._lock (and the
    SwapController._lock -> Tracer._lock edge on the promote path) to
    the running lattice; the shared conftest witness asserts at teardown
    that the static graph models everything observed here."""
    yield


@pytest.fixture(autouse=True)
def _reset_obs():
    yield
    obs.TRACER.reset()
    obs.FLIGHT.disarm()
    obs.PERF.reset()
    fp.reset_for_tests()


class _RaisingLock:
    """Proof object for the zero-overhead contract: acquiring it fails
    the test, so any lock touch on a supposedly lock-free path is loud."""

    def __enter__(self):
        raise AssertionError("lock acquired on the disabled-tracer path")

    def __exit__(self, *exc):
        pass

    def acquire(self, *a, **kw):
        raise AssertionError("lock acquired on the disabled-tracer path")

    def release(self):
        pass


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

class TestTracerCore:
    def test_disabled_no_ring_no_lock_no_spans(self):
        t = Tracer()
        assert t._ring is None and t._events is None
        t._lock = _RaisingLock()     # any acquisition now fails the test
        sp = t.start_span("x", a=1)
        assert sp is NOOP_SPAN
        t.end(sp)
        t.event("e", k=1)
        t.record("r", 0.0, 1.0)
        with t.span("y") as sp2:
            assert sp2 is NOOP_SPAN
            t.set_attrs(z=1)         # no-op, no allocation
        t.gauge("g", 1)
        assert t._ring is None and t._events is None
        assert t._gauges is None

    def test_enable_records_parent_child_tree(self):
        t = Tracer()
        t.enable()
        with t.span("root", trace_id="t1") as root:
            with t.span("child") as child:
                assert child.trace_id == "t1"
                assert child.parent_id == root.span_id
            t.event("mark", k=3)
        spans, events = t.snapshot()
        assert [s.name for s in spans] == ["child", "root"]  # end order
        assert events[0]["name"] == "mark"
        assert events[0]["trace_id"] == "t1"   # inherited from context

    def test_explicit_parent_crosses_threads(self):
        t = Tracer()
        t.enable()
        root = t.start_span("root")
        child = t.start_span("c", parent=root)
        t.end(child)
        t.end(root)
        assert child.parent_id == root.span_id
        assert child.trace_id == root.trace_id

    def test_ring_bounded(self):
        t = Tracer(capacity=4)
        t.enable()
        for i in range(10):
            with t.span(f"s{i}"):
                pass
        spans, _ = t.snapshot()
        assert len(spans) == 4
        assert spans[-1].name == "s9"        # newest kept

    def test_end_idempotent_and_error_attr(self):
        t = Tracer()
        t.enable()
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        spans, _ = t.snapshot()
        assert spans[0].attrs["error"] == "RuntimeError('x')"
        t.end(spans[0], late=True)           # second end: no-op
        assert "late" not in spans[0].attrs

    def test_chrome_trace_is_perfetto_valid(self):
        t = Tracer()
        t.enable()
        with t.span("a", k="v"):
            t.event("inst")
        doc = t.chrome_trace()
        # the Perfetto/chrome://tracing contract: JSON object with a
        # traceEvents list of {name, ph, ts, pid, tid}; "X" complete
        # events carry dur, "i" instants carry scope
        assert isinstance(doc["traceEvents"], list)
        text = json.dumps(doc)               # must serialize
        assert json.loads(text)["traceEvents"]
        phases = set()
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
            assert isinstance(ev["ts"], float)
            phases.add(ev["ph"])
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] == "i":
                assert ev["s"] == "t"
        assert phases == {"X", "i"}

    def test_snapshot_last_n(self):
        t = Tracer()
        t.enable()
        for i in range(6):
            with t.span(f"s{i}"):
                pass
        spans, _ = t.snapshot(last=2)
        assert [s.name for s in spans] == ["s4", "s5"]


# ---------------------------------------------------------------------------
# span-tree integrity through a REAL scheduler batch
# ---------------------------------------------------------------------------

class TestSchedulerSpans:
    def test_span_tree_through_real_batch(self):
        obs.TRACER.enable()
        r = msm.Registry()

        async def main():
            sched = ContinuousScheduler(
                lambda lines: [ln.upper() for ln in lines],
                registry=r, version_fn=lambda: "bundle-7",
                window_s=0.005)
            sched.start()
            # two concurrent requests coalesce into one device batch
            f1 = sched.submit(["a b", "c d"], trace_id="req0001")
            f2 = sched.submit(["e f"], trace_id="req0002")
            assert await f1 == ["A B", "C D"]
            assert await f2 == ["E F"]
            await sched.stop()

        run(main())
        spans, _ = obs.TRACER.snapshot()
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        # per-request tree: serve.request -> serve.queue + serve.dispatch
        roots = {s.trace_id: s for s in by_name["serve.request"]}
        assert set(roots) == {"req0001", "req0002"}
        for q in by_name["serve.queue"]:
            assert q.parent_id == roots[q.trace_id].span_id
        for d in by_name["serve.dispatch"]:
            assert d.parent_id == roots[d.trace_id].span_id
            assert d.attrs["model_version"] == "bundle-7"   # tagged
            assert d.attrs["outcome"] == "ok"
        assert all(r.attrs["model_version"] == "bundle-7"
                   for r in by_name["serve.request"])
        # batch level: one serve.batch holding both traces, with its
        # serve.translate child on the device worker thread
        batches = by_name["serve.batch"]
        assert len(batches) == 1
        assert set(batches[0].attrs["traces"]) == {"req0001", "req0002"}
        tr = by_name["serve.translate"][0]
        assert tr.parent_id == batches[0].span_id
        assert tr.thread != batches[0].thread      # executor thread
        # dispatch spans back-reference the batch span
        assert all(d.attrs["batch_span"] == batches[0].span_id
                   for d in by_name["serve.dispatch"])

    def test_reply_metadata_breakdown(self):
        r = msm.Registry()

        async def main():
            sched = ContinuousScheduler(
                lambda lines: list(lines), registry=r,
                version_fn=lambda: "vX")
            sched.start()
            meta = {}
            await sched.submit(["hello"], meta=meta, trace_id="m1")
            await sched.stop()
            return meta

        meta = run(main())
        assert meta["outcome"] == "ok"
        assert meta["model_version"] == "vX"
        assert meta["trace_id"] == "m1"
        assert meta["queue_s"] >= 0.0
        assert meta["service_s"] > 0.0

    def test_disabled_no_ring_no_lock_on_hot_path(self):
        """The acceptance overhead guard (extended for ISSUE 9): tracer
        off AND perf accounting off AND no --slo-* ⇒ the per-batch
        dispatch path allocates no ring and acquires neither the tracer
        lock nor the perf meter's lock (the SLO engine is not even
        constructed without an objective flag, so it has no lock to
        guard against)."""
        assert not obs.enabled()
        obs.PERF.reset()
        assert not obs.PERF.enabled
        saved = obs.TRACER._lock
        saved_perf = obs.PERF._lock
        obs.TRACER._lock = _RaisingLock()
        obs.PERF._lock = _RaisingLock()
        try:
            r = msm.Registry()

            async def main():
                sched = ContinuousScheduler(
                    lambda lines: list(lines), registry=r)
                sched.start()
                out = await sched.submit(["x y", "z"])
                await sched.stop()
                return out

            assert run(main()) == ["X Y".lower(), "z"]
        finally:
            obs.TRACER._lock = saved
            obs.PERF._lock = saved_perf
        assert obs.TRACER._ring is None
        assert obs.TRACER._events is None


# ---------------------------------------------------------------------------
# /tracez endpoint round-trip
# ---------------------------------------------------------------------------

class TestTracezEndpoint:
    def test_tracez_roundtrip_perfetto_valid(self):
        obs.TRACER.enable()
        with obs.span("served", who="test"):
            pass
        srv = msm.MetricsServer(0, registry=msm.Registry(),
                                routes=obs.trace_routes()).start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/tracez?last=10").read()
            doc = json.loads(body)
            assert doc["otherData"]["tracer_enabled"] is True
            names = [e["name"] for e in doc["traceEvents"]]
            assert "served" in names
            ev = doc["traceEvents"][names.index("served")]
            assert ev["ph"] == "X" and ev["dur"] >= 0
            assert ev["args"]["who"] == "test"
        finally:
            srv.close()

    def test_tracez_disabled_still_valid_document(self):
        srv = msm.MetricsServer(0, registry=msm.Registry(),
                                routes=obs.trace_routes()).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/tracez").read())
            assert doc["traceEvents"] == []
            assert doc["otherData"]["tracer_enabled"] is False
        finally:
            srv.close()

    def test_tracez_last_bounds_spans(self):
        obs.TRACER.enable()
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
        srv = msm.MetricsServer(0, registry=msm.Registry(),
                                routes=obs.trace_routes()).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/tracez?last=2").read())
            xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            assert [e["name"] for e in xs] == ["s3", "s4"]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _flight_dumps(d):
    return sorted(p for p in os.listdir(d) if p.startswith("flight-"))


class TestFlightRecorder:
    def test_watchdog_trip_dumps_victim_span_tree(self, tmp_path):
        """Acceptance: an injected MARIAN_FAULTS stall trips the dispatch
        watchdog and the dump holds the victim's full
        ingest→dispatch→failure tree."""
        obs.TRACER.enable()
        obs.FLIGHT.arm(str(tmp_path))

        async def main():
            # default (process-wide) registry: the dump snapshots it,
            # like production
            sched = ContinuousScheduler(
                lambda lines: list(lines),
                stall_timeout=0.15, version_fn=lambda: "vLive")
            sched.start()
            with fp.active("serving.translate=hang:1.2"):
                with pytest.raises(DispatchStalled):
                    await sched.submit(["victim sentence"],
                                       trace_id="victim01")
            await sched.stop()

        run(main())
        # the watchdog dump is written on a background thread (the trip
        # site is the event loop — a synchronous dump would freeze every
        # connection mid-incident): wait for it
        deadline = time.time() + 5.0
        while not _flight_dumps(str(tmp_path)) and time.time() < deadline:
            time.sleep(0.02)
        dumps = _flight_dumps(str(tmp_path))
        assert len(dumps) == 1 and "watchdog" in dumps[0]
        payload = json.loads((tmp_path / dumps[0]).read_text())
        assert payload["reason"] == "watchdog"
        assert payload["trace_id"] == "victim01"
        # the victim's complete tree: ingest (serve.request/serve.queue)
        # → dispatch → failure outcome, plus the watchdog event
        evs = payload["trace"]["traceEvents"]
        victim = [e for e in evs
                  if e.get("args", {}).get("trace_id") == "victim01"]
        names = {e["name"] for e in victim}
        assert {"serve.request", "serve.queue", "serve.dispatch"} <= names
        dispatch = next(e for e in victim
                        if e["name"] == "serve.dispatch")
        assert dispatch["args"]["outcome"] == "stalled"
        assert any(e["name"] == "serve.watchdog_trip" for e in evs)
        # timeline context + metrics snapshot ride along
        assert "marian_serving_watchdog_trips_total" in payload["metrics"]
        assert payload["faultpoints"]["hits"]["serving.translate"] >= 1

    def test_canary_rollback_dumps(self, tmp_path):
        """Acceptance: a canary auto-rollback produces a dump with the
        failing batches' span trees still in the ring."""
        obs.TRACER.enable()
        obs.FLIGHT.arm(str(tmp_path))
        mp = str(tmp_path / "m.npz")

        def bad_factory(bundle_dir, manifest):
            calls = {"n": 0}

            def translate(lines):
                if calls["n"]:       # golden smoke passes, traffic dies
                    raise RuntimeError("canary decode explodes")
                calls["n"] += 1
                return list(lines)
            return translate

        ctrl = SwapController(bad_factory,
                              metrics_registry=msm.Registry(),
                              canary_fraction=1.0,
                              rollback_error_rate=0.5,
                              rollback_min_batches=2)
        ctrl.seed_live(0, "boot", lambda lines: [f"v1:{ln}"
                                                 for ln in lines])
        bdir = bdl.write_bundle(mp, {"m.npz": lambda p: open(p, "w").close()})
        v = ctrl.ingest(bdir, bdl.validate_bundle(bdir)[2])
        assert v.state == "canary"
        for i in range(6):
            assert ctrl.route([f"s{i}"])[0].startswith("v1:")
        assert v.state == "failed"
        dumps = _flight_dumps(str(tmp_path))
        assert len(dumps) == 1 and "canary-rollback" in dumps[0]
        payload = json.loads((tmp_path / dumps[0]).read_text())
        assert payload["reason"] == "canary-rollback"
        assert "failure rate" in payload["detail"]
        assert payload["extra"]["version"] == os.path.basename(bdir)
        # the event timeline shows the lifecycle history up to the trip
        ev_names = [e["name"] for e in payload["trace"]["traceEvents"]
                    if e["ph"] == "i"]
        assert "lifecycle.transition" in ev_names
        assert "lifecycle.rollback" in ev_names

    def test_fault_kill_hook_dumps_before_exit(self, tmp_path,
                                               monkeypatch):
        """MARIAN_FAULTS kill mode dumps the ring before os._exit."""
        obs.configure(None)    # no options: env-driven arming below
        monkeypatch.setenv(obs.ENV_TRACE, "1")
        monkeypatch.setenv(obs.ENV_DUMP, str(tmp_path))
        assert obs.configure(None) is True
        exits = []
        monkeypatch.setattr(fp.os, "_exit", lambda code:
                            exits.append(code))
        with obs.span("last-request", trace_id="dying01"):
            pass
        fp.activate("serving.dispatch=kill@1")
        fp.fault_point("serving.dispatch")
        assert exits == [fp.FAULT_EXIT_CODE]
        dumps = _flight_dumps(str(tmp_path))
        assert len(dumps) == 1 and "fault-kill" in dumps[0]
        payload = json.loads((tmp_path / dumps[0]).read_text())
        assert "serving.dispatch" in payload["detail"]
        names = [e["name"] for e in payload["trace"]["traceEvents"]]
        assert "last-request" in names     # the ring survived into disk
        assert "fault.fire" in names       # the firing itself on timeline

    def test_disarmed_trip_is_noop(self, tmp_path):
        assert obs.FLIGHT.trip("whatever") is None
        assert _flight_dumps(str(tmp_path)) == []

    def test_dump_counter_emitted(self, tmp_path):
        obs.TRACER.enable()
        obs.FLIGHT.arm(str(tmp_path))
        before = msm.REGISTRY.counter(
            "marian_flight_dumps_total", "", labels=("reason",)
        ).labels("manual-test").value
        assert obs.FLIGHT.trip("manual-test") is not None
        after = msm.REGISTRY.counter(
            "marian_flight_dumps_total", "", labels=("reason",)
        ).labels("manual-test").value
        assert after == before + 1


# ---------------------------------------------------------------------------
# server protocol: #trace header + reply metadata
# ---------------------------------------------------------------------------

def _stub_app(translate=None, **extra):
    opts = {"metrics-port": 0, "max-queue": 64, "port": 0}
    opts.update(extra)
    return ServingApp(Options(opts),
                      translate_lines=translate
                      or (lambda lines: [ln.upper() for ln in lines]))


class TestServerTraceProtocol:
    def test_split_trace_header(self):
        assert split_trace_header("#trace:abc123\nhello") \
            == ("abc123", "hello")
        assert split_trace_header("hello\nworld") == (None, "hello\nworld")
        # malformed ids are payload, never an error
        assert split_trace_header("#trace:\nx") == (None, "#trace:\nx")
        assert split_trace_header("#trace:has space\nx") \
            == (None, "#trace:has space\nx")
        assert split_trace_header("#trace:" + "a" * 65 + "\nx")[0] is None

    def test_reply_metadata_roundtrip(self):
        async def main():
            app = _stub_app()
            await app.start()
            try:
                reply = await app.handle_text("#trace:cafe01\nhello\nworld")
            finally:
                await app.shutdown(drain_timeout=2)
            return reply

        reply = run(main())
        meta_line, _, body = reply.partition("\n")
        assert meta_line.startswith("#trace:cafe01 ")
        assert "outcome=ok" in meta_line
        assert "queue_ms=" in meta_line and "service_ms=" in meta_line
        assert body == "HELLO\nWORLD"

    def test_plain_clients_see_old_protocol(self):
        async def main():
            app = _stub_app()
            await app.start()
            try:
                return await app.handle_text("hello")
            finally:
                await app.shutdown(drain_timeout=2)

        assert run(main()) == "HELLO"

    def test_shed_reply_still_carries_metadata(self):
        obs.TRACER.enable()

        async def main():
            app = _stub_app(**{"max-queue": 1})
            app.admission.begin_drain()
            return await app.handle_frame("#trace:x1\nhello")

        reply, done = run(main())
        done(len(reply))
        first, _, rest = reply.partition("\n")
        assert first.startswith("#trace:x1 outcome=shed")
        assert rest.startswith("!!SERVER-OVERLOADED")
        # the shed's timeline event is tied to the victim (admit runs
        # inside the request's span context)
        _, events = obs.TRACER.snapshot()
        shed = [e for e in events if e["name"] == "admission.shed"]
        assert shed and shed[-1]["trace_id"] == "x1"

    def test_request_span_covers_reply_write(self):
        obs.TRACER.enable()

        async def main():
            app = _stub_app()
            await app.start()
            try:
                reply, done = await app.handle_frame("#trace:w1\nhello")
                done(len(reply))
            finally:
                await app.shutdown(drain_timeout=2)

        run(main())
        spans, _ = obs.TRACER.snapshot()
        by_name = {s.name: s for s in spans if s.trace_id == "w1"}
        assert "request" in by_name and "reply.write" in by_name
        root = by_name["request"]
        assert by_name["reply.write"].parent_id == root.span_id
        assert root.attrs["outcome"] == "ok"
        assert by_name["reply.write"].attrs["nbytes"] > 0
        # scheduler children hang under the same root
        assert by_name["serve.queue"].parent_id == root.span_id


# ---------------------------------------------------------------------------
# histogram exemplars
# ---------------------------------------------------------------------------

class TestExemplars:
    def test_exemplar_rendered_only_on_request(self):
        r = msm.Registry()
        h = r.histogram("t_ex_seconds", "x", buckets=(0.1, 1.0))
        h.observe(0.05, trace_id="fast01")
        h.observe(5.0, trace_id="slow99")
        h.observe(0.07)                      # no trace id: keeps fast01
        plain = r.render()
        assert "trace_id" not in plain       # strict 0.0.4 by default
        ex = r.render(exemplars=True)
        assert '# {trace_id="fast01"} 0.05' in ex
        assert '# {trace_id="slow99"} 5' in ex

    def test_scrape_query_param(self):
        r = msm.Registry()
        h = r.histogram("t_q_seconds", "x", buckets=(1.0,))
        h.observe(0.5, trace_id="qq1")
        srv = msm.MetricsServer(0, registry=r).start()
        try:
            base = f"http://127.0.0.1:{srv.port}/metrics"
            plain = urllib.request.urlopen(base).read().decode()
            assert "trace_id" not in plain
            with_ex = urllib.request.urlopen(
                base + "?exemplars=1").read().decode()
            assert 'trace_id="qq1"' in with_ex
        finally:
            srv.close()

    def test_scheduler_latency_carries_exemplar(self):
        r = msm.Registry()

        async def main():
            sched = ContinuousScheduler(lambda lines: list(lines),
                                        registry=r)
            sched.start()
            await sched.submit(["x"], trace_id="lat0001")
            await sched.stop()

        run(main())
        out = r.render(exemplars=True)
        assert 'trace_id="lat0001"' in out


# ---------------------------------------------------------------------------
# totals, the profiler as a second switch, and what went (ISSUE 24)
# ---------------------------------------------------------------------------

class TestTotalsAndProfilerSink:
    def test_totals_self_time_same_thread_only(self):
        """Self seconds = duration less same-thread children: a child
        recorded from another thread does not shrink its parent."""
        t = Tracer()
        t.enable()
        with t.span("outer") as outer:
            with t.span("inner"):
                time.sleep(0.01)
            th = threading.Thread(
                target=lambda: t.end(t.start_span("elsewhere",
                                                  parent=outer)))
            th.start()
            th.join()
        tot = t.totals()
        assert tot["outer"]["calls"] == tot["inner"]["calls"] == 1
        assert tot["inner"]["self_seconds"] == tot["inner"]["seconds"]
        assert tot["outer"]["self_seconds"] == pytest.approx(
            tot["outer"]["seconds"] - tot["inner"]["seconds"], abs=1e-9)
        assert tot["elsewhere"]["thread"] != tot["outer"]["thread"]
        t.reset()
        assert t.totals() == {} and t._totals is None

    def test_record_counts_toward_its_parent(self):
        t = Tracer()
        t.enable()
        with t.span("outer"):
            now = time.perf_counter()
            t.record("past", now - 0.5, now)
        tot = t.totals()
        assert tot["past"]["seconds"] == pytest.approx(0.5)
        assert tot["outer"]["self_seconds"] == 0.0      # clamped, not < 0

    def test_profiler_session_turns_spans_on(self, tmp_path):
        """No --trace: a collecting jax.profiler session makes spans live
        (a TraceMe each, totals kept), the ring stays unallocated, and
        they go quiet again when it stops."""
        import jax
        from marian_tpu.obs import trace as tr
        assert not tr.profiler_collecting()
        assert obs.start_span("off") is NOOP_SPAN
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=popts)
        try:
            assert tr.profiler_collecting()
            with obs.span("on.profiler", k=1) as sp:
                assert sp and sp._ann is not None
                sp.set_attrs(late=2)
        finally:
            jax.profiler.stop_trace()
        assert sp._ann is None and sp.attrs == {"k": 1, "late": 2}
        assert obs.TRACER._ring is None and not obs.enabled()
        assert obs.TRACER.totals()["on.profiler"]["calls"] == 1
        assert obs.start_span("off") is NOOP_SPAN

    def test_step_timer_and_its_flag_are_gone(self):
        """--trace-sync-phases serialised host and device to make up for
        host-clock stamps; spans on the profiler's clock replace it."""
        from marian_tpu.common import config_parser as cp
        from marian_tpu.common import profiling
        from marian_tpu.obs import profiling as obs_profiling
        assert "trace-sync-phases" not in cp.ConfigParser("training").flags
        assert not hasattr(profiling, "StepTimer")
        assert not hasattr(obs_profiling, "StepTimer")
        assert hasattr(obs_profiling, "TraceWindow")
        with pytest.raises(SystemExit):
            cp.parse_options(["--trace-sync-phases"], mode="training")


# ---------------------------------------------------------------------------
# gauges and the allocator's word (ISSUE 38)
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


class TestGauges:
    def test_off_keeps_nothing(self):
        t = Tracer()
        t._lock = _RaisingLock()
        for v in (3, 1, 2):
            t.gauge("hbm.free", v)
        assert t._gauges is None
        t._lock = threading.Lock()
        assert t.gauges() == {}

    def test_last_min_max_n_over_a_sequence(self):
        t = Tracer()
        t.enable()
        for v in (5, 2, 9, 4):
            t.gauge("hbm.free", v)
        t.gauge("hbm.limit", 16)
        assert t.gauges() == {
            "hbm.free": {"last": 4, "min": 2, "max": 9, "n": 4},
            "hbm.limit": {"last": 16, "min": 16, "max": 16, "n": 1}}
        assert t.totals() == {} and t.counters() == {}

    def test_gauge_returns_the_count_of_its_samples(self):
        """1 on a name's first sample of a stretch (a caller writes what
        does not change then), None when off."""
        t = Tracer()
        assert t.gauge("g", 1) is None
        t.enable()
        assert [t.gauge("g", v) for v in (4, 5, 6)] == [1, 2, 3]
        assert t.gauge("h", 0) == 1
        t.disable()
        assert t.gauge("g", 7) is None
        t.enable()
        assert t.gauge("g", 7) == 1

    def test_live_under_a_collecting_profiler(self, monkeypatch):
        """The guard count_lazy uses: the tracer's own switch OR the
        profiler's flag; the ring stays unallocated."""
        from marian_tpu.obs import trace as tr
        t = Tracer()
        t.gauge("g", 1)
        assert t.gauges() == {}
        monkeypatch.setattr(tr, "profiler_collecting", lambda: True)
        t.gauge("g", 7)
        monkeypatch.setattr(tr, "profiler_collecting", lambda: False)
        t.gauge("g", 8)                         # off again: not kept
        assert t.gauges() == {"g": {"last": 7, "min": 7, "max": 7, "n": 1}}
        assert t._ring is None and not t.enabled

    @pytest.mark.parametrize("clear", ["reset", "disable"])
    def test_reset_and_disable_clear(self, clear):
        t = Tracer()
        t.enable()
        t.gauge("g", 1)
        getattr(t, clear)()
        assert t._gauges is None and t.gauges() == {}
        t.enable()
        t.gauge("g", 5)                 # a new stretch, a new minimum
        assert t.gauges()["g"] == {"last": 5, "min": 5, "max": 5, "n": 1}

    def test_device_memory_is_none_on_the_cpu(self):
        import jax
        from marian_tpu.training import hbm as tr
        assert jax.local_devices()[0].memory_stats() is None
        assert tr.device_memory() is None

    def test_device_memory_reads_the_fullest_device(self, monkeypatch):
        """Host integers under the ledger's names, whichever keys the
        runtime gives, of the device with the most in use."""
        import jax
        from marian_tpu.training import hbm as tr
        devs = [_FakeDevice(None),
                _FakeDevice({"bytes_limit": 100, "bytes_in_use": 10,
                             "peak_bytes_in_use": 20}),
                _FakeDevice({"bytes_limit": 100, "bytes_in_use": 60,
                             "peak_bytes_in_use": 70,
                             "largest_free_block_bytes": 30,
                             "bytes_reserved": 25, "num_allocs": 5})]
        monkeypatch.setattr(jax, "local_devices", lambda: devs)
        assert tr.device_memory() == {"limit": 100, "in_use": 60,
                                      "peak": 70, "reserved": 25,
                                      "largest_free": 30}
        monkeypatch.setattr(jax, "local_devices", lambda: devs[:2])
        assert tr.device_memory() == {"limit": 100, "in_use": 10,
                                      "peak": 20}
        monkeypatch.setattr(jax, "local_devices", lambda: devs[:1])
        assert tr.device_memory() is None


# ---------------------------------------------------------------------------
# configure() knobs
# ---------------------------------------------------------------------------

class TestConfigure:
    def test_options_flags(self, tmp_path):
        opts = Options({"trace": True, "trace-ring": 128})
        assert obs.configure(opts) is True
        assert obs.TRACER.enabled and obs.TRACER.capacity == 128
        assert not obs.FLIGHT.armed

    def test_trace_dump_implies_trace(self, tmp_path):
        opts = Options({"trace-dump": str(tmp_path / "dumps")})
        assert obs.configure(opts) is True
        assert obs.FLIGHT.armed
        assert os.path.isdir(tmp_path / "dumps")

    def test_off_by_default(self):
        assert obs.configure(Options({})) is False
        assert not obs.TRACER.enabled
