"""Performance & capacity observability plane (ISSUE 9): live
chip-seconds/token + MFU + headroom accounting (obs/perf.py), per-bucket
compile telemetry through warmup and the scheduler, the SLO burn-rate
engine (obs/slo.py) with /sloz and flight-dump integration, process
self-metrics, the Prometheus text-format lint, and loadgen --sweep
against a real CPU TCP server. Everything runs with stub translate
functions under JAX_PLATFORMS=cpu.

Acceptance-critical tier-1 properties:
- a slow-translate MARIAN_FAULTS fault drives the fast-burn SLO alert →
  timeline event + flight dump containing SLO state;
- the lifecycle swap observes warmup compile telemetry per shape bucket
  and ZERO steady-state recompile events;
- a scheduler run on CPU exports chip-seconds/token and headroom gauges
  that loadgen --sweep reads back;
- disabled mode adds no lock acquisitions on the batch path (the
  raising-lock guard in test_obs.py now covers PerfMeter._lock too).
"""

import asyncio
import importlib.util
import json
import os
import threading
import time
import urllib.request

import pytest

from marian_tpu import obs
from marian_tpu.common import Options
from marian_tpu.common import faultpoints as fp
from marian_tpu.obs.perf import PerfMeter, width_bucket_key
from marian_tpu.obs.slo import SloEngine, maybe_build_engine, slo_routes
from marian_tpu.serving import metrics as msm
from marian_tpu.serving.lifecycle import SwapController
from marian_tpu.serving.lifecycle.warmup import (DEFAULT_GOLDEN,
                                                 WarmupError,
                                                 golden_buckets,
                                                 smoke_buckets,
                                                 warm_executor)
from marian_tpu.serving.promlint import lint_metrics_text
from marian_tpu.serving.scheduler import ContinuousScheduler
from marian_tpu.server.server import ServingApp, _make_tcp_handler
from marian_tpu.training import bundle as bdl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _lockdep_witness(lockdep_witness):
    """PerfMeter._lock / SloEngine._lock join the running lattice here;
    the shared conftest witness asserts observed ⊆ static at module
    teardown."""
    yield


@pytest.fixture(autouse=True)
def _reset_obs():
    yield
    obs.TRACER.reset()
    obs.FLIGHT.disarm()
    obs.PERF.reset()
    fp.reset_for_tests()


def run(coro):
    return asyncio.run(coro)


def enable_perf(registry=None):
    obs.PERF.reset()
    obs.PERF.enable(registry=registry or msm.REGISTRY, hook_jax=False)
    return obs.PERF


# ---------------------------------------------------------------------------
# PerfMeter core math
# ---------------------------------------------------------------------------

class TestPerfMeter:
    def test_record_batch_updates_integrals_and_rates(self):
        r = msm.Registry()
        p = enable_perf(r)
        p.record_batch("vA", rows=4, width=8, src_tokens=20,
                       trg_tokens=18, device_s=0.5)
        p.record_batch("vA", rows=2, width=8, src_tokens=10,
                       trg_tokens=9, device_s=0.25)
        assert r.get("marian_perf_device_seconds_total") \
                .labels("vA").value == pytest.approx(0.75)
        assert r.get("marian_perf_tokens_total").labels("vA").value == 30
        assert r.get("marian_perf_trg_tokens_total") \
                .labels("vA").value == 27
        cspt = r.get("marian_perf_chip_seconds_per_token") \
                .labels("vA").value
        assert cspt == pytest.approx(0.75 / 30)
        assert r.get("marian_perf_tokens_per_second") \
                .labels("vA").value > 0
        assert 0 < r.get("marian_perf_device_busy_ratio").value <= 1

    def test_busy_and_throughput_decay_at_idle(self):
        """busy/tokens-per-second are scrape-time over the window: an
        idle replica must read 0, not the last burst's rate — else the
        autoscaler sees phantom saturation (review fix)."""
        r = msm.Registry()
        p = enable_perf(r)
        p.window_s = 0.05
        p.record_batch("v", rows=1, width=8, src_tokens=10,
                       trg_tokens=10, device_s=0.05)
        assert r.get("marian_perf_device_busy_ratio").value > 0.5
        time.sleep(0.12)                 # the burst ages out of the window
        assert r.get("marian_perf_device_busy_ratio").value == 0.0
        assert r.get("marian_perf_tokens_per_second") \
                .labels("v").value == 0.0
        # the COST gauge deliberately holds its last value (a $/token
        # figure does not decay)
        assert r.get("marian_perf_chip_seconds_per_token") \
                .labels("v").value > 0

    def test_stalled_batch_bills_stall_window(self):
        """A watchdog-stalled device call never returns through the
        timing fence — the stall window itself must be billed as device
        time so repeated stalls do not read as an idle replica
        (review fix)."""
        r = msm.Registry()
        enable_perf(r)

        async def main():
            sched = ContinuousScheduler(lambda lines: list(lines),
                                        stall_timeout=0.1, registry=r,
                                        window_s=0)
            sched.start()
            with fp.active("serving.translate=hang:5"):
                from marian_tpu.serving.scheduler import DispatchStalled
                with pytest.raises(DispatchStalled):
                    await sched.submit(["victim"])
            await sched.stop()

        run(main())
        assert r.get("marian_perf_device_seconds_total") \
                .labels("unversioned").value >= 0.1
        # but NO tokens: the stalled batch delivered nothing, so the
        # throughput/cost signals spike instead of reading "healthy"
        assert r.get("marian_perf_tokens_total") \
                .labels("unversioned").value == 0

    def test_mfu_against_explicit_peak(self):
        r = msm.Registry()
        p = enable_perf(r)
        p.set_geometry(emb=64, ffn=256, enc_depth=2, dec_depth=2,
                       vocab=1000, beam=2, n_devices=1, peak_flops=1e9)
        assert r.get("marian_perf_roofline_peak_flops").value == 1e9
        assert r.get("marian_perf_devices").value == 1
        p.record_batch("vA", rows=2, width=16, src_tokens=20,
                       trg_tokens=20, device_s=1.0)
        from marian_tpu.common.flops import transformer_serve_flops
        # trg_width = average generated length = trg_tokens / rows
        expect = transformer_serve_flops(64, 256, 2, 2, 1000,
                                         src_tokens=20, trg_tokens=20,
                                         src_width=16, trg_width=10,
                                         beam=2) / 1e9
        assert r.get("marian_perf_mfu").labels("vA").value \
            == pytest.approx(expect, rel=1e-6)

    def test_mfu_zero_without_known_peak(self):
        r = msm.Registry()
        p = enable_perf(r)
        # CPU probe: device_kind has no 'tpu' → peak None → mfu 0
        p.set_geometry(emb=64, ffn=256, enc_depth=1, dec_depth=1,
                       vocab=100, device_kind="cpu", n_devices=1)
        p.record_batch("vA", rows=1, width=8, src_tokens=5,
                       trg_tokens=5, device_s=0.1)
        assert r.get("marian_perf_mfu").labels("vA").value == 0.0
        assert r.get("marian_perf_roofline_peak_flops").value == 0.0

    def test_headroom_idle_busy_and_queue_pressure(self):
        r = msm.Registry()
        p = enable_perf(r)
        p.window_s = 10.0
        depth = {"n": 0}
        p.set_capacity_inputs(lambda: depth["n"], 100)
        assert p.headroom() == pytest.approx(1.0)       # idle, empty queue
        # saturate the window: 10s of device time in a 10s window
        p.record_batch("v", rows=1, width=8, src_tokens=10,
                       trg_tokens=10, device_s=10.0)
        assert p.headroom() == pytest.approx(0.0, abs=1e-3)
        p.reset()
        p = enable_perf(r)
        p.set_capacity_inputs(lambda: depth["n"], 100)
        depth["n"] = 50                                  # half-full queue
        assert p.headroom() == pytest.approx(0.5, abs=1e-6)
        depth["n"] = 100
        assert p.headroom() == pytest.approx(0.0, abs=1e-6)
        # the exported gauge samples the same function at scrape time
        assert "marian_capacity_headroom_ratio 0" in r.render()

    def test_headroom_unbounded_queue_prices_debt_per_sentence(self):
        r = msm.Registry()
        p = enable_perf(r)
        p.window_s = 10.0
        p.set_capacity_inputs(lambda: 100, 0)     # unbounded admission
        # 0.1 device-seconds per SENTENCE (depth counts sentences, so
        # the price must too) → 100 queued = 10s of work = one full
        # window horizon → pressure 1.0
        p.record_batch("v", rows=10, width=8, src_tokens=100,
                       trg_tokens=100, device_s=1.0)
        assert p.headroom() == pytest.approx(0.0, abs=1e-6)

    def test_per_version_cost_gauges_not_blended(self):
        """A hot-swap's new version must not inherit the old version's
        window samples in its cost gauge (review fix: the rolling sums
        are per version label)."""
        r = msm.Registry()
        p = enable_perf(r)
        p.record_batch("vOld", rows=1, width=8, src_tokens=10,
                       trg_tokens=10, device_s=1.0)     # 0.1 s/token
        p.record_batch("vNew", rows=1, width=8, src_tokens=10,
                       trg_tokens=10, device_s=0.1)     # 0.01 s/token
        assert r.get("marian_perf_chip_seconds_per_token") \
                .labels("vOld").value == pytest.approx(0.1)
        assert r.get("marian_perf_chip_seconds_per_token") \
                .labels("vNew").value == pytest.approx(0.01)
        st = p.state()
        assert st["versions"]["vNew"]["chip_seconds_per_token"] \
            == pytest.approx(0.01)

    def test_disabled_record_is_noop(self):
        p = PerfMeter()
        p.record_batch("v", 1, 8, 5, 5, 0.1)      # no metrics attrs: would
        p.record_train_window(10, 10, 1, 1.0)     # raise if not guarded
        assert p.headroom() == pytest.approx(1.0)
        assert p.state() == {"enabled": False}

    def test_train_window_chip_seconds_and_mfu(self):
        r = msm.Registry()
        p = enable_perf(r)
        p.set_geometry(emb=32, ffn=64, enc_depth=1, dec_depth=1,
                       vocab=200, n_devices=2, peak_flops=1e9)
        p.record_train_window(labels=100, src_words=120, sentences=10,
                              dt=2.0)
        assert r.get("marian_train_chip_seconds_per_token").value \
            == pytest.approx(2.0 * 2 / 100)
        assert r.get("marian_train_mfu").value > 0


# ---------------------------------------------------------------------------
# compile telemetry: warmup buckets vs steady-state recompiles
# ---------------------------------------------------------------------------

class TestCompileTelemetry:
    def test_golden_buckets_grouping(self):
        groups = golden_buckets(list(DEFAULT_GOLDEN))
        # "hello" (2) and "a b c d" (5) land in w8; the 10-token probe
        # in w16 — the built-in golden set warms two buckets
        assert list(groups) == [8, 16]
        assert groups[8] == ["hello", "a b c d"]

    def test_warm_bucket_then_dispatch_no_recompile(self):
        r = msm.Registry()
        p = enable_perf(r)
        obs.TRACER.enable()
        p.warm_bucket("v1", width_bucket_key(8), 0.2, "swap-warmup")
        p.record_batch("v1", rows=2, width=8, src_tokens=6,
                       trg_tokens=6, device_s=0.01)
        assert p.steady_recompiles() == 0
        _, events = obs.TRACER.snapshot()
        assert not [e for e in events if e["name"] == "perf.recompile"]
        assert r.get("marian_compile_total") \
                .labels("swap-warmup", "w8").value == 1
        assert r.get("marian_compile_seconds_total") \
                .labels("swap-warmup", "w8").value == pytest.approx(0.2)

    def test_unwarmed_bucket_is_steady_state_recompile_once(self):
        r = msm.Registry()
        p = enable_perf(r)
        obs.TRACER.enable()
        p.record_batch("v1", rows=1, width=32, src_tokens=20,
                       trg_tokens=20, device_s=0.7)
        p.record_batch("v1", rows=1, width=32, src_tokens=20,
                       trg_tokens=20, device_s=0.1)   # second hit: warm now
        assert p.steady_recompiles() == 1
        assert r.get("marian_compile_total") \
                .labels("steady-state", "w32").value == 1
        _, events = obs.TRACER.snapshot()
        rec = [e for e in events if e["name"] == "perf.recompile"]
        assert len(rec) == 1
        assert rec[0]["attrs"]["bucket"] == "w32"
        assert rec[0]["attrs"]["model_version"] == "v1"

    def test_smoke_buckets_calls_per_bucket_and_arity(self):
        r = msm.Registry()
        p = enable_perf(r)
        calls = []

        def executor(lines):
            calls.append(list(lines))
            return list(lines)

        smoke_buckets(executor, list(DEFAULT_GOLDEN), "vX",
                      "boot-warmup", "here")
        assert len(calls) == 2                   # one call per bucket
        assert r.get("marian_compile_total") \
                .labels("boot-warmup", "w8").value == 1
        assert r.get("marian_compile_total") \
                .labels("boot-warmup", "w16").value == 1
        with pytest.raises(WarmupError):
            smoke_buckets(lambda lines: ["too", "many", "outputs", "!"],
                          ["hello"], "vX", "boot-warmup", "here")

    def test_warm_executor_single_call_without_perf(self):
        assert not obs.PERF.enabled
        calls = []

        def factory(bundle_dir, manifest):
            def translate(lines):
                calls.append(list(lines))
                return list(lines)
            return translate

        warm_executor("/b", None, factory, list(DEFAULT_GOLDEN))
        # perf plane off → the historical ONE combined smoke call
        assert calls == [list(DEFAULT_GOLDEN)]


# ---------------------------------------------------------------------------
# ACCEPTANCE: lifecycle swap — per-bucket warmup telemetry, zero
# steady-state recompiles
# ---------------------------------------------------------------------------

class TestSwapCompileTelemetry:
    def test_swap_warms_buckets_and_traffic_never_recompiles(self,
                                                             tmp_path):
        r = msm.Registry()
        p = enable_perf(r)
        obs.TRACER.enable()
        mp = str(tmp_path / "m.npz")

        def factory(bundle_dir, manifest):
            return lambda lines: [f"b{manifest['seq']}:{ln}"
                                  for ln in lines]

        ctrl = SwapController(factory, metrics_registry=r)
        ctrl.seed_live(0, "boot", lambda lines: [f"v1:{ln}"
                                                 for ln in lines])
        bdir = bdl.write_bundle(
            mp, {"m.npz": lambda pth: open(pth, "w").close()})
        v = ctrl.ingest(bdir, bdl.validate_bundle(bdir)[2])
        assert v.state == "live"
        name = os.path.basename(bdir)
        # warmup compile telemetry PER SHAPE BUCKET, trigger=swap-warmup
        assert r.get("marian_compile_total") \
                .labels("swap-warmup", "w8").value == 1
        assert r.get("marian_compile_total") \
                .labels("swap-warmup", "w16").value == 1
        assert r.get("marian_compile_seconds_total") \
                .labels("swap-warmup", "w8").value > 0

        async def traffic():
            sched = ContinuousScheduler(ctrl.route, registry=r,
                                        version_fn=ctrl.live_version_name,
                                        window_s=0)
            sched.start()
            # every sentence lands in a warmed bucket (w8 or w16)
            await sched.submit(["x y z", "a b"])
            await sched.submit(
                ["one two three four five six seven eight nine"])
            await sched.stop()

        run(traffic())
        # ZERO steady-state recompile events after the warmed swap
        assert p.steady_recompiles() == 0
        _, events = obs.TRACER.snapshot()
        assert not [e for e in events if e["name"] == "perf.recompile"]
        # and the capacity integrals carry the new version's label
        assert r.get("marian_perf_device_seconds_total") \
                .labels(name).value > 0
        assert r.get("marian_perf_tokens_total").labels(name).value \
            == 4 + 3 + 10             # whitespace tokens + EOS each


class TestBootWarmup:
    def test_boot_warmup_matches_scheduler_version_label(self):
        """--warmup-on-boot without a lifecycle: buckets must be warmed
        under the scheduler's own version label ('unversioned'), else
        every warmed bucket still reads as a steady-state recompile —
        the exact false incident the flag exists to prevent."""
        r = msm.Registry()
        p = enable_perf(r)
        obs.TRACER.enable()

        async def main():
            app = ServingApp(
                Options({"metrics-port": 0, "max-queue": 64,
                         "warmup-on-boot": True}),
                translate_lines=lambda lines: [ln.upper()
                                               for ln in lines],
                registry=r)
            await app.start()
            try:
                # golden buckets are w8 and w16; traffic lands in both
                await app.handle_text("a b c")
                await app.handle_text(
                    "one two three four five six seven eight nine")
            finally:
                await app.shutdown(drain_timeout=2)

        run(main())
        assert r.get("marian_compile_total") \
                .labels("boot-warmup", "w8").value == 1
        assert r.get("marian_compile_total") \
                .labels("boot-warmup", "w16").value == 1
        assert p.steady_recompiles() == 0
        _, events = obs.TRACER.snapshot()
        assert not [e for e in events if e["name"] == "perf.recompile"]

    def test_boot_warmup_runs_even_with_perf_off(self):
        """--warmup-on-boot is about warm jit caches, not telemetry: it
        must run (executor called per golden bucket) even when
        --perf-accounting is off — only the compile telemetry is
        skipped."""
        assert not obs.PERF.enabled
        calls = []

        async def main():
            app = ServingApp(
                Options({"metrics-port": 0, "max-queue": 64,
                         "warmup-on-boot": True}),
                translate_lines=lambda lines: (calls.append(list(lines))
                                               or list(lines)),
                registry=msm.Registry())
            await app.start()
            await app.shutdown(drain_timeout=2)

        run(main())
        # one warmup call per golden width bucket, before any traffic
        assert calls == [["hello", "a b c d"],
                         ["the quick brown fox jumps over the lazy dog"]]

    def test_boot_warmup_failure_stops_the_boot(self):
        """A warm-up that cannot translate is the failure the first
        client would meet — it is not logged and carried on from."""
        def refused(lines):
            raise RuntimeError("RESOURCE_EXHAUSTED: memory space smem")

        async def main():
            app = ServingApp(
                Options({"metrics-port": 0, "max-queue": 64,
                         "warmup-on-boot": True}),
                translate_lines=refused, registry=msm.Registry())
            try:
                await app.start()
            finally:
                await app.shutdown(drain_timeout=2)

        with pytest.raises(Exception, match="memory space smem"):
            run(main())


# ---------------------------------------------------------------------------
# scheduler exports (CPU stub): chip-seconds/token + headroom
# ---------------------------------------------------------------------------

class TestSchedulerPerfExports:
    def test_batch_path_exports_capacity_gauges(self):
        r = msm.Registry()
        p = enable_perf(r)

        def slowish(lines):
            time.sleep(0.01)
            return [ln.upper() for ln in lines]

        async def main():
            sched = ContinuousScheduler(slowish, registry=r,
                                        version_fn=lambda: "vCPU",
                                        window_s=0)
            p.set_capacity_inputs(sched.queued_units, 64)
            sched.start()
            for i in range(3):
                await sched.submit([f"w{i} w w", f"v{i} v"])
            await sched.stop()

        run(main())
        text = r.render()
        assert 'marian_perf_chip_seconds_per_token{model_version="vCPU"}' \
            in text
        cspt = r.get("marian_perf_chip_seconds_per_token") \
                .labels("vCPU").value
        assert cspt > 0
        assert r.get("marian_perf_device_seconds_total") \
                .labels("vCPU").value >= 0.03
        hr = p.headroom()
        assert 0.0 <= hr <= 1.0
        assert "marian_capacity_headroom_ratio" in text
        # device seconds are measured on the worker thread to the result
        # fence — the serve.batch span of a traced run carries them too
        assert lint_metrics_text(text) == []

    def test_bisection_device_time_still_accounted(self):
        r = msm.Registry()
        enable_perf(r)
        state = {"n": 0}

        def poison(lines):
            state["n"] += 1
            if "bad" in lines:
                raise ValueError("poison")
            return list(lines)

        async def main():
            sched = ContinuousScheduler(poison, registry=r, window_s=0.01)
            sched.start()
            f1 = sched.submit(["good one"])
            f2 = sched.submit(["bad"])
            assert await f1 == ["good one"]
            with pytest.raises(RuntimeError):
                await f2
            await sched.stop()

        run(main())
        # the failed + bisected batch's device time was spent and is
        # integrated (labels: version_fn default "unversioned")
        assert r.get("marian_perf_device_seconds_total") \
                .labels("unversioned").value > 0


# ---------------------------------------------------------------------------
# SLO engine: burn-rate math
# ---------------------------------------------------------------------------

def outcomes_counter(r):
    return r.counter("marian_serving_request_outcomes_total", "",
                     labels=("outcome", "model_version"))


def latency_hist(r):
    return r.histogram("marian_serving_request_latency_seconds", "")


class TestSloEngineMath:
    def test_availability_burn_and_budget(self):
        r = msm.Registry()
        c = outcomes_counter(r)
        clock = {"t": 0.0}
        eng = SloEngine(registry=r, availability=0.99, window_s=10,
                        clock=lambda: clock["t"])
        eng.tick(now=0.0)        # baseline: pre-engine history excluded
        c.labels("ok", "v").inc(99)
        c.labels("failure", "v").inc(1)
        st = eng.tick(now=1.0)
        av = st["objectives"]["availability"]
        # 1% bad on a 1% budget → burn exactly 1.0
        assert av["burn"]["10s"] == pytest.approx(1.0)
        assert not av["fast_burn"] and not av["slow_burn"]
        # burn 1.0 consumes budget at exactly the sustainable rate
        assert av["budget_remaining"] == pytest.approx(0.0, abs=1e-6)
        assert r.get("marian_slo_burn_rate") \
                .labels("availability", "10s").value \
            == pytest.approx(1.0)
        assert r.get("marian_slo_objective_target") \
                .labels("availability").value == pytest.approx(0.99)
        assert r.get("marian_slo_budget_remaining_ratio") \
                .labels("availability").value == pytest.approx(0.0,
                                                               abs=1e-6)

    def test_windowed_burn_recovers_as_errors_age_out(self):
        r = msm.Registry()
        c = outcomes_counter(r)
        clock = {"t": 0.0}
        eng = SloEngine(registry=r, availability=0.9, window_s=10,
                        clock=lambda: clock["t"])
        eng.tick(now=0.0)
        c.labels("failure", "v").inc(10)          # a burst of pure errors
        st = eng.tick(now=1.0)
        assert st["objectives"]["availability"]["burn"]["10s"] \
            == pytest.approx(10.0)                # 100% bad / 10% budget
        # 30s later the short window holds only fresh, clean traffic
        c.labels("ok", "v").inc(100)
        eng.tick(now=20.0)
        st = eng.tick(now=40.0)
        assert st["objectives"]["availability"]["burn"]["10s"] \
            == pytest.approx(0.0)
        # the slow (100s) window still remembers the burst
        assert st["objectives"]["availability"]["burn"]["100s"] > 0

    def test_latency_objective_reads_histogram_buckets(self):
        r = msm.Registry()
        h = latency_hist(r)
        eng = SloEngine(registry=r, p99_ms=250, window_s=10,
                        clock=lambda: 0.0)
        eng.tick(now=0.0)        # baseline
        for _ in range(98):
            h.observe(0.05)                        # under target
        h.observe(0.5)
        h.observe(2.0)                             # two breaches / 100
        st = eng.tick(now=1.0)
        lat = st["objectives"]["latency_p99"]
        # 2% over target on a 1% budget → burn 2.0
        assert lat["burn"]["10s"] == pytest.approx(2.0)

    def test_fast_burn_fires_event_alert_and_flight_dump(self, tmp_path):
        r = msm.Registry()
        c = outcomes_counter(r)
        obs.TRACER.enable()
        obs.FLIGHT.arm(str(tmp_path))
        eng = SloEngine(registry=r, availability=0.999, window_s=10,
                        clock=lambda: 0.0)
        obs.FLIGHT.add_snapshot_provider("slo", eng.state)
        try:
            eng.tick(now=0.0)
            c.labels("failure", "v").inc(50)       # 100% bad: burn 1000x
            eng.tick(now=1.0)
            assert r.get("marian_slo_alerts_total") \
                    .labels("availability", "fast").value == 1
            _, events = obs.TRACER.snapshot()
            names = [e["name"] for e in events]
            assert "slo.fast_burn" in names
            # the async dump lands shortly after
            deadline = time.time() + 5
            dumps = []
            while not dumps and time.time() < deadline:
                dumps = [f for f in os.listdir(tmp_path)
                         if f.startswith("flight-")
                         and "slo-fast-burn" in f]
                time.sleep(0.02)
            assert dumps, "fast-burn flight dump never appeared"
            payload = json.loads((tmp_path / dumps[0]).read_text())
            # the dump shows the PROMISE being broken, not just latencies
            assert payload["extra"]["slo"]["objectives"]["availability"][
                "fast_burn"] is True
            assert payload["slo"]["objectives"]["availability"][
                "target"] == 0.999
            # recovery emits the falling-edge event and no second alert
            c.labels("ok", "v").inc(100000)
            eng.tick(now=2.0)
            eng.tick(now=150.0)
            _, events = obs.TRACER.snapshot()
            assert "slo.recovered" in [e["name"] for e in events]
            assert r.get("marian_slo_alerts_total") \
                    .labels("availability", "fast").value == 1
        finally:
            obs.FLIGHT.remove_snapshot_provider("slo")

    def test_maybe_build_engine_flags(self):
        assert maybe_build_engine(Options({})) is None
        eng = maybe_build_engine(Options({"slo-p99-ms": 100,
                                          "slo-window": 5}),
                                 registry=msm.Registry())
        assert eng is not None and eng.window_s == 5
        with pytest.raises(ValueError):
            SloEngine(registry=msm.Registry())


# ---------------------------------------------------------------------------
# ACCEPTANCE: slow-translate fault → fast-burn → dump with SLO state
# ---------------------------------------------------------------------------

class TestSlowTranslateDrivesFastBurn:
    def test_injected_slow_decode_breaks_latency_slo(self, tmp_path):
        """MARIAN_FAULTS serving.translate=hang:0.05@* makes every device
        call slow; with --slo-p99-ms 10 declared, the burn-rate engine
        must raise the fast-burn alert, stamp the timeline, and dump
        flight state that shows the latency promise being broken."""
        # the process-wide registry, like production: the flight dump's
        # metrics member must hold the promise-breaking histogram
        obs.TRACER.enable()
        obs.FLIGHT.arm(str(tmp_path))
        eng = SloEngine(p99_ms=10, window_s=10, clock=time.monotonic)
        obs.FLIGHT.add_snapshot_provider("slo", eng.state)
        try:
            async def main():
                sched = ContinuousScheduler(lambda lines: list(lines),
                                            window_s=0)
                sched.start()
                eng.tick()
                with fp.active("serving.translate=hang:0.05@*"):
                    for i in range(4):
                        await sched.submit([f"slow {i}"])
                await sched.stop()

            run(main())
            st = eng.tick()
            lat = st["objectives"]["latency_p99"]
            assert lat["fast_burn"] is True      # 100% breach / 1% budget
            _, events = obs.TRACER.snapshot()
            assert "slo.fast_burn" in [e["name"] for e in events]
            deadline = time.time() + 5
            dumps = []
            while not dumps and time.time() < deadline:
                # .json only: the recorder writes a .<name>.json.tmp
                # and os.replace()s it into place — matching the tmp
                # name races the rename and read_text() gets ENOENT
                dumps = [f for f in os.listdir(tmp_path)
                         if "slo-fast-burn" in f
                         and f.endswith(".json")]
                time.sleep(0.02)
            assert dumps
            payload = json.loads((tmp_path / dumps[0]).read_text())
            assert payload["slo"]["objectives"]["latency_p99"][
                "fast_burn"] is True
            assert "marian_serving_request_latency_seconds" \
                in payload["metrics"]
        finally:
            obs.FLIGHT.remove_snapshot_provider("slo")


# ---------------------------------------------------------------------------
# /sloz endpoint
# ---------------------------------------------------------------------------

class TestSlozEndpoint:
    def test_sloz_roundtrip_with_engine_and_perf(self):
        r = msm.Registry()
        enable_perf(r)
        c = outcomes_counter(r)
        c.labels("ok", "v").inc(10)
        eng = SloEngine(registry=r, availability=0.99, window_s=10)
        eng.tick()
        srv = msm.MetricsServer(0, registry=r,
                                routes=slo_routes(lambda: eng)).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/sloz").read())
            assert doc["slo"]["enabled"] is True
            assert "availability" in doc["slo"]["objectives"]
            assert doc["perf"]["enabled"] is True
            assert "headroom" in doc["perf"]
        finally:
            srv.close()

    def test_sloz_disabled_still_answers(self):
        srv = msm.MetricsServer(0, registry=msm.Registry(),
                                routes=slo_routes(lambda: None)).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/sloz").read())
            assert doc["slo"] == {"enabled": False}
        finally:
            srv.close()

    def test_serving_app_routes_sloz_and_stops_engine(self):
        async def main():
            app = ServingApp(
                Options({"metrics-port": 0, "max-queue": 16,
                         "slo-p99-ms": 100.0, "slo-eval-interval": 0.1}),
                translate_lines=lambda lines: list(lines),
                registry=msm.Registry())
            assert app.slo is not None
            await app.start()
            assert app.slo._thread is not None
            await app.shutdown(drain_timeout=2)
            assert app.slo._thread is None

        run(main())


# ---------------------------------------------------------------------------
# process self-metrics + Prometheus text-format lint of a real scrape
# ---------------------------------------------------------------------------

class TestProcessMetricsAndPromlint:
    def test_process_metrics_registered_and_sane(self):
        r = msm.Registry()
        msm.register_process_metrics(r)
        text = r.render()
        for name in ("process_start_time_seconds",
                     "process_uptime_seconds",
                     "process_resident_memory_bytes",
                     "process_open_fds"):
            assert name in text
        assert r.get("process_resident_memory_bytes").value > 1e6
        assert r.get("process_open_fds").value > 0
        assert 0 <= r.get("process_uptime_seconds").value < 1e7

    def test_real_scrape_lints_clean_default_and_exemplars(self):
        r = msm.Registry()
        h = r.histogram("t_lat_seconds", "x", buckets=(0.1, 1.0),
                        labels=("lane",))
        h.labels("a").observe(0.05, trace_id="ex01")
        h.labels("a").observe(5.0)
        r.counter("t_ok_total", "x").inc(3)
        g = r.gauge("t_depth", "x")
        g.set(7)
        srv = msm.MetricsServer(0, registry=r).start()
        try:
            base = f"http://127.0.0.1:{srv.port}/metrics"
            plain = urllib.request.urlopen(base).read().decode()
            assert lint_metrics_text(plain) == []
            # process self-metrics rode along with the server start
            assert "process_open_fds" in plain
            with_ex = urllib.request.urlopen(
                base + "?exemplars=1").read().decode()
            assert 'trace_id="ex01"' in with_ex
            assert lint_metrics_text(with_ex, allow_exemplars=True) == []
            # and the exemplar form is a violation under strict 0.0.4
            assert any("exemplar" in p
                       for p in lint_metrics_text(with_ex))
        finally:
            srv.close()

    @pytest.mark.parametrize("bad,why", [
        ("up 1", "no preceding # TYPE"),
        ("# TYPE m counter\nm{le=} 1", "malformed labels"),
        ("# TYPE m counter\nm notanumber", "unparseable value"),
        ("# TYPE m counter\nm 1\nm 1", "duplicate series"),
        ("# TYPE h histogram\nh_bucket{le=\"1\"} 2\n"
         "h_bucket{le=\"+Inf\"} 1\nh_sum 1\nh_count 1",
         "not cumulative"),
        ("# TYPE h histogram\nh_bucket{le=\"1\"} 1\nh_sum 1\nh_count 1",
         "missing +Inf"),
        ("# TYPE h histogram\nh_bucket{le=\"+Inf\"} 2\nh_sum 1\n"
         "h_count 1", "!= _count"),
        ("# TYPE m counter\nm{a=\"x\" b=\"y\"} 1", "malformed labels"),
        ("# TYPE m counter\nm{a=\"x\"b=\"y\"} 1", "malformed labels"),
    ])
    def test_lint_catches_classic_breakage(self, bad, why):
        probs = lint_metrics_text(bad)
        assert probs, why
        assert any(why.split()[0] in p or why in p for p in probs), \
            (why, probs)

    def test_lint_allows_trailing_comma_labels(self):
        # legal per the text format; parsers accept it
        assert lint_metrics_text(
            "# TYPE m counter\nm{a=\"1\",} 1") == []


# ---------------------------------------------------------------------------
# ACCEPTANCE: loadgen --sweep reads the gauges back over a real server
# ---------------------------------------------------------------------------

def _load_loadgen():
    spec = importlib.util.spec_from_file_location(
        "loadgen", os.path.join(ROOT, "scripts", "loadgen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestLoadgenSweep:
    def test_sweep_capacity_table_against_cpu_server(self, capsys):
        registry = msm.REGISTRY        # loadgen scrapes the real surface
        enable_perf(registry)
        started = threading.Event()
        info = {}

        def server_thread():
            async def main():
                app = ServingApp(
                    Options({"metrics-port": 0, "max-queue": 256,
                             "batch-token-budget": 256}),
                    translate_lines=lambda lines: [ln.upper()
                                                   for ln in lines])
                obs.PERF.set_capacity_inputs(app.scheduler.queued_units,
                                             256)
                await app.start()
                server = await asyncio.start_server(
                    _make_tcp_handler(app), "127.0.0.1", 0)
                info["port"] = server.sockets[0].getsockname()[1]
                info["loop"] = asyncio.get_event_loop()
                info["stop"] = asyncio.Event()
                started.set()
                async with server:
                    await info["stop"].wait()
                await app.shutdown(drain_timeout=2)

            asyncio.run(main())

        t = threading.Thread(target=server_thread, daemon=True)
        t.start()
        assert started.wait(10)
        metrics_srv = msm.MetricsServer(0, registry=registry).start()
        try:
            loadgen = _load_loadgen()
            rc = loadgen.main([
                "--port", str(info["port"]), "--transport", "tcp",
                "--metrics-port", str(metrics_srv.port),
                "--sweep", "20,40", "--duration", "0.5",
                "--sentences", "2", "--words", "4"])
            assert rc == 0
        finally:
            metrics_srv.close()
            info["loop"].call_soon_threadsafe(info["stop"].set)
            t.join(timeout=10)
        out = capsys.readouterr().out
        assert "chip_s/tok" in out and "headroom" in out \
            and "hr_gauge" in out
        rows = [ln for ln in out.splitlines()
                if ln.strip().startswith(("20", "40"))]
        assert len(rows) == 2
        # chip-seconds/token + both headroom readings (step-local and
        # the server's rolling gauge) read back as real numbers
        for ln in rows:
            cspt = float(ln.split()[-3])
            assert cspt > 0
            for col in (-2, -1):
                hr = float(ln.split()[col])
                assert 0.0 <= hr <= 1.0
        assert "capacity:" in out


# ---------------------------------------------------------------------------
# metric census: every registered series is exercised by a test
# (MT-METRIC-UNTESTED's corpus — see analysis/rules/metrics_hygiene.py)
# ---------------------------------------------------------------------------

class TestMetricCensus:
    def test_training_scheduler_series_render(self):
        from marian_tpu.training.scheduler import Scheduler
        from marian_tpu.training.training_state import TrainingState
        enable_perf()
        obs.PERF.set_geometry(emb=16, ffn=32, enc_depth=1, dec_depth=1,
                              vocab=50, n_devices=1, peak_flops=1e9)
        sched = Scheduler(Options({"disp-freq": "1u"}), TrainingState())
        sched.update(2.5, labels=10, sentences=2, src_words=12, lr=0.1)
        text = msm.REGISTRY.render()
        for name in ("marian_train_cost", "marian_train_words_per_second",
                     "marian_train_learn_rate",
                     "marian_train_updates_total",
                     "marian_train_labels_total",
                     "marian_train_chip_seconds_per_token",
                     "marian_train_mfu"):
            assert name in text, name
        assert msm.REGISTRY.get(
            "marian_train_chip_seconds_per_token").value > 0

    def test_lifecycle_controller_series_render(self):
        r = msm.Registry()
        ctrl = SwapController(lambda d, m: (lambda lines: list(lines)),
                              metrics_registry=r)
        ctrl.seed_live(0, "boot", lambda lines: list(lines))
        ctrl.route(["x"])
        text = r.render()
        for name in ("marian_lifecycle_warming",
                     "marian_model_latency_seconds",
                     "marian_model_requests_total"):
            assert name in text, name

    def test_compile_backend_series_registered(self):
        r = msm.Registry()
        enable_perf(r)
        # the jax listener path is environment-dependent; the series
        # itself must exist (and stay parseable) regardless
        obs.PERF.m_backend_s.labels("steady-state").inc(0.0)
        assert "marian_compile_backend_seconds_total" in r.render()
