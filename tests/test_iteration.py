"""Iteration-level continuous batching (ISSUE 10): the paged slot
engine (translator/iteration.py), the paged greedy restructuring
(translator/greedy.py), and the serving scheduler's
--batching-mode iteration worker — mid-decode joins, page-priced
admission, pool-exhaustion behavior (defer or shed, never a deadlocked
step), join-time queue accounting, and deterministic replay. Runs
under JAX_PLATFORMS=cpu with a tiny real transformer."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.data.vocab import DefaultVocab
from marian_tpu.serving import metrics as msm
from marian_tpu.serving.admission import AdmissionController, Overloaded
from marian_tpu.serving.scheduler import ContinuousScheduler
from marian_tpu.translator.greedy import greedy_decode, greedy_decode_paged
from marian_tpu.translator.iteration import (FATAL_REASONS,
                                             PagedDecodeEngine)

from tests.test_beam_search import tiny_model


@pytest.fixture(scope="module", autouse=True)
def _lockdep_witness(lockdep_witness):
    """KVPool._lock / PagedDecodeEngine._lock cross the device-worker
    and metrics-scrape threads here; the shared witness asserts the
    observed acquisition orders stay inside the static lattice."""
    yield


@pytest.fixture(scope="module", autouse=True)
def _ownership_witness(ownership_witness):
    """Every page this suite's engines claim/release/adopt records its
    acting call site; the shared witness asserts observed ownership
    pairings stay inside the static ownership graph (ISSUE 15)."""
    yield


@pytest.fixture(scope="module", autouse=True)
def _jitwit_witness(jitwit_witness):
    """Every backend compile this suite's engines trigger is attributed
    to its jit site; the shared witness asserts compiles stay inside the
    static jit model and no instrumented key retraced (ISSUE 17)."""
    yield


VOCAB_WORDS = [" ".join(f"w{i}" for i in range(35))]


@pytest.fixture(scope="module")
def tiny():
    vocab = DefaultVocab.build(VOCAB_WORDS)
    model, params, _ = tiny_model(vocab=len(vocab), seed=7,
                                  **{"dec-depth": 2, "enc-depth": 2})
    return model, params, vocab


def make_engine(tiny, registry=None, **kw):
    model, params, vocab = tiny
    args = dict(max_rows=4, page_len=4, src_len_cap=8,
                max_length_cap=12, registry=registry)
    args.update(kw)
    return PagedDecodeEngine(model, params, vocab, vocab, **args)


TEXTS = ["w3 w4 w5", "w6 w7", "w8 w9 w10 w11", "w2 w3",
         "w4 w4 w4 w4 w4"]


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# paged greedy restructuring: rows as slots
# ---------------------------------------------------------------------------

class TestGreedyPaged:
    def test_matches_dense_greedy(self, rng, tiny):
        model, params, _ = tiny
        b, ts = 5, 7
        ids = np.zeros((b, ts), np.int32)
        mask = np.zeros((b, ts), np.float32)
        for i, n in enumerate(rng.randint(3, ts + 1, size=b)):
            ids[i, :n] = rng.randint(3, 35, n)
            mask[i, :n] = 1.0
        dense = greedy_decode(model, params, jnp.asarray(ids),
                              jnp.asarray(mask), 12)
        paged = greedy_decode_paged(model, params, jnp.asarray(ids),
                                    jnp.asarray(mask), 12, page_len=4)
        n = min(dense.shape[1], paged.shape[1])
        assert (np.asarray(dense)[:, :n] == paged[:, :n]).all()


# ---------------------------------------------------------------------------
# the slot engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_outputs_independent_of_join_schedule(self, tiny):
        """THE iteration-batching correctness property: a sentence's
        tokens cannot depend on who shares its steps or when it
        joined."""
        batch = make_engine(tiny, max_rows=4).decode_texts(TEXTS)
        solo = [make_engine(tiny, max_rows=1).decode_texts([t])[0]
                for t in TEXTS]
        assert batch == solo

    def test_mid_decode_join_and_early_leave(self, tiny):
        eng = make_engine(tiny, max_rows=3)
        r0 = eng.admit_and_step([(0, TEXTS[0]), (2, TEXTS[2])])
        assert sorted(r0.accepted) == [0, 2]
        assert r0.mid_decode_joins == 0         # nothing was running yet
        for _ in range(3):
            eng.admit_and_step([])
        r = eng.admit_and_step([(1, TEXTS[1])])
        assert r.accepted == [1]
        assert r.mid_decode_joins == 1          # joined a RUNNING decode
        outs = dict(r0.finished + r.finished)
        guard = 0
        free_seen = []
        while not eng.idle():
            free_seen.append(eng.free_pages())
            rr = eng.admit_and_step([])
            outs.update(dict(rr.finished))
            guard += 1
            assert guard < 100
        # early leave: pages were released as sentences finished, not
        # all at once at the end
        assert eng.free_pages() == eng.pool.usable_pages
        assert len(set(free_seen)) > 1
        solo = [make_engine(tiny, max_rows=1).decode_texts([t])[0]
                for t in TEXTS[:3]]
        assert [outs[i] for i in (0, 1, 2)] == solo

    def test_multi_step_rounds_same_outputs(self, tiny):
        """steps_per_round > 1 (one jitted scan per round) must yield
        EXACTLY the per-step engine's outputs — the greedy chain is the
        same; only the admission granularity changes. A row finishing
        mid-scan self-feeds until the host cuts at its EOS; the
        overshoot must never leak into any sentence's text."""
        one = make_engine(tiny, max_rows=4).decode_texts(TEXTS)
        four = make_engine(tiny, max_rows=4,
                           steps_per_round=4).decode_texts(TEXTS)
        assert one == four

    def test_deterministic_replay(self, tiny):
        """An identical join/evict schedule replayed on a fresh engine
        yields identical outputs (the acceptance criterion's replay
        pin: trash-page writes and page reuse are deterministic)."""
        def one_run():
            eng = make_engine(tiny, max_rows=2)
            outs = {}
            sched = [[(0, TEXTS[0]), (1, TEXTS[1])], [], [(2, TEXTS[2])],
                     [], [(3, TEXTS[3])], [(4, TEXTS[4])]]
            pending = []
            i = 0
            guard = 0
            while i < len(sched) or pending or not eng.idle():
                joins = (sched[i] if i < len(sched) else []) + pending
                pending = []
                res = eng.admit_and_step(joins)
                for key, why in res.rejected:
                    assert why not in FATAL_REASONS
                    pending.append((key, dict(enumerate(TEXTS))[key]))
                outs.update(dict(res.finished))
                i += 1
                guard += 1
                assert guard < 200
            return [outs[k] for k in sorted(outs)]
        assert one_run() == one_run()

    def test_eviction_mid_decode_frees_pages(self, tiny):
        eng = make_engine(tiny, max_rows=2)
        eng.admit_and_step([(0, TEXTS[0]), (1, TEXTS[2])])
        used_before = eng.pool.used_pages()
        assert used_before > 0
        res = eng.admit_and_step([], evicts=[0])
        assert eng.pool.used_pages() < used_before
        assert eng.active_rows() == 1
        # the evicted key never appears in finished afterwards
        guard = 0
        while not eng.idle():
            res = eng.admit_and_step([])
            assert all(k != 0 for k, _ in res.finished)
            guard += 1
            assert guard < 100

    def test_pool_exhaustion_defers_join_never_stalls_step(self, tiny):
        """A pool too small for two sentences: the second DEFERS
        (reason no_pages) while the first keeps decoding — the step
        loop never deadlocks — and joins once pages free up."""
        # one sentence needs ceil(12/4)=3 pages; pool holds exactly 3
        eng = make_engine(tiny, max_rows=2,
                          pool_bytes=3 * 2 * 2 * 2 * 4 * 8 * 4)
        assert eng.pool.usable_pages == 3
        r = eng.admit_and_step([(0, TEXTS[0]), (1, TEXTS[1])])
        assert r.accepted == [0]
        assert r.rejected == [(1, "no_pages")]
        guard = 0
        joined_late = False
        outs = {}
        while not eng.idle() or not joined_late:
            res = eng.admit_and_step(
                [] if joined_late else [(1, TEXTS[1])])
            if 1 in res.accepted:
                joined_late = True
            for key, why in res.rejected:
                assert why == "no_pages"
            outs.update(dict(res.finished))
            guard += 1
            assert guard < 200
        while not eng.idle():
            outs.update(dict(eng.admit_and_step([]).finished))
        assert set(outs) == {0, 1}

    def test_oversized_sentence_is_a_fatal_reject(self, tiny):
        """A sentence that could NEVER fit (needs more pages than the
        whole pool) must be rejected permanently — deferring it would
        deadlock the queue head forever."""
        eng = make_engine(tiny, max_rows=2,
                          pool_bytes=1 * 2 * 2 * 2 * 4 * 8 * 4)
        assert eng.pool.usable_pages == 1
        r = eng.admit_and_step([(0, TEXTS[0])])   # cap 12 -> 3 pages
        assert r.rejected and r.rejected[0][1] in FATAL_REASONS

    def test_rows_past_the_kernel_smem_bound_fail_at_startup(self, tiny):
        # tests/test_chip_compile.py compiles the kernel AT the bound;
        # past it the chip compiler refuses, so the engine must first
        from marian_tpu.ops.pallas.kv_pool import paged_kernel_max_rows
        bound = paged_kernel_max_rows(3)
        with pytest.raises(ValueError, match=f"SMEM bound of {bound} rows"):
            make_engine(tiny, max_rows=bound + 1, row_buckets=(bound + 1,))

    def test_src_too_long_is_fatal(self, tiny):
        eng = make_engine(tiny)
        long_text = " ".join("w3" for _ in range(50))
        r = eng.admit_and_step([(0, long_text)])
        assert r.rejected == [(0, "src_too_long")]

    def test_fragmentation_and_gauges(self, tiny):
        reg = msm.Registry()
        eng = make_engine(tiny, registry=reg)
        eng.admit_and_step([(0, TEXTS[0])])
        text = reg.render()
        assert "marian_serving_kv_pool_pages" in text
        assert "marian_serving_kv_pool_pages_free" in text
        assert "marian_serving_kv_pool_fragmentation_ratio" in text
        assert "marian_serving_active_rows 1" in text
        # one token written into 3 claimed pages of 4 slots each
        assert 0.0 < eng.fragmentation() < 1.0
        guard = 0
        while not eng.idle():
            eng.admit_and_step([])
            guard += 1
            assert guard < 100
        assert eng.fragmentation() == 0.0


# ---------------------------------------------------------------------------
# scheduler: --batching-mode iteration
# ---------------------------------------------------------------------------

def make_sched(tiny, registry=None, engine=None, **kw):
    reg = registry if registry is not None else msm.Registry()
    eng = engine if engine is not None else make_engine(tiny,
                                                        registry=reg)
    sched = ContinuousScheduler(None, registry=reg,
                                batching_mode="iteration", engine=eng,
                                window_s=0.0, **kw)
    return sched, eng, reg


class TestIterationScheduler:
    def test_requires_engine(self):
        with pytest.raises(ValueError):
            ContinuousScheduler(lambda ls: ls,
                                registry=msm.Registry(),
                                batching_mode="iteration")
        with pytest.raises(ValueError):
            ContinuousScheduler(lambda ls: ls,
                                registry=msm.Registry(),
                                batching_mode="bogus")

    def test_end_to_end_resolves_and_counts_joins(self, tiny):
        sched, eng, reg = make_sched(tiny)

        async def main():
            sched.start()
            f1 = sched.submit(TEXTS[:2])
            await asyncio.sleep(0.05)
            f2 = sched.submit([TEXTS[2]])     # lands mid-decode
            r1, r2 = await f1, await f2
            await sched.stop()
            return r1, r2

        r1, r2 = run(main())
        solo = [make_engine(tiny, max_rows=1).decode_texts([t])[0]
                for t in TEXTS[:3]]
        assert r1 == solo[:2] and r2 == [solo[2]]
        assert sched.m_joins.value == 3
        assert sched.m_mid_joins.value >= 1
        assert sched.m_steps.value > 0
        text = reg.render()
        assert "marian_serving_joins_total 3" in text
        assert "marian_serving_mid_decode_joins_total" in text
        assert "marian_serving_decode_steps_total" in text
        assert "marian_serving_step_active_rows" in text
        assert "marian_serving_queue_depth_pages 0" in text
        assert "marian_serving_evictions_total 0" in text

    def test_queue_ms_stops_at_join_time(self, tiny):
        """ISSUE 10 small fix: a sentence that QUEUED behind a full
        pool must report that wait as queue_ms and only its own decode
        as service_ms — it must not inherit the running decode's
        dispatch-time accounting. (#trace breakdown regression)"""
        # pool fits ONE sentence: the second must queue until the
        # first finishes
        eng = make_engine(tiny, max_rows=2,
                          pool_bytes=3 * 2 * 2 * 2 * 4 * 8 * 4)
        sched, eng, reg = make_sched(tiny, engine=eng)
        meta_a, meta_b = {}, {}

        async def main():
            sched.start()
            fa = sched.submit([TEXTS[0]], meta=meta_a, trace_id="ta")
            await asyncio.sleep(0.02)
            fb = sched.submit([TEXTS[3]], meta=meta_b, trace_id="tb")
            await fa
            await fb
            await sched.stop()

        run(main())
        assert meta_a["outcome"] == "ok" and meta_b["outcome"] == "ok"
        # b queued behind a's pool claim: it must have WAITED in queue
        # and then decoded quickly — the wait lands in queue_s, not in
        # service_s (inheriting a's dispatch time would zero it)
        assert meta_b["queue_s"] > 0.0
        assert meta_b["service_s"] > 0.0
        # a joined immediately; essentially no queueing
        assert meta_a["queue_s"] <= meta_b["queue_s"]
        # b's queue wait covers most of a's decode: service began only
        # at b's OWN join
        assert meta_b["queue_s"] >= 0.5 * meta_a["service_s"]

    def test_cancellation_mid_decode_evicts(self, tiny):
        sched, eng, reg = make_sched(tiny)

        async def main():
            sched.start()
            f1 = sched.submit([TEXTS[4]])
            await asyncio.sleep(0.05)         # decoding now
            f1.cancel()
            f2 = sched.submit([TEXTS[1]])     # keeps the loop turning
            await f2
            for _ in range(50):
                if sched.m_evictions.value:
                    break
                await asyncio.sleep(0.01)
            await sched.stop()

        run(main())
        assert sched.m_evictions.value >= 1
        assert eng.idle()
        assert eng.free_pages() == eng.pool.usable_pages

    def test_oversized_request_fails_explicitly(self, tiny):
        """Pool exhaustion of the permanent kind sheds EXPLICITLY: a
        sentence larger than the whole pool resolves with an error —
        never a hung future, never a stalled step loop."""
        eng = make_engine(tiny, max_rows=2,
                          pool_bytes=1 * 2 * 2 * 2 * 4 * 8 * 4)
        sched, eng, reg = make_sched(tiny, engine=eng)

        async def main():
            sched.start()
            f = sched.submit([TEXTS[0]])
            with pytest.raises(RuntimeError, match="cannot be admitted"):
                await asyncio.wait_for(f, timeout=10)
            await sched.stop()

        run(main())

    def test_admission_prices_pages(self, tiny):
        """Page-debt admission: queued page estimates gate new requests
        (the iteration-mode analog of the sentence bound)."""
        sched, eng, reg = make_sched(tiny)
        adm = AdmissionController(0, sched.queued_units, registry=reg,
                                  max_queue_pages=5,
                                  pages_fn=sched.queued_pages)
        # nothing queued: a 2-page request passes
        adm.admit(1, n_pages=2)
        with pytest.raises(Overloaded, match="page debt"):
            adm.admit(1, n_pages=6)
        assert "pages_full" in reg.render()

    def test_queued_pages_counts_backlog(self, tiny):
        """With the worker NOT running, queued sentences owe pages."""
        sched, eng, reg = make_sched(tiny)

        async def main():
            fut = sched.submit(TEXTS[:3])     # worker never started
            pages = sched.queued_pages()
            assert pages == sum(eng.pages_for_text(t)
                                for t in TEXTS[:3])
            fut.cancel()
            # cancellation discounts the dead units immediately
            await asyncio.sleep(0)
            assert sched.queued_pages() == 0

        run(main())


# ---------------------------------------------------------------------------
# compile-cache hygiene (ISSUE 17): the closed shape set + round-key
# warmup telemetry
# ---------------------------------------------------------------------------

class TestClosedShapeSet:
    def test_grid_warmed_engine_pays_zero_postwarm_compiles(self, tiny):
        """THE closed-shape-set regression: warm a real engine across
        its full bucket grid (warm_grid), then drive mixed-length
        mixed-batch traffic through it — the jit retrace witness must
        observe ZERO backend compiles in the window. This is the
        executable form of 'compile once, serve forever': every shape
        steady-state traffic can reach was already compiled off the
        serving path."""
        from marian_tpu.common import jitwit
        eng = make_engine(tiny)
        driven = eng.warm_grid()
        assert driven, "warm_grid drove nothing"
        with jitwit.strict() as w:
            out = eng.decode_texts(TEXTS)          # mixed lengths, 5 rows
            out2 = eng.decode_texts(TEXTS[1:3])    # different mix
        assert len(out) == len(TEXTS) and len(out2) == 2
        assert w.compiles == [], (
            "post-warm traffic recompiled — the warm grid does not "
            f"close the engine's shape set: {w.compiles}")

    def test_unwarmed_engine_does_compile_in_window(self, tiny):
        """Sanity for the regression above: the SAME traffic on a cold
        engine does compile — proving the strict window actually
        observes this engine's compiles (no vacuous pass)."""
        from marian_tpu.common import jitwit
        eng = make_engine(tiny)
        with jitwit.strict() as w:
            eng.decode_texts(TEXTS[:2])
        assert any("translator/iteration.py" in site
                   for site, _ in w.compiles)


class TestRoundKeyWarmup:
    def test_round_key_vocabulary(self):
        from marian_tpu.obs.perf import round_bucket_key
        assert round_bucket_key(4, 16, 2) == "r4.w16.s2"

    def test_engine_grid_smoke_closes_steady_state_rounds(self, tiny):
        """Satellite 1: lifecycle warmup smokes the engine's bucket
        grid and registers every (row bucket, encode width, steps)
        round key as warm — a steady-state round landing on any grid
        key is NOT a recompile incident, while an off-grid key still
        fires one (same discipline as request-mode width buckets)."""
        from marian_tpu import obs
        from marian_tpu.obs.perf import TRIGGER_SWAP, round_bucket_key
        from marian_tpu.serving.lifecycle.warmup import smoke_engine_grid
        from marian_tpu.translator.iteration import EngineExecutor

        reg = msm.Registry()
        obs.PERF.reset()
        obs.PERF.enable(registry=reg, hook_jax=False)
        eng = make_engine(tiny)
        smoke_engine_grid(EngineExecutor(eng), "vG", TRIGGER_SWAP, "test")
        # every grid pairing is warm: a round on any (rb, enc_w, steps)
        # from the engine's own tables is not an incident
        steps = eng.steps_per_round
        for rb in eng.row_buckets:
            for enc_w in eng.encode_widths():
                obs.PERF.record_batch(
                    "vG", rows=rb, width=rb, src_tokens=4, trg_tokens=4,
                    device_s=0.01,
                    bucket_key=round_bucket_key(rb, enc_w, steps))
        assert obs.PERF.steady_recompiles() == 0
        # an off-grid round key is still a steady-state incident
        obs.PERF.record_batch(
            "vG", rows=1, width=1, src_tokens=4, trg_tokens=4,
            device_s=0.01, bucket_key=round_bucket_key(99, 512, 7))
        assert obs.PERF.steady_recompiles() == 1

    def test_warm_executor_drives_engine_grid(self, tiny):
        """warm_executor on an iteration-mode executor reaches the
        engine grid smoke (the lifecycle wiring, not just the helper)."""
        from marian_tpu import obs
        from marian_tpu.obs.perf import round_bucket_key
        from marian_tpu.serving.lifecycle import warmup
        from marian_tpu.translator.iteration import EngineExecutor

        reg = msm.Registry()
        obs.PERF.reset()
        obs.PERF.enable(registry=reg, hook_jax=False)
        eng = make_engine(tiny)
        ex = warmup.warm_executor(
            "bundle-x", None, lambda d, m: EngineExecutor(eng),
            ["w3 w4"], version="vW")
        assert ex.engine is eng
        # a grid round key was registered warm by the smoke
        obs.PERF.record_batch(
            "vW", rows=1, width=1, src_tokens=2, trg_tokens=2,
            device_s=0.01,
            bucket_key=round_bucket_key(eng.row_buckets[0],
                                        eng.encode_widths()[0],
                                        eng.steps_per_round))
        assert obs.PERF.steady_recompiles() == 0
