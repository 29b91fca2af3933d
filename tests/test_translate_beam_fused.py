"""ISSUE 18: the on-device fused beam merge + multi-step beam rounds.

Pins, against the per-step HOST merge (the pre-ISSUE-18 path, kept as
the A/B baseline):
- token AND raw-score parity on mixed-length traffic, single-step and
  multi-step rounds (different caps freeze sentences MID-round — the
  in-scan EOS masks carry frozen hypotheses through remaining steps);
- the flat top-k tie-break EXACTLY (value desc, flat index asc — a
  numpy reference over an engineered all-ties grid);
- shortlist and force-decode parity through the fused path;
- COW safety: the pool auditor runs every round (MARIAN_POOL_AUDIT=1,
  conftest) over state produced by DEVICE-computed retable diffs, and
  a seeded bad diff (beam.diff_corrupt) is proven to be CAUGHT;
- the closed shape set: a warm_grid-warmed fused engine serves mixed
  traffic with ZERO backend compiles in a strict jitwit window;
- the merge/steps option surface (engine clamps + boot validation).

Runs under JAX_PLATFORMS=cpu with the same tiny real transformer as
tests/test_beam_iteration.py."""

import numpy as np
import pytest

from marian_tpu.common import faultpoints as fp
from marian_tpu.common import jitwit
from marian_tpu.data.vocab import DefaultVocab
from marian_tpu.ops.pallas.kv_pool import PoolCorruption
from marian_tpu.translator.beam_iteration import (PagedBeamEngine,
                                                  fused_merge)
from marian_tpu.translator.beam_search import NEG_INF
from marian_tpu.translator.decode_features import FeaturePlane

from tests.test_beam_search import tiny_model
from tests.test_decode_features import sl_gen  # noqa: F401  (fixture)


@pytest.fixture(scope="module", autouse=True)
def _lockdep_witness(lockdep_witness):
    yield


@pytest.fixture(scope="module", autouse=True)
def _ownership_witness(ownership_witness):
    """The fused round's roundfresh/cow hold owners ride the same
    claim/share/retable handoffs the witness audits."""
    yield


@pytest.fixture(scope="module", autouse=True)
def _jitwit_witness(jitwit_witness):
    """The beam-scan jit (bstep) compiles here must map to sites the
    static jit model predicts, with no instrumented-key retrace."""
    yield


VOCAB_WORDS = [" ".join(f"w{i}" for i in range(35))]
# mixed lengths on purpose: sentences reach EOS/cap at different step
# counts, so multi-step rounds freeze some sentences mid-scan while
# others keep decoding — the masking the fused path must get right
TEXTS = ["w3 w4 w5", "w6 w7", "w8 w9 w10 w11", "w2 w3",
         "w4 w4 w4 w4 w4"]
K = 3


@pytest.fixture(scope="module")
def tiny():
    vocab = DefaultVocab.build(VOCAB_WORDS)
    model, params, _ = tiny_model(vocab=len(vocab), seed=7,
                                  **{"dec-depth": 2, "enc-depth": 2})
    return model, params, vocab


def make_engine(tiny, registry=None, prefix=None, features=None, **kw):
    model, params, vocab = tiny
    args = dict(beam_size=K, normalize=0.6, max_rows=2 * K, page_len=4,
                src_len_cap=8, max_length_cap=12, registry=registry,
                prefix_cache=prefix, features=features)
    args.update(kw)
    return PagedBeamEngine(model, params, vocab, vocab, **args)


def drive(eng, texts, metas=None):
    outs, infos = {}, {}
    pending = list(enumerate(texts))
    guard = 0
    while pending or not eng.idle():
        joins = []
        while pending and len(joins) < max(1, eng.free_slots()):
            key, text = pending.pop(0)
            if metas is not None:
                joins.append((key, text, metas[key]))
            else:
                joins.append((key, text))
        res = eng.admit_and_step(joins)
        for key, why in res.rejected:
            assert why in ("no_slot", "no_pages"), (key, why)
            pending.insert(0, (key, texts[key]))
        for key in res.pool_evicted:
            pending.insert(0, (key, texts[key]))
        outs.update(dict(res.finished))
        infos.update(res.finished_info)
        guard += 1
        assert guard < 1000, "beam decode failed to converge"
    assert eng.audit(context="test") == []
    return outs, infos


def assert_parity(a_infos, b_infos):
    """Token lists AND raw f32 path scores bitwise equal per sentence."""
    assert set(a_infos) == set(b_infos)
    for k in a_infos:
        assert a_infos[k]["tokens"] == b_infos[k]["tokens"], k
        assert np.float32(a_infos[k]["score"]) \
            == np.float32(b_infos[k]["score"]), k
        assert a_infos[k]["length"] == b_infos[k]["length"], k


# ---------------------------------------------------------------------------
# merge parity: fused vs host, plain / multi-step / shortlist / forced
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def host_baseline(tiny):
    """One host-merge drive of TEXTS — the baseline arm every parity
    test compares against. Module-scoped: the host engine is the A/B
    reference, identical for every test, so building it per test would
    just re-pay its jit warm cost on a 1-core CI box."""
    return drive(make_engine(tiny, merge="host"), TEXTS)


@pytest.fixture(scope="module")
def fused3_run(tiny):
    """One steps=3 fused engine driven over TEXTS once, shared by the
    multi-step parity test and the audit/drain test — same engine,
    same traffic: one asserts what came OUT, the other what the pool
    looks like AFTER."""
    eng = make_engine(tiny, merge="fused", steps_per_round=3)
    o, i = drive(eng, TEXTS)
    return eng, o, i


class TestMergeParity:
    def test_fused_matches_host_single_step(self, tiny, host_baseline):
        """THE merge-parity property: one fused round step produces the
        tokens and raw path scores of the per-sentence host merge, on
        mixed-length traffic (mid-stream joins, staggered finishes)."""
        host_o, host_i = host_baseline
        fused_o, fused_i = drive(make_engine(tiny, merge="fused"), TEXTS)
        assert host_o == fused_o
        assert_parity(host_i, fused_i)

    def test_fused_multistep_matches_host(self, host_baseline,
                                          fused3_run):
        """steps_per_round>1 (the tentpole's whole point — one host
        sync per N tokens): sentences hit EOS at different steps INSIDE
        a round, so the in-scan freeze masks carry frozen hypotheses as
        {EOS: score} candidates through the remaining steps. Output
        must not change by a bit vs the single-step host baseline.
        steps=3 does not divide the tiny cap, so rounds truncate AND
        freeze mid-scan; steps=2 adds no distinct regime (the
        shortlist + diff-safety tests drive it)."""
        host_o, host_i = host_baseline
        _, o, i = fused3_run
        assert host_o == o
        assert_parity(host_i, i)

    def test_fused_shortlist_matches_host(self, tiny, sl_gen):  # noqa: F811
        """Shortlisted rows merge in COORD space on device and map back
        through the block's shortlist in-graph (take_along_axis) — the
        host merge's coord->vocab mapping, fused. EOS sits at coord 0
        by shortlist construction, which the frozen-row candidate
        relies on."""
        plane = FeaturePlane(shortlist_gen=sl_gen, k_static=24)
        host_o, host_i = drive(
            make_engine(tiny, features=plane, merge="host"), TEXTS)
        plane2 = FeaturePlane(shortlist_gen=sl_gen, k_static=24)
        fused_o, fused_i = drive(
            make_engine(tiny, features=plane2, merge="fused",
                        steps_per_round=2), TEXTS)
        assert host_o == fused_o
        assert_parity(host_i, fused_i)

    def test_fused_force_decode_matches_host(self, tiny):
        """The forced-trunk gate is applied per scan step from the
        [rows, steps] forced array (host path reads one step at a
        time); forced scores must carry the TRUE logp either way."""
        _, _, vocab = tiny
        texts = ["w3 w4 w5\tw5 w5", "w6 w7\tw9", "w8 w9 w10 w11"]
        host_o, host_i = drive(
            make_engine(tiny, features=FeaturePlane(force_decode=True),
                        merge="host"), texts)
        fused_o, fused_i = drive(
            make_engine(tiny, features=FeaturePlane(force_decode=True),
                        merge="fused", steps_per_round=2), texts)
        assert host_o == fused_o
        assert_parity(host_i, fused_i)
        forced = vocab.encode("w5 w5", add_eos=False)
        assert fused_i[0]["tokens"][:2] == [int(t) for t in forced]


class TestFusedMergeTieBreak:
    def test_flat_topk_tiebreak_exact(self):
        """fused_merge vs the dense reference sort (-value, flat index
        asc) on a grid ENGINEERED to tie: NEG_INF saturates f32, and
        repeated finite values tie across rows and coords. The winner
        set AND its order must match the numpy reference exactly —
        this is the property that makes fused-vs-host parity hold
        through ties, not just in expectation."""
        import jax.numpy as jnp
        k, width, nb = 3, 7, 2
        rng = np.random.RandomState(5)
        lp = rng.choice([-1.0, -2.0, NEG_INF],
                        size=(nb * k, width)).astype(np.float32)
        score = rng.choice([0.0, -1.0], size=(nb * k,)).astype(np.float32)
        fin = np.zeros((nb * k,), bool)
        fin[1] = True               # one frozen row: {EOS: score} only
        eos_flat = 0
        vals, lanes, coords = fused_merge(
            jnp.asarray(lp), jnp.asarray(score), jnp.asarray(fin),
            k, eos_flat)
        vals, lanes, coords = (np.asarray(vals), np.asarray(lanes),
                               np.asarray(coords))
        for b in range(nb):
            cands = []
            for j in range(k):
                row = b * k + j
                if fin[row]:
                    for c in range(width):
                        cands.append((score[row] if c == eos_flat
                                      else NEG_INF, j * width + c))
                    continue
                for c in range(width):
                    cands.append((np.float32(score[row] + lp[row, c]),
                                  j * width + c))
            cands.sort(key=lambda t: (-t[0], t[1]))
            for i in range(k):
                want_val, want_flat = cands[i]
                assert np.float32(vals[b, i]) == np.float32(want_val), \
                    (b, i)
                assert lanes[b, i] * width + coords[b, i] == want_flat, \
                    (b, i, "tie-break order diverged from the dense "
                     "(-value, flat asc) rule")


# ---------------------------------------------------------------------------
# COW safety over device-computed diffs (satellite: audit + drill)
# ---------------------------------------------------------------------------

class TestDeviceDiffSafety:
    def test_audit_clean_and_pool_drains_after_fused_rounds(
            self, fused3_run):
        """Every round of the shared fused3_run drive already audited
        (conftest arms MARIAN_POOL_AUDIT=1): the device-computed
        retable diffs must keep refcounts, table mirrors and the
        write-target-refcount-1 COW invariant coherent. On exit the
        pool must drain to empty — no page leaked through a
        roundfresh/cow hold."""
        eng, _, _ = fused3_run
        assert eng.pool.free_pages() == eng.pool.usable_pages
        assert eng.pool.owners() == []

    def test_pressure_round_falls_back_to_host_merge(self, tiny):
        """A pool too tight for the WORST-CASE fused preclaim must not
        shed traffic the host path could serve: the round falls back to
        one single-step host-merge round (lazy claims at actual
        demand), and output stays bitwise the unpressured fused run's.
        The squeeze: one sentence (max_rows=K) over a pool pinned by
        pool_bytes to ONE PAGE UNDER what its K rows own at full
        divergence and full cap. A two-step round at a page boundary
        preclaims k + (k-1) fresh pages on top of what the beams
        already hold, which that pool cannot give once the beams have
        forked, while the pages the merge really forks into always
        fit — so rounds fall back and nothing is evicted. (K whole
        full-cap rows, the sizing this fixture had, leave the preclaim
        room on this model: the beams share their trunk. The unsized
        default adds round-preclaim headroom since ISSUE 18 — exactly
        to make this fallback rare — so the squeeze needs an explicit
        sizing, like a production --kv-pool-bytes brownout would.)"""
        ref = make_engine(tiny, merge="fused", steps_per_round=2)
        tight = make_engine(
            tiny, merge="fused", steps_per_round=2, max_rows=K,
            pool_bytes=ref.page_bytes * (K * ref.max_pages - 1))
        o, i = drive(tight, [TEXTS[2]])
        assert tight._counters.get("fused_fallback_rounds", 0) > 0, \
            "the squeeze never hit the fallback — tighten the fixture"
        assert tight._counters.get("pool_evictions", 0) == 0
        ref_o, ref_i = drive(ref, [TEXTS[2]])
        assert o == ref_o
        assert_parity(i, ref_i)

    def test_seeded_bad_diff_is_caught(self, tiny):
        """Detection drill (beam.diff_corrupt): one live slot's diff is
        applied TRUNCATED while the engine's table mirror keeps the
        full device row — the bad-device-diff bug class. The per-round
        auditor must catch the divergence in the SAME round, proving
        the table/claim cross-check guards real device-diff application
        (not a mocked report)."""
        eng = make_engine(tiny, merge="fused", steps_per_round=2)
        with fp.active("beam.diff_corrupt=fail@1"):
            with pytest.raises(PoolCorruption, match="pool audit"):
                # enough rounds that at least one sentence continues
                # past its first fused round (the drill site)
                eng.decode_texts(TEXTS[:2])


# ---------------------------------------------------------------------------
# closed shape set (satellite: jitwit strict window over the beam scan)
# ---------------------------------------------------------------------------

class TestClosedShapeSet:
    # steps=3 alone covers both key families: the fused s=3 round keys
    # AND the s=1 pressure-fallback keys the grid must also warm (the
    # steps=1 engine's window is a strict subset of that shape set).
    @pytest.mark.parametrize("steps", [3])
    def test_warmed_fused_engine_zero_postwarm_compiles(self, tiny,
                                                        steps):
        """The beam form of 'compile once, serve forever': warm_grid
        drives every block bucket x encode width, then mixed traffic —
        joins, forks, mid-round freezes, staggered finishes — must
        compile NOTHING (the fused path has no per-round fork jits at
        all: the COW forks live inside the scan)."""
        eng = make_engine(tiny, merge="fused", steps_per_round=steps)
        driven = eng.warm_grid()
        assert driven, "warm_grid drove nothing"
        assert {rb for rb, _, _, _ in driven} == set(eng.row_buckets)
        # fused round keys at the engine's steps, PLUS s=1 keys for the
        # pressure-fallback host rounds (warmed per width so even a
        # pool-squeezed steady-state round compiles nothing)
        assert {s for _, _, s, _ in driven} == {steps, 1}
        for rb in eng.row_buckets:
            assert any(r == rb and s == 1 for r, _, s, _ in driven)
        with jitwit.strict() as w:
            out = eng.decode_texts(TEXTS)
            out2 = eng.decode_texts(TEXTS[1:3])
        assert len(out) == len(TEXTS) and len(out2) == 2
        assert w.compiles == [], (
            "post-warm beam traffic recompiled — the block grid does "
            f"not close the fused engine's shape set: {w.compiles}")

    def test_cold_fused_engine_does_compile(self, tiny):
        """No vacuous pass: the same traffic on a cold fused engine
        does compile, attributed to the beam engine's scan-step site."""
        eng = make_engine(tiny, merge="fused", steps_per_round=2)
        with jitwit.strict() as w:
            eng.decode_texts(TEXTS[:2])
        assert any("translator/beam_iteration.py" in site
                   for site, _ in w.compiles)


# ---------------------------------------------------------------------------
# option surface (satellite: steps/merge validation + clamps)
# ---------------------------------------------------------------------------

class TestOptionSurface:
    def test_bad_merge_value_refused(self, tiny):
        with pytest.raises(ValueError, match="iteration-beam-merge"):
            make_engine(tiny, merge="gpu")

    def test_host_merge_pins_single_step(self, tiny):
        """merge='host' needs the host between steps: the engine clamps
        steps_per_round to 1 rather than silently mis-decoding."""
        eng = make_engine(tiny, merge="host", steps_per_round=4)
        assert eng.steps_per_round == 1 and eng.merge == "host"

    def test_cow_off_and_sampling_force_host_merge(self, tiny):
        """The replication baseline and sampled beams (independent
        trajectories — no k*k grid exists) stay on the host path."""
        eng = make_engine(tiny, cow=False, merge="fused",
                          steps_per_round=3)
        assert eng.merge == "host" and eng.steps_per_round == 1
        plane = FeaturePlane(sampling=("full", 1.0), seed=7)
        eng2 = make_engine(tiny, features=plane, steps_per_round=3)
        assert eng2.merge == "host" and eng2.steps_per_round == 1

    def test_row_buckets_are_block_multiples(self, tiny):
        """Fused mode needs k-aligned blocks: every compiled row bucket
        must be a whole number of sentences."""
        eng = make_engine(tiny)
        assert all(rb % K == 0 for rb in eng.row_buckets)
        assert max(eng.row_buckets) == eng.max_rows

    def test_boot_validator_rejects_host_multistep_beam(self):
        """--iteration-beam-merge host + --iteration-steps>1 + beam>1
        must refuse LOUDLY at boot (the engine would silently clamp;
        the operator asked for a combination that cannot run)."""
        from marian_tpu.server.server import ServingApp
        v = ServingApp._validate_iteration_options

        class Opts(dict):
            def get(self, k, d=None):
                return super().get(k, d)

        def opts(**kw):
            base = {"beam-size": 2, "iteration-steps": 1,
                    "iteration-beam-merge": "fused", "models": ["m"]}
            base.update(kw)
            return Opts(base)

        v(opts())                                      # default: fine
        v(opts(**{"iteration-steps": 4}))              # fused multi: fine
        v(opts(**{"iteration-beam-merge": "host"}))    # host single: fine
        with pytest.raises(ValueError, match="host merge needs"):
            v(opts(**{"iteration-beam-merge": "host",
                      "iteration-steps": 4}))
        with pytest.raises(ValueError, match="iteration-beam-merge"):
            v(opts(**{"iteration-beam-merge": "gpu"}))
        # 0 reads as unset (the codebase-wide `or default` idiom);
        # a NEGATIVE count is unambiguously wrong and must refuse
        with pytest.raises(ValueError, match="iteration-steps"):
            v(opts(**{"iteration-steps": -2}))
