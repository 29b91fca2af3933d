"""End-to-end training tests (the regression-suite analogue of the reference's
marian-regression-tests: tiny fixture data, fixed seeds, pinned behavior —
SURVEY.md §4)."""

import math
import os
import pathlib

import jax
import numpy as np
import pytest
import yaml

from marian_tpu.common import Options
from marian_tpu.common import io as mio
from marian_tpu.data import DefaultVocab, Corpus, BatchGenerator, EOS_ID
from marian_tpu.models.encoder_decoder import create_model, batch_to_arrays
from marian_tpu.optimizers.schedule import LRSchedule
from marian_tpu.optimizers.optimizers import OptimizerConfig, init_state, apply_update
from marian_tpu.training import Train, GraphGroup, TrainingState
from marian_tpu.translator.greedy import greedy_decode

from tests.time_limit import time_limit


def train_options(tmp_path, src, tgt, **over):
    base = {
        "type": "transformer",
        "dim-emb": 32, "transformer-heads": 4, "transformer-dim-ffn": 64,
        "enc-depth": 2, "dec-depth": 2,
        "tied-embeddings-all": False,
        "precision": ["float32", "float32"],
        "max-length": 64,
        "train-sets": [src, tgt],
        "vocabs": [src + ".v.yml", tgt + ".v.yml"],
        "model": str(tmp_path / "model.npz"),
        "mini-batch": 8, "maxi-batch": 2, "mini-batch-words": 0,
        "learn-rate": 0.01, "optimizer": "adam", "clip-norm": 1.0,
        "label-smoothing": 0.0,
        "cost-type": "ce-mean-words",
        "after-epochs": 0, "after-batches": 30, "after": "0e",
        "disp-freq": "10u", "save-freq": "100u", "valid-freq": "100u",
        "seed": 42, "shuffle": "data",
        "exponential-smoothing": 0.0,
        "optimizer-delay": 1.0,
        "quiet": True,
    }
    base.update(over)
    return Options(base)


class TestAdamOracle:
    def test_adam_matches_numpy_reference(self):
        """Marian Adam semantics vs a hand-written numpy implementation."""
        rs = np.random.RandomState(0)
        p0 = rs.randn(4, 3).astype(np.float32)
        cfg = OptimizerConfig(name="adam", beta1=0.9, beta2=0.98, eps=1e-9,
                              clip_norm=0.0, smoothing=0.0)
        import jax.numpy as jnp
        params = {"w": jnp.asarray(p0)}
        state = init_state(cfg, params)
        m = np.zeros_like(p0); v = np.zeros_like(p0); p = p0.copy()
        lr = 0.001
        for t in range(1, 6):
            g = rs.randn(4, 3).astype(np.float32)
            state, params = apply_update(cfg, state, params,
                                         {"w": jnp.asarray(g)}, lr)
            m = 0.9 * m + 0.1 * g
            v = 0.98 * v + 0.02 * g * g
            mhat = m / (1 - 0.9 ** t)
            vhat = v / (1 - 0.98 ** t)
            p = p - lr * mhat / (np.sqrt(vhat) + 1e-9)
            np.testing.assert_allclose(np.asarray(params["w"]), p, rtol=2e-5,
                                       atol=2e-6)

    def test_lr_schedule_warmup_invsqrt(self):
        opts = Options({"learn-rate": 0.0003, "lr-warmup": "100",
                        "lr-decay-inv-sqrt": ["100"]})
        sched = LRSchedule.from_options(opts)
        assert float(sched(50)) == pytest.approx(0.0003 * 0.5, rel=1e-5)
        assert float(sched(100)) == pytest.approx(0.0003, rel=1e-5)
        assert float(sched(400)) == pytest.approx(0.0003 * 0.5, rel=1e-5)


class TestTrainEndToEnd:
    def test_loss_decreases_and_decodes(self, tmp_corpus, tmp_path):
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt, **{"after-batches": 40})
        Train(opts).run()
        model_path = str(tmp_path / "model.npz")
        assert os.path.exists(model_path)
        assert os.path.exists(model_path + ".progress.yml")
        assert os.path.exists(model_path + ".optimizer.npz")

        # config embedded in checkpoint
        params, config = mio.load_model(model_path)
        assert config is not None
        assert yaml.safe_load(config)["type"] == "transformer"

        # overfit check: greedy decode of a training sentence should produce
        # mostly-gold tokens after 40 updates on 8 sentences
        vs = DefaultVocab.load(src + ".v.yml")
        vt = DefaultVocab.load(tgt + ".v.yml")
        model = create_model(opts, len(vs), len(vt), inference=True)
        import jax.numpy as jnp
        jparams = {k: jnp.asarray(v) for k, v in params.items()}
        ids = vs.encode("hello world")
        src_ids = jnp.asarray([ids], jnp.int32)
        src_mask = jnp.ones_like(src_ids, jnp.float32)
        out = greedy_decode(model, jparams, src_ids, src_mask, max_len=10)
        decoded = vt.decode([int(x) for x in out[0]])
        assert len(decoded) > 0  # produced something non-empty

    def test_progress_state_counts(self, tmp_corpus, tmp_path):
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt, **{"after-batches": 5})
        Train(opts).run()
        st = TrainingState.load(str(tmp_path / "model.npz.progress.yml"))
        assert st.batches == 5
        assert st.labels_total > 0
        assert st.corpus is not None

    def test_exact_resume(self, tmp_corpus, tmp_path):
        """Stop at update 6, resume to 12: parameters must be bitwise-close to
        an uninterrupted 12-update run (the reference's same-cost-trajectory
        regression gate)."""
        src, tgt, _ = tmp_corpus

        d1 = tmp_path / "run_full"; d1.mkdir()
        opts_full = train_options(d1, src, tgt, **{"after-batches": 12})
        Train(opts_full).run()
        p_full, _ = mio.load_model(str(d1 / "model.npz"))

        d2 = tmp_path / "run_split"; d2.mkdir()
        opts_a = train_options(d2, src, tgt, **{"after-batches": 6})
        Train(opts_a).run()
        opts_b = train_options(d2, src, tgt, **{"after-batches": 12})
        Train(opts_b).run()
        p_split, _ = mio.load_model(str(d2 / "model.npz"))

        assert set(p_full) == set(p_split)
        for k in p_full:
            np.testing.assert_allclose(p_full[k], p_split[k], rtol=1e-4,
                                       atol=1e-5, err_msg=k)

    def test_sigterm_like_save(self, tmp_corpus, tmp_path):
        """signal flag → finish update, save, exit 0 (reference:
        common/signal_handling.cpp contract)."""
        from marian_tpu.common import signal_handling
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt, **{"after-batches": 1000})
        import signal as _sig
        signal_handling._flags[_sig.SIGTERM] = True
        try:
            Train(opts).run()
        finally:
            signal_handling.clear_signal_flags()
        st = TrainingState.load(str(tmp_path / "model.npz.progress.yml"))
        assert st.batches < 1000  # stopped early but saved


class TestEMA:
    def test_ema_saved(self, tmp_corpus, tmp_path):
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt,
                             **{"after-batches": 3,
                                "exponential-smoothing": 0.01})
        Train(opts).run()
        base = str(tmp_path / "model")
        assert os.path.exists(base + ".ema.npz")


class TestCompactTransfer:
    def test_compact_batch_is_equivalent(self, tmp_corpus, tmp_path):
        """batch_to_arrays(compact=True) ships uint16 tokens + row
        lengths; the jitted step rebuilds ids/masks on device — the
        update must be numerically IDENTICAL to the full form."""
        import jax.numpy as jnp
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt)
        vs = DefaultVocab.build(open(src).read().splitlines())
        vt = DefaultVocab.build(open(tgt).read().splitlines())
        model = create_model(opts, len(vs), len(vt))
        corpus = Corpus([src, tgt], [vs, vt], opts)
        batch = next(iter(BatchGenerator(corpus, opts, prefetch=False)))

        full = batch_to_arrays(batch, compact=False)
        comp = batch_to_arrays(batch, compact=True)
        assert "src_tok" in comp and comp["src_tok"].dtype == jnp.uint16
        assert "src_mask" not in comp
        # transfer bytes actually shrink (the point of the feature)
        assert sum(v.nbytes for v in comp.values()) < \
            0.5 * sum(v.nbytes for v in full.values())

        def run(arrays):
            gg = GraphGroup(model, opts, donate=False)
            gg.initialize(jax.random.key(0))
            out = gg.update(dict(arrays), 1, jax.random.key(3))
            return float(out.loss_sum), gg.params

        l_full, p_full = run(full)
        l_comp, p_comp = run(comp)
        assert l_full == l_comp
        for k in p_full:
            np.testing.assert_array_equal(np.asarray(p_full[k]),
                                          np.asarray(p_comp[k]), err_msg=k)

    def test_compact_equivalent_on_composed_mesh(self, tmp_corpus,
                                                 tmp_path):
        """Compact batches must also be exact through the GSPMD path on
        a composed dp×tp×sp mesh (the manual-DP path only runs on pure-
        data meshes; _tok/_len leaves carry their own sharding specs)."""
        import jax.numpy as jnp
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt).with_(
            **{"mesh": ["data:2", "model:2", "seq:2"]})
        vs = DefaultVocab.build(open(src).read().splitlines())
        vt = DefaultVocab.build(open(tgt).read().splitlines())
        model = create_model(opts, len(vs), len(vt))
        corpus = Corpus([src, tgt], [vs, vt], opts)
        batch = next(iter(BatchGenerator(corpus, opts, prefetch=False)))

        def run(arrays):
            gg = GraphGroup(model, opts, donate=False)
            gg.initialize(jax.random.key(0))
            out = gg.update(dict(arrays), 1, jax.random.key(3))
            return float(out.loss_sum), gg.params

        l_full, p_full = run(batch_to_arrays(batch, compact=False))
        l_comp, p_comp = run(batch_to_arrays(batch, compact=True))
        # same ids/masks VALUES, but the partitioner schedules the
        # in-jit expansion differently than a transferred mask →
        # reduction orders differ at float-associativity level (the
        # pure-DP manual path above is bitwise; this one is merely
        # numerically tight)
        np.testing.assert_allclose(l_full, l_comp, rtol=1e-6)
        for k in p_full:
            np.testing.assert_allclose(np.asarray(p_full[k]),
                                       np.asarray(p_comp[k]),
                                       rtol=1e-5, atol=1e-7, err_msg=k)

    def test_ragged_mask_falls_back_to_full_form(self, tmp_corpus,
                                                 tmp_path):
        """A mask that is not a prefix run (hand-built hole) must ship
        in the classic ids+mask form rather than corrupt silently."""
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt)
        vs = DefaultVocab.build(open(src).read().splitlines())
        corpus = Corpus([src, tgt], [vs, vs], opts)
        batch = next(iter(BatchGenerator(corpus, opts, prefetch=False)))
        batch.src.mask[0, 0] = 0.0          # hole at position 0
        arrays = batch_to_arrays(batch, compact=True)
        assert "src_ids" in arrays and "src_mask" in arrays
        # the target stream is untouched and still compacts
        assert "trg_tok" in arrays


# ---------------------------------------------------------------------------
# --optimizer-delay: ONE path for a list of micro-batches (ISSUE 48)
# ---------------------------------------------------------------------------

_V = 40          # both vocabularies of the delay tests' model


def _micro_batch(seed, rows, width, compact=False):
    """One hand-made micro-batch [rows, width] of ragged rows, in the
    ids + mask form or, `compact`, as the loader's own batch through
    batch_to_arrays (uint16 tokens + row lengths)."""
    from marian_tpu.data.batch_generator import CorpusBatch, SubBatch
    rs = np.random.RandomState(seed)
    subs = []
    for _ in range(2):
        lens = rs.randint(2, width + 1, rows)
        lens[0] = width
        mask = (np.arange(width)[None] < lens[:, None]).astype(np.float32)
        ids = (rs.randint(2, _V, (rows, width)) * mask).astype(np.int32)
        subs.append(SubBatch(ids, mask))
    batch = CorpusBatch(subs, np.arange(rows), None, None, None)
    arrays = batch_to_arrays(batch, compact=compact, vocab_sizes=[_V, _V])
    assert ("trg_tok" in arrays) == compact
    return arrays, batch


def _concatenated(batches):
    """The micro-batches as ONE batch: rows stacked, widths padded."""
    out = {}
    for i, prefix in enumerate(("src", "trg")):
        width = max(b.sub[i].ids.shape[1] for b in batches)
        for field in ("ids", "mask"):
            out[f"{prefix}_{field}"] = np.concatenate([
                np.pad(getattr(b.sub[i], field),
                       ((0, 0), (0, width - b.sub[i].ids.shape[1])))
                for b in batches])
    return out


# rows x width of up to three micro-batches
_MICRO_SHAPES = {"same": [(8, 9)] * 3,
                 "widths": [(8, 7), (8, 11), (8, 9)],
                 "rows": [(16, 9), (8, 9), (24, 9)]}


class _DelayRig:
    """One GraphGroup over the tiny model, not donating, so that every
    case starts from the same parameters and the jitted programs of one
    configuration compile once a process."""
    _made = {}

    def __init__(self, **over):
        opts = train_options(pathlib.PurePath("unused"), "x", "y", **{
            "enc-depth": 1, "dec-depth": 1, "optimizer": "sgd",
            "learn-rate": 0.1, **over})
        self.model = create_model(opts, _V, _V)
        self.gg = GraphGroup(self.model, opts, donate=False)
        self.gg.initialize(jax.random.key(0))
        self.start = (self.gg.params, self.gg.opt_state)

    @classmethod
    def get(cls, **over):
        key = tuple(sorted((k, str(v)) for k, v in over.items()))
        if key not in cls._made:
            cls._made[key] = cls(**over)
        return cls._made[key].reset()

    def reset(self):
        self.gg.params, self.gg.opt_state = self.start
        return self

    def after(self, batches, step=1, rng=None):
        """The parameters' CHANGE over one update from the start."""
        self.reset().gg.update(batches, step, jax.random.key(9)
                               if rng is None else rng)
        return {k: np.asarray(v) - np.asarray(self.start[0][k])
                for k, v in self.gg.params.items()}


def _assert_same_change(got, want):
    for k in want:
        # one update of plain SGD is linear in the gradient: the two
        # differ by float32 summation order only
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-7,
                                   err_msg=k)
    assert max(np.abs(v).max() for v in want.values()) > 1e-4


class TestDelay:
    """A list of micro-batches has one path: one dispatch of the
    accumulating gradient program per micro-batch, whatever its shape,
    then one update from the float32 sum (SyncGraphGroup's accumulation
    semantics)."""

    @pytest.mark.parametrize("delay", [2, 3])
    @pytest.mark.parametrize("shapes", sorted(_MICRO_SHAPES))
    @time_limit(120)
    def test_delayed_update_equals_the_concatenated_batch(self, shapes,
                                                          delay):
        """ce-mean-words: N micro-batches through ONE delayed update move
        the parameters as one update on their concatenation does. The
        `rows` cases run on the 8-device mesh (the sum is ZeRO-1 sharded)
        and ship compact batches, as the trainer's loop does."""
        wide = shapes == "rows"
        rig = _DelayRig.get(**({} if wide else {"devices": [0]}))
        assert rig.gg.mesh.shape["data"] == (8 if wide else 1)
        micro = [_micro_batch(i, r, w, compact=wide) for i, (r, w)
                 in enumerate(_MICRO_SHAPES[shapes][:delay])]
        got = rig.after([a for a, _ in micro])
        want = rig.after(_concatenated([b for _, b in micro]))
        _assert_same_change(got, want)

    @pytest.mark.parametrize("cost_type", ["ce-sum", "ce-mean",
                                           "ce-mean-words"])
    @time_limit(120)
    def test_cost_types_normalize_over_the_whole_sum(self, cost_type):
        """ce-sum divides by nothing, ce-mean by the SENTENCES of all
        micro-batches, ce-mean-words by their labels: micro-batches of
        other rows and widths against their concatenation; without the
        clip, which would hide a wrong divisor."""
        rig = _DelayRig.get(**{"devices": [0], "cost-type": cost_type,
                               "clip-norm": 0.0, "learn-rate": 0.01})
        micro = [_micro_batch(10 + i, r, w) for i, (r, w)
                 in enumerate([(16, 7), (8, 12)])]
        got = rig.after([a for a, _ in micro])
        want = rig.after(_concatenated([b for _, b in micro]))
        _assert_same_change(got, want)

    @time_limit(120)
    def test_dropout_keys_fold_by_step_then_by_micro_batch(self):
        """With dropout on, micro-batch i's gradients are model.loss's
        under fold_in(fold_in(rng, step - 1), i): a hand accumulation
        through the update program gives the delayed update's result."""
        import jax.numpy as jnp
        rig = _DelayRig.get(**{"devices": [0], "transformer-dropout": 0.1})
        gg, (p0, o0) = rig.gg, rig.start
        micro = [_micro_batch(20 + i, r, w)[0] for i, (r, w)
                 in enumerate([(8, 9), (8, 6)])]
        step, rng = 3, jax.random.key(5)
        base = jax.random.fold_in(rng, step - 1)
        total = {"grads": {k: jnp.zeros(v.shape, jnp.float32)
                           for k, v in p0.items()},
                 "ce_sum": 0.0, "labels": 0.0}
        grads_of = jax.jit(jax.value_and_grad(
            lambda p, b, key: rig.model.loss(p, b, key, train=True),
            has_aux=True))
        for i, b in enumerate(micro):
            (_, aux), g = grads_of(p0, b, jax.random.fold_in(base, i))
            total = {"grads": {k: total["grads"][k] + g[k] for k in g},
                     "ce_sum": total["ce_sum"] + aux["ce_sum"],
                     "labels": total["labels"] + aux["labels"]}
        want_p, _, metrics = gg._update_fn(p0, o0, total, np.float32(step),
                                           np.float32(16))
        got = rig.after(micro, step=step, rng=rng)
        _assert_same_change(got, {k: np.asarray(v) - np.asarray(p0[k])
                                  for k, v in want_p.items()})
        # and the keys matter: the same micro-batches at another step
        other = rig.after(micro, step=step + 1, rng=rng)
        assert any(np.abs(other[k] - got[k]).max() > 1e-6 for k in got)
        assert float(metrics["labels"]) == sum(
            float(b["trg_mask"].sum()) for b in micro)

    @pytest.mark.parametrize("n", [2, 3])
    @time_limit(120)
    def test_n_micro_batches_are_n_plus_one_dispatches(self, n):
        """Tracer on: an update of N micro-batches of mixed shapes opens
        exactly N + 1 `train.dispatch` spans under its step number, and a
        second update of the same shapes retraces nothing."""
        from marian_tpu import obs
        rig = _DelayRig.get(**{"devices": [0]})
        micro = [_micro_batch(30 + i, r, w, compact=True)[0] for i, (r, w)
                 in enumerate([(8, 9), (16, 5), (8, 9)][:n])]
        rig.after(micro, step=1)            # compiles, tracer off
        obs.TRACER.reset()
        obs.TRACER.enable()
        try:
            rig.after(micro, step=2)
            spans = [s for s in obs.TRACER.snapshot()[0]
                     if s.name == "train.dispatch"]
            h2d = obs.TRACER.totals()["train.h2d"]["calls"]
        finally:
            obs.TRACER.reset()
        assert len(spans) == n + 1
        assert [s.attrs["step"] for s in spans] == [2] * (n + 1)
        assert [s.attrs["retraced"] for s in spans] == [0] * (n + 1)
        assert h2d == n

    @time_limit(120)
    def test_the_running_sum_is_float32_under_bfloat16_gradients(self):
        """--gradient-dtype bfloat16: gradients are produced in bfloat16
        (after one micro-batch every element of the sum is a bfloat16
        value) and summed in float32 (after two it is not)."""
        import jax.numpy as jnp
        rig = _DelayRig.get(**{"devices": [0], "gradient-dtype": "bfloat16",
                               "precision": ["bfloat16", "float32"]})
        gg = rig.gg
        step, rng = np.int32(1), jax.random.key(9)
        total = gg._zero_sum()
        finer = []
        for i in range(2):
            b = _micro_batch(40 + i, 8, 9)[0]
            total, _ = gg._grad_fn(gg.params, total, b, step, np.int32(i),
                                   rng)
            leaves = jax.tree_util.tree_leaves(total["grads"])
            assert {v.dtype for v in leaves} == {jnp.dtype(jnp.float32)}
            finer.append(any(
                bool((v != v.astype(jnp.bfloat16).astype(jnp.float32)).any())
                for v in leaves))
        assert finer == [False, True]
        out = rig.after([_micro_batch(40 + i, 8, 9)[0] for i in range(2)])
        assert all(np.isfinite(v).all() for v in out.values())

    @time_limit(120)
    def test_a_skipped_delayed_update_costs_nothing(self):
        """--check-gradient-nan: one poisoned micro-batch skips the whole
        update, the parameters stay, and the update reports no cost and no
        labels, as the fused step does (a NaN cost would read as the
        divergence the skip just averted)."""
        rig = _DelayRig.get(**{"devices": [0], "check-gradient-nan": True})
        micro = [_micro_batch(60 + i, 8, 9)[0] for i in range(2)]
        micro[1]["trg_mask"] = micro[1]["trg_mask"] * float("nan")
        out = rig.gg.update(micro, 1, jax.random.key(9))
        assert float(out.skipped) == 1.0
        assert float(out.loss_sum) == 0.0 == float(out.labels)
        for k, v in rig.gg.params.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(rig.start[0][k]))
        clean = rig.after([_micro_batch(60 + i, 8, 9)[0] for i in range(2)])
        assert max(np.abs(v).max() for v in clean.values()) > 1e-4

    @time_limit(180)
    def test_step_counters_add_over_the_micro_batches(self):
        """A layer-plan model's routing counts over one update of two
        micro-batches are the sums of the two single-batch counts."""
        from marian_tpu import obs
        from marian_tpu.common.config_parser import parse_options
        opts = parse_options([
            "--type", "transformer-lm", "--transformer-layer-plan",
            "mla:experts", "--dim-emb", "64", "--transformer-heads", "4",
            "--transformer-dim-ffn", "128", "--plan-mla-dim-nope", "16",
            "--plan-mla-dim-shared", "8", "--plan-mla-dim-v", "16",
            "--plan-mla-latent", "32", "--plan-experts", "16",
            "--plan-experts-held", "0", "8", "--plan-experts-top-k", "4",
            "--plan-experts-dim-ffn", "32", "--precision", "float32",
            "float32", "--train-sets", "x", "--vocabs", "v",
            "--optimizer-delay", "2", "--devices", "0", "--quiet"],
            mode="training")
        model = create_model(opts, 96, 96)
        gg = GraphGroup(model, opts)
        gg.initialize(jax.random.key(0))
        assert gg.delay == 2 and "moe.assignments" in model.step_counters

        def lm_batch(seed, rows, width):
            b = _micro_batch(seed, rows, width)[0]
            return {"src_ids": b["trg_ids"], "src_mask": b["trg_mask"],
                    "trg_ids": b["trg_ids"], "trg_mask": b["trg_mask"]}
        micro = [lm_batch(50, 2, 24), lm_batch(51, 4, 16)]
        want = sum(np.asarray(jax.jit(
            lambda p, b: model.loss(p, b, None, True)[1]["counters"])(
                gg.params, b)) for b in micro)
        obs.TRACER.reset()
        obs.TRACER.enable()
        try:
            gg.update(micro, 1, jax.random.key(9))
            obs.TRACER.fetch_counters()
            got = obs.TRACER.counters()
        finally:
            obs.TRACER.reset()
        assert got == dict(zip(model.step_counters, want.tolist()))
        labels = sum(float(b["trg_mask"].sum()) for b in micro)
        assert got["moe.assignments"] == labels * 4 > 0


class TestOneLoader:
    """The Python BatchGenerator is the trainer's only loader."""

    def test_the_parser_refuses_data_backend(self):
        from marian_tpu.common.config_parser import parse_options
        argv = ["--train-sets", "x", "--vocabs", "v"]
        parse_options(argv, mode="training")
        with pytest.raises(SystemExit, match="Unknown option.*data-backend"):
            parse_options(argv + ["--data-backend", "native"],
                          mode="training")

    @time_limit(180)
    def test_a_native_loaders_checkpoint_restarts_its_epoch(
            self, tmp_corpus, tmp_path, monkeypatch):
        """A checkpoint is input from outside: one whose corpus state the
        C++ loader of earlier versions saved (`backend: native`, a position
        in ITS order) resumes at position 0 of its epoch, with a warning,
        never at the wrong sentence."""
        from marian_tpu.training import train as T
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt, **{
            "enc-depth": 1, "dec-depth": 1, "after-batches": 1,
            "devices": [0]})
        Train(opts).run()
        load = T.load_checkpoint

        def saved_by_the_native_loader(path, gg):
            params, extra, state = load(path, gg)
            assert state.corpus["backend"] == "python"
            state.corpus = dict(state.corpus, backend="native", position=5)
            return params, extra, state
        restore, restored, warned = T.Corpus.restore, [], []
        monkeypatch.setattr(T, "load_checkpoint", saved_by_the_native_loader)
        monkeypatch.setattr(T.Corpus, "restore", lambda self, d: (
            restored.append(dict(d)), restore(self, d))[1])
        monkeypatch.setattr(T.log, "warn", lambda msg, *a: warned.append(
            msg.format(*a)))
        Train(opts.with_(**{"after-batches": 2})).run()
        assert restored and restored[0]["position"] == 0
        assert restored[0]["epoch"] >= 1
        assert any("saved by the 'native' data backend" in w
                   and "restarting epoch" in w for w in warned), warned
