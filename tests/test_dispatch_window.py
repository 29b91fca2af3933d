"""--dispatch-window: K full optimizer updates inside ONE jitted dispatch
(lax.scan over a leading window axis — parallel/zero.py build_train_step
n_updates>1). The lever amortizes per-dispatch host latency (host-bound
pods); the reference has no equivalent because
its SyncGraphGroup host loop runs per update
(src/training/graph_group_sync.cpp :: SyncGraphGroup::update)."""

import jax
import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.data import Corpus, DefaultVocab
from marian_tpu.data.batch_generator import BatchGenerator
from marian_tpu.models.encoder_decoder import batch_to_arrays, create_model
from marian_tpu.training import GraphGroup, Train, TrainingState

from tests.test_training import train_options


def _fixed_batches(src, tgt, n):
    vs = DefaultVocab.build(open(src).read().splitlines())
    vt = DefaultVocab.build(open(tgt).read().splitlines())
    c = Corpus([src, tgt], [vs, vt],
               Options({"max-length": 64, "shuffle": "none"}))
    bg = BatchGenerator(c, mini_batch=2, maxi_batch=1, prefetch=False,
                        shuffle_batches=False, pad_batch=True,
                        batch_multiple=8)
    batches = [batch_to_arrays(b) for b in list(bg)[:n]]
    assert len(batches) == n
    # the scanned window needs one shared padded shape — pad every leaf's
    # time dim to the widest bucket among the picked batches (mask-correct:
    # batch_to_arrays pads with zeros/EOS-masked columns)
    import jax.numpy as jnp
    w = {k: max(b[k].shape[1] for b in batches) for k in batches[0]}
    batches = [{k: jnp.pad(v, ((0, 0), (0, w[k] - v.shape[1])))
                for k, v in b.items()} for b in batches]
    return (vs, vt), batches


class TestDispatchWindow:
    def test_window_equals_sequential_updates(self, tmp_corpus, tmp_path):
        """K=3 scanned updates must reproduce 3 sequential update() calls
        exactly (same step numbers; both paths derive sub-step keys from
        the same raw stream key by absolute step)."""
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt)
        (vs, vt), batches = _fixed_batches(src, tgt, 3)
        model = create_model(opts, len(vs), len(vt))
        key, rng = jax.random.key(0), jax.random.key(9)

        gg_w = GraphGroup(model, opts.with_(**{"dispatch-window": 3}),
                          donate=False)
        gg_w.initialize(key)
        outs = gg_w.update_window([dict(b) for b in batches], 1, rng)
        assert len(outs) == 3

        gg_s = GraphGroup(model, opts, donate=False)
        gg_s.initialize(key)
        # update() folds the raw stream key by step-1 in-jit, so passing
        # rng to both paths yields identical sub-step keys
        seq = [gg_s.update(dict(b), 1 + i, rng)
               for i, b in enumerate(batches)]

        # per-sub-update metrics line up with the sequential trajectory
        for o_w, o_s in zip(outs, seq):
            np.testing.assert_allclose(np.asarray(o_w.loss_sum),
                                       np.asarray(o_s.loss_sum),
                                       rtol=1e-5)
            np.testing.assert_allclose(np.asarray(o_w.grad_norm),
                                       np.asarray(o_s.grad_norm),
                                       rtol=1e-4)
        for k in gg_s.params:
            if k.endswith("_bk"):
                continue  # zero-gradient leaves: pure float noise
            np.testing.assert_allclose(np.asarray(gg_w.params[k]),
                                       np.asarray(gg_s.params[k]),
                                       rtol=5e-4, atol=5e-6, err_msg=k)

    def test_window_composes_with_ema_and_clipping(self, tmp_corpus,
                                                   tmp_path):
        """Optimizer-state features (EMA, clip, dynamic scaling stats) live
        in the scan carry — the windowed trajectory must track sequential
        with them enabled."""
        src, tgt, _ = tmp_corpus
        over = {"exponential-smoothing": 0.01, "clip-norm": 0.5}
        opts = train_options(tmp_path, src, tgt, **over)
        (vs, vt), batches = _fixed_batches(src, tgt, 2)
        model = create_model(opts, len(vs), len(vt))
        key, rng = jax.random.key(1), jax.random.key(5)

        gg_w = GraphGroup(model, opts.with_(**{"dispatch-window": 2}),
                          donate=False)
        gg_w.initialize(key)
        gg_w.update_window([dict(b) for b in batches], 1, rng)

        gg_s = GraphGroup(model, opts, donate=False)
        gg_s.initialize(key)
        for i, b in enumerate(batches):
            gg_s.update(dict(b), 1 + i, rng)

        sm_w, sm_s = gg_w.smoothed(), gg_s.smoothed()
        for k in sm_s:
            if k.endswith("_bk"):
                continue
            np.testing.assert_allclose(np.asarray(sm_w[k]),
                                       np.asarray(sm_s[k]),
                                       rtol=5e-4, atol=5e-6, err_msg=k)

    def test_window_with_delay_refused(self, tmp_corpus, tmp_path):
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt,
                             **{"dispatch-window": 4, "optimizer-delay": 2.0})
        vs = DefaultVocab.build(open(src).read().splitlines())
        model = create_model(opts, len(vs), len(vs))
        with pytest.raises(ValueError, match="dispatch-window"):
            GraphGroup(model, opts)  # loud refusal, matching the CLI help

    def test_after_batches_not_overshot(self, tmp_corpus, tmp_path):
        """An update-counted hard limit must cap the window fill: with
        --after-batches 5 and window 4, the final window is partial and
        training stops at exactly 5 updates (the unwindowed contract),
        not at the next multiple of the window."""
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt,
                             **{"dispatch-window": 4, "after-batches": 5})
        Train(opts).run()
        st = TrainingState.load(str(tmp_path / "model.npz.progress.yml"))
        assert st.batches == 5

    def test_trigger_crossing_mid_window(self):
        """A save/valid freq boundary that falls INSIDE a dispatched
        window must still fire at the drain (should_*_since range test),
        and never before all K applied updates are accounted."""
        from marian_tpu.training.scheduler import Scheduler
        from marian_tpu.training.training_state import TrainingState
        sch = Scheduler(Options({"save-freq": "3u", "valid-freq": "5u",
                                 "disp-freq": "100u", "quiet": True}),
                        TrainingState())
        before_b, before_l = sch.state.batches, sch.state.labels_total
        for _ in range(4):                        # one window of K=4
            sch.update(0.0, 10, 2)
        assert sch.state.batches == 4
        assert sch.should_save_since(before_b, before_l)       # 3 in (0,4]
        assert not sch.should_validate_since(before_b, before_l)  # 5 not
        before_b, before_l = sch.state.batches, sch.state.labels_total
        for _ in range(4):                        # next window: updates 5-8
            sch.update(0.0, 10, 2)
        assert sch.should_save_since(before_b, before_l)       # 6 in (4,8]
        assert sch.should_validate_since(before_b, before_l)   # 5 in (4,8]

    def test_train_loop_end_to_end(self, tmp_corpus, tmp_path):
        """Full Train.run() with --dispatch-window 2: the loop groups
        same-shape batches, flushes stragglers at epoch end, and the
        progress count matches the updates applied."""
        src, tgt, _ = tmp_corpus
        opts = train_options(tmp_path, src, tgt,
                             **{"dispatch-window": 2, "after-batches": 6})
        Train(opts).run()
        st = TrainingState.load(str(tmp_path / "model.npz.progress.yml"))
        assert st.batches >= 6


class TestLabelsLimitWindowCap:
    """--after Nt (labels-counted) must cap the dispatch-window fill:
    r4-advisor finding (window could overshoot a labels stop by K-1
    updates) + r5 review (first window, before any per-update label
    count is observed, must cap at ONE update)."""

    def _sched(self, after):
        from marian_tpu.common.options import Options
        from marian_tpu.training.scheduler import Scheduler
        from marian_tpu.training.training_state import TrainingState
        opts = Options({"after": after, "disp-freq": "1000u",
                        "learn-rate": 1e-3})
        return Scheduler(opts, TrainingState())

    def test_first_window_caps_at_one_update(self):
        s = self._sched("300t")
        assert s.updates_remaining() == 1

    def test_estimate_tracks_max_labels_per_update(self):
        s = self._sched("300t")
        for _ in range(3):
            s.update(0.0, labels=50, sentences=4)
        # 150 labels consumed, 150 remain, max 50/update → 3 updates
        assert s.updates_remaining() == 3

    def test_no_labels_limit_returns_none(self):
        s = self._sched("0e")
        s.update(0.0, labels=50, sentences=4)
        assert s.updates_remaining() is None
