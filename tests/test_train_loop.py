"""The trainer's one loop (training/train.py::_epoch_loop): one update per
dispatch, or one per --optimizer-delay micro-batches. Stopping conditions,
save/validate triggers and SIGTERM are looked at after every applied
update, so limits are met exactly and triggers fire on their update."""

import signal

import jax
import numpy as np
import pytest

from marian_tpu.common import Options, signal_handling
from marian_tpu.common.config_parser import parse_options
from marian_tpu.data import Corpus, DefaultVocab
from marian_tpu.data.batch_generator import BatchGenerator
from marian_tpu.models.encoder_decoder import batch_to_arrays, create_model
from marian_tpu.training import GraphGroup, Train, TrainingState
from marian_tpu.training import bundle as bdl
from marian_tpu.training import train as train_mod
from marian_tpu.training.scheduler import Scheduler

from tests.test_training import train_options
from tests.time_limit import time_limit


def loop_options(tmp_path, tmp_corpus, delay, **over):
    """Eight sentences in batches of two: four micro-batches an epoch,
    so --optimizer-delay 2 makes two updates of each epoch."""
    src, tgt, _ = tmp_corpus
    return train_options(tmp_path, src, tgt, **{
        "mini-batch": 2, "optimizer-delay": float(delay), **over})


def progress(tmp_path):
    return TrainingState.load(str(tmp_path / "model.npz.progress.yml"))


@pytest.mark.parametrize("delay", [1, 2])
@time_limit(240)
def test_after_batches_stops_at_exactly_five(tmp_corpus, tmp_path, delay):
    Train(loop_options(tmp_path, tmp_corpus, delay,
                       **{"after-batches": 5})).run()
    assert progress(tmp_path).batches == 5


@pytest.mark.parametrize("delay", [1, 2])
@time_limit(240)
def test_triggers_fire_on_their_own_update(tmp_corpus, tmp_path, delay,
                                           monkeypatch):
    """--save-freq 3u --valid-freq 5u over seven updates: saves at 3 and
    6 (and the final one at 7), one validation at 5 — counted in
    updates, not micro-batches, under --optimizer-delay 2."""
    saves, valids = [], []
    real_save = train_mod.save_checkpoint

    def save(model_path, params, config_yaml, gg, state, **kw):
        saves.append(state.batches)
        return real_save(model_path, params, config_yaml, gg, state, **kw)

    class CountingValidator:
        name, lower_is_better, training_state = "count", True, None

        def validate(self, params):
            valids.append(self.training_state.batches)
            return 1.0

    monkeypatch.setattr(train_mod, "save_checkpoint", save)
    monkeypatch.setattr(train_mod, "create_validators",
                        lambda *a: [CountingValidator()])
    Train(loop_options(tmp_path, tmp_corpus, delay, **{
        "after-batches": 7, "save-freq": "3u", "valid-freq": "5u"})).run()
    assert saves == [3, 6, 7]
    assert valids == [5]
    assert progress(tmp_path).batches == 7


@time_limit(240)
def test_label_limit_stops_within_one_update(tmp_corpus, tmp_path,
                                             monkeypatch):
    """--after Nt: the loop stops with the first update that reaches N
    target labels — the one before it was still under the limit."""
    limit, per_update = 100, []
    real_update = Scheduler.update

    def update(self, loss_sum, labels, *a, **kw):
        per_update.append(int(labels))
        return real_update(self, loss_sum, labels, *a, **kw)

    monkeypatch.setattr(Scheduler, "update", update)
    Train(loop_options(tmp_path, tmp_corpus, 1, **{
        "after-batches": 0, "after": f"{limit}t"})).run()
    st = progress(tmp_path)
    assert st.labels_total == sum(per_update) >= limit
    assert st.labels_total - per_update[-1] < limit
    assert st.batches == len(per_update) > 3


@pytest.mark.parametrize("mode, saved_updates", [
    ("save-and-exit", 2), ("exit-immediately", None)])
@time_limit(240)
def test_sigterm_between_updates(tmp_corpus, tmp_path, monkeypatch, mode,
                                 saved_updates):
    """A termination flag raised during update 2 ends the loop right
    after it: save-and-exit commits a bundle holding two updates,
    exit-immediately leaves run() with nothing written."""
    real_update = Scheduler.update

    def update(self, *a, **kw):
        real_update(self, *a, **kw)
        if self.state.batches == 2:
            signal_handling._flags[signal.SIGTERM] = True

    monkeypatch.setattr(Scheduler, "update", update)
    try:
        Train(loop_options(tmp_path, tmp_corpus, 1, **{
            "after-batches": 1000, "sigterm": mode})).run()
    finally:
        signal_handling.clear_signal_flags()
    model_path = str(tmp_path / "model.npz")
    bundles = bdl.list_bundles(bdl.bundle_root(model_path))
    if saved_updates is None:
        assert bundles == []
        assert not (tmp_path / "model.npz").exists()
        assert not (tmp_path / "model.npz.progress.yml").exists()
    else:
        assert bundles
        assert progress(tmp_path).batches == saved_updates


@time_limit(240)
def test_sequential_updates_repeat_bit_for_bit(tmp_corpus, tmp_path):
    """EMA and --clip-norm live in the optimizer state the step carries:
    the same three updates on a fresh GraphGroup give the same smoothed
    parameters and the same costs, bit for bit."""
    src, tgt, _ = tmp_corpus
    opts = train_options(tmp_path, src, tgt, **{
        "exponential-smoothing": 0.01, "clip-norm": 0.5})
    vs = DefaultVocab.build(open(src).read().splitlines())
    vt = DefaultVocab.build(open(tgt).read().splitlines())
    corpus = Corpus([src, tgt], [vs, vt],
                    Options({"max-length": 64, "shuffle": "none"}))
    batches = [batch_to_arrays(b) for b in list(BatchGenerator(
        corpus, mini_batch=2, maxi_batch=1, prefetch=False,
        shuffle_batches=False, pad_batch=True, batch_multiple=8))[:3]]
    assert len(batches) == 3
    model = create_model(opts, len(vs), len(vt))

    def three_updates():
        gg = GraphGroup(model, opts, donate=False)
        gg.initialize(jax.random.key(1))
        costs = [np.asarray(gg.update(dict(b), 1 + i, jax.random.key(5))
                            .loss_sum) for i, b in enumerate(batches)]
        return {k: np.asarray(v) for k, v in gg.smoothed().items()}, costs

    first, first_costs = three_updates()
    again, again_costs = three_updates()
    assert [c.tobytes() for c in first_costs] \
        == [c.tobytes() for c in again_costs]
    assert set(first) == set(again)
    for k in first:
        assert first[k].tobytes() == again[k].tobytes(), k
    # and the three updates did move the average off the initial weights
    fresh = GraphGroup(model, opts, donate=False)
    fresh.initialize(jax.random.key(1))
    assert any(np.asarray(v).tobytes() != first[k].tobytes()
               for k, v in fresh.smoothed().items())


def test_removed_flag_is_refused_like_any_unknown_flag(tmp_path):
    """The trainer has one loop and no option that selects another: the
    parser takes the run's own flags and refuses the removed one by
    name, with no alias behind it."""
    argv = ["--train-sets", "a", "b", "--vocabs", "a.yml", "b.yml",
            "--model", str(tmp_path / "m.npz"), "--optimizer-delay", "2"]
    assert parse_options(argv, mode="training").get("optimizer-delay") == 2
    with pytest.raises(SystemExit) as refused:
        parse_options(["--dispatch-window", "2"] + argv, mode="training")
    assert str(refused.value) == "Unknown option(s): --dispatch-window 2"
