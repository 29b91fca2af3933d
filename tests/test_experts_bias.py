"""A router's selection bias, moved by the load and by no gradient
(--plan-experts-bias-rate; ops/experts.py::route, loads, load_signal;
parallel/zero.py::take_load_signals, move_by_load), on the layer plan of
benchmark/configs/lfm2-24b-a2b.json at its rehearsal's widths and on the
helpers of tests/test_layer_plan_conv.py:

  the choice   the top k are chosen by score + bias and weighted by the
      scores without it, under either scoring; padding is no load
  the signal   what the backward leaves in the bias's place is its experts'
      load less the mean, checkpointed or not, and `y` passes unchanged
  the update   one update moves every entry by exactly the rate against its
      signal's sign, through the fused step and through --optimizer-delay's
      sum; the optimizer neither moves the leaf nor norms the signal
  it balances  a router whose inputs share a direction sends most tokens to
      a few experts; the rule alone evens the loads out
  read alike   program and reference read a bias that is not zero the same
      way, and a program that ignores it is caught
  no flag, no leaf   and nothing is taken from the gradients
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common import prng
from marian_tpu.common.config_parser import parse_options
from marian_tpu.models import layer_plan as P
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.ops import experts as X
from marian_tpu.parallel import zero
from marian_tpu.training.graph_group import GraphGroup
from test_layer_plan import F32_LIMIT, _batch
from test_layer_plan_conv import PLAN, _dims, _model, _token_error
from time_limit import time_limit

RATE = 0.01
E, K = 16, 4


def _scores(score):
    x = jax.random.normal(jax.random.PRNGKey(0), (300, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32, E))
    logits = x @ w
    return x, w, jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)


@pytest.mark.parametrize("score", X.SCORES)
def test_the_bias_chooses_and_does_not_weigh(score):
    x, w, s = _scores(score)
    bias = jnp.zeros((1, E)).at[0, 3].set(5.0).at[0, 7].set(-5.0)
    idx, weights = X.route(x, w, K, 2.0, score, bias)
    assert bool((idx == 3).any(axis=1).all()) and not bool((idx == 7).any())
    np.testing.assert_array_equal(
        np.sort(idx, axis=1),
        np.sort(jax.lax.top_k(s + bias, K)[1], axis=1))
    chosen = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    # a bias at zero is no bias
    for got, want in zip(X.route(x, w, K, 2.0, score, jnp.zeros((1, E))),
                         X.route(x, w, K, 2.0, score)):
        np.testing.assert_array_equal(got, want)


def test_loads_count_real_tokens_over_the_whole_router():
    x, w, _ = _scores("sigmoid")
    idx = X.route(x, w, K, 1.0)[0]
    mask = (jnp.arange(300) < 200).astype(jnp.float32)
    load = np.asarray(X.loads(idx, mask, E))
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(idx[:200]).reshape(-1), minlength=E))
    assert load.sum() == 200 * K


@pytest.mark.parametrize("remat", [False, True])
def test_the_signal_is_the_load_less_its_mean(remat):
    x, w, _ = _scores("sigmoid")
    mask = jnp.ones((300,))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (1, E))

    def f(bias, x):
        idx, _ = X.route(x, w, K, 1.0, "sigmoid", bias)
        y = X.load_signal(2.0 * x, bias, X.loads(idx, mask, E))
        return jnp.sum(y ** 2), y

    g = jax.checkpoint(f) if remat else f
    (_, y), (d_bias, dx) = jax.value_and_grad(g, argnums=(0, 1),
                                              has_aux=True)(bias, x)
    np.testing.assert_array_equal(y, 2.0 * x)
    np.testing.assert_allclose(dx, 8.0 * x, rtol=1e-6)   # y's own gradient
    load = X.loads(X.route(x, w, K, 1.0, "sigmoid", bias)[0], mask, E)
    np.testing.assert_allclose(d_bias, (load - load.mean())[None], atol=1e-3)
    assert d_bias.shape == bias.shape and abs(float(d_bias.sum())) < 1e-2


def _group(extra=()):
    """The rehearsal's plan under the trainer's own update, at RATE."""
    dims, config = _dims()
    flags = list(config["task_flags"])
    i = flags.index("--transformer-layer-plan")
    flags[i + 1:i + 6] = PLAN
    flags[flags.index("--plan-experts-bias-rate") + 1] = str(RATE)
    tiny = list(config["rehearse"]["flags"])
    opts = parse_options(
        flags + tiny + ["--train-sets", "x", "--vocabs", "v",
                        "--cost-type", "ce-mean-words", *extra],
        mode="training")
    gg = GraphGroup(create_model(opts, dims["vocab"], dims["vocab"]), opts)
    gg.initialize(prng.root_key(7))
    return gg, dims


def _plain():
    """The same plan without the bias: the configuration's flags less
    the rate."""
    flags = _dims()[1]["task_flags"]
    i = flags.index("--plan-experts-bias-rate")
    return _model(drop=tuple(flags[i:i + 2]))[0]


def _signs(gg, batch):
    """The sign of every bias's signal at the group's parameters."""
    g = jax.grad(lambda p: gg.model.loss(p, batch, jax.random.PRNGKey(0),
                                         True)[0])(gg.params)
    return {k: np.sign(np.asarray(v)) for k, v in g.items()
            if k.endswith("_experts_bias")}


@pytest.mark.parametrize("delay", [1, 2])
@time_limit(300)
def test_one_update_moves_the_bias_by_its_rate_and_nothing_else_does(delay):
    gg, dims = _group(["--optimizer-delay", str(delay)] if delay > 1 else [])
    batch = _batch(dims["vocab"])
    names = [k for k in gg.params if k.endswith("_experts_bias")]
    assert len(names) == 2 and gg.model.load_moved == ("_experts_bias", RATE)
    before = {k: np.asarray(gg.params[k]) for k in names}
    assert all(not b.any() for b in before.values())
    signs = _signs(gg, batch)
    out = gg.update([batch] * delay, 1, prng.root_key(3))
    for k in names:
        moved = np.asarray(gg.params[k]) - before[k]
        assert np.abs(signs[k]).sum() > 0
        np.testing.assert_allclose(moved, -RATE * signs[k], atol=1e-7)
    # the signal (loads in the hundreds) is in no norm: a plan without
    # the bias reads the same gradient norm and the same cost
    plain = _plain()
    assert plain.load_moved[1] == 0.0
    opts = gg.options
    other = GraphGroup(plain, opts)
    other.initialize(prng.root_key(7))
    assert not any(k.endswith("_experts_bias") for k in other.params)
    want = other.update([batch] * delay, 1, prng.root_key(3))
    np.testing.assert_allclose(float(out.grad_norm), float(want.grad_norm),
                               rtol=1e-5)
    np.testing.assert_allclose(float(out.loss_sum), float(want.loss_sum),
                               rtol=1e-6)
    # the optimizer's moments of the leaf never saw the signal
    for group in ("m", "v"):
        for k in names:
            assert not np.asarray(gg.opt_state[group][k]).any()


def test_the_rule_alone_evens_out_a_router_that_collapsed():
    """Inputs that share a direction (what a stack's hidden states grow
    in their first updates) send most tokens to the experts that
    direction favours; the bias, moved by the sign of the excess load,
    takes the loads back to even without touching router or inputs."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 32)) \
        + jax.random.normal(jax.random.PRNGKey(5), (1, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32, E))
    mask = jnp.ones((2048,))

    @jax.jit
    def step(bias):
        load = X.loads(X.route(x, w, K, 1.0, "sigmoid", bias)[0], mask, E)
        return bias - RATE * jnp.sign(load - load.mean())[None], load

    bias = jnp.zeros((1, E))
    bias, first = step(bias)
    for _ in range(150):
        bias, load = step(bias)
    assert float(first.max() / first.mean()) > 3.0
    assert float(load.max() / load.mean()) < 1.3
    assert float(load.min() / load.mean()) > 0.7


@time_limit(300)
def test_program_and_reference_read_a_bias_alike(monkeypatch):
    model, dims = _model()
    params = model.init(jax.random.PRNGKey(7))
    batch = _batch(dims["vocab"])
    moved = {k: 0.3 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
             for i, (k, v) in enumerate(sorted(params.items()))
             if k.endswith("_experts_bias")}
    assert len(moved) == 2
    biased = dict(params, **moved)
    assert _token_error(model, dims, biased, batch) < F32_LIMIT
    # the bias changes who is chosen: the same program without it differs
    assert _token_error(model, dims, biased, batch, given=params) \
        > 10 * F32_LIMIT
    # and a bias that weighed as well as chose is caught
    route = X.route

    def weighing(x, w_router, top_k, scale, score="sigmoid", bias=None):
        idx, _ = route(x, w_router, top_k, scale, score, bias)
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ w_router) \
            + bias.reshape(-1)
        vals = jnp.take_along_axis(s, idx, axis=-1)
        return idx, vals / jnp.sum(vals, -1, keepdims=True) * scale
    monkeypatch.setattr(X, "route", weighing)
    assert _token_error(model, dims, biased, batch) > 10 * F32_LIMIT


def test_no_flag_no_leaf_and_the_gradients_pass_untouched():
    model = _plain()
    assert model.cfg.experts_bias_rate == 0.0
    assert P.load_moved(model.cfg) == ("_experts_bias", 0.0)
    grads = {"a_experts_bias": jnp.ones((1, 4)), "b": jnp.ones((2,))}
    got, signals = zero.take_load_signals(model, grads)
    assert got is grads and signals == {}
    assert zero.move_by_load(model, grads, grads, signals) == grads


def test_a_rate_below_zero_is_refused_in_so_many_words():
    with pytest.raises(ValueError, match="AGAINST its expert's excess load"):
        _model(extra=["--plan-experts-bias-rate", "-0.1"])
