"""The per-layer plan of the decoder-only stack (models/layer_plan.py) and
its three mechanisms, on seeded random weights at small sizes, against
straightforward forms of the same mathematics:

  the delta rule with a per-channel decay   chunked == token by token,
      forward and gradient; the Pallas kernels == the chunk's
      preparation and the chunked carry, and both together == the
      recurrence
  latent attention without rotary            == a dense masked softmax at
      key width 192 / value width 128; flash at unequal widths
  the expert layer told which experts it holds == a masked loop over the
      held experts; the shares add up; nothing is dropped
  the whole cut model                        == the plain float32
      reference beside the benchmark's configuration
      (benchmark/configs/kimi_linear_reference.py), costs and gradients,
      and a lower precision in the state or the router is caught
"""

import collections
import dataclasses
import importlib
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common.config_parser import parse_options
from marian_tpu.models import layer_plan as P
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.ops import experts as X
from marian_tpu.ops import kda
from marian_tpu.ops.attention import dense_attention
from marian_tpu.ops.ops import short_conv
from marian_tpu.ops.pallas import kda_chunk, kda_prep
from marian_tpu.ops.pallas.flash_attention import flash_attention
from test_flash_attention import _equations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the delta rule
# ---------------------------------------------------------------------------

def _kda_inputs(seed, b=1, h=2, t=100, dk=16, dv=24, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (b, h, t, dk)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    # log decay per channel: mild (a memory of ~7 positions) or so strong
    # that a channel forgets everything within a sub-block
    g = -jnp.exp(jax.random.normal(ks[3], (b, h, t, dk))
                 * (2.0 if strong else 1.0) + (2.0 if strong else -2.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, g, beta


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("t", [100, 7])
def test_kda_chunked_is_the_recurrence(t, strong):
    """T not a multiple of the chunk, and less than a sub-block; forward
    and jax.grad of every input."""
    args = _kda_inputs(t, t=t, strong=strong)
    want = kda.kda_recurrent(*args, 0.25)
    got = kda.kda_chunked(*args, 0.25)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-4)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a, 0.25) ** 2),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g_got, g_want in zip(grads(kda.kda_chunked),
                             grads(kda.kda_recurrent)):
        assert bool(jnp.isfinite(g_got).all())
        np.testing.assert_allclose(g_got, g_want, atol=5e-4)


def test_kda_masked_tail_changes_nothing_before_it():
    """What stands after a row's last real token (padding) cannot reach
    the outputs before it, and repeated identical keys with b = 1 (the
    worst case of the triangular solve) stay finite and right."""
    q, k, v, g, beta = _kda_inputs(3, t=90)
    k = k.at[:, :, 20:60].set(k[:, :, 20:21])
    beta = beta.at[:, :, 20:60].set(1.0)
    g = g.at[:, :, 20:60].set(0.0)
    want = kda.kda_recurrent(q, k, v, g, beta, 0.25)
    got = kda.kda_chunked(q, k, v, g, beta, 0.25)
    np.testing.assert_allclose(got, want, atol=1e-4)
    junk = [x.at[:, :, 70:].set(7.0) for x in (q, k, v)]
    tail = kda.kda_chunked(*junk, g.at[:, :, 70:].set(-9.0),
                           beta.at[:, :, 70:].set(1.0), 0.25)
    np.testing.assert_allclose(tail[:, :, :70], got[:, :, :70], atol=1e-6)


def test_kda_kernels_are_the_chunked_carry():
    """kda_chunk_fwd / kda_chunk_bwd (interpret mode, one tiny shape)
    against ops/kda.py :: state_carry, outputs and all six cotangents."""
    args = _kda_inputs(5, b=1, h=4, t=192, dk=128, dv=128)
    terms = kda.chunk_terms(*args, 0.1)
    want = kda.state_carry(*terms)
    got = kda_chunk.kda_state_carry(*terms, heads=2, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                        argnums=tuple(range(6)))(*terms)
    kernel = lambda *a: kda_chunk.kda_state_carry(   # noqa: E731
        *a, heads=2, interpret=True)
    for g_got, g_want in zip(grads(kernel), grads(kda.state_carry)):
        np.testing.assert_allclose(g_got, g_want, atol=1e-4)
    assert kda_chunk.heads_a_step(32) == 4
    assert kda_chunk.heads_a_step(6, 4) == 3      # divisors only


def _prep_case(case):
    """Inputs at the kernels' widths (dk = dv = 128): T = 192, a T that
    pads (100 -> 128, as kda_chunked pads it: positions that change
    nothing), decays mild or forgetting everything within a sub-block,
    and forty identical keys with b = 1 and no decay (the worst case of
    the triangular solve)."""
    t = 100 if "pads" in case else 192
    q, k, v, g, beta = _kda_inputs(11, b=1, h=4, t=t, dk=128, dv=128,
                                   strong="strong" in case)
    if "identical" in case:
        k = k.at[:, :, 20:60].set(k[:, :, 20:21])
        beta = beta.at[:, :, 20:60].set(1.0)
        g = g.at[:, :, 20:60].set(0.0)
    return q, k, v, g, beta


_PREP_CASES = ("mild-192", "strong-192", "mild-pads", "strong-pads",
               "identical-keys-192", "identical-keys-pads")


@pytest.mark.parametrize("case", _PREP_CASES + ("mild-192-single",
                                                "strong-pads-single"))
def test_kda_prep_kernels_are_the_chunk_terms(case):
    """kda_prep_fwd / kda_prep_bwd (interpret mode) against ops/kda.py ::
    chunk_terms: all six terms and, under one weighted sum of them, all
    five cotangents, each within a share of its own largest value (the
    kernels' small products take three bfloat16 passes, as chunk_terms'
    do on a TPU; a forgetting decay's exponent is a difference of large
    sums; where keys repeat it is chunk_terms that is 3e-4 off, through
    the eighth power of a block of ones: the kernels' inverse goes by
    halves and stays within 5e-6 of float64). A grid step's heads go two
    to a tile, or singly where their number is odd."""
    args = _prep_case(case)
    pad = -args[0].shape[2] % kda.CHUNK
    args = tuple(jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),)
                         * (x.ndim - 3)) for x in args)
    kernel = lambda *a: kda_prep.kda_chunk_terms(       # noqa: E731
        *a, 0.1, heads=1 if "single" in case else 2, interpret=True)
    want = kda.chunk_terms(*args, 0.1)
    got = kernel(*args)
    rtol = 5e-5 if "mild" in case else 1e-3
    for name, x_got, x_want in zip("qg wk wv kd gc p".split(), got, want):
        assert x_got.shape == x_want.shape and x_got.dtype == jnp.float32
        assert bool(jnp.isfinite(x_got).all()), name
        np.testing.assert_allclose(
            x_got, x_want, rtol=0, err_msg=name,
            atol=rtol * max(float(jnp.abs(x_want).max()), 1e-6))
    ws = [jax.random.normal(jax.random.PRNGKey(9 + i), x.shape)
          for i, x in enumerate(want)]

    def grads(fn):
        return jax.grad(lambda *a: sum(jnp.sum(x * w)
                                       for x, w in zip(fn(*a), ws)),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for name, g_got, g_want in zip(
            "dq dk dv dg db".split(), grads(kernel),
            grads(lambda *a: kda.chunk_terms(*a, 0.1))):
        assert bool(jnp.isfinite(g_got).all()), name
        if name == "dg" and "identical" in case:
            # g = 0 there: chunk_terms' min(ref - G, 0) sits on its tie,
            # where autodiff halves the gradient; the recurrence below
            # holds this dg
            continue
        np.testing.assert_allclose(
            g_got, g_want, rtol=0, err_msg=name,
            atol=2 * rtol * float(jnp.abs(g_want).max()))


@pytest.mark.parametrize("case", _PREP_CASES)
def test_kda_chunked_on_both_kernel_pairs_is_the_recurrence(case):
    """The layer as a TPU runs it (preparation and carry both Pallas, in
    interpret mode) against the token-by-token oracle, forward and
    jax.grad of every input."""
    args = _prep_case(case)
    kernels = dict(
        terms=lambda *a: kda_prep.kda_chunk_terms(*a, heads=2,
                                                  interpret=True),
        carry=lambda *a: kda_chunk.kda_state_carry(*a, heads=2,
                                                   interpret=True))
    want = kda.kda_recurrent(*args, 0.1)
    got = kda.kda_chunked(*args, 0.1, **kernels)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-4)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a, 0.1) ** 2),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g_got, g_want in zip(
            grads(lambda *a: kda.kda_chunked(*a, **kernels)),
            grads(kda.kda_recurrent)):
        assert bool(jnp.isfinite(g_got).all())
        np.testing.assert_allclose(g_got, g_want, atol=5e-4)


def test_short_conv_is_causal():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 5))
    y = short_conv(x, w)
    want = sum(w[j] * jnp.pad(x, ((0, 0), (3 - j, 0), (0, 0)))[:, :9]
               for j in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6)
    y2 = short_conv(x.at[:, 5:].set(0.0), w)
    np.testing.assert_allclose(y2[:, :5], y[:, :5], atol=1e-6)


# ---------------------------------------------------------------------------
# latent attention at unequal widths
# ---------------------------------------------------------------------------

def _attention_inputs(t=300, dq=192, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, h = 2, 2
    q = jax.random.normal(ks[0], (b, h, t, dq)) * 0.3
    k = jax.random.normal(ks[1], (b, h, t, dq)) * 0.3
    v = jax.random.normal(ks[2], (b, h, t, dv))
    kvm = (jnp.arange(t)[None] < jnp.array([t, t * 3 // 5])[:, None]
           ).astype(jnp.float32)
    w = jax.random.normal(ks[3], (b, h, t, dv)) * kvm[:, None, :, None]
    return q, k, v, kvm, w


def test_flash_attention_takes_unequal_key_and_value_widths():
    q, k, v, kvm, w = _attention_inputs()
    t = q.shape[2]
    mask = jnp.tril(jnp.ones((t, t)))[None, None] * kvm[:, None, None, :]

    def flash(q, k, v):
        return flash_attention(q, k, v, kv_mask=kvm, causal=True,
                               block_q=128, block_k=128, interpret=True)

    def dense(q, k, v):
        return dense_attention(q, k, v, mask)
    assert flash(q, k, v).shape == (2, 2, t, 128)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(
        q, k, v) for f in (flash, dense)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, atol=5e-5)


def _plan_model(extra=(), precision="float32", plan=("kda:dense",
                "kda:experts", "mla:experts"), held=(0, 8), experts=16):
    argv = ["--type", "transformer-lm", "--transformer-layer-plan", *plan,
            "--dim-emb", "64", "--transformer-heads", "4",
            "--transformer-dim-ffn", "128", "--plan-kda-dim-head", "16",
            "--plan-kda-low-rank", "8", "--plan-mla-dim-nope", "16",
            "--plan-mla-dim-shared", "8", "--plan-mla-dim-v", "16",
            "--plan-mla-latent", "32", "--plan-experts", str(experts),
            "--plan-experts-held", *map(str, held),
            "--plan-experts-top-k", "4", "--plan-experts-dim-ffn", "32",
            "--plan-experts-scale", "2.446", "--precision", precision,
            "float32", "--train-sets", "x", "--vocabs", "v", *extra]
    return create_model(parse_options(argv, mode="training"), 96, 96)


def test_mla_is_a_dense_masked_softmax_at_192_and_128():
    """The layer at the published head widths (keys 128 + 64 shared and
    unrotated, values 128) against scores and softmax written out."""
    cfg = P.PlanConfig(src_vocab=8, trg_vocab=8, dim_emb=96, heads=2,
                       plan=(("mla", "dense"),), flash_attention="off",
                       compute_dtype=jnp.float32)
    p = {k: v for k, v in P.init_params(cfg, jax.random.PRNGKey(4)).items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 96))
    mask = (jnp.arange(40)[None] < jnp.array([40, 25])[:, None]
            ).astype(jnp.float32)
    got = P._mla(cfg, p, "decoder_l1", x, mask)
    lp = "decoder_l1_mla"
    q = (x @ p[f"{lp}_Wq"]).reshape(2, 40, 2, 192)
    kva = x @ p[f"{lp}_Wkva"]
    lat = kva[..., :512]
    lat = lat / jnp.sqrt(jnp.mean(lat ** 2, -1, keepdims=True) + 1e-5)
    kv = (lat @ p[f"{lp}_Wkvb"]).reshape(2, 40, 2, 256)
    key = jnp.concatenate([kv[..., :128], jnp.broadcast_to(
        kva[:, :, None, 512:], (2, 40, 2, 64))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, key) / np.sqrt(192.0)
    see = (jnp.tril(jnp.ones((40, 40)))[None, None]
           * mask[:, None, None, :]) > 0
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(see, s, -1e30), -1),
                   kv[..., 128:])
    want = o.reshape(2, 40, 256) @ p[f"{lp}_Wo"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    flash = P._mla(dataclasses.replace(cfg, flash_attention="on"), p,
                   "decoder_l1", x, mask)
    real = mask[..., None] > 0
    np.testing.assert_allclose(jnp.where(real, flash, 0),
                               jnp.where(real, want, 0), atol=5e-5)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

T_, D_, F_, E_, K_ = 300, 32, 48, 16, 4


def _expert_inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T_, D_))
    router = jax.random.normal(ks[1], (D_, E_))
    wg = jax.random.normal(ks[2], (E_, D_, F_)) * 0.2
    wu = jax.random.normal(ks[3], (E_, D_, F_)) * 0.2
    wd = jax.random.normal(ks[4], (E_, F_, D_)) * 0.2
    mask = (jnp.arange(T_) < 280).astype(jnp.float32)
    return x, router, wg, wu, wd, mask, ks[5]


def _held_loop(x, router, wg, wu, wd, mask, first):
    """A loop over the held experts with a mask: every token through
    every held expert, weighted by what the router gave it."""
    idx, w = X.route(x, router, K_, 2.5)
    y = 0.0
    for i in range(wg.shape[0]):
        mine = jnp.sum(jnp.where(idx == first + i, w, 0.0), -1) * mask
        y = y + mine[:, None] * ((jax.nn.silu(x @ wg[i]) * (x @ wu[i]))
                                 @ wd[i])
    return y


def _held_layer(x, router, wg, wu, wd, mask, first, rows=512):
    idx, w = X.route(x, router, K_, 2.5)
    return X.held_experts(x, mask, idx, w, wg, wu, wd, first, rows)


def _whole_batches(n):
    """The most batches (2 .. 10) that cut a list of n rows evenly."""
    return max(d for d in range(2, 11) if n % d == 0)


# 280 tokens x 4 picks over 16 experts are 70 rows an expert, so the
# lists here hold 194 (3 held) to 1120 (all 16) rows. A batch's rows,
# given or from the list's length n, and the trips that must run: a list
# shorter than one batch; one that ends exactly on a batch's edge (after
# one batch, after several); one of several batches that ends inside the
# last; batches smaller than a bucket, so that many trips run and a
# bucket spans several of them (what a loop of blocks was once kept for)
@pytest.mark.parametrize("rows,trips", [
    (1536, 1), (lambda n: n, 1),
    (lambda n: n // _whole_batches(n), _whole_batches),
    (lambda n: -(-3 * n // 10), 4), (lambda n: -(-3 * n // 5), 2),
    (136, None), (32, None)],
    ids=["short", "edge-1", "edge-several", "several", "two", "many-136",
         "many-32"])
@pytest.mark.parametrize("first,count", [(0, 8), (8, 8), (0, 16), (5, 3)])
def test_held_experts_are_the_masked_loop(first, count, rows, trips):
    x, router, wg, wu, wd, mask, kw = _expert_inputs()
    sl = slice(first, first + count)
    idx = X.route(x, router, K_, 2.5)[0]
    n = int(jnp.sum((idx >= first) & (idx < first + count)
                    & (mask[:, None] > 0)))
    rows, trips = (f(n) if callable(f) else f for f in (rows, trips))
    want = _held_loop(x, router, wg[sl], wu[sl], wd[sl], mask, first)
    got, counters = _held_layer(x, router, wg[sl], wu[sl], wd[sl], mask,
                                first, rows)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = dict(zip(X.COUNTERS, np.asarray(counters).tolist()))
    assert counts["moe.assignments"] == 280 * K_ and counts["moe.dropped"] == 0
    assert counts["moe.assignments_held"] == n
    assert counts["moe.pool_calls"] == 1.0
    assert counts["moe.pool_trips"] == -(-n // rows)
    assert trips is None or counts["moe.pool_trips"] == trips
    assert counts["moe.pool_rows"] == counts["moe.pool_trips"] * rows >= n
    w = jax.random.normal(kw, want.shape)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a, mask, first) * w),
                      argnums=(0, 1, 2, 3, 4))(x, router, wg[sl], wu[sl],
                                               wd[sl])
             for f in (lambda *a: _held_layer(*a, rows)[0], _held_loop)]
    for g_got, g_want in zip(*grads):
        np.testing.assert_allclose(g_got, g_want, atol=1e-4)


@pytest.mark.parametrize("end", [0, 5, 12, 13, 29, 30, 100])
def test_a_batch_s_groups_hold_every_row_of_it(end):
    """Whatever part of the list a batch of 10 rows covers (buckets of 5,
    0, 8, 17 rows: 30 in all), its group sizes are its rows of each
    bucket before `end`, none negative, and they sum to 10: the chip's
    grouped matmul reads past its operand otherwise."""
    sizes = jnp.array([5, 0, 8, 17])
    starts = jnp.cumsum(sizes) - sizes
    for off in (0, 10, 20):
        groups = np.asarray(X._batch_groups(sizes, starts, 10, off, end))
        rows = np.arange(off, off + 10)
        want = [int(np.sum((rows >= s) & (rows < s + n) & (rows < end)))
                for s, n in zip(np.asarray(starts), np.asarray(sizes))]
        want[-1] += 10 - sum(want)
        assert groups.tolist() == want and groups.min() >= 0


def _held_grad_eqns(jaxpr=None, inside=False):
    """(equation, whether it sits inside a while loop's body) over the
    jaxpr of the held layer's gradient (a batch of 256 rows) and
    everything it calls."""
    if jaxpr is None:
        x, router, wg, wu, wd, mask, _ = _expert_inputs()
        grad = jax.grad(
            lambda *a: jnp.sum(_held_layer(*a, mask, 0, 256)[0]),
            argnums=(0, 2, 3, 4))
        jaxpr = jax.make_jaxpr(grad)(x, router, wg, wu, wd).jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _held_grad_eqns(
                sub, inside or eqn.primitive.name == "while")


def test_the_program_holds_the_batch_once():
    """The list is ONE batch in a loop that runs as often as the list is
    long, forward (3 grouped matmuls) and backward (the 3 again and
    their 6 transposes), all of one batch's rows and all inside a while
    loop's body: no second copy of the code for a longer list, nothing
    outside the loop that runs whether or not a row arrived."""
    dots = [(eqn.invars[0].aval.shape[0], inside)
            for eqn, inside in _held_grad_eqns()
            if eqn.primitive.name.startswith("ragged_dot")]
    assert dots == [(256, True)] * 12


def test_a_trip_s_gradients_go_into_the_carried_accumulators():
    """A trip scatters its rows' output, and in the backward their
    gradient, into the [T, d] accumulator its loop carries:
    nothing else of the tokens' shape is built inside a loop's body (no
    zeros to scatter into, no second array to add)."""
    built = [eqn.primitive.name for eqn, inside in _held_grad_eqns()
             if inside and any(getattr(v.aval, "shape", None) == (T_, D_)
                               for v in eqn.outvars)]
    assert built == ["scatter-add"] * 2, built      # forward, backward


@pytest.mark.parametrize("experts,held,width,extra", [
    (16, 8, 32, ()), (256, 16, 768, ()),
    (128, 8, 32, ("--plan-experts-top-k", "8", "--plan-experts-scale",
                  "2.826"))],
    ids=["2x8-of-16", "16x16-of-256-at-768", "16x8-of-128-top-8-scaled"])
def test_the_shares_add_up(experts, held, width, extra):
    """Every chip's share of the layer (8 of 16 experts; 16 of 256 at
    expert width 768, one of 16 chips; 8 of 128 under a top 8 and a route
    scale of 2.826, one of 16 chips): their outputs, with the shared
    expert counted once, sum to the uncut layer's output; and the routing
    counters of the shares sum to the uncut layer's."""
    x, router, wg, wu, wd, mask, _ = _expert_inputs()
    whole, c_all = _held_layer(x, router, wg, wu, wd, mask, 0)
    lo, c_lo = _held_layer(x, router, wg[:8], wu[:8], wd[:8], mask, 0)
    hi, c_hi = _held_layer(x, router, wg[8:], wu[8:], wd[8:], mask, 8)
    np.testing.assert_allclose(lo + hi, whole, atol=2e-5)
    assert float(c_all[1]) == float(c_all[0]) == 280 * K_
    assert float(c_lo[1] + c_hi[1]) == float(c_all[1])
    # through the layer, shared expert and all: the shares' sum, less the
    # shared expert once for every share but one
    size = ("--plan-experts-dim-ffn", str(width), *extra)
    shares = [(first, held) for first in range(0, experts, held)]
    models = [_plan_model(size, plan=("mla:experts",), held=h,
                          experts=experts) for h in [(0, experts)] + shares]
    full = P.init_params(models[0].cfg, jax.random.PRNGKey(3))
    assert full["decoder_l1_experts_Wg"].shape == (experts, 64, width)
    xs = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    m2 = jnp.ones((2, 24))
    outs, counts = [], []
    for m, (first, n) in zip(models, [(0, experts)] + shares):
        p = {k: (v[first:first + n] if "_experts_W" in k else v)
             for k, v in full.items()}
        y, c = P._experts(m.cfg, p, "decoder_l1", xs, m2)
        outs.append(y)
        counts.append(c)
    shared = X.gated_mlp(xs.reshape(-1, 64), full["decoder_l1_shared_Wg"],
                         full["decoder_l1_shared_Wu"],
                         full["decoder_l1_shared_Wd"]).reshape(xs.shape)
    np.testing.assert_allclose(
        sum(outs[1:]) - (len(shares) - 1) * shared, outs[0],
        atol=2e-5 * len(shares))
    assert float(sum(c[1] for c in counts[1:])) == float(counts[0][1]) \
        == 2 * 24 * models[0].cfg.experts_top_k
    assert models[0].cfg.experts_scale == (2.826 if extra else 2.446)


@pytest.mark.parametrize("rows", [64, 20])
def test_no_token_is_dropped_when_all_pick_one_expert(rows):
    x, _, wg, wu, wd, mask, _ = _expert_inputs()
    idx = jnp.tile(jnp.array([[3, 17, 18, 19]]), (T_, 1))   # 3 is held
    w = jnp.full((T_, 4), 0.25)
    y, counters = jax.jit(lambda: X.held_experts(
        x, mask, idx, w, wg[:8], wu[:8], wd[:8], 0, rows))()
    want = 0.25 * mask[:, None] * (
        (jax.nn.silu(x @ wg[3]) * (x @ wu[3])) @ wd[3])
    np.testing.assert_allclose(y, want, atol=2e-5)
    # all 280 on one expert: T k / R trips (5 of 64 rows, 14 of 20) of a
    # grouped matmul whose one group holds every row
    trips = -(-280 // rows)
    assert [float(c) for c in counters] == [
        1120.0, 280.0, 280.0, 35.0, 0.0, 1.0, trips, trips * rows]
    # an expert nobody picked costs no trip: nothing arrives, nothing runs
    none, c0 = X.held_experts(x, mask, idx + 20, w, wg[:8], wu[:8], wd[:8],
                              0, rows)
    assert float(jnp.abs(none).max()) == 0.0
    assert [float(c0[i]) for i in (1, 6, 7)] == [0.0, 0.0, 0.0]


def test_a_batch_is_half_a_share_in_whole_tiles():
    # the four cells at their widest update (tokens, top k, held, experts):
    # half of the even share, 256 rows a held expert or more, and a third
    # of the 1.5 shares that once ALWAYS ran, so no list there is given
    # more rows than it was
    for sizes, rows in (((16384, 8, 8, 256), 2048),
                        ((16384, 8, 16, 256), 4096),
                        ((32768, 8, 16, 128), 16384),
                        ((16384, 8, 8, 128), 4096)):
        tokens, top_k, held, experts = sizes
        share = tokens * top_k * held // experts
        assert X.pool_rows(*sizes) == rows == share // 2
        assert (3 * share // 2) % rows == 0 == rows % 512
        assert rows >= 256 * held
    # a narrower update's batch follows its share, rounded up to a tile;
    # a call too small for one gets one
    assert X.pool_rows(11264, 8, 8, 256) == 1536
    assert X.pool_rows(48, 4, 8, 32) == 512 == X.pool_rows(1, 1, 1, 64)
    # a plan that holds ALL its experts: a list of exactly one share on
    # every call, two trips, where 1.5 shares ran
    assert X.pool_rows(4096, 8, 64, 64) == 4096 * 8 // 2


# ---------------------------------------------------------------------------
# the whole cut model against the benchmark's plain reference
# ---------------------------------------------------------------------------

def _reference():
    return importlib.import_module("benchmark.configs.kimi_linear_reference")


def _tiny_dims():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as fh:
        config = json.load(fh)
    return dict(config, **config["rehearse"]["dims"]), config


def _tiny_model(precision="float32", extra=()):
    dims, config = _tiny_dims()
    flags = [f for f in config["task_flags"]]
    i = flags.index("--precision")
    flags[i + 1:i + 3] = [precision, "float32"]
    tiny = [f for f in config["rehearse"]["flags"]]
    j = tiny.index("--precision")
    del tiny[j:j + 3]
    argv = flags + tiny + ["--precision", precision, "float32",
                           "--train-sets", "x", "--vocabs", "v", *extra]
    model = create_model(parse_options(argv, mode="training"),
                         dims["vocab"], dims["vocab"])
    return model, dims


def _batch(vocab, rows=2, width=80):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, width), 2, vocab)
    lens = jnp.array([width, width * 2 // 3, width // 3])[:rows]
    mask = (jnp.arange(width)[None] < lens[:, None]).astype(jnp.float32)
    return {"src_ids": ids, "src_mask": mask, "trg_ids": ids,
            "trg_mask": mask}


def _token_costs(model, params, batch):
    """Per-token costs of the program through `data_weights`, one-hot a
    token at a time being too slow: the gradient of the weighted loss
    with respect to the weights IS the per-token cost."""
    w = jnp.ones_like(batch["trg_mask"])
    return jax.grad(lambda w: model.loss(
        params, dict(batch, data_weights=w), None, False)[0])(w)


@pytest.fixture(scope="module")
def tiny():
    model, dims = _tiny_model()
    params = model.init(jax.random.PRNGKey(7))
    return model, dims, params, _batch(dims["vocab"])


def test_the_cut_model_costs_what_the_reference_costs(tiny):
    model, dims, params, batch = tiny
    assert model.cfg.plan == tuple(tuple(e.split(":"))
                                   for e in dims["layer_plan"])
    want = _reference().token_costs(params, dims, None, None,
                                    batch["trg_ids"], batch["trg_mask"])
    got = _token_costs(model, params, batch)
    real = batch["trg_mask"] > 0
    np.testing.assert_allclose(jnp.where(real, got, 0),
                               jnp.where(real, want, 0), atol=3e-5)


@pytest.mark.parametrize("held", ["share", "whole", "share-flash"])
def test_every_parameter_group_gets_the_reference_gradient(tiny, held):
    """Every leaf's gradient is the reference's, with the plan's halves
    checkpointed (the file's `task_flags`). A share of the layer (8 of 32
    experts, as the benchmark's cut) passes nothing to its router, in
    program and reference alike; holding the whole layer trains it; with
    the flash kernel forced on, the `mla` half's checkpoint keeps the
    kernel's output and statistics and the gradient is the same."""
    model, dims, params, batch = tiny
    assert model.cfg.gradient_checkpointing
    if held == "share-flash":
        model, _ = _tiny_model(extra=["--transformer-flash-attention", "on"])
    if held == "whole":
        n = dims["router_width"]
        model, _ = _tiny_model(extra=["--plan-experts-held", "0", str(n)])
        dims = dict(dims, num_experts=n)
        params = model.init(jax.random.PRNGKey(7))
    ref = _reference()

    def ref_loss(p):
        return jnp.sum(ref.token_costs(p, dims, None, None, batch["trg_ids"],
                                       batch["trg_mask"])
                       * batch["trg_mask"])
    want = jax.grad(ref_loss)(params)
    got = jax.grad(lambda p: model.loss(p, batch, None, True)[0])(params)
    assert set(got) == set(want) == set(params)
    for name in sorted(params):
        scale = float(jnp.abs(want[name]).max())
        if held != "whole" and name.endswith("_experts_router"):
            assert scale == 0 == float(jnp.abs(got[name]).max()), name
            continue
        assert scale > 0, f"{name}: the reference's gradient is zero"
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale,
                                   err_msg=name)


def _token_error(model, dims, params, batch):
    """RMS error of a token's cost over the spread of the reference's
    costs: what the benchmark's `token_rtol` bounds."""
    want = _reference().token_costs(params, dims, None, None,
                                    batch["trg_ids"], batch["trg_mask"])
    got = _token_costs(model, params, batch)
    real = np.asarray(batch["trg_mask"]) > 0
    want, got = np.asarray(want)[real], np.asarray(got)[real]
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


# Limits on the RMS error of a token's cost over the spread of the
# tokens' costs, at the rehearsal's tiny widths (readings on the CPU, PR 28:
# float32 1.8e-6; bfloat16 compute 1.4e-1, nearly all of it top-k picks
# that flip on bfloat16-rounded activations, a quarter of which land on a
# held expert here against 1/32 at the published router width; a bfloat16
# state alone 1.7e-2, a bfloat16 router alone 1.6e-1, bfloat16 compute
# with a bfloat16 router 1.9e-1). The benchmark's own limit, at the
# published widths on the chip, is in benchmark/traffic/train-docs8k.json.
F32_LIMIT = 1e-4
BF16_LIMIT = 1.65e-1


def _bf16_state(qg, wk, wv, kd, gc, p):
    """ops/kda.py :: state_carry with the state rounded to bfloat16
    after every chunk."""
    out, st = [], jnp.zeros((*qg.shape[:2], wv.shape[-1], qg.shape[-1]))
    for n in range(qg.shape[2]):
        u = wv[:, :, n] - jnp.einsum("bhck,bhvk->bhcv", wk[:, :, n], st)
        out.append(jnp.einsum("bhck,bhvk->bhcv", qg[:, :, n], st)
                   + jnp.einsum("bhci,bhiv->bhcv", p[:, :, n], u))
        st = (gc[:, :, n] * st + jnp.einsum(
            "bhcv,bhck->bhvk", u, kd[:, :, n])
              ).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.stack(out, 2)


def _bf16_route(x, w_router, top_k, scale, score="sigmoid", bias=None):
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.bfloat16),
                               w_router.astype(jnp.bfloat16)))
    vals, idx = jax.lax.top_k(s, top_k)
    vals = vals.astype(jnp.float32)
    return idx, vals / jnp.sum(vals, -1, keepdims=True) * scale


def test_one_precision_lower_is_caught(tiny, monkeypatch):
    """float32 is tight; bfloat16 compute (float32 state and router) is
    within its stated limit; a bfloat16 delta-rule state or a bfloat16
    router, each alone in a float32 model, exceeds the float32 limit (at
    these widths bfloat16 compute is too noisy to tell a second rounding
    under it: that comparison is the chip's, at the published widths)."""
    model, dims, params, batch = tiny
    assert _token_error(model, dims, params, batch) < F32_LIMIT
    low, _ = _tiny_model("bfloat16")
    assert F32_LIMIT < _token_error(low, dims, params, batch) < BF16_LIMIT
    monkeypatch.setattr(P.K, "state_carry", _bf16_state)
    assert _token_error(model, dims, params, batch) > F32_LIMIT
    monkeypatch.undo()
    monkeypatch.setattr(X, "route", _bf16_route)
    assert _token_error(model, dims, params, batch) > F32_LIMIT


def test_counters_leave_the_step_lazily_and_reach_the_tracer(tiny):
    """model.loss hands the routing counts back as one lazy vector; the
    tracer keeps such vectors untouched while spans are live and sums
    them where the Scheduler syncs (no device op, nothing to compile)."""
    from marian_tpu.obs import TRACER
    model, _, params, batch = tiny
    assert model.step_counters == X.COUNTERS
    _, aux = jax.jit(lambda p: model.loss(p, batch, None, True))(params)
    names = model.step_counters
    counts = dict(zip(names, np.asarray(aux["counters"]).tolist()))
    layers = sum(1 for _, f in model.cfg.plan if f == "experts")
    labels = float(batch["trg_mask"].sum())
    assert counts["moe.assignments"] == layers * labels * 4
    assert 0 < counts["moe.assignments_held"] < counts["moe.assignments"]
    assert counts["moe.dropped"] == 0.0
    assert counts["moe.load_max"] >= counts["moe.load_mean"]
    # every expert layer's call is counted; at this size the first pool
    # is one tile and there is no second: what passes it is the loop's
    assert counts["moe.pool_calls"] == layers
    assert counts["moe.pool_calls"] <= counts["moe.pool_trips"]
    assert counts["moe.pool_rows"] >= counts["moe.assignments_held"]
    TRACER.reset()
    TRACER.count_lazy(names, aux["counters"])          # off: not kept
    TRACER.fetch_counters()
    assert TRACER.counters() == {}
    TRACER.enable()
    try:
        TRACER.count_lazy(names, aux["counters"])
        TRACER.count_lazy(names, aux["counters"])
        assert TRACER.counters() == {}                 # lazy until fetched
        TRACER.fetch_counters()
        got = TRACER.counters()
    finally:
        TRACER.reset()
    assert got == {k: 2 * v for k, v in counts.items()}


def test_the_plan_comes_from_flags_and_names_no_model():
    with pytest.raises(ValueError):
        P.parse_plan(["kda:moe"])
    with pytest.raises(ValueError):
        _plan_model(held=(12, 8))
    cfg = _plan_model(extra=("--plan-kda-head-groups", "2")).cfg
    assert cfg.lm and cfg.dec_depth == 3 and cfg.kda_head_groups == 2
    assert not (cfg.tied_embeddings_all or cfg.tied_embeddings)
    # the program cites the family ("Kimi Linear", the URL) in two
    # docstrings; no identifier, flag or branch carries the name
    hits = subprocess.run(
        ["grep", "-rn", "kimi", os.path.join(ROOT, "marian_tpu"),
         "--include=*.py"], capture_output=True, text=True).stdout
    assert hits == ""
    cited = subprocess.run(
        ["grep", "-rli", "kimi", os.path.join(ROOT, "marian_tpu"),
         "--include=*.py"], capture_output=True, text=True).stdout.split()
    assert {os.path.basename(f) for f in cited} <= {"layer_plan.py",
                                                    "kda.py"}


def test_head_groups_change_the_schedule_not_the_function(tiny):
    model, dims, params, batch = tiny
    assert model.cfg.kda_head_groups == 2          # the rehearsal's
    other, _ = _tiny_model(extra=("--plan-kda-head-groups", "1"))
    a = model.loss(params, batch, None, True)[0]
    b = other.loss(params, batch, None, True)[0]
    np.testing.assert_allclose(a, b, rtol=1e-6)


# ---------------------------------------------------------------------------
# what a checkpointed latent-attention half keeps across the backward
# ---------------------------------------------------------------------------

_KEEP_PLANS = {
    "one-mla": (("mla:dense",), ()),
    "rotated-low-rank-and-a-module": (
        ("mla:dense", "mla:experts", "mla:experts"),
        ("--plan-mla-q-rank", "24", "--plan-mla-rope-theta", "32e6",
         "--plan-mtp-modules", "1")),
}


def _kernel_calls(jaxpr):
    """{pallas_call name: equations} of a jaxpr and everything under it."""
    return dict(collections.Counter(
        eqn.params["name"] for eqn in _equations(jaxpr)
        if eqn.primitive.name == "pallas_call"))


@pytest.fixture(scope="module", params=list(_KEEP_PLANS))
def checkpointed(request):
    """A plan under --gradient-checkpointing with the flash kernel forced
    on (interpret mode): (model, params, batch, its `mla` entries)."""
    plan, extra = _KEEP_PLANS[request.param]
    model = _plan_model(plan=plan, extra=(
        "--gradient-checkpointing", "--transformer-flash-attention", "on",
        *extra))
    assert model.cfg.gradient_checkpointing
    return (model, model.init(jax.random.PRNGKey(3)), _batch(96),
            sum(1 for mix, _ in model.cfg.plan if mix == "mla"))


def _loss_gradient(model, batch):
    return jax.grad(lambda p: model.loss(p, batch, None, True)[0])


def test_a_checkpointed_mla_half_runs_the_flash_forward_once(checkpointed,
                                                             monkeypatch):
    """The gradient's program holds flash_attention_fwd once for each
    `mla` entry of the plan, the module's among them, like the two
    backward kernels; with nothing kept (the checkpoint as it was) the
    forward kernel is there twice. Fails if the policy stops engaging."""
    model, params, batch, n = checkpointed
    calls = _kernel_calls(jax.make_jaxpr(
        _loss_gradient(model, batch))(params).jaxpr)
    assert calls == {"flash_attention_fwd": n, "flash_attention_dq": n,
                     "flash_attention_dkv": n}
    monkeypatch.setattr(P, "_FLASH_KEEPS", ())
    calls = _kernel_calls(jax.make_jaxpr(
        _loss_gradient(model, batch))(params).jaxpr)
    assert calls == {"flash_attention_fwd": 2 * n, "flash_attention_dq": n,
                     "flash_attention_dkv": n}


def test_what_is_kept_changes_no_gradient(checkpointed, monkeypatch):
    """Every parameter group's gradient with the kernel's output and
    statistics kept is, bit for bit, the gradient with the forward
    kernel run again: the kept arrays are what it would have written."""
    model, params, batch, _ = checkpointed
    kept = _loss_gradient(model, batch)(params)
    monkeypatch.setattr(P, "_FLASH_KEEPS", ())
    again = _loss_gradient(model, batch)(params)
    assert set(kept) == set(again) == set(params)
    for name in sorted(params):
        # (a share of the experts passes its router no gradient)
        assert float(jnp.abs(kept[name]).max()) > 0 \
            or name.endswith("_experts_router"), name
        np.testing.assert_array_equal(kept[name], again[name], err_msg=name)


def _keep_events(model, params, batch):
    from marian_tpu.obs import TRACER
    TRACER.reset()
    TRACER.enable()
    try:
        jax.make_jaxpr(_loss_gradient(model, batch))(params)
        _, events = TRACER.snapshot()
    finally:
        TRACER.disable()
        TRACER.reset()
    return [e["attrs"] for e in events if e["name"] == "plan.remat_keep"]


@pytest.mark.parametrize("kind,post_norms,names", [
    ("kda", False, ()),
    ("dense", False, ()),
    ("experts", False, ()),
    ("mla", False, P._FLASH_KEEPS),
    ("gqa", False, P._FLASH_KEEPS + P._PROJECTION_KEEPS),
    ("swa", False, P._FLASH_KEEPS + P._PROJECTION_KEEPS),
    ("kda", True, (P.BRANCH_OUT,)),
    ("experts", True, (P.BRANCH_OUT,)),
    ("mla", True, P._FLASH_KEEPS + (P.BRANCH_OUT,)),
    ("swa", True, P._FLASH_KEEPS + P._PROJECTION_KEEPS + (P.BRANCH_OUT,))])
def test_what_a_half_keeps_follows_its_kind_and_the_output_norms(
        kind, post_norms, names):
    """The names a checkpointed half keeps are decided by what the plan
    states, the half's kind and whether a norm reads its output: a `kda`
    or `mla` half holds no projection, a half without an output norm no
    branch output, and a half that keeps no name is the plain checkpoint."""
    cfg = P.PlanConfig(src_vocab=96, trg_vocab=96, post_norms=post_norms)
    assert P._keeps(cfg, kind) == names


def test_the_kept_bytes_are_said_as_an_mla_half_is_traced(checkpointed):
    """`plan.remat_keep`, once a checkpointed `mla` half of a traced
    step: the layer, the two names and the bytes kept under them, the
    kernel's output [B, H, Tq padded, dv] in the compute type and its
    statistics [B, H, Tq padded] in float32; 0 bytes where the dense
    path runs and names nothing; nothing said without checkpointing, or
    with the tracer off."""
    model, params, batch, n = checkpointed
    cfg = model.cfg
    b, t = batch["trg_ids"].shape
    rows = b * cfg.heads * 128                  # 80 positions pad to 128
    said = _keep_events(model, params, batch)
    assert [e["layer"] for e in said] == [
        lp for lp, (mix, _) in P._blocks(cfg) if mix == "mla"]
    assert len(said) == n and t == 80
    for e in said:
        assert e["half"] == "mixing"
        assert e["names"] == P._keeps(cfg, "mla") == P._FLASH_KEEPS == (
            "flash_attention_out", "flash_attention_lse")
        assert e["bytes"] == rows * (cfg.mla_dim_v * 4 + 4)
    kept = model.cfg
    try:
        model.cfg = dataclasses.replace(kept, flash_attention="off")
        assert [e["bytes"] for e in _keep_events(model, params, batch)] \
            == [0] * n
        model.cfg = dataclasses.replace(kept, gradient_checkpointing=False)
        assert _keep_events(model, params, batch) == []
    finally:
        model.cfg = kept
    from marian_tpu.obs import TRACER
    jax.make_jaxpr(_loss_gradient(model, batch))(params)    # tracer off
    assert TRACER.snapshot()[1] == []
