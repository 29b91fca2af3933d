"""Pallas flash attention vs the dense reference path.

Runs in interpreter mode on CPU (conftest forces JAX_PLATFORMS=cpu); the same
kernels compile through Mosaic on TPU. Mirrors the reference's operator-parity
test tier (src/tests/units/attention_tests.cpp): small-tensor agreement
between two independent implementations, plus autodiff agreement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.ops.attention import (attention, causal_mask, combine_masks,
                                      dense_attention)
from marian_tpu.ops.pallas.flash_attention import flash_attention


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


def _kv_mask(rng, b, t):
    m = (rng.rand(b, t) > 0.25).astype(np.float32)
    m[:, 0] = 1.0  # never fully-masked rows
    return jnp.asarray(m)


@pytest.mark.parametrize("tq,tk", [(64, 64), (70, 90), (128, 256), (200, 130)])
def test_flash_matches_dense_padding_mask(rng, tq, tk):
    b, h, dh = 2, 4, 32
    q, k, v = _rand(rng, b, h, tq, dh), _rand(rng, b, h, tk, dh), _rand(rng, b, h, tk, dh)
    m = _kv_mask(rng, b, tk)
    out = flash_attention(q, k, v, kv_mask=m)
    ref = dense_attention(q, k, v, mask=m[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [64, 100, 256])
def test_flash_matches_dense_causal(rng, t):
    b, h, dh = 2, 2, 32
    q, k, v = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    m = _kv_mask(rng, b, t)
    out = flash_attention(q, k, v, kv_mask=m, causal=True)
    ref = dense_attention(q, k, v,
                          mask=combine_masks(causal_mask(t),
                                             m[:, None, None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_no_mask(rng):
    b, h, t, dh = 2, 2, 96, 16
    q, k, v = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    out = flash_attention(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(rng, causal):
    b, h, t, dh = 2, 2, 96, 16
    q, k, v = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    m = _kv_mask(rng, b, t)
    dense_mask = combine_masks(causal_mask(t) if causal else None,
                               m[:, None, None, :])

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, kv_mask=m, causal=causal) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_attention(q, k, v, mask=dense_mask) ** 2).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs under it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_vjp_keeps_lane_dense_statistics_under_two_names(rng):
    """The custom VJP's residuals: `out` [B,H,Tq,Dv] and the row
    statistics WITHOUT their trailing 1, [B,H,Tq] (a [..., 1] float32
    array kept across layers is tiled to 128 lanes on the chip), each
    under the name a checkpoint policy may keep it by. A checkpoint that
    keeps both runs the forward kernel once where a plain one runs it
    twice, and all three gradients are the same bit for bit: without a
    policy a name is the identity."""
    from marian_tpu.ops.pallas.flash_attention import (
        RESIDUAL_LSE, RESIDUAL_OUT, _flash_fwd)
    b, h, t, dh, dv = 2, 2, 128, 32, 16
    q, k = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    v, m = _rand(rng, b, h, t, dv), _kv_mask(rng, b, t)
    out, res = _flash_fwd(q, k, v, m[:, None, :], dh ** -0.5, True,
                          128, 128, True)
    assert out.shape == res[4].shape == (b, h, t, dv)
    assert res[5].shape == (b, h, t) and res[5].dtype == jnp.float32

    def f(q, k, v):
        return (flash_attention(q, k, v, kv_mask=m, causal=True) ** 2).sum()
    keep = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            RESIDUAL_OUT, RESIDUAL_LSE))
    fns = {"plain": f, "keep": keep, "again": jax.checkpoint(f)}
    grads, forwards = {}, {}
    for name, fn in fns.items():
        grad = jax.grad(fn, argnums=(0, 1, 2))
        eqns = list(_equations(jax.make_jaxpr(grad)(q, k, v).jaxpr))
        forwards[name] = sum(
            e.primitive.name == "pallas_call"
            and e.params["name"] == "flash_attention_fwd" for e in eqns)
        named = {e.params["name"]: e.outvars[0].aval.shape for e in eqns
                 if e.primitive.name == "name"}
        assert named == {RESIDUAL_OUT: (b, h, t, dv),
                         RESIDUAL_LSE: (b, h, t)}
        grads[name] = grad(q, k, v)
    assert forwards == {"plain": 1, "keep": 1, "again": 2}
    for kept, again, plain in zip(grads["keep"], grads["again"],
                                  grads["plain"]):
        np.testing.assert_array_equal(kept, plain)
        np.testing.assert_array_equal(again, plain)


def test_flash_under_jit_and_vmapless_batch(rng):
    b, h, t, dh = 2, 2, 128, 32
    q, k, v = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    m = _kv_mask(rng, b, t)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, kv_mask=m,
                                                 causal=True))
    out = fn(q, k, v)
    ref = dense_attention(q, k, v,
                          mask=combine_masks(causal_mask(t),
                                             m[:, None, None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dispatcher_selects_flash_and_dense(rng):
    b, h, t, dh = 1, 2, 64, 16
    q, k, v = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    m = _kv_mask(rng, b, t)
    # flash "on": weights slot must be None
    out_f, w = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                         flash="on")
    assert w is None
    # flash "off": dense path
    out_d, _ = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                         flash="off")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)
    # return_weights forces dense even when flash requested
    _, w2 = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                      flash="on", return_weights=True)
    assert w2 is not None


def test_bf16_inputs(rng):
    b, h, t, dh = 2, 2, 128, 32
    q = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, mask=causal_mask(t))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


class TestBlockEnvOverrides:
    """MARIAN_FLASH_BLOCK_Q/K sweep overrides: malformed values fall back
    to the 512/2048 defaults with a warning instead of raising at trace
    time, and block_k is clamped (halved) for heads wider than the
    dh=64 the defaults were validated at (ISSUE 1 satellite)."""

    def test_env_block_parses_and_falls_back(self):
        from marian_tpu.ops.pallas.flash_attention import _env_block
        import os
        for bad in ("banana", "12.5", "-64", "0", " "):
            os.environ["MARIAN_FLASH_BLOCK_Q"] = bad
            try:
                assert _env_block("MARIAN_FLASH_BLOCK_Q", 512) == 512
            finally:
                del os.environ["MARIAN_FLASH_BLOCK_Q"]
        os.environ["MARIAN_FLASH_BLOCK_Q"] = "256"
        try:
            assert _env_block("MARIAN_FLASH_BLOCK_Q", 512) == 256
        finally:
            del os.environ["MARIAN_FLASH_BLOCK_Q"]
        assert _env_block("MARIAN_FLASH_BLOCK_Q", 512) == 512  # unset

    def test_malformed_env_does_not_break_trace(self, rng, monkeypatch):
        monkeypatch.setenv("MARIAN_FLASH_BLOCK_Q", "not-a-number")
        monkeypatch.setenv("MARIAN_FLASH_BLOCK_K", "")
        q = _rand(rng, 1, 2, 16, 8)
        k = _rand(rng, 1, 2, 16, 8)
        v = _rand(rng, 1, 2, 16, 8)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_wide_head_runs_with_halved_default_k_block(self, rng):
        # dh=128 > 64: the default k block is halved (VMEM headroom);
        # numerics must be unchanged
        q = _rand(rng, 1, 1, 16, 128)
        k = _rand(rng, 1, 1, 16, 128)
        v = _rand(rng, 1, 1, 16, 128)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
