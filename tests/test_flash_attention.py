"""Pallas flash attention vs the dense reference path.

Runs in interpreter mode on CPU (conftest forces JAX_PLATFORMS=cpu); the same
kernels compile through Mosaic on TPU. Mirrors the reference's operator-parity
test tier (src/tests/units/attention_tests.cpp): small-tensor agreement
between two independent implementations, plus autodiff agreement.
"""

import importlib

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


# ---------------------------------------------------------------------------
# A rule in place of `causal`: block-diffusion training over a doubled row,
# grouped-query heads, and `causal=True` as it was
# ---------------------------------------------------------------------------

from time_limit import time_limit  # noqa: E402

from marian_tpu.ops.attention import block_diffusion_mask  # noqa: E402
# (the package re-exports the FUNCTION under the module's name)
F = importlib.import_module("marian_tpu.ops.pallas.flash_attention")


def _written_out(length, block):
    """The rule's three sentences, index by index, in plain Python."""
    see = np.zeros((2 * length, 2 * length), bool)
    for q in range(2 * length):
        for k in range(2 * length):
            q_noised, k_noised = q < length, k < length
            qb, kb = (q % length) // block, (k % length) // block
            see[q, k] = (q_noised and k_noised and qb == kb) \
                or (q_noised and not k_noised and kb < qb) \
                or (not q_noised and not k_noised and kb <= qb)
    return see


@pytest.mark.parametrize("length,block", [(10, 4), (37, 4), (24, 3),
                                          (16, 1), (9, 16)])
@time_limit(60)
def test_the_rule_is_its_three_sentences(length, block):
    got = np.asarray(block_diffusion_mask(length, block))[0, 0] > 0
    np.testing.assert_array_equal(got, _written_out(length, block))


_TILINGS = [(10, 4, 8, 8), (37, 4, 16, 8), (64, 4, 16, 32), (50, 3, 8, 16),
            (33, 5, 16, 16), (1024, 4, 512, 1024), (8192, 4, 512, 1024)]


@pytest.mark.parametrize("length,block,bq,bk", _TILINGS)
@time_limit(120)
def test_a_tile_is_live_iff_it_holds_a_pair_that_sees(length, block, bq, bk):
    """Both tile tests (the key tiles of a query tile: forward and dq; the
    query tiles of a key tile: dkv) against the mask itself, and the tile
    a dead step stays on: a live one, already fetched."""
    rule = F.BlockDiffusion(length, block)
    n = 2 * length
    n_q, n_k = -(-n // bq), -(-n // bk)
    see = np.array(F.rule_mask(rule, np.arange(n_q * bq)[:, None],
                               np.arange(n_k * bk)[None, :]))
    see[n:], see[:, n:] = False, False
    want = see.reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
    i, j = np.arange(n_q)[:, None], np.arange(n_k)[None, :]
    with jax.ensure_compile_time_eval():
        by_q = np.broadcast_to(np.asarray(F._live(rule, i, j, bq, bk)),
                               want.shape)
        by_k = np.broadcast_to(np.asarray(F._in_spans(
            i, F._q_spans(rule, j, bq, bk))), want.shape)
        stay_k = np.broadcast_to(np.asarray(F._resident(
            j, F._kv_spans(rule, i, bq, bk), n_k)), want.shape)
        stay_q = np.broadcast_to(np.asarray(F._resident(
            i, F._q_spans(rule, j, bq, bk), n_q)), want.shape)
    np.testing.assert_array_equal(by_q, want)
    np.testing.assert_array_equal(by_k, want)
    assert F.live_tiles(rule, n_q, n_k, bq, bk) == want.sum()
    # a live step asks for its own tile, a dead one for a live tile at or
    # before it (or the row's first live one)
    np.testing.assert_array_equal(stay_k[want], np.broadcast_to(j, want.shape)[want])
    np.testing.assert_array_equal(stay_q[want], np.broadcast_to(i, want.shape)[want])
    assert want[np.broadcast_to(i, want.shape), stay_k].all()
    assert want[stay_q, np.broadcast_to(j, want.shape)].all()
    if length == 8192:
        assert want.sum() / want.size < 0.35


# (whole, live) tiles a row and head on the benchmark cells' shapes: the
# widest and the narrowest rows of their documents at blocks 512 / 1024
_CELL_TILES = {("causal", 8192): (56, 72), ("causal", 1024): (0, 2),
               ("block_diffusion(8192,4)", 16384): (112, 160),
               ("block_diffusion(1024,4)", 2048): (0, 6)}


@pytest.mark.parametrize("rule,n,bq,bk", [
    (F.BlockDiffusion(length, block), 2 * length, bq, bk)
    for length, block, bq, bk in _TILINGS] + [
    (True, 20, 8, 8), (True, 100, 16, 8), (True, 90, 8, 32),
    (True, 300, 128, 128), (True, 1024, 512, 1024),
    (True, 8192, 512, 1024)],
    ids=lambda x: F._rule_name(x) if isinstance(x, (bool, tuple)) else None)
@time_limit(120)
def test_a_whole_tile_holds_no_pair_that_does_not_see(rule, n, bq, bk):
    """`_whole` (the plan event's count) against the mask itself (numpy,
    off the kernels): every real pair of a whole tile sees, a whole tile
    is live, and on the cells' shapes the test finds every tile the rule
    leaves whole. And the tile a dead step asks for under either rule: a
    live one."""
    n_q, n_k = -(-n // bq), -(-n // bk)
    qpos, kpos = np.arange(n_q * bq)[:, None], np.arange(n_k * bk)[None, :]
    see = qpos >= kpos if rule is True else np.array(
        F.rule_mask(rule, qpos, kpos))
    tiles = see.reshape(n_q, bq, n_k, bk)
    real = np.broadcast_to((qpos < n) & (kpos < n), see.shape).reshape(
        tiles.shape)
    all_see, live = (tiles | ~real).all(axis=(1, 3)), \
        (tiles & real).any(axis=(1, 3))
    ii, jj = (np.ascontiguousarray(a, np.int32) for a in np.broadcast_arrays(
        np.arange(n_q)[:, None], np.arange(n_k)[None, :]))
    with jax.ensure_compile_time_eval():
        whole = np.asarray(F._whole(rule, ii, jj, bq, bk))
        stay_k = np.asarray(F._key_tile(rule, ii, jj, bq, bk, n_k))
        stay_q = np.asarray(F._query_tile(rule, ii, jj, bq, bk, n_q))
        np.testing.assert_array_equal(F._live(rule, ii, jj, bq, bk), live)
    assert not (whole & ~all_see).any() and not (whole & ~live).any()
    assert F.whole_tiles(rule, n_q, n_k, bq, bk) == whole.sum()
    cell = _CELL_TILES.get((F._rule_name(rule), n))
    if cell:
        np.testing.assert_array_equal(whole, all_see)
        assert (whole.sum(), live.sum()) == cell
    np.testing.assert_array_equal(stay_k[live], jj[live])
    np.testing.assert_array_equal(stay_q[live], ii[live])
    assert live[ii, stay_k].all() and live[stay_q, jj].all()


def _doubled_case(rng, length, heads, kv_heads, dh=16, rows=2):
    q = _rand(rng, rows, heads, 2 * length, dh)
    k = _rand(rng, rows, kv_heads, 2 * length, dh)
    v = _rand(rng, rows, kv_heads, 2 * length, dh)
    real = np.arange(length)[None] < np.array([length, length - 7])[:, None]
    mask = jnp.asarray(np.concatenate([real, real], 1), jnp.float32)
    return q, k, v, mask, _rand(rng, rows, heads, 2 * length, dh)


@pytest.mark.parametrize("length,block,group,bq,bk", [
    (37, 4, 1, 16, 8),        # T no multiple of the block or of a tile
    (50, 3, 4, 8, 16),
    (40, 4, 8, None, None),   # one tile
    (300, 4, 8, 128, 128),
    (160, 4, 4, 128, 256)])
@time_limit(240)
def test_block_diffusion_kernels_match_dense(rng, length, block, group, bq,
                                             bk):
    """Forward and the three gradients of the kernels (interpret mode)
    against the dense path under the same rule: padded keys, T no
    multiple of block or tile, 1, 4 and 8 query heads a key/value head."""
    q, k, v, mask, w = _doubled_case(rng, length, group * (2 if group < 8
                                                           else 1),
                                     2 if group < 8 else 1)
    rule = F.BlockDiffusion(length, block)
    real = mask[:, None, :, None]

    def dense(q, k, v):
        out, _ = attention(q, k, v, kv_mask=mask, causal=rule, flash="off",
                           packed="off")
        return jnp.sum(out * w * real), out

    def flash(q, k, v):
        out = flash_attention(q, k, v, kv_mask=mask, causal=rule,
                              block_q=bq, block_k=bk)
        return jnp.sum(out * w * real), out
    (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(q, k, v)
    (_, got), got_g = jax.value_and_grad(flash, (0, 1, 2), True)(q, k, v)
    np.testing.assert_allclose(got * real, want * real, atol=2e-5)
    for a, b in zip(got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


@time_limit(120)
def test_the_dispatcher_hands_flash_the_rule_and_the_shared_heads(rng):
    q, k, v, mask, _ = _doubled_case(rng, 72, 4, 2)
    rule = F.BlockDiffusion(72, 4)
    got, _ = attention(q, k, v, kv_mask=mask, causal=rule, flash="on")
    want, _ = attention(q, k, v, kv_mask=mask, causal=rule, flash="off")
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError):
        flash_attention(q[:, :, :100], k, v, causal=rule)
    with pytest.raises(ValueError):
        flash_attention(q[:, :3], k, v, causal=True)


@time_limit(120)
def test_grouped_heads_are_repeated_heads_under_causal(rng):
    """8 query heads on 2 key/value heads, causal with a padding mask: the
    kernels reading a shared head in place against the same kernels on
    the heads repeated; dk and dv are the group's sum."""
    b, t, dh = 2, 200, 16
    q, k, v = _rand(rng, b, 8, t, dh), _rand(rng, b, 2, t, dh), \
        _rand(rng, b, 2, t, dh)
    m = _kv_mask(rng, b, t)

    def shared(q, k, v):
        return (flash_attention(q, k, v, kv_mask=m, causal=True,
                                block_q=128, block_k=128) ** 2).sum()

    def repeated(q, k, v):
        return shared(q, jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1))
    got = jax.grad(shared, (0, 1, 2))(q, k, v)
    want = jax.grad(repeated, (0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-5)


def _grouped_causal(rng):
    """8 query heads on 2 key/value heads over 300 positions, padded keys:
    3 x 3 tiles of 128, three of them dead."""
    q, k, v = _rand(rng, 2, 8, 300, 16), _rand(rng, 2, 2, 300, 16), \
        _rand(rng, 2, 2, 300, 16)
    return q, k, v, _kv_mask(rng, 2, 300), True, _rand(rng, 2, 8, 300, 16)


def _grouped_doubled(rng):
    """8 query heads on one key/value head over [noised ; clean] of 384
    each: 6 x 6 tiles of 128, 21 of them dead."""
    q, k, v, mask, w = _doubled_case(rng, 384, 8, 1)
    return q, k, v, mask, F.BlockDiffusion(384, 4), w


def _grouped_window(rng):
    """4 query heads on one key/value head over 400 positions under a
    window of 130, padded keys: 4 x 4 tiles of 128, seven of them dead
    (six in the future, one behind the window)."""
    q, k, v = _rand(rng, 1, 4, 400, 16), _rand(rng, 1, 1, 400, 16), \
        _rand(rng, 1, 1, 400, 16)
    return q, k, v, _kv_mask(rng, 1, 400), F.Window(130), \
        _rand(rng, 1, 4, 400, 16)


@pytest.mark.parametrize("case", [_grouped_causal, _grouped_doubled,
                                  _grouped_window])
@time_limit(600)
def test_a_dead_step_that_fetches_nothing_changes_no_bit(rng, monkeypatch,
                                                         case):
    """out, dq, dk and dv are the same BITS whether a dead step's block
    index is clamped to a live tile or every step asks for its own: an
    unfetched dead tile only leaves work out."""
    q, k, v, mask, rule, w = case(rng)
    n = -(-q.shape[2] // 128)
    assert F.live_tiles(rule, n, n, 128, 128) < n * n

    def run():
        def f(q, k, v):
            out = flash_attention(q, k, v, kv_mask=mask, causal=rule,
                                  block_q=128, block_k=128)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(f, (0, 1, 2), True)(q, k, v)
        return (out,) + grads
    got = run()
    monkeypatch.setattr(F, "_key_tile", lambda rule, i, j, bq, bk, n_k: j)
    monkeypatch.setattr(F, "_query_tile", lambda rule, i, j, bq, bk, n_q: i)
    for a, b in zip(got, run()):
        np.testing.assert_array_equal(a, b)


def _bodies(kernel_jaxpr):
    return sum(e.primitive.name == "cond" for e in kernel_jaxpr.eqns)


@pytest.mark.parametrize("rule,bodies", [
    (False, 2), (True, 3), (F.BlockDiffusion(128, 4), 3),
    (F.Window(100), 3)],
    ids=F._rule_name)
@time_limit(120)
def test_a_kernel_holds_one_tile_body(rng, rule, bodies):
    """A kernel's conditional bodies: set-up and the write-back, and
    under a rule ONE more, the live tile's. A second, unmasked body for
    the tiles a rule leaves whole was built and measured (PERF.md 6, PR
    36): the kernels were no faster and an attention's compiled code
    half again as large, so it went. Without a rule the tile's body
    stands under no condition, as it did."""
    q = _rand(rng, 1, 2, 256, 16)

    def f(q):
        return flash_attention(q, q, q, causal=rule, block_q=128,
                               block_k=128).sum()
    kernels = {e.params["name"]: _bodies(e.params["jaxpr"])
               for e in _equations(jax.make_jaxpr(jax.grad(f))(q).jaxpr)
               if e.primitive.name == "pallas_call"}
    assert kernels == {f"flash_attention_{k}": bodies
                       for k in ("fwd", "dq", "dkv")}


# sha256 over the bytes of (out, dq, dk, dv) of `_causal_as_it_was` through
# the kernels of the commit before they took a rule or shared heads (PR 33,
# 590c63a), on this machine's CPU in interpret mode
_CAUSAL_SHA256 = \
    "d51e34cc0b7d41673bae681c48fefa05f617ae3b8b184eb5cf848614294b1ec5"


def _causal_as_it_was():
    rng = np.random.RandomState(1234)
    b, h, t, dh, dv = 2, 3, 300, 24, 16
    q, k = _rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh)
    v, m = _rand(rng, b, h, t, dv), _kv_mask(rng, b, t)

    def f(q, k, v):
        out = flash_attention(q, k, v, kv_mask=m, causal=True, block_q=128,
                              block_k=128)
        return (out ** 2).sum(), out
    (_, out), grads = jax.value_and_grad(f, (0, 1, 2), True)(q, k, v)
    import hashlib
    h = hashlib.sha256()
    for a in (out,) + grads:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@time_limit(120)
def test_causal_through_the_edited_kernels_is_bitwise_what_it_was():
    assert _causal_as_it_was() == _CAUSAL_SHA256


@time_limit(60)
def test_the_plan_event_says_how_many_tiles_are_live(rng):
    from marian_tpu.obs import TRACER
    q, k, v, mask, _ = _doubled_case(rng, 300, 8, 1)
    TRACER.reset()
    TRACER.enable()
    try:
        flash_attention(q, k, v, kv_mask=mask,
                        causal=F.BlockDiffusion(300, 4), block_q=128,
                        block_k=128)
        flash_attention(q, q, q, causal=True, block_q=128, block_k=128)
        events = [e for e in TRACER.snapshot()[1]
                  if e["name"] == "flash_attention.plan"]
    finally:
        TRACER.disable()
        TRACER.reset()
    rule, causal = (e["attrs"] for e in events)
    assert rule == {"tq": 600, "tk": 600, "block_q": 128, "block_k": 128,
                    "rule": "block_diffusion(300,4)", "tiles_live": 15,
                    "tiles_whole": 1, "tiles": 25, "kv_group": 8}
    assert (causal["rule"], causal["tiles_live"], causal["tiles_whole"],
            causal["tiles"], causal["kv_group"]) == ("causal", 15, 10, 25, 1)


# ---------------------------------------------------------------------------
# A third rule: a sliding window, the last W keys up to the query's own
# ---------------------------------------------------------------------------

from marian_tpu.ops.attention import window_mask  # noqa: E402


def _window_written_out(n, window):
    """query i sees key j iff i - window < j <= i, index by index."""
    see = np.zeros((n, n), bool)
    for q in range(n):
        for k in range(max(0, q - window + 1), q + 1):
            see[q, k] = True
    return see


@pytest.mark.parametrize("n,window", [(10, 4), (37, 1), (24, 24), (9, 16)])
@time_limit(60)
def test_the_window_is_its_sentence(n, window):
    got = np.asarray(window_mask(n, window))[0, 0] > 0
    np.testing.assert_array_equal(got, _window_written_out(n, window))
    assert got.sum() == n * min(n, window) \
        - min(n, window) * (min(n, window) - 1) // 2


# (whole, live) tiles a row and head on the benchmark cell's shapes under
# a window of 2048 at blocks 512 / 1024: the narrowest and the widest row
_WINDOW_CELL_TILES = {4096: (6, 18), 16384: (30, 90)}


@pytest.mark.parametrize("n,window,bq,bk", [
    (20, 5, 8, 8), (100, 17, 16, 8), (90, 40, 8, 32), (64, 64, 16, 16),
    (50, 1, 8, 16), (300, 130, 128, 128), (400, 128, 128, 256),
    (4096, 2048, 512, 1024), (16384, 2048, 512, 1024)])
@time_limit(120)
def test_window_tiles_against_a_brute_force_count(n, window, bq, bk):
    """`_live` and `_whole` (both edges), `live_tiles` / `whole_tiles`, and
    the tile a dead step of either grid asks for, against the mask itself
    (numpy, off the kernels): a live step asks for its own tile, a dead
    one for a LIVE tile of its row (its column under dkv), so it fetches
    nothing new; each row's and column's live tiles are ONE run."""
    rule = F.Window(window)
    n_q, n_k = -(-n // bq), -(-n // bk)
    live, all_see, some_real, all_real = (
        np.zeros((n_q, n_k), bool) for _ in range(4))
    for i in range(n_q):                 # a tile at a time, from the sentence
        q = np.arange(i * bq, (i + 1) * bq)[:, None]
        for j in range(n_k):
            k = np.arange(j * bk, (j + 1) * bk)[None, :]
            real = (q < n) & (k < n)
            see = (k <= q) & (k > q - window) & real
            np.testing.assert_array_equal(
                see, np.asarray(F.rule_mask(rule, q, k)) & real)
            live[i, j], some_real[i, j] = see.any(), real.any()
            all_real[i, j] = real.all()
            all_see[i, j] = see.any() and (see | ~real).all()
    ii, jj = (np.ascontiguousarray(a, np.int32) for a in np.broadcast_arrays(
        np.arange(n_q)[:, None], np.arange(n_k)[None, :]))
    with jax.ensure_compile_time_eval():
        got_live = np.asarray(F._live(rule, ii, jj, bq, bk))
        whole = np.asarray(F._whole(rule, ii, jj, bq, bk))
        stay_k = np.asarray(F._key_tile(rule, ii, jj, bq, bk, n_k))
        stay_q = np.asarray(F._query_tile(rule, ii, jj, bq, bk, n_q))
    # a tile of padding alone may be computed (the test reads indices, not
    # the row's end); no tile with a pair that sees is left out
    assert not (live & ~got_live).any()
    assert not (got_live & ~live & some_real).any()
    assert not (whole & ~all_see & all_real).any()
    assert not (whole & ~got_live).any()
    assert F.live_tiles(rule, n_q, n_k, bq, bk) == got_live.sum()
    assert F.whole_tiles(rule, n_q, n_k, bq, bk) == whole.sum()
    if n in _WINDOW_CELL_TILES and window == 2048:
        np.testing.assert_array_equal(whole, all_see)
        np.testing.assert_array_equal(got_live, live)
        assert (whole.sum(), live.sum()) == _WINDOW_CELL_TILES[n]
        assert F.tile_plan(rule, n, n, 128) == {
            "block_q": bq, "block_k": bk, "rule": "window(2048)",
            "tiles_live": live.sum(), "tiles_whole": whole.sum(),
            "tiles": n_q * n_k}
    np.testing.assert_array_equal(stay_k[got_live], jj[got_live])
    np.testing.assert_array_equal(stay_q[got_live], ii[got_live])
    assert got_live[ii, stay_k].all() and got_live[stay_q, jj].all()
    for runs in (got_live, got_live.T):            # one run a row, a column
        assert (np.abs(np.diff(np.pad(runs.astype(int), ((0, 0), (1, 1))),
                               axis=1)).sum(axis=1) == 2).all()


@pytest.mark.parametrize("t,window,group,bq,bk", [
    (100, 200, 2, None, None),    # T < W: one tile
    (256, 256, 1, 128, 128),      # T = W
    (300, 130, 4, 128, 128),      # T > W, T no multiple of the blocks
    (260, 100, 8, 128, 128),      # 8 query heads a key/value head
    (400, 128, 4, 128, 256),      # W a whole tile, blocks that differ
    (300, 1, 2, 128, 128)])       # a query sees itself alone
@time_limit(240)
def test_window_kernels_match_dense(rng, t, window, group, bq, bk):
    """Forward and the three gradients of the kernels (interpret mode)
    against dense attention under the mask written out index by index:
    T under, at and over the window, T no multiple of the blocks, padded
    keys, 1 to 8 query heads a key/value head (dk and dv the group's
    sum)."""
    kv_heads = 2 if group < 8 else 1
    q = _rand(rng, 2, group * kv_heads, t, 16)
    k, v = _rand(rng, 2, kv_heads, t, 16), _rand(rng, 2, kv_heads, t, 16)
    w = _rand(rng, 2, group * kv_heads, t, 16)
    mask = jnp.asarray(np.arange(t)[None] < np.array([[t], [t - 9]]),
                       jnp.float32)
    see = jnp.asarray(_window_written_out(t, window), jnp.float32)[
        None, None] * mask[:, None, None, :]
    real = mask[:, None, :, None]

    def dense(q, k, v):
        out = dense_attention(q, jnp.repeat(k, group, 1),
                              jnp.repeat(v, group, 1), see)
        return jnp.sum(out * w * real), out

    def flash(q, k, v):
        out = flash_attention(q, k, v, kv_mask=mask, causal=F.Window(window),
                              block_q=bq, block_k=bk)
        return jnp.sum(out * w * real), out
    (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(q, k, v)
    (_, got), got_g = jax.value_and_grad(flash, (0, 1, 2), True)(q, k, v)
    np.testing.assert_allclose(got * real, want * real, atol=2e-5)
    for a, b in zip(got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("t,window", [(200, 200), (300, 1000)])
@time_limit(240)
def test_a_row_inside_the_window_is_a_causal_row_to_the_bit(rng, t, window):
    """T <= W: out, dq, dk and dv are `causal=True`'s, bit for bit, and
    the plan event says so."""
    from marian_tpu.obs import TRACER
    q, k, v = _rand(rng, 2, 4, t, 16), _rand(rng, 2, 2, t, 16), \
        _rand(rng, 2, 2, t, 16)
    m, w = _kv_mask(rng, 2, t), _rand(rng, 2, 4, t, 16)

    def run(rule):
        def f(q, k, v):
            out = flash_attention(q, k, v, kv_mask=m, causal=rule,
                                  block_q=128, block_k=128)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(f, (0, 1, 2), True)(q, k, v)
        return (out,) + grads
    TRACER.reset()
    TRACER.enable()
    try:
        got = run(F.Window(window))
        events = [e["attrs"]["rule"] for e in TRACER.snapshot()[1]
                  if e["name"] == "flash_attention.plan"]
    finally:
        TRACER.disable()
        TRACER.reset()
    assert events == ["causal"]
    for a, b in zip(got, run(True)):
        np.testing.assert_array_equal(a, b)


@time_limit(120)
def test_the_dispatcher_hands_flash_the_window(rng):
    q, k, v = _rand(rng, 2, 4, 200, 16), _rand(rng, 2, 2, 200, 16), \
        _rand(rng, 2, 2, 200, 16)
    mask = jnp.asarray(np.arange(200)[None] < np.array([[200], [150]]),
                       jnp.float32)
    rule = F.Window(70)
    got, _ = attention(q, k, v, kv_mask=mask, causal=rule, flash="on")
    want, _ = attention(q, k, v, kv_mask=mask, causal=rule, flash="off")
    real = mask[:, None, :, None]
    np.testing.assert_allclose(got * real, want * real, atol=2e-5)
    causal, _ = attention(q, k, v, kv_mask=mask, causal=True, flash="off")
    assert float(jnp.abs((want - causal) * real).max()) > 1e-3
    with pytest.raises(ValueError):
        flash_attention(q[:, :, :100], k, v, causal=rule)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=F.Window(0))


@time_limit(60)
def test_the_plan_event_names_the_window(rng):
    from marian_tpu.obs import TRACER
    q, k = _rand(rng, 1, 8, 600, 16), _rand(rng, 1, 2, 600, 16)
    TRACER.reset()
    TRACER.enable()
    try:
        flash_attention(q, k, k, causal=F.Window(200), block_q=128,
                        block_k=128)
        events = [e["attrs"] for e in TRACER.snapshot()[1]
                  if e["name"] == "flash_attention.plan"]
    finally:
        TRACER.disable()
        TRACER.reset()
    # 5 x 5 tiles of 128: the diagonal, one behind it everywhere and two
    # where a tile's first query still reaches (200 > 128 + 1)
    assert events == [{"tq": 600, "tk": 600, "block_q": 128, "block_k": 128,
                       "rule": "window(200)", "tiles_live": 12,
                       "tiles_whole": 0, "tiles": 25, "kv_group": 4}]
