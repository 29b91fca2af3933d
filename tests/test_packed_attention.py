"""Head-packed Pallas attention vs the dense reference path (tier-1).

Runs in interpreter mode on CPU (conftest forces JAX_PLATFORMS=cpu); the
same kernel compiles through Mosaic on TPU. Golden parity against
ops/attention.py::dense_attention at the shapes the kernel exists for —
the dh=64 x T=48-64 MXU-tile-geometry regime — plus the pack-group
edges (g=1 wide heads, g=8 narrow heads), padding, bf16, and the custom
VJP in both backward orientations.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from marian_tpu.ops.attention import (attention, causal_mask, combine_masks,
                                      dense_attention)
from marian_tpu.ops.pallas.packed_attention import (cell_plan, cell_vmem,
                                                    pack_group,
                                                    packed_attention,
                                                    rows_a_tile)

from tests.time_limit import time_limit

# the module (the package exports the function under the same name)
pa = importlib.import_module("marian_tpu.ops.pallas.packed_attention")


def _forget_plans():
    """The budget is read when a call is traced: drop what was traced
    under another."""
    pa._fwd_call.clear_cache()
    pa._bwd_call.clear_cache()


def _kernel_t(t):
    """The width a sequence of t positions reaches the kernel at: its
    own, as a multiple of 8, up to 64; multiples of 64 past it."""
    return -(-t // 8) * 8 if t <= 64 else -(-t // 64) * 64


@pytest.fixture
def rows_a_cell(monkeypatch):
    """Hold the kernel's cells to at most a given number of rows (all
    heads) for a [b, h, t, dh] f32 call whose tiles hold r rows each, by
    shrinking the budget cell_plan works to — the only way to several
    cells at test sizes."""
    def hold(rows, h, t, dh, heads=None, backward=True, r=1):
        g = pack_group(h, dh)
        tp = _kernel_t(t)
        monkeypatch.setattr(pa, "_CELL_BUDGET", cell_vmem(
            rows, heads or h, g, tp, tp, dh, 4, backward, r))
        _forget_plans()
        assert cell_plan(4 * rows, h, tp, tp, dh, 4, backward, r=r) == (
            rows, heads or h)
    yield hold
    _forget_plans()


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


def _kv_mask(rng, b, t):
    m = (rng.rand(b, t) > 0.25).astype(np.float32)
    m[:, 0] = 1.0  # never fully-masked rows
    return jnp.asarray(m)


class TestPackGroup:
    def test_pack_group_geometry(self):
        assert pack_group(16, 64) == 2      # transformer-big: 2x64 = 128
        assert pack_group(8, 64) == 2
        assert pack_group(8, 32) == 4
        assert pack_group(8, 16) == 8
        assert pack_group(2, 128) == 1      # wide heads: nothing to pack
        assert pack_group(3, 64) == 1       # g must divide the head count
        assert pack_group(6, 64) == 2


@pytest.mark.parametrize("tq,tk,b,rows", [
    (48, 48, 2, None), (50, 70, 2, None),
    # 6 rows in cells of 3; 7 under the same most: one row a cell (no
    # ragged last cell, see cell_plan); 2 rows, fewer than a cell holds
    (48, 48, 6, 3), (48, 48, 7, 3), (50, 70, 2, 3),
    # multi-bucket asymmetric Tk (200 pads to 256) — slow tier
    pytest.param(64, 200, 2, None, marks=pytest.mark.slow)])
def test_packed_matches_dense_padding_mask(rng, rows_a_cell, tq, tk, b, rows):
    h, dh = 4, 64                           # the bench regime: g = 2
    if rows:
        rows_a_cell(rows, h, max(tq, tk), dh, backward=False)
    q, k, v = (_rand(rng, b, h, tq, dh), _rand(rng, b, h, tk, dh),
               _rand(rng, b, h, tk, dh))
    m = _kv_mask(rng, b, tk)
    out = packed_attention(q, k, v, kv_mask=m)
    ref = dense_attention(q, k, v, mask=m[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,b,rows", [
    (100, 2, None),
    (40, 6, 2), (40, 5, 2),                 # three cells of 2; five of 1
    # single-pad 48->64 causal geometry — slow tier
    pytest.param(48, 2, None, marks=pytest.mark.slow)])
def test_packed_matches_dense_causal(rng, rows_a_cell, t, b, rows):
    h, dh = 4, 64
    if rows:
        rows_a_cell(rows, h, t, dh, backward=False)
    q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
               _rand(rng, b, h, t, dh))
    m = _kv_mask(rng, b, t)
    out = packed_attention(q, k, v, kv_mask=m, causal=True)
    ref = dense_attention(q, k, v,
                          mask=combine_masks(causal_mask(t),
                                             m[:, None, None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,dh", [(8, 16), (2, 128), (8, 32), (8, 64),
                                  (16, 16)])
def test_pack_group_edges_match_dense(rng, h, dh):
    """g=8 (narrow heads) and the g=1 wide-head degenerate pack must
    stay numerically exact; (8, 32) and (8, 64) are transformer-base's
    8 heads as g=4 x 2 groups and g=2 x 4 groups a cell, (16, 16) two
    g=8 groups."""
    b, t = 2, 48
    q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
               _rand(rng, b, h, t, dh))
    m = _kv_mask(rng, b, t)
    out = packed_attention(q, k, v, kv_mask=m, causal=True)
    ref = dense_attention(q, k, v,
                          mask=combine_masks(causal_mask(t),
                                             m[:, None, None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_packed_no_mask(rng):
    b, h, t, dh = 2, 2, 96, 64
    q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
               _rand(rng, b, h, t, dh))
    out = packed_attention(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,b,t,rows,heads", [
    (False, 2, 48, None, None), (True, 2, 48, None, None),
    # 4 rows in cells of 2, 2 rows under a most of 3 (fewer than a cell)
    (False, 4, 48, 2, None), (True, 4, 48, 2, None),
    (False, 2, 48, 3, None), (True, 2, 48, 3, None),
    # the T cap at dh 32 (128), a cell down to one row's single head
    # group: today's floor
    (True, 2, 128, 1, 4)])
def test_packed_gradients_match_dense(rng, rows_a_cell, causal, b, t, rows,
                                      heads):
    """The custom VJP: both backward orientations (dq via the packed
    Tk contraction, dk/dv via the packed Tq contraction) against the
    dense path's autodiff."""
    h, dh = 4, 32
    if rows:
        rows_a_cell(rows, h, t, dh, heads)
    q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
               _rand(rng, b, h, t, dh))
    m = _kv_mask(rng, b, t)
    dense_mask = combine_masks(causal_mask(t) if causal else None,
                               m[:, None, None, :])

    def f_packed(q, k, v):
        return (packed_attention(q, k, v, kv_mask=m, causal=causal) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_attention(q, k, v, mask=dense_mask) ** 2).sum()

    gp = jax.grad(f_packed, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_packed_gradients_with_padding(rng):
    """Tq/Tk not multiples of the 64-pad: cotangents of padded rows are
    exact zeros (pad/slice transposes outside the custom VJP). Slow
    tier: tier-1 carries the unpadded fwd+bwd parity above and the
    padded FORWARD parity; this pins the padded backward specifically."""
    b, h, tq, tk, dh = 2, 2, 50, 70, 64
    q, k, v = (_rand(rng, b, h, tq, dh), _rand(rng, b, h, tk, dh),
               _rand(rng, b, h, tk, dh))
    m = _kv_mask(rng, b, tk)

    def f_packed(q, k, v):
        return (packed_attention(q, k, v, kv_mask=m) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_attention(q, k, v, mask=m[:, None, None, :]) ** 2).sum()

    gp = jax.grad(f_packed, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,t", [(2, 64), (8, 16), (4, 24)])
def test_bf16_inputs(rng, b, t):
    h, dh = 4, 64
    q = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
    out = packed_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, mask=causal_mask(t))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_packed_under_jit(rng):
    b, h, t, dh = 2, 2, 64, 64
    q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
               _rand(rng, b, h, t, dh))
    m = _kv_mask(rng, b, t)
    fn = jax.jit(lambda q, k, v: packed_attention(q, k, v, kv_mask=m,
                                                  causal=True))
    out = fn(q, k, v)
    ref = dense_attention(q, k, v,
                          mask=combine_masks(causal_mask(t),
                                             m[:, None, None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- what fills a tile (PR 31): 64 // T rows under a row mask ----

def test_rows_a_tile():
    """64 // max(Tq, Tk), reduced to a divisor of the batch; one row
    past 64 positions and at a prime batch."""
    assert [rows_a_tile(b, t, t) for b, t in (
        (512, 8), (256, 16), (168, 24), (128, 32), (80, 48), (64, 64),
        (45, 128))] == [8, 4, 2, 2, 1, 1, 1]
    assert [rows_a_tile(b, 16, 16) for b in (6, 7, 9, 10, 12)] == [
        3, 1, 3, 2, 4]
    assert rows_a_tile(5, 8, 8) == 5 and rows_a_tile(11, 8, 8) == 1
    assert rows_a_tile(8, 24, 16) == 2 and rows_a_tile(8, 16, 32) == 2
    assert rows_a_tile(8, 8, 40) == 1 and rows_a_tile(8, 72, 8) == 1


def _dense_and_packed(rng, b, h, tq, tk, dh, causal, m=None):
    """Output and q/k/v gradients of (packed, dense) under one cotangent."""
    q, k, v, do = (_rand(rng, b, h, t, dh) for t in (tq, tk, tk, tq))
    m = _kv_mask(rng, b, tk) if m is None else m
    dense_mask = combine_masks(causal_mask(tq) if causal else None,
                               m[:, None, None, :])
    out_p, vjp_p = jax.vjp(
        lambda q, k, v: packed_attention(q, k, v, kv_mask=m, causal=causal),
        q, k, v)
    out_d, vjp_d = jax.vjp(
        lambda q, k, v: dense_attention(q, k, v, mask=dense_mask), q, k, v)
    return (out_p, *vjp_p(do)), (out_d, *vjp_d(do))


@pytest.mark.parametrize("b,tq,tk,causal,h,dh,cell", [
    # big.train's six batches, scaled down in rows: 8, 4, 2, 2, 1, 1 rows
    # a tile; encoder (key mask) and decoder (causal) each
    (16, 8, 8, False, 4, 64, None), (8, 8, 8, True, 4, 64, None),
    (8, 16, 16, False, 4, 64, None), (8, 16, 16, True, 4, 64, None),
    (4, 24, 24, False, 4, 64, None), (4, 24, 24, True, 4, 64, None),
    (4, 32, 32, False, 4, 64, None), (4, 32, 32, True, 4, 64, None),
    (2, 48, 48, False, 4, 64, None), (2, 48, 48, True, 4, 64, None),
    (2, 64, 64, False, 4, 64, None), (2, 64, 64, True, 4, 64, None),
    # cross attention, Tq != Tk, inside one tile
    (4, 24, 16, False, 4, 64, None), (4, 16, 32, False, 4, 64, None),
    (8, 8, 16, False, 4, 64, None),
    # a width that is no multiple of 8 (20 -> 24, 2 rows a tile; 13 x 9)
    (4, 20, 20, True, 4, 64, None), (4, 13, 9, False, 4, 64, None),
    # g 4 and g 8
    (8, 16, 16, True, 8, 32, None), (4, 32, 32, False, 8, 32, None),
    (8, 16, 16, False, 8, 16, None), (8, 8, 8, True, 8, 16, None),
    # a batch 64 // T does not divide: 6 -> 3 rows a tile, 7 and 5 -> 1
    (6, 16, 16, True, 4, 64, None), (7, 16, 16, False, 4, 64, None),
    (5, 8, 8, True, 4, 64, None),
    # several cells of one tile group each, and of two
    (16, 16, 16, True, 4, 64, 4), (16, 16, 16, False, 4, 64, 8),
    (12, 24, 24, True, 4, 64, 2),
])
@time_limit(120)
def test_folded_tiles_match_dense(rng, rows_a_cell, b, tq, tk, causal, h, dh,
                                  cell):
    """Forward and all three gradients against the dense path where a
    tile holds several rows at their own width."""
    if cell:
        rows_a_cell(cell, h, max(tq, tk), dh,
                    r=rows_a_tile(4 * cell, tq, tk))
    got, want = _dense_and_packed(rng, b, h, tq, tk, dh, causal)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for a, d in zip(got[1:], want[1:]):
        assert a.shape == d.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(d),
                                   rtol=1e-4, atol=1e-4)


def _call_both(q, k, v, m, do, causal):
    """The kernel pair itself on a folded batch: (out, dq, dk, dv)."""
    b, h, t, dh = q.shape
    g, kvm, scale = pack_group(h, dh), m[:, None, :], 1.0 / dh ** 0.5
    out = pa._fwd_call(q, k, v, kvm, scale, causal, g, True)
    return (out, *pa._bwd_call(q, k, v, kvm, do, out, scale, causal, g, True))


@pytest.mark.parametrize("t,causal,dtype", [
    (16, False, "float32"), (16, True, "bfloat16"), (8, True, "float32"),
    (24, False, "bfloat16"), (32, True, "float32")])
@time_limit(120)
def test_no_leakage_between_the_rows_of_a_tile(rng, t, causal, dtype):
    """Changing one row's k, v, key mask, q or cotangent leaves every
    other row of its tile bit for bit the same: output, dq, dk and dv.
    Cross-row probabilities are exact zeros, not small numbers."""
    b, h, dh, dt = 64 // t * 2, 4, 64, jnp.dtype(dtype)
    r = rows_a_tile(b, t, t)
    assert r == 64 // t and r >= 2
    q, k, v, do = (jnp.asarray(rng.randn(b, h, t, dh), dt) for _ in range(4))
    m = _kv_mask(rng, b, t)
    base = _call_both(q, k, v, m, do, causal)
    j = 1                                   # inside the first tile
    others = np.array([i for i in range(b) if i != j])
    changed = {
        "k": (q, k.at[j].set(7.0 * k[j] + 3.0), v, m, do),
        "v": (q, k, v.at[j].set(-5.0 * v[j] + 100.0), m, do),
        "mask": (q, k, v, m.at[j].set(1.0 - m[j]).at[j, 0].set(1.0), do),
        "all masked": (q, k, v, m.at[j].set(0.0), do),
        "q, do": (q.at[j].set(9.0 * q[j]), k, v, m, do.at[j].set(-do[j])),
    }
    for what, args in changed.items():
        got = _call_both(*args, causal)
        assert not np.array_equal(np.asarray(got[0][j], np.float32),
                                  np.asarray(base[0][j], np.float32)), what
        for a, ref in zip(got, base):
            a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
            assert np.isfinite(a).all(), what
            np.testing.assert_array_equal(a[others], ref[others],
                                          err_msg=what)


@pytest.mark.parametrize("t,causal", [(16, False), (16, True), (24, False),
                                      (8, True)])
@time_limit(120)
def test_a_row_with_every_key_masked_leaves_its_neighbours_exact(rng, t,
                                                                 causal):
    """A row whose keys are all masked (its own output is uniform
    attention, which callers discard) does not move its tile's other
    rows off the dense path, forward or backward, and is finite itself."""
    b, h, dh = 64 // t * 2, 4, 64
    m = _kv_mask(rng, b, t).at[1].set(0.0).at[b - 1].set(0.0)
    got, want = _dense_and_packed(rng, b, h, t, t, dh, causal, m=m)
    live = np.array([i for i in range(b) if i not in (1, b - 1)])
    for a, d, tol in zip(got, want, (2e-5, 1e-4, 1e-4, 1e-4)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a)[live], np.asarray(d)[live],
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("b,t", [(8, 16), (4, 24), (6, 16), (2, 48)])
@time_limit(120)
def test_folded_equals_one_row_a_tile(rng, monkeypatch, b, t):
    """The fold against the same kernel held to one row a tile (its own
    width, zero rows up to 64): the same numbers to rounding."""
    h, dh = 4, 64
    q, k, v, do = (_rand(rng, b, h, t, dh) for _ in range(4))
    m = _kv_mask(rng, b, t)
    folded = _call_both(q, k, v, m, do, True)
    monkeypatch.setattr(pa, "rows_a_tile", lambda b, tq, tk: 1)
    _forget_plans()
    alone = _call_both(q, k, v, m, do, True)
    _forget_plans()
    for a, ref in zip(folded, alone):
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@time_limit(60)
def test_the_plan_is_a_trace_event(rng):
    """Once per distinct call, forward and backward: how many rows share
    a tile and how many tiles that leaves."""
    from marian_tpu import obs
    b, h, t, dh = 8, 4, 16, 64
    q, k, v = (_rand(rng, b, h, t, dh) for _ in range(3))
    _forget_plans()
    obs.TRACER.reset()
    obs.TRACER.enable()
    try:
        for _ in range(2):                  # the second call is not traced
            jax.grad(lambda q: packed_attention(q, k, v).sum())(q)
        _, events = obs.TRACER.snapshot()
    finally:
        obs.TRACER.disable()
        obs.TRACER.reset()
        _forget_plans()
    plans = [e["attrs"] for e in events
             if e["name"] == "packed_attention.plan"]
    assert plans == [
        dict(b=8, tq=16, tk=16, backward=backward, rows_a_tile=4, tiles=4,
             tiles_unfolded=16) for backward in (False, True)]


# ---- the cell: cell_plan alone, and the kernel against one tile a step ----

_PLAN_SHAPES = [  # (b, h, tq, tk, dh, itemsize)
    # big.train's batches at their own width: 8, 4, 2, 2, 1 rows a tile
    (512, 16, 8, 8, 64, 2), (256, 16, 16, 16, 64, 2),
    (168, 16, 24, 24, 64, 2), (128, 16, 32, 32, 64, 2),
    (80, 16, 48, 48, 64, 2), (168, 16, 24, 16, 64, 2),
    (6, 8, 16, 16, 32, 4), (7, 8, 16, 16, 16, 4), (30, 16, 8, 8, 64, 2),
    (512, 16, 64, 64, 64, 2), (128, 16, 64, 64, 64, 2),
    (168, 16, 64, 64, 64, 2), (3, 16, 64, 64, 64, 2),
    (45, 16, 128, 64, 64, 2), (2, 16, 256, 256, 64, 2),
    (64, 8, 64, 64, 64, 2), (64, 8, 128, 128, 32, 4),
    (9, 8, 64, 64, 16, 4), (4, 2, 128, 128, 128, 2)]


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("shape", _PLAN_SHAPES)
@time_limit(30)
def test_cell_plan_fits_its_budget(shape, backward):
    """Never zero rows or heads, rows that divide the batch (no ragged
    last cell) and are whole tiles (a multiple of the rows a tile),
    whole head groups that divide the heads, and within the budget
    unless the cell is already the floor (r, g)."""
    b, h, tq, tk, dh, itemsize = shape
    g, r = pack_group(h, dh), rows_a_tile(b, tq, tk)
    for budget in (0, 1 << 20, 4 << 20, 16 << 20, None, 100 << 20):
        rows, heads = cell_plan(b, h, tq, tk, dh, itemsize, backward,
                                budget=budget, r=r)
        assert r <= rows <= b and b % rows == 0 and rows % r == 0
        assert g <= heads <= h and heads % g == 0 and h % heads == 0
        assert rows == r or heads == h      # part of a row only alone
        vmem = cell_vmem(rows, heads, g, tq, tk, dh, itemsize, backward, r)
        assert (vmem <= (pa._CELL_BUDGET if budget is None else budget)
                or (rows, heads) == (r, g))
    assert cell_plan(b, h, tq, tk, dh, itemsize, backward,
                     budget=0, r=r) == (r, g)
    assert pa._CELL_BUDGET < pa._VMEM_LIMIT


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("h,dh,itemsize", [(16, 64, 2), (8, 64, 2),
                                           (8, 32, 4), (8, 16, 4)])
@time_limit(30)
def test_cell_plan_holds_at_every_width(h, dh, itemsize, backward):
    """Every width a caller can bring, 8 to the cap, at the trainer's
    rows (4096 words, a multiple of 8) and at a prime batch: whole tiles,
    a divisor of the batch, inside the budget, all heads up to 64."""
    from marian_tpu.ops.auto_tuner import packed_attention_max_t
    g = pack_group(h, dh)
    for t in range(8, packed_attention_max_t(dh) + 1, 8):
        tp = _kernel_t(t)
        for b in (max(8, 4096 // t // 8 * 8), 13):
            r = rows_a_tile(b, tp, tp)
            assert r == (1 if b == 13 else max(
                x for x in range(1, max(1, 64 // tp) + 1) if b % x == 0))
            rows, heads = cell_plan(b, h, tp, tp, dh, itemsize, backward,
                                    r=r)
            assert rows % r == 0 and b % rows == 0 and h % heads == 0
            assert tp > 64 or heads == h
            assert cell_vmem(rows, heads, g, tp, tp, dh, itemsize, backward,
                             r) <= pa._CELL_BUDGET


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("h,dh,itemsize", [(16, 64, 2), (8, 64, 2),
                                           (8, 32, 4), (8, 16, 4)])
@time_limit(30)
def test_cell_plan_is_monotone_in_t(h, dh, itemsize, backward):
    """A longer sequence never gets a larger cell; the backward never a
    larger one than the forward; at the cap a cell is a row or less."""
    from marian_tpu.ops.auto_tuner import packed_attention_max_t
    b, cells = 4096, []
    for t in range(64, packed_attention_max_t(dh) + 1, 64):
        rows, heads = cell_plan(b, h, t, t, dh, itemsize, backward)
        cells.append(rows * heads)
        if backward:
            fr, fh = cell_plan(b, h, t, t, dh, itemsize, False)
            assert rows * heads <= fr * fh
    assert cells == sorted(cells, reverse=True)
    assert cell_plan(b, 16, 256, 256, 64, 2, True)[0] == 1


@time_limit(30)
def test_cell_plan_takes_divisors_of_the_batch():
    """Under a most of 4 rows a cell: 3 go in one cell, 6 in 3 + 3, 7
    (prime) one row a cell, 10 in fives of 2; the benchmark cell's 168
    and 80 rows under a most of 7 go in 7s and 5s."""
    g = pack_group(16, 64)
    plan = functools.partial(cell_plan, h=16, tq=64, tk=64, dh=64,
                             itemsize=2, backward=False)
    most4 = cell_vmem(4, 16, g, 64, 64, 64, 2, False)
    assert [plan(b, budget=most4)[0]
            for b in (3, 4, 6, 7, 8, 10, 512)] == [3, 4, 3, 1, 4, 2, 4]
    most7 = cell_vmem(7, 16, g, 64, 64, 64, 2, False)
    assert [plan(b, budget=most7)[0]
            for b in (512, 256, 168, 128, 80, 64)] == [4, 4, 7, 4, 5, 4]


def _tile_a_step(q, k, v, kvm, do=None, out=None, *, causal):
    """The geometry before PR 26, kept here and not in the package: one
    grid step per (row, head group), blocks (1, g, T, dh), one row a
    tile, the package's own tile functions called once a step."""
    b, h, tq, dh = q.shape
    tk, g = k.shape[2], pack_group(h, dh)
    kw = dict(scale=1.0 / dh ** 0.5, g=g, bk=tk, dh=dh)
    qspec = pl.BlockSpec((1, g, tq, dh), lambda r, hg: (r, hg, 0, 0))
    kspec = pl.BlockSpec((1, g, tk, dh), lambda r, hg: (r, hg, 0, 0))
    mspec = pl.BlockSpec((1, 1, tk), lambda r, hg: (r, 0, 0))

    def heads(ref):
        return pa._heads(ref, 0, 0, g, 1, ref.shape[2])

    def bias(kvm_ref):
        return pa._bias(kvm_ref[0],
                        pa._live_pairs(1, tq, tk, tq, tk, causal), tk)

    def fwd(q_ref, k_ref, v_ref, kvm_ref, o_ref):
        o = pa._fwd_tile(heads(q_ref), heads(k_ref), heads(v_ref),
                         bias(kvm_ref), **kw)
        for j in range(g):
            o_ref[0, j] = o[j].astype(o_ref.dtype)

    def bwd(q_ref, k_ref, v_ref, kvm_ref, do_ref, o_ref, *grads):
        tiles = pa._bwd_tile(heads(q_ref), heads(k_ref), heads(v_ref),
                             bias(kvm_ref), heads(do_ref), heads(o_ref),
                             **kw)
        for ref, tile in zip(grads, tiles):
            for j in range(g):
                ref[0, j] = tile[j].astype(ref.dtype)

    if do is None:
        return pl.pallas_call(
            fwd, grid=(b, h // g), in_specs=[qspec, kspec, kspec, mspec],
            out_specs=qspec, out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=True)(q, k, v, kvm)
    return pl.pallas_call(
        bwd, grid=(b, h // g),
        in_specs=[qspec, kspec, kspec, mspec, qspec, qspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        interpret=True)(q, k, v, kvm, do, out)


@pytest.mark.parametrize("b,h,tq,tk,dh,causal,dtype,rows,heads", [
    (6, 4, 64, 64, 64, False, "float32", 3, None),     # 3 + 3
    (8, 4, 64, 64, 64, True, "bfloat16", 4, None),     # 4 + 4
    (4, 16, 64, 64, 64, True, "bfloat16", 2, None),    # big's 8 groups a row
    (2, 4, 64, 64, 64, False, "float32", 3, None),     # fewer rows than a cell
    (6, 4, 128, 64, 64, False, "bfloat16", 2, None),   # cross
    (3, 8, 64, 64, 32, True, "float32", 3, None),      # g = 4, two groups
    (3, 8, 64, 64, 32, True, "float32", 1, 4),         # the floor (1, g)
    (2, 4, 256, 256, 64, True, "bfloat16", 1, 2),      # the cap, part of a row
    # a ragged last cell, which cell_plan never gives (it hung the chip):
    # forced here, 3 + 3 + 1 and 2 + 2 + 1, to hold that the kernel
    # itself is right on it
    (7, 4, 64, 64, 64, True, "bfloat16", -3, None),
    (5, 4, 128, 64, 64, False, "float32", -2, None),
])
@time_limit(120)
def test_cells_equal_one_tile_a_step_bit_for_bit(rng, monkeypatch, b, h, tq,
                                                 tk, dh, causal, dtype, rows,
                                                 heads):
    """The refactor's pin: taking a block of rows and all heads a grid
    step and looping over the tiles inside gives, bit for bit, what one
    (row, head group) a step gives — output and all three gradients. On
    a ragged last cell too (rows < 0: forced): what a cell reads past
    the batch's end reaches only writes past the end, and those are
    dropped."""
    g, dt = pack_group(h, dh), jnp.dtype(dtype)
    q, k, v, do = (jnp.asarray(rng.randn(b, h, t, dh), dt)
                   for t in (tq, tk, tk, tq))
    kvm = _kv_mask(rng, b, tk).reshape(b, 1, tk)
    scale = 1.0 / dh ** 0.5
    if rows < 0:
        monkeypatch.setattr(pa, "cell_plan", lambda *a, **kw: (-rows, h))
    for backward in (False, True):
        monkeypatch.setattr(pa, "_CELL_BUDGET", cell_vmem(
            abs(rows), heads or h, g, tq, tk, dh, dt.itemsize, backward))
        _forget_plans()
        assert pa._plan(b, h, tq, tk, dh, dt.itemsize, backward) == (
            min(abs(rows), b), heads or h, 1)
        if not backward:
            out = pa._fwd_call(q, k, v, kvm, scale, causal, g, True)
            ref = _tile_a_step(q, k, v, kvm, causal=causal)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        else:
            got = pa._bwd_call(q, k, v, kvm, do, out, scale, causal, g, True)
            want = _tile_a_step(q, k, v, kvm, do, out, causal=causal)
            for a, r in zip(got, want):
                assert np.isfinite(np.asarray(a, np.float32)).all()
                np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    _forget_plans()


class TestDispatcherGate:
    """ops/attention.py::attention routing for the packed gate."""

    def test_packed_on_selects_kernel_and_matches_dense(self, rng):
        b, h, t, dh = 1, 2, 48, 64
        q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
                   _rand(rng, b, h, t, dh))
        m = _kv_mask(rng, b, t)
        out_p, w = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                             flash="off", packed="on")
        assert w is None
        out_d, _ = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                             flash="off", packed="off")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_stays_dense_off_tpu(self, rng):
        """packed='auto' must NOT engage on the CPU backend (interpret
        mode is a debug path, not a fast one): weights stay available."""
        b, h, t, dh = 1, 2, 48, 64
        q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
                   _rand(rng, b, h, t, dh))
        m = _kv_mask(rng, b, t)
        _, w = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                         flash="off", packed="auto", return_weights=True)
        assert w is not None

    # big.train's six batches of 4096 words (rows x width) as encoder /
    # decoder self-attention, and three cross shapes (rows, Tq, Tk)
    TRAINER_SHAPES = [(rows, t, t, causal)
                      for rows, t in ((512, 8), (256, 16), (168, 24),
                                      (128, 32), (80, 48), (64, 64))
                      for causal in (False, True)] + [
        (256, 16, 8, False), (80, 32, 48, False), (64, 64, 40, False)]

    @pytest.mark.parametrize("rows,tq,tk,causal", TRAINER_SHAPES)
    def test_auto_is_dense_on_tpu_and_on_forces_the_kernel(
            self, monkeypatch, rows, tq, tk, causal):
        """PR 52: on a v5e the einsum beat the kernel 2.3-5.5 x at every
        one of these shapes, so 'auto' picks the kernel for none of them
        on ANY backend; 'on' still does."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q = jax.ShapeDtypeStruct((rows, 16, tq, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((rows, 16, tk, 64), jnp.bfloat16)
        m = jax.ShapeDtypeStruct((rows, tk), jnp.float32)

        def traced(packed):
            def f(q, k, v, m):
                mask = m[:, None, None, :]
                if causal:
                    mask = combine_masks(mask, causal_mask(tq))
                return attention(q, k, v, mask=mask, kv_mask=m,
                                 causal=causal, flash="off",
                                 packed=packed)[0]
            return str(jax.make_jaxpr(f)(q, kv, kv, m))
        assert "pallas_call" not in traced("auto")
        assert traced("on").count("pallas_call") == 1

    def test_return_weights_forces_dense(self, rng):
        b, h, t, dh = 1, 2, 48, 64
        q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
                   _rand(rng, b, h, t, dh))
        m = _kv_mask(rng, b, t)
        _, w = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                         flash="off", packed="on", return_weights=True)
        assert w is not None

    def test_over_cap_falls_back_to_dense(self, rng):
        """Sequences past the auto_tuner VMEM cap leave the shape to
        dense/flash even under packed='on'."""
        b, h, t, dh = 1, 2, 48, 64
        q, k, v = (_rand(rng, b, h, t, dh), _rand(rng, b, h, t, dh),
                   _rand(rng, b, h, t, dh))
        m = _kv_mask(rng, b, t)
        _, w = attention(q, k, v, mask=m[:, None, None, :], kv_mask=m,
                         flash="off", packed="on", packed_max_len=32,
                         return_weights=False)
        # dense path executed: weights slot is None either way, so pin
        # via numerics instead — the dense and packed paths agree, and
        # the call must not raise trying to pack past the cap
        assert w is None


class TestAutoTunerRegistry:
    """Block-size entries for both r6 kernels follow the dh-scaled VMEM
    convention (the r5 flash dh>64 halving; ISSUE 3 satellite)."""

    def test_dh_scaling_halves_past_64(self):
        from marian_tpu.ops.auto_tuner import (decode_attention_max_len,
                                               packed_attention_max_t)
        assert packed_attention_max_t(64) == 256
        assert packed_attention_max_t(128) == 128
        assert packed_attention_max_t(256) == 64
        assert decode_attention_max_len(64) == 2048
        assert decode_attention_max_len(128) == 1024
        # NARROW heads shrink too: the backward kernel's packed blocks
        # are [g*T, g*T] f32, so the cap bounds g*T (g = 128//dh) at
        # the validated 512 — not T alone
        assert packed_attention_max_t(32) == 128
        assert packed_attention_max_t(16) == 64
        assert packed_attention_max_t(8) == 64      # floor
        assert decode_attention_max_len(16) == 2048

    def test_registry_floor(self):
        from marian_tpu.ops.auto_tuner import kernel_block
        # absurd widths floor at one 64-wide block, never 0 (a 0 cap
        # would turn 'degrade' into 'never runs' silently)
        assert kernel_block("packed_attention", "max_t", 4096) == 64
