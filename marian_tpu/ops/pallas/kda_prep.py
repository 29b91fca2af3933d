"""The chunk preparation of the chunked delta rule (ops/kda.py ::
chunk_terms) as Pallas TPU kernels: `kda_prep_fwd` and `kda_prep_bwd`
under one custom VJP, beside the state carry (kda_chunk.py).

What chunk_terms computes for one chunk of 64 positions of one head is
chunk-local: the cumulative log decay G, the two pairwise products with
the decay between their positions (k k^T for the WY / UT transform, q k^T
for the chunk's own attention), the unit-lower inverse T = (I + A)^-1 and
four decayed copies. A grid step takes q, k, v, g [C, d] and b [1, C] of
`heads` heads of one chunk and writes the six terms; nothing but the
five inputs is kept for the backward, which recomputes the chunk in VMEM
and turns the six cotangents into dq, dk, dv, dg, db by hand:

    dR = T^T dY,  dA = -dR Y^T                       (Y = T R)
    M[r, i] = sum_c x[r, c] y[i, c] e^(G[r, c] - G[i, c]):
        dx = (dM with the same decays) y,  dy likewise transposed,
        dG[r] += x dx,  dG[i] -= y dy
    dg = the reverse cumulative sum of dG plus the chunk-end terms

The grid is (rows, head groups, chunks), every axis parallel and an exact
quotient (`heads` divides H). Inside a step the heads go two to a TILE,
their rows one after another ([2 C, d]): every [C, C] matrix of a head is
a block on the diagonal of a [2 C, 2 C] one, so two heads fill the 128
lanes and the 128 x 128 MXU that one would leave half empty, and products
of such matrices stay block diagonal. The kernels are bound by the MXU
(rows x passes; PERF.md, PR 29), and the inverse is a chain of ten
dependent products: the tiles of a step walk it level by level together.

No exponential of a positive number is taken, as in ops/kda.py: a decay
between rows r > i is split around a reference row between the two,
exp(G_r - ref) exp(ref - G_i), both <= 1. Where ops/kda.py forms the
decays inside a 16-row diagonal block directly (a [16, 16, d] product
summed over channels: on the chip a lane reduction a column, 256 a
(chunk, head)), the kernel keeps halving: the pairs (r, i) whose highest
differing bit is L (r in the second half of an aligned block of 2 L
rows, i in its first) take the block's middle row as reference, L = 32,
16, ... 1. Each level is then one exponential of a tile,
exp(-|G - G_ref|), and one matmul that both products share; six masks
tile the strict lower triangle. The inverse goes by the same halves.

Everything is float32. The chunk's small products take three bfloat16
passes on the MXU with float32 accumulation (the split written out: what
XLA's Precision.HIGH is, which Mosaic's dot does not offer); the sums
along the chunk (G, and dg back) are a triangle of ones, exact in
bfloat16, times all 24 bits of the summands in three passes. Both calls
sit under a jit of their own (PERF.md, PR 26).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kda import CHUNK
from .kda_chunk import (HEADS_A_STEP, _AB, _ABT, _ATB, _dot,
                        _interpret_default, heads_a_step)

_LEVELS = tuple(CHUNK >> s for s in range(1, CHUNK.bit_length()))  # 32 .. 1
_PAIR = 2                # heads a tile: [C, C] matrices fill half a vreg row
_F32 = jnp.float32
_BF16 = jnp.bfloat16


def _iota(n, dim):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), dim)


def _split(x):
    """x as two bfloat16 addends (16 of its 24 bits of mantissa)."""
    hi = x.astype(_BF16)
    return hi, (x - hi.astype(_F32)).astype(_BF16)


def _mm(a, b, dims):
    """The product of two split operands in three bfloat16 passes."""
    (ah, al), (bh, bl) = a, b
    return (_dot(al, bh, dims) + _dot(ah, bl, dims)) + _dot(ah, bh, dims)


def _pieces(x):
    """All 24 bits of x's mantissa as three bfloat16 addends, smallest
    first."""
    hi = x.astype(_BF16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return (rest - mid.astype(_F32)).astype(_BF16), mid, hi


def _level_mask(n, level):
    """The (r, i) of one head whose highest differing bit is `level`,
    r > i."""
    r, i = _iota(n, 0), _iota(n, 1)
    same = (r & -(2 * level)) == (i & -(2 * level))
    return same & ((r & level) != 0) & ((i & level) == 0)


def _tri(n):
    """[n, n] ones on and below the diagonal of every head's [C, C]."""
    r, i = _iota(n, 0), _iota(n, 1)
    return ((r >= i) & ((r ^ i) < CHUNK)).astype(_BF16)


def _block_rows(x, offset, rows):
    """[n, d]: to every row the row at `offset` of its aligned block of
    `rows` rows (whole (8, d) tiles: rows >= 8)."""
    n, d = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(x[o + offset:o + offset + 1], (rows, d))
         for o in range(0, n, rows)], axis=0)


def _ref_rows(g, level):
    """[n, d]: to every row the middle row of its aligned block of
    2 * level rows; a block of fewer than 8 rows is picked by row out of
    the tiles of its neighbours."""
    if 2 * level >= 8:
        return _block_rows(g, level, 2 * level)
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) & 7
    out = _block_rows(g, level, 8)
    for first in range(2 * level, 8, 2 * level):
        out = jnp.where(row >= first, _block_rows(g, first + level, 8), out)
    return out


def _heads_of(x):
    """[hp C, ...] -> hp x [C, ...]: each head's rows."""
    return [x[o:o + CHUNK] for o in range(0, x.shape[0], CHUNK)]


def _col(rows):
    """hp x [1, C] -> [hp C, 1] without a transpose: the diagonal's lane
    sums."""
    eye = _iota(CHUNK, 0) == _iota(CHUNK, 1)
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)
         for row in rows], axis=0)


def _row(col):
    """[hp C, 1] -> hp x [1, C]: _col back, by the diagonal's sublane
    sums."""
    eye = _iota(CHUNK, 0) == _iota(CHUNK, 1)
    return [jnp.sum(jnp.where(eye, x, 0.0), axis=0, keepdims=True)
            for x in _heads_of(col)]


def _block_diag(blocks):
    """hp x [C, C] -> [hp C, hp C]."""
    if len(blocks) == 1:
        return blocks[0]
    zero = jnp.zeros_like(blocks[0])
    return jnp.concatenate(
        [jnp.concatenate([x if j == i else zero for j in range(len(blocks))],
                         axis=1) for i, x in enumerate(blocks)], axis=0)


def _pairwise(q, k, decays, with_q=True):
    """k k^T and q k^T with the decay between the two positions, strictly
    below each head's diagonal: [n, n] each."""
    n = k.shape[0]
    kk = jnp.zeros((n, n), _F32)
    qk = jnp.zeros((n, n), _F32)
    for level, e in zip(_LEVELS, decays):
        mask = _level_mask(n, level)
        ks = _split(k * e)
        if not with_q:
            kk = jnp.where(mask, _mm(ks, ks, _ABT), kk)
            continue
        kh, kl = ks
        qh, ql = _split(q * e)
        both = _mm((jnp.concatenate([kh, qh], axis=0),
                    jnp.concatenate([kl, ql], axis=0)), ks, _ABT)
        kk = jnp.where(mask, both[:n], kk)
        qk = jnp.where(mask, both[n:], qk)
    return kk, qk


def _inverses_unit_lower(mats):
    """(I + a)^-1 for each a [n, n], strictly lower triangular within
    each head, by halves over the same levels: with the inverse of every
    aligned L-row block on the diagonal in hand (I at L = 1) and Z the
    part of `a` that joins two of them into a block of 2 L rows, that
    block's inverse is [[X1, 0], [-X2 Z X1, X2]], for all blocks at once
    inv - inv Z inv. Only inverses of diagonal blocks are ever formed, so
    nothing grows that the result does not hold (ops/kda.py ::
    _solve_unit_lower goes through D^8, whose entries reach thousands
    where keys repeat: fine in XLA's float32 on the CPU, too coarse at
    three bfloat16 passes). The ten products of one matrix each wait for
    the one before: the matrices of a grid step go level by level
    together, so that one's matmul hides another's."""
    n = mats[0].shape[0]
    invs = [(_iota(n, 0) == _iota(n, 1)).astype(_F32)] * len(mats)
    for level in reversed(_LEVELS):
        mask = _level_mask(n, level)
        zs = [jnp.where(mask, a, 0.0) for a in mats]
        if level > 1:
            halves = [_split(inv) for inv in invs]
            zs = [_mm(h, _split(z), _AB) for h, z in zip(halves, zs)]
            zs = [_mm(_split(z), h, _AB) for h, z in zip(halves, zs)]
        invs = [inv - z for inv, z in zip(invs, zs)]
    return invs


class _Chunk(NamedTuple):
    """One tile before its solve: the decays from the chunk's start and
    to its end, per level exp(-|G - G_ref|), the two pairwise products
    and b as a column."""
    start: jax.Array
    end: jax.Array
    decays: list
    kk: jax.Array
    qk: jax.Array
    b: jax.Array


def _prepare(q, k, v, g, b_rows, with_q=True):
    """What forward and backward share of one chunk of hp heads before
    the solve, the heads' rows one after another ([n, d], n = hp C):
    every [n, n] matrix is block diagonal, a [C, C] block a head."""
    # the inclusive cumulative sum: a triangle of ones (exact in bfloat16)
    # times all 24 bits of g, float32 sums
    gc_ = sum(_dot(_tri(k.shape[0]), x, _AB) for x in _pieces(g))
    end = jnp.exp(_block_rows(gc_, CHUNK - 1, CHUNK) - gc_)
    # the left factor of a second-half row, the right factor of a
    # first-half row; <= 1 everywhere
    decays = [jnp.exp(-jnp.abs(gc_ - _ref_rows(gc_, level)))
              for level in _LEVELS]
    kk, qk = _pairwise(q, k, decays, with_q)
    return _Chunk(jnp.exp(gc_), end, decays, kk, qk, _col(b_rows))


def _prepare_and_invert(inputs, with_q=True):
    """Per tile its _Chunk and T = (I + b * kk)^-1."""
    pre = [_prepare(*x, with_q=with_q) for x in inputs]
    return pre, _inverses_unit_lower([c.b * c.kk for c in pre])


def _solve(t, c, k, v):
    """Y = T R for R = b * [k e^G, v]: the split T, k e^G, wk, wv."""
    ks = k * c.start
    t = _split(t)
    return (t, ks, _mm(t, _split(c.b * ks), _AB),
            _mm(t, _split(c.b * v), _AB))


def _tiles_fwd(inputs, scale):
    """The tiles of a grid step, each q, k, g [n, dk], v [n, dv], b hp x
    [1, C] -> qg, wk, wv, kd [n, d], gc [n, dk] (a head's in its last
    row), p [n, n] (a head's in its diagonal block), as ops/kda.py ::
    chunk_terms has them."""
    out = []
    for (q, k, v, _, _), c, t in zip(inputs, *_prepare_and_invert(inputs)):
        _, _, wk, wv = _solve(t, c, k, v)
        n = k.shape[0]
        diag = jnp.sum(q * k, axis=1, keepdims=True)
        out.append((scale * q * c.start, wk, wv, k * c.end, c.start,
                    scale * jnp.where(_iota(n, 0) == _iota(n, 1), diag,
                                      c.qk)))
    return out


def _tiles_bwd(inputs, cotangents, scale):
    """Per tile the six cotangents (dgc hp x [1, dk], dp hp x [C, C]) ->
    dq, dk, dv, dg [n, d], db hp x [1, C]."""
    pre, invs = _prepare_and_invert(inputs, with_q=False)
    return [_tile_bwd(x, c, t, ct, scale)
            for x, c, t, ct in zip(inputs, pre, invs, cotangents)]


def _tile_bwd(inputs, c, t, cotangents, scale):
    q, k, v, _, _ = inputs
    dqg, dwk, dwv, dkd, dgc, dp = cotangents
    n = k.shape[0]
    t, ks, wk, wv = _solve(t, c, k, v)
    # Y = T R, T = (I + A)^-1, A = b * kk, R = b * [k e^G, v]; what dA
    # holds outside a head's strict lower triangle the level masks drop
    drk = _mm(t, _split(dwk), _ATB)
    drv = _mm(t, _split(dwv), _ATB)
    da = -(_mm(_split(drk), _split(wk), _ABT)
           + _mm(_split(drv), _split(wv), _ABT))
    db = jnp.sum(da * c.kk, axis=1, keepdims=True) \
        + jnp.sum(drk * ks, axis=1, keepdims=True) \
        + jnp.sum(drv * v, axis=1, keepdims=True)
    dkk = c.b * da
    dqk = scale * _block_diag(dp)
    # the copies decayed from the chunk's start and to its end
    dq = scale * c.start * dqg
    dk = c.b * drk * c.start
    dkend = dkd * c.end
    dg_ = q * dq + k * dk - k * dkend
    dk = dk + dkend
    row = jax.lax.broadcasted_iota(jnp.int32, dg_.shape, 0)
    for j, (x, dgc_j) in enumerate(zip(_heads_of(k * dkend), dgc)):
        last = j * CHUNK + CHUNK - 1
        dgend = jnp.sum(x, axis=0, keepdims=True) \
            + dgc_j * c.start[last:last + 1]
        dg_ = dg_ + jnp.where(row == last, dgend, 0.0)
    # q k^T's diagonal carries no decay
    ddiag = jnp.sum(jnp.where(_iota(n, 0) == _iota(n, 1), dqk, 0.0),
                    axis=1, keepdims=True)
    dq = dq + ddiag * k
    dk = dk + ddiag * q
    # the pairwise products, level by level with the forward's decays
    zero = jnp.zeros_like(k)
    dk_left, dk_right, dq_pair = zero, zero, zero
    for level, e in zip(_LEVELS, c.decays):
        mask = _level_mask(n, level)
        kh, kl = _split(k * e)
        qh, ql = _split(q * e)
        dm = _split(jnp.concatenate([jnp.where(mask, dkk, 0.0),
                                     jnp.where(mask, dqk, 0.0)], axis=0))
        left = _mm(dm, (kh, kl), _AB)                      # [2 n, dk]
        right = _mm(dm, (jnp.concatenate([kh, qh], axis=0),
                         jnp.concatenate([kl, ql], axis=0)), _ATB)
        dk_left = dk_left + e * left[:n]
        dq_pair = dq_pair + e * left[n:]
        dk_right = dk_right + e * right
    dq = dq + dq_pair
    dk = dk + dk_left + dk_right
    dg_ = dg_ + q * dq_pair + k * (dk_left - dk_right)
    # G = (a triangle of ones) g: the reverse sum takes dG back
    dg = sum(_dot(_tri(n), x, _ATB) for x in _pieces(dg_))
    return dq, dk, c.b * drv, dg, _row(db)


def _tiles(heads):
    """(first head, heads) of each tile of a grid step."""
    hp = _PAIR if heads % _PAIR == 0 else 1
    return [(i, hp) for i in range(0, heads, hp)]


def _load(ref, tile):
    """hp heads' [C, d] blocks, one after another: [hp C, d]."""
    i, hp = tile
    x = ref[0, i:i + hp, 0]
    return x.reshape(hp * x.shape[1], x.shape[2])


def _each(ref, tile):
    i, hp = tile
    return [ref[0, i + j, 0] for j in range(hp)]


def _store(ref, tile, x):
    i, hp = tile
    ref[0, i:i + hp, 0] = x.reshape(hp, x.shape[0] // hp, x.shape[1])


def _inputs(refs, b_ref, tiles):
    return [(*(_load(ref, tile) for ref in refs), _each(b_ref, tile))
            for tile in tiles]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref,
                qg_ref, wk_ref, wv_ref, kd_ref, gc_ref, p_ref,
                *, tiles, scale):
    out = _tiles_fwd(_inputs((q_ref, k_ref, v_ref, g_ref), b_ref, tiles),
                     scale)
    for tile, (qg, wk, wv, kd, start, p) in zip(tiles, out):
        for ref, x in zip((qg_ref, wk_ref, wv_ref, kd_ref),
                          (qg, wk, wv, kd)):
            _store(ref, tile, x)
        i, hp = tile
        for j in range(hp):
            lo, hi = j * CHUNK, (j + 1) * CHUNK
            gc_ref[0, i + j, 0] = start[hi - 1:hi]
            p_ref[0, i + j, 0] = p[lo:hi, lo:hi]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref,
                dqg_ref, dwk_ref, dwv_ref, dkd_ref, dgc_ref, dp_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *, tiles, scale):
    cotangents = [
        (*(_load(ref, tile)
           for ref in (dqg_ref, dwk_ref, dwv_ref, dkd_ref)),
         _each(dgc_ref, tile), _each(dp_ref, tile)) for tile in tiles]
    out = _tiles_bwd(_inputs((q_ref, k_ref, v_ref, g_ref), b_ref, tiles),
                     cotangents, scale)
    for tile, (*wide, db) in zip(tiles, out):
        for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), wide):
            _store(ref, tile, x)
        i, hp = tile
        for j in range(hp):
            db_ref[0, i + j, 0] = db[j]


def _call(kernel, name, ins, outs, args, scale, heads, interpret):
    """One pallas_call over (rows, head groups, chunks), every axis an
    exact quotient; `ins` and `outs` name each operand's last two
    dimensions."""
    bsz, h, n, c, dk = args[0].shape
    dims = {"qk": (c, dk), "v": (c, args[2].shape[-1]), "b": (1, c),
            "gc": (1, dk), "p": (c, c)}
    spec = lambda x: pl.BlockSpec(                          # noqa: E731
        (1, heads, 1, *dims[x]), lambda b, h, n: (b, h, n, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, tiles=_tiles(heads), scale=scale),
        name=name,
        grid=(bsz, h // heads, n),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, n, *dims[x]), _F32)
                   for x in outs],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(*args)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _fwd_call(q, k, v, g, b, scale, heads, interpret):
    return _call(_fwd_kernel, "kda_prep_fwd",
                 ("qk", "qk", "v", "qk", "b"),
                 ("qk", "qk", "v", "qk", "gc", "p"),
                 (q, k, v, g, b), scale, heads, interpret)


@functools.partial(jax.jit, static_argnums=(11, 12, 13))
def _bwd_call(q, k, v, g, b, dqg, dwk, dwv, dkd, dgc, dp, scale, heads,
              interpret):
    return _call(_bwd_kernel, "kda_prep_bwd",
                 ("qk", "qk", "v", "qk", "b", "qk", "qk", "v", "qk", "gc",
                  "p"),
                 ("qk", "qk", "v", "qk", "b"),
                 (q, k, v, g, b, dqg, dwk, dwv, dkd, dgc, dp),
                 scale, heads, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _prep(q, k, v, g, b, scale, heads, interpret):
    return tuple(_fwd_call(q, k, v, g, b, scale, heads, interpret))


def _prep_fwd(q, k, v, g, b, scale, heads, interpret):
    return _prep(q, k, v, g, b, scale, heads, interpret), (q, k, v, g, b)


def _prep_bwd(scale, heads, interpret, res, cts):
    return tuple(_bwd_call(*res, *cts, scale, heads, interpret))


_prep.defvjp(_prep_fwd, _prep_bwd)


def kda_chunk_terms(q, k, v, g, b, scale, chunk=CHUNK, heads=None,
                    interpret=None):
    """ops/kda.py :: chunk_terms on the chip: q, k, g [B, H, T, dk],
    v [B, H, T, dv], b [B, H, T], T a multiple of the chunk -> the six
    terms the state carry consumes, float32."""
    assert chunk == CHUNK, "the kernels' levels are the chunk's bits"
    if interpret is None:
        interpret = _interpret_default()
    bsz, h, t, _ = k.shape
    n = t // chunk
    q, k, v, g = (x.astype(_F32).reshape(bsz, h, n, chunk, -1)
                  for x in (q, k, v, g))
    b = b.astype(_F32).reshape(bsz, h, n, 1, chunk)
    return _prep(q, k, v, g, b, float(scale),
                 heads_a_step(h, heads or HEADS_A_STEP), bool(interpret))
