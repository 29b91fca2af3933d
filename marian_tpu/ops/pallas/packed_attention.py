"""Head-packed attention as a Pallas TPU kernel — kept, not selected.

MEASURED on a v5e (PRs 26, 31, 52; docs/PERFORMANCE.md, PERF.md 6): XLA's
dense einsum beats this kernel 2.3-5.5 x forward + backward at every NMT
batch shape (4096 words at widths 8-64, 32 x 128, dh 32), alone and in the
train step (+8.3 % of big.train's rate without it), so since PR 52 only
`--transformer-packed-attention on` runs it (ops/attention.py). The idea:
a dh=64 contraction fills half the 128-deep systolic array and a T=48-64
output ~37-50% of its lanes (the r5 table read those einsums at 21.7% /
30.6% of MXU peak), so PACK head groups into one full tile with
block-diagonal operand packing:

  scores, per group of g = 128//dh heads (g=2 at dh=64):
      [Tq, g*dh] = [q_0 | q_1]          (heads concatenated on contraction)
      [g*dh, g*Tk] = diag(k_0^T, k_1^T) (block-diagonal keys)
      one dot -> [Tq, g*Tk] = [s_0 | s_1]: contraction g*dh = 128 (full
      sublanes), output g*Tk ~ 128 (full lanes)
  apply:
      [Tq, g*Tk] = [p_0 | p_1]  @  diag(v_0, v_1) [g*Tk, g*dh]
      -> [Tq, g*dh] = [o_0 | o_1]: contraction g*Tk = 128, output 128.

The zero blocks double the nominal FLOPs, and the MXU pays per tile PASS,
not per useful FLOP; but a tile is a chain of small dependent ops that
costs its LATENCY, 166-211 / 309-383 us a call forward / backward whatever
it holds: that lost. The custom VJP keeps full tiles in all four backward dots:
dp/dq pack the dh- and Tk-contractions exactly like the forward against
the same block-diagonal K/V, dk/dv contract the packed probs against the
lane-concatenated q/do over Tq and read each head's gradient off the
diagonal block of the [g*Tk, g*dh] output tile.

Grid geometry (PR 26): one grid step takes a CELL — a block of batch
rows with all their heads, blocks [rows, H, T, dh] — and loops over its
tiles inside, four to a loop body, the tile's math as above. One tile is
a chain of small dependent ops that costs its latency (~0.7 us forward
on a v5e) whether a grid step or a loop step holds it; four independent
tiles in one body overlap (~0.37 us a tile). The first geometry, one
tile a grid step, left the kernel at 5.5 % of its roofline. `cell_plan`
sizes the cell from the call's shapes against a fixed VMEM budget, rows
a divisor of the batch's; where not even one row fits (T toward the cap)
a cell is one row's head groups, as many as fit, down to one. The
backward takes delta = rowsum(do * out) tile by tile from `do` and `out`
(a [T, 1] operand would pad 1 -> 128 lanes).

What fills a tile (PR 31): a tile costs the same whatever it holds, so
up to 64 positions it is 64 query x 64 key positions of one head group
FILLED WITH ROWS at the batch's own width: `rows_a_tile` = 64 // max(Tq,
Tk) batch rows (the largest such number that divides the batch), each
row's [T, dh] slab loaded from the cell's block, upcast and set one
under the other in VMEM, zero rows up to 64 where the rows leave a rest
(widths 24 and 48). Operands reach the kernel at their own width (no
`pad` in HBM, 1.3-8 x fewer operand bytes), and a row mask joins the key
mask and the causal mask in one additive bias a tile group: a query
attends to keys of its own row alone, every other pair at -inf, whose
probability is an exact zero — also beside a row whose keys are all
masked. Outputs and gradients go back row by row; padded positions are
never written. A batch of 512 rows x 8 words is 512 tiles a call, not
4096. Past 64 positions a tile is one row, padded to multiples of 64 in
HBM, as before.

Under "on" this kernel takes T <= packed-cap (NMT sentence lengths);
flash_attention.py owns the long-sequence end. Same structured-mask
interface as flash: kv_mask [B, Tk] (1.0 = attend) and/or causal.
Attention dropout and returned weights fall back to the dense path via
the dispatcher (ops/attention.py).

Shapes: q [B,H,Tq,Dh], k/v [B,H,Tk,Dh] -> out [B,H,Tq,Dh]. Compute is
f32 on the MXU regardless of input dtype.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import obs
from ...common import logging as log
from .flash_attention import MASK_VALUE, _interpret_default, _round_up

# A tile's sequence dims are multiples of 64, so a g=2 pack lands on
# exactly 128 lanes/sublanes (the MXU tile edge); g>2 packs (dh 32/16)
# land on multiples of it. Up to 64 positions the tile is filled in VMEM
# with rows at their own width (a multiple of _SUBLANES); longer
# sequences pad to multiples of 64 in HBM.
_PAD = 64
_SUBLANES = 8


def pack_group(heads: int, dh: int) -> int:
    """Heads per MXU tile: the largest divisor of `heads` with
    g*dh <= 128. g=1 means packing buys nothing (dh > 64)."""
    g = max(1, 128 // max(dh, 1))
    while g > 1 and heads % g:
        g -= 1
    return g


def rows_a_tile(b: int, tq: int, tk: int) -> int:
    """Batch rows that share one tile: 64 // max(Tq, Tk), reduced to the
    largest number that divides the batch (a tile never straddles the
    batch's end; a prime batch gives 1). Past 64 positions, 1."""
    r = max(1, _PAD // max(tq, tk))
    while b % r:
        r -= 1
    return r


def _live_pairs(r, tq, tk, bq, bk, causal):
    """Which (query, key) positions of a tile may attend, from the
    shapes alone: [bq, bk] bool, or None when every pair may. The tile's
    positions hold r rows of tq (tk) positions from the top, then zero
    rows; a pair is live when both are of the same row and, under
    causal, the query's offset in the row is not before the key's. The
    zero query positions count to the last row (their scores stay
    finite; nothing of them is written), the zero keys to none."""
    if not causal and r == 1 and tk == bk:
        return None
    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = None
    for i in range(r):
        pair = (kpos >= i * tk) & (kpos < (i + 1) * tk) & (qpos >= i * tq)
        if i < r - 1:
            pair &= qpos < (i + 1) * tq
        if causal:
            pair &= qpos - i * tq >= kpos - i * tk
        live = pair if live is None else live | pair
    return live


def _bias(kvm, live, bk):
    """The additive score bias of one tile group (r rows, every head):
    MASK_VALUE on a masked key of the query's own row, -inf on every
    pair that is not live — exp() makes that an exact zero whatever the
    row's other scores are, so no probability crosses rows. kvm is the
    rows' [1, r*tk] key masks side by side."""
    if kvm.shape[1] < bk:
        kvm = jnp.concatenate(
            [kvm, jnp.zeros((1, bk - kvm.shape[1]), kvm.dtype)], axis=1)
    bias = (1.0 - kvm.astype(jnp.float32)) * MASK_VALUE   # [1, bk]
    return bias if live is None else jnp.where(live, bias, -jnp.inf)


def _block_diag(blocks):
    """diag(x_0 .. x_{g-1}) of equal [r, c] blocks -> [g*r, g*c], built
    from concatenations with zero blocks (the TPU lowering has no
    dynamic_update_slice)."""
    g = len(blocks)
    zero = jnp.zeros_like(blocks[0])
    return jnp.concatenate(
        [jnp.concatenate([blocks[j] if i == j else zero for i in range(g)],
                         axis=1) for j in range(g)], axis=0)


def _packed_scores(qc, kd, bias, scale, g, bk):
    """The packed score dot + per-head mask/softmax. qc is the group's
    queries concatenated on the contraction [bq, g*dh], kd the
    block-diagonal keys [g*bk, g*dh], bias the tile group's [1, bk] or
    [bq, bk] additive mask (_bias); returns the packed probs
    [bq, g*bk] f32."""
    s2 = jax.lax.dot_general(
        qc, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [bq, g*bk]
    ps = []
    for j in range(g):
        s = s2[:, j * bk:(j + 1) * bk] + bias         # static lane slice
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        # l >= 1 always (the row-max key contributes exp(0) even on a
        # fully-masked row, which then yields UNIFORM probs over its own
        # row's keys — the dense path's softmax-of-all-MASK behavior,
        # and callers discard those rows), so no zero-divisor guard is
        # needed
        l = jnp.sum(p, axis=1, keepdims=True)
        ps.append(p / l)
    return jnp.concatenate(ps, axis=1)


def _heads(ref, c, first, g, r, fill):
    """Heads first .. first+g-1 of the cell's tile group c (rows c*r ..
    c*r+r-1), upcast to f32: per head the rows' [t, dh] slabs one under
    the other, zero rows up to the tile's `fill` positions. The rows
    come in one load a head ([r, t, dh], t a multiple of 8: the reshape
    moves nothing), so the kernel's size to trace and lower does not
    grow with r."""
    t, dh = ref.shape[2:]
    rest = ([jnp.zeros((fill - r * t, dh), jnp.float32)]
            if fill > r * t else [])
    tiles = []
    for j in range(g):
        if r == 1:
            x = ref[c, first + j].astype(jnp.float32)
        else:
            x = ref[pl.ds(c * r, r), first + j].astype(jnp.float32)
            x = x.reshape(r * t, dh)
        tiles.append(jnp.concatenate([x] + rest, axis=0) if rest else x)
    return tiles


def _put(ref, c, first, tiles, r):
    """Write the g heads' tiles back at the rows' own width, one store
    a head; the tile's zero rows are never written."""
    t, dh = ref.shape[2:]
    for j, x in enumerate(tiles):
        if r == 1:
            ref[c, first + j] = x[:t].astype(ref.dtype)
        else:
            ref[pl.ds(c * r, r), first + j] = (
                x[:r * t].reshape(r, t, dh).astype(ref.dtype))


def _fwd_tile(q, k, v, bias, *, scale, g, bk, dh):
    """One (tile group, head group): q/k/v are the group's g heads
    [bq | bk, dh] f32, bias the tile group's additive mask; returns the
    g outputs [bq, dh] f32."""
    qc = jnp.concatenate(q, axis=1)                   # [bq, g*dh]
    kd = _block_diag(k)                               # [g*bk, g*dh]
    vd = _block_diag(v)
    p2 = _packed_scores(qc, kd, bias, scale, g, bk)
    o2 = jax.lax.dot_general(
        p2, vd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, g*dh]
    return [o2[:, j * dh:(j + 1) * dh] for j in range(g)]


def _bwd_tile(q, k, v, bias, do, o, *, scale, g, bk, dh):
    """One (tile group, head group) of the backward: recomputes the
    packed probs, then runs all four backward dots on full tiles. dp and
    dq reuse the forward's dh-/Tk-contraction packing against the same
    block-diagonal K/V; dk and dv contract the packed [bq, g*bk] probs
    against the lane-concatenated q/do over Tq, which fills the OUTPUT
    tile [g*bk, g*dh] — head j's gradient is its diagonal block.
    Returns (dq, dk, dv), each a list of the g heads' [bq | bk, dh]
    f32."""
    qc = jnp.concatenate(q, axis=1)                   # [bq, g*dh]
    doc = jnp.concatenate(do, axis=1)
    kd = _block_diag(k)                               # [g*bk, g*dh]
    vd = _block_diag(v)
    p2 = _packed_scores(qc, kd, bias, scale, g, bk)

    # dp: [do_0 | do_1] against diag(v_0, v_1) — forward-score geometry
    dp2 = jax.lax.dot_general(
        doc, vd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, g*bk]
    # delta = rowsum(do * o) per query row, in f32, from the tile's own
    # do and out (a [T, 1] f32 operand pads its lane 1 -> 128: twice a
    # bf16 [T, 64] block in VMEM, four times in HBM)
    ds2 = jnp.concatenate(
        [p2[:, j * bk:(j + 1) * bk]
         * (dp2[:, j * bk:(j + 1) * bk]
            - jnp.sum(do[j] * o[j], axis=-1, keepdims=True)) * scale
         for j in range(g)], axis=1)                  # [bq, g*bk]

    # dq: [ds_0 | ds_1] @ diag(k_0, k_1) — forward-apply geometry
    dq2 = jax.lax.dot_general(
        ds2, kd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, g*dh]
    dk2 = jax.lax.dot_general(
        ds2, qc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [g*bk, g*dh]
    dv2 = jax.lax.dot_general(
        p2, doc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq, dk, dv = [], [], []
    for j in range(g):
        rows, cols = slice(j * bk, (j + 1) * bk), slice(j * dh, (j + 1) * dh)
        dq.append(dq2[:, cols])
        dk.append(dk2[rows, cols])
        dv.append(dv2[rows, cols])
    return dq, dk, dv


def _each_tile(q_ref, k_ref, kvm_ref, *, rows, heads, g, r, bq, bk, causal,
               tile):
    """Run tile(c, first_head, bias) over the cell's tile groups (r rows
    each) and head groups: two nested loops whose body is a few
    independent tiles (see _TILES_A_STEP). The position mask is built
    once, the bias once a tile group."""
    groups, some = heads // g, _tiles_a_step(heads, g)
    live = _live_pairs(r, q_ref.shape[2], k_ref.shape[2], bq, bk, causal)

    def group(c, carry):
        bias = _bias(kvm_ref[c], live, bk)

        def step(i, carry):
            for u in range(some):
                tile(c, (i * some + u) * g, bias)
            return carry
        return jax.lax.fori_loop(0, groups // some, step, carry)
    jax.lax.fori_loop(0, rows // r, group, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, *, g, r, bq, bk,
                scale, **cell):
    def tile(c, first, bias):
        o = _fwd_tile(_heads(q_ref, c, first, g, r, bq),
                      _heads(k_ref, c, first, g, r, bk),
                      _heads(v_ref, c, first, g, r, bk), bias,
                      scale=scale, g=g, bk=bk, dh=q_ref.shape[3])
        _put(o_ref, c, first, o, r)
    _each_tile(q_ref, k_ref, kvm_ref, g=g, r=r, bq=bq, bk=bk, tile=tile,
               **cell)


def _bwd_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, o_ref,
                dq_ref, dk_ref, dv_ref, *, g, r, bq, bk, scale, **cell):
    def tile(c, first, bias):
        grads = _bwd_tile(
            _heads(q_ref, c, first, g, r, bq),
            _heads(k_ref, c, first, g, r, bk),
            _heads(v_ref, c, first, g, r, bk), bias,
            _heads(do_ref, c, first, g, r, bq),
            _heads(o_ref, c, first, g, r, bq),
            scale=scale, g=g, bk=bk, dh=q_ref.shape[3])
        for ref, x in zip((dq_ref, dk_ref, dv_ref), grads):
            _put(ref, c, first, x, r)
    _each_tile(q_ref, k_ref, kvm_ref, g=g, r=r, bq=bq, bk=bk, tile=tile,
               **cell)


# ---------------------------------------------------------------------------
# The cell: how many tiles one grid step takes, and how many of them one
# loop step inside it. A tile alone is a chain of small dependent ops that
# costs its latency, ~0.7 us forward on a v5e, whether a grid step or a
# loop step holds it; _TILES_A_STEP independent tiles in one loop body
# overlap (4: 0.37 us a tile; 8 spill and lose it again). How many rows a
# cell takes hardly matters beyond one (PERF.md section 6, PR 26: 1 to 14
# rows read within 1 %), so the budget is a modest one.
# ---------------------------------------------------------------------------

# what Mosaic may use in all (the v5e has 128 MiB; the default scoped
# limit of 16 MiB is why this is explicit) and what the plan gives the
# cell's blocks and the tiles' intermediates of it
_VMEM_LIMIT = 48 << 20
_CELL_BUDGET = 16 << 20
_TILES_A_STEP = 4


def _tiles_a_step(heads, g):
    """Tiles in one loop body: _TILES_A_STEP, or the largest divisor of
    the cell's head groups under it."""
    return math.gcd(heads // g, _TILES_A_STEP)


def _block_vmem(t, dh, itemsize):
    """Bytes of one head's [t, dh] block as Mosaic lays it out in VMEM:
    lanes pad to 128, sublanes to 8 words of 32 bits (16 rows of bf16).
    16 KB for a bf16 [64, 64], 4 KB for a bf16 [8, 64]."""
    return (_round_up(t, _SUBLANES * max(1, 4 // itemsize))
            * _round_up(dh, 128) * itemsize)


def cell_vmem(rows, heads, g, tq, tk, dh, itemsize, backward, r=1):
    """VMEM bytes of a (rows, heads) cell whose tiles hold r rows each:
    every operand's block twice (the pipeline's double buffer) — forward
    q, out | k, v, backward q, do, out, dq | k, v, dk, dv — at the rows'
    own width, the tile groups' [1, r*tk] f32 masks (8 sublanes each),
    and the f32 intermediates of the tiles in one loop body at the
    tile's edges bq x bk ([bq, g*bk] scores and probs, [g*bk, g*dh]
    block diagonals and gradient tiles, [bq, g*dh] packed operands)."""
    n = 4 if backward else 2
    blocks = 2 * n * heads * (_block_vmem(tq, dh, itemsize)
                              + _block_vmem(tk, dh, itemsize))
    mask = 2 * 8 * _round_up(r * tk, 128) * 4
    bq, bk = _round_up(tq, _PAD), _round_up(tk, _PAD)
    wide, tall = _round_up(g * bk, 128), _round_up(g * dh, 128)
    tile = 4 * ((6 if backward else 4) * bq * wide
                + (4 if backward else 2) * g * bk * tall
                + (4 if backward else 2) * bq * tall)
    return (rows * blocks + rows // r * mask
            + _tiles_a_step(heads, g) * tile)


def cell_plan(b, h, tq, tk, dh, itemsize, backward, budget=None, r=1):
    """(rows, heads) of one grid cell, from the call's shapes alone: the
    most rows, all h heads each, whose cell_vmem fits the budget AND
    that divide b AND are whole tiles (a multiple of r, which divides
    b); when not even r rows fit (T toward the cap, where r is 1), r
    rows and as many head groups as fit and divide the heads, down to
    (r, g) — at r 1 the geometry this kernel was first written with.

    Only divisors of b: a ragged last cell (Pallas reads past the batch's
    end and drops the writes) is right in interpret mode and hung the
    v5e twice (PERF.md section 6, PR 26). The trainer's rows are
    multiples of 8, so its cells hold 2 to 16 rows; a prime b is one row
    a cell, which costs no more than the first geometry did."""
    g = pack_group(h, dh)
    budget = _CELL_BUDGET if budget is None else budget

    def fits(rows, heads):
        return cell_vmem(rows, heads, g, tq, tk, dh, itemsize,
                         backward, r) <= budget

    if fits(r, h):
        return max(rows for rows in range(r, b + 1, r)
                   if b % rows == 0 and fits(rows, h)), h
    groups = h // g
    n = max((n for n in range(1, groups + 1)
             if groups % n == 0 and fits(r, n * g)), default=1)
    return r, n * g


def _plan(b, h, tq, tk, dh, itemsize, backward):
    """(rows, heads, r): cell_plan at the call's rows_a_tile, said at
    DEBUG and as the trace event `packed_attention.plan` (once per
    shape: the calls below are traced once per shape). With `train.h2d`'s
    widths the event tells which share of updates folded, and by how
    much."""
    r = rows_a_tile(b, tq, tk)
    rows, heads = cell_plan(b, h, tq, tk, dh, itemsize, backward, r=r)
    groups = h // pack_group(h, dh)
    log.log("debug", "packed_attention{}: b={} h={} tq={} tk={} -> {} rows "
            "a tile, {} of {} tiles, {} rows x {} heads, {} steps",
            "_bwd" if backward else "", b, h, tq, tk, r, b // r * groups,
            b * groups, rows, heads, pl.cdiv(b, rows) * (h // heads))
    obs.event("packed_attention.plan", b=b, tq=tq, tk=tk, backward=backward,
              rows_a_tile=r, tiles=b // r * groups,
              tiles_unfolded=b * groups)
    return rows, heads, r


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _specs(rows, heads, r, tq, tk, dh):
    """Block specs shared by fwd and bwd: a cell is `rows` batch rows x
    `heads` heads, full sequences at the width they come in — the kernel
    owns the short-T regime, so no k-streaming is needed. The grid is
    (b // rows, h // heads); cell_plan's rows divide b. (Rows are
    independent, so on a ragged last cell what is read past the batch's
    end would reach only writes past the end, which Pallas drops — true
    in interpret mode, tests/test_packed_attention.py holds it — but the
    chip hung.)"""
    qspec = pl.BlockSpec((rows, heads, tq, dh), lambda c, hc: (c, hc, 0, 0))
    kspec = pl.BlockSpec((rows, heads, tk, dh), lambda c, hc: (c, hc, 0, 0))
    # the mask rides as [B // r, 1, r * Tk], a tile group's keys side by
    # side, so its block's last two dims equal the array's (the TPU
    # (8, 128) block rule)
    mspec = pl.BlockSpec((rows // r, 1, r * tk), lambda c, hc: (c, 0, 0))
    return qspec, kspec, mspec


def _kernel_and_specs(kernel, q, k, scale, causal, g, backward):
    """The kernel closed over the call's plan, its grid and block specs."""
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    rows, heads, r = _plan(b, h, tq, tk, dh, q.dtype.itemsize, backward)
    kernel = functools.partial(
        kernel, rows=rows, heads=heads, g=g, r=r, scale=scale,
        causal=causal, bq=_round_up(tq, _PAD), bk=_round_up(tk, _PAD))
    return (kernel, (pl.cdiv(b, rows), h // heads),
            *_specs(rows, heads, r, tq, tk, dh))


def _side_by_side(kvm, mspec):
    """[B, 1, Tk] key masks as the kernel reads them: a tile group's r
    rows side by side, [B // r, 1, r * Tk] (a free reshape)."""
    return kvm.reshape(-1, *mspec.block_shape[1:])


# Both calls sit under a jit of their own: a train step calls the kernel 18
# times forward and 18 backward, and JAX then traces and lowers each
# DISTINCT call once (two of each in a step, not 18). A cell's loop body of
# four tiles is four times the first geometry's kernel to trace and lower;
# without this every start of the trainer paid 140 s more for it, compile
# cache warm or not (PERF.md section 6, PR 26).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _fwd_call(q, k, v, kvm, scale, causal, g, interpret):
    kernel, grid, qspec, kspec, mspec = _kernel_and_specs(
        _fwd_kernel, q, k, scale, causal, g, False)
    return pl.pallas_call(
        kernel,
        name="packed_attention_fwd",
        grid=grid,
        in_specs=[qspec, kspec, kspec, mspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, _side_by_side(kvm, mspec))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _bwd_call(q, k, v, kvm, do, out, scale, causal, g, interpret):
    kernel, grid, qspec, kspec, mspec = _kernel_and_specs(
        _bwd_kernel, q, k, scale, causal, g, True)
    return pl.pallas_call(
        kernel,
        name="packed_attention_bwd",
        grid=grid,
        in_specs=[qspec, kspec, kspec, mspec, qspec, qspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, _side_by_side(kvm, mspec), do, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _packed(q, k, v, kvm, scale, causal, g, interpret):
    return _fwd_call(q, k, v, kvm, scale, causal, g, interpret)


def _packed_fwd(q, k, v, kvm, scale, causal, g, interpret):
    out = _fwd_call(q, k, v, kvm, scale, causal, g, interpret)
    return out, (q, k, v, kvm, out)


def _packed_bwd(scale, causal, g, interpret, res, do):
    q, k, v, kvm, out = res
    # the bwd kernel recomputes probs, flash-style, and takes delta =
    # rowsum(do * o) from do and out tile by tile, so no stats ride the
    # residuals
    dq, dk, dv = _bwd_call(q, k, v, kvm, do, out, scale, causal, g,
                           interpret)
    return dq, dk, dv, jnp.zeros_like(kvm)


_packed.defvjp(_packed_fwd, _packed_bwd)


def packed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_mask: Optional[jax.Array] = None,
                     causal: bool = False,
                     scale: Optional[float] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """softmax(scale * Q K^T + mask) V with head-group-packed MXU tiles.

    q [B,H,Tq,Dh], k/v [B,H,Tk,Dh], kv_mask [B,Tk] (1.0 = attend) or
    None. Up to 64 positions the operands go in at their own width (a
    width that is no multiple of 8 pads to one); longer sequences pad to
    multiples of 64. Padded keys are masked out, padded query rows
    sliced off; the custom VJP runs on the padded shapes, so cotangents
    of padded rows are exact zeros.
    """
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    g = pack_group(h, dh)
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    to = _SUBLANES if max(tq, tk) <= _PAD else _PAD
    tq_p, tk_p = _round_up(tq, to), _round_up(tk, to)
    if kv_mask is None:
        kvm = jnp.ones((b, 1, tk), jnp.float32)
    else:
        kvm = kv_mask.astype(jnp.float32).reshape(b, 1, tk)
    if tk_p != tk:
        kvm = jnp.pad(kvm, ((0, 0), (0, 0), (0, tk_p - tk)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
    if tq_p != tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tq_p - tq), (0, 0)))

    out = _packed(q, k, v, kvm, float(scale), bool(causal), g,
                  bool(interpret))
    if tq_p != tq:
        out = out[:, :, :tq, :]
    return out
