"""Head-packed attention as a Pallas TPU kernel — the short-sequence MXU fix.

The r5 GEMM truth table (docs/PERFORMANCE.md) measured the attention
score/apply einsums at 21.7%/30.6% of MXU peak at bench shapes: a dh=64
contraction fills only half the 128-deep systolic array, and a T=48-64
output fills only ~37-50% of its lanes, so XLA's per-(b,h) batched dot
burns a full 128x128 tile pass per head while using ~a fifth of it. No
XLA flag changes tile geometry (the TVM line of work, PAPERS.md, shows
graph compilers don't recover this class automatically) — the fix is to
PACK head groups into one full tile, which this kernel does with
block-diagonal operand packing:

  scores, per group of g = 128//dh heads (g=2 at dh=64):
      [Tq, g*dh] = [q_0 | q_1]          (heads concatenated on contraction)
      [g*dh, g*Tk] = diag(k_0^T, k_1^T) (block-diagonal keys)
      one dot -> [Tq, g*Tk] = [s_0 | s_1]: contraction g*dh = 128 (full
      sublanes), output g*Tk ~ 128 (full lanes)
  apply:
      [Tq, g*Tk] = [p_0 | p_1]  @  diag(v_0, v_1) [g*Tk, g*dh]
      -> [Tq, g*dh] = [o_0 | o_1]: contraction g*Tk = 128, output 128.

The zero blocks double the nominal FLOPs, but the MXU pays per tile PASS,
not per useful FLOP: two heads per pass at full geometry vs one head per
pass at ~22% is the win (analytic ~2.3x on the score dot; not measured
on the chip). The custom VJP keeps full tiles in all four backward dots:
dp/dq pack the dh- and Tk-contractions exactly like the forward against
the same block-diagonal K/V, dk/dv contract the packed probs against the
lane-concatenated q/do over Tq and read each head's gradient off the
diagonal block of the [g*Tk, g*dh] output tile.

This kernel owns the T <= packed-cap regime (NMT sentence lengths);
flash_attention.py owns the long-sequence end. Same structured-mask
interface as flash: kv_mask [B, Tk] (1.0 = attend) and/or causal.
Attention dropout and returned weights fall back to the dense path via
the dispatcher (ops/attention.py).

Shapes: q [B,H,Tq,Dh], k/v [B,H,Tk,Dh] -> out [B,H,Tq,Dh]. Compute is
f32 on the MXU regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import MASK_VALUE, _interpret_default, _round_up

# Sequence dims pad to multiples of 64 so a g=2 pack lands on exactly
# 128 lanes/sublanes (the MXU tile edge); g>2 packs (dh 32/16) land on
# multiples of it.
_PAD = 64


def pack_group(heads: int, dh: int) -> int:
    """Heads per MXU tile: the largest divisor of `heads` with
    g*dh <= 128. g=1 means packing buys nothing (dh > 64)."""
    g = max(1, 128 // max(dh, 1))
    while g > 1 and heads % g:
        g -= 1
    return g


def _causal_rows(bq: int, bk: int):
    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


def _block_diag(blocks):
    """diag(x_0 .. x_{g-1}) of equal [r, c] blocks -> [g*r, g*c], built
    from concatenations with zero blocks (the TPU lowering has no
    dynamic_update_slice)."""
    g = len(blocks)
    zero = jnp.zeros_like(blocks[0])
    return jnp.concatenate(
        [jnp.concatenate([blocks[j] if i == j else zero for i in range(g)],
                         axis=1) for j in range(g)], axis=0)


def _packed_scores(qc, kd, kvm, scale, causal, g, bq, bk):
    """The packed score dot + per-head mask/softmax. qc is the group's
    queries concatenated on the contraction [bq, g*dh], kd the
    block-diagonal keys [g*bk, g*dh], kvm the [1, bk] key mask; returns
    the packed probs [bq, g*bk] f32."""
    s2 = jax.lax.dot_general(
        qc, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [bq, g*bk]
    bias = (1.0 - kvm) * MASK_VALUE                   # [1, bk]
    live = _causal_rows(bq, bk) if causal else None
    ps = []
    for j in range(g):
        s = s2[:, j * bk:(j + 1) * bk] + bias         # static lane slice
        if causal:
            s = jnp.where(live, s, MASK_VALUE)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        # l >= 1 always (the row-max key contributes exp(0) even on a
        # fully-masked row, which then yields UNIFORM probs — exactly
        # the dense path's softmax-of-all-MASK behavior, and callers
        # discard those rows), so no zero-divisor guard is needed
        l = jnp.sum(p, axis=1, keepdims=True)
        ps.append(p / l)
    return jnp.concatenate(ps, axis=1)


def _group(ref, g):
    return [ref[0, j].astype(jnp.float32) for j in range(g)]


def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, *, scale, causal, g,
                bq, bk, dh):
    qc = jnp.concatenate(_group(q_ref, g), axis=1)    # [bq, g*dh]
    kd = _block_diag(_group(k_ref, g))                # [g*bk, g*dh]
    vd = _block_diag(_group(v_ref, g))
    p2 = _packed_scores(qc, kd, kvm_ref[0], scale, causal, g, bq, bk)
    o2 = jax.lax.dot_general(
        p2, vd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, g*dh]
    for j in range(g):
        o_ref[0, j] = o2[:, j * dh:(j + 1) * dh].astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, scale, causal, g, bq, bk, dh):
    """One pass per (b, head-group): recomputes the packed probs, then
    runs all four backward dots on full tiles. dp and dq reuse the
    forward's dh-/Tk-contraction packing against the same block-diagonal
    K/V; dk and dv contract the packed [bq, g*bk] probs against the
    lane-concatenated q/do over Tq, which fills the OUTPUT tile
    [g*bk, g*dh] — head j's gradient is its diagonal block."""
    qc = jnp.concatenate(_group(q_ref, g), axis=1)    # [bq, g*dh]
    doc = jnp.concatenate(_group(do_ref, g), axis=1)
    kd = _block_diag(_group(k_ref, g))                # [g*bk, g*dh]
    vd = _block_diag(_group(v_ref, g))
    p2 = _packed_scores(qc, kd, kvm_ref[0], scale, causal, g, bq, bk)

    # dp: [do_0 | do_1] against diag(v_0, v_1) — forward-score geometry
    dp2 = jax.lax.dot_general(
        doc, vd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, g*bk]
    ds2 = jnp.concatenate(
        [p2[:, j * bk:(j + 1) * bk]
         * (dp2[:, j * bk:(j + 1) * bk] - delta_ref[0, j]) * scale
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
    for j in range(g):
        rows, cols = slice(j * bk, (j + 1) * bk), slice(j * dh, (j + 1) * dh)
        dq_ref[0, j] = dq2[:, cols].astype(dq_ref.dtype)
        dk_ref[0, j] = dk2[rows, cols].astype(dk_ref.dtype)
        dv_ref[0, j] = dv2[rows, cols].astype(dv_ref.dtype)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))


def _specs(b, g, tq, tk, dh):
    """Block specs shared by fwd and bwd: one (batch, head-group) cell
    per grid point, full (padded) sequences per cell — the kernel owns
    the short-T regime, so no k-streaming is needed."""
    qspec = pl.BlockSpec((1, g, tq, dh), lambda b_, hg: (b_, hg, 0, 0))
    kspec = pl.BlockSpec((1, g, tk, dh), lambda b_, hg: (b_, hg, 0, 0))
    # the mask rides as [B, 1, Tk] so its block's last two dims equal the
    # array's (the TPU (8, 128) block rule)
    mspec = pl.BlockSpec((1, 1, tk), lambda b_, hg: (b_, 0, 0))
    return qspec, kspec, mspec


def _fwd_call(q, k, v, kvm, scale, causal, g, interpret):
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    qspec, kspec, mspec = _specs(b, g, tq, tk, dh)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               g=g, bq=tq, bk=tk, dh=dh)
    return pl.pallas_call(
        kernel,
        name="packed_attention_fwd",
        grid=(b, h // g),
        in_specs=[qspec, kspec, kspec, mspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, dh), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm)


def _bwd_call(q, k, v, kvm, do, delta, scale, causal, g, interpret):
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    qspec, kspec, mspec = _specs(b, g, tq, tk, dh)
    dspec = pl.BlockSpec((1, g, tq, 1), lambda b_, hg: (b_, hg, 0, 0))
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               g=g, bq=tq, bk=tk, dh=dh)
    return pl.pallas_call(
        kernel,
        name="packed_attention_bwd",
        grid=(b, h // g),
        in_specs=[qspec, kspec, kspec, mspec, qspec, dspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, dh), q.dtype),
            jax.ShapeDtypeStruct((b, h, tk, dh), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, dh), v.dtype),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm, do, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _packed(q, k, v, kvm, scale, causal, g, interpret):
    return _fwd_call(q, k, v, kvm, scale, causal, g, interpret)


def _packed_fwd(q, k, v, kvm, scale, causal, g, interpret):
    out = _fwd_call(q, k, v, kvm, scale, causal, g, interpret)
    return out, (q, k, v, kvm, out)


def _packed_bwd(scale, causal, g, interpret, res, do):
    q, k, v, kvm, out = res
    # delta = rowsum(do * o) per (b,h,row) — cheap elementwise outside
    # the kernel (the bwd kernel recomputes probs, flash-style, so no
    # stats ride the residuals)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)           # [B,H,Tq,1]
    dq, dk, dv = _bwd_call(q, k, v, kvm, do, delta, scale, causal, g,
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
    None. Sequence dims pad internally to multiples of 64 (padded keys
    masked out, padded query rows sliced off; the custom VJP runs on the
    padded shapes, so cotangents of padded rows are exact zeros).
    """
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    g = pack_group(h, dh)
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    tq_p, tk_p = _round_up(tq, _PAD), _round_up(tk, _PAD)
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
