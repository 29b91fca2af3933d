"""Blockwise (flash) attention as Pallas TPU kernels.

The reference computes attention as two strided-batched cuBLAS GEMMs with a
materialized [B,H,Tq,Tk] score tensor in between (src/tensors/gpu/prod.cpp ::
ProdBatched + gpu::Softmax); fine for NMT sentence lengths, but the O(L^2)
score tensor becomes the HBM-bandwidth bottleneck for doc-level contexts.
This module computes the same masked softmax(QK^T)V with the online-softmax
recurrence, streaming K/V blocks through VMEM so the score matrix never
touches HBM, with a matching blockwise backward (custom VJP).

Supported masking covers every attention pattern in the model zoo:
  - kv_mask [B, Tk]: key padding mask (1.0 = attend), and/or
  - causal: future mask (query position >= key position).
Attention-weight dropout and returned weights are NOT supported here; the
dispatcher (ops/attention.py :: attention) falls back to the dense path for
those cases.

Shapes: q [B, H, Tq, Dh], k [B, H, Tk, Dh], v [B, H, Tk, Dv] -> out
[B, H, Tq, Dv]: the value width may differ from the key width (latent
attention: keys of 128 + 64, values of 128). Compute is f32 on the MXU regardless of input dtype (bf16 in training).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e9       # additive bias for masked scores (matches ops.NEG_INF)
STATS_INIT = -1e30      # running-max init; NOT -inf so exp() stays finite
_LANES = 128            # TPU lane width; running stats are lane-replicated
# The two residuals of the custom VJP that only the forward kernel can
# produce, by the names a `jax.checkpoint` policy may keep them under
# (jax.checkpoint_policies.save_only_these_names): a checkpoint that keeps
# both runs the backward without running flash_attention_fwd again. Without
# such a policy a name is the identity.
RESIDUAL_OUT = "flash_attention_out"
RESIDUAL_LSE = "flash_attention_lse"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _env_block(name: str, default: int) -> int:
    """Parse a MARIAN_FLASH_BLOCK_* sweep override: positive int, or the
    default with a warning on anything malformed."""
    import os as _os
    raw = _os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        v = int(raw)
        if v <= 0:
            raise ValueError("must be positive")
    except ValueError:
        from ...common import logging as log
        log.warn("{}={!r} is not a positive integer — using the default "
                 "block size {}", name, raw, default)
        return default
    return v


# ---------------------------------------------------------------------------
# Forward kernel: grid (B, H, nq, nk); the k-block axis is innermost and
# sequential on TPU, so running stats live in VMEM scratch across k-blocks.
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                n_k):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, STATS_INIT)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: skip k-blocks that are entirely in the future of this q-block.
    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        kvm = kvm_ref[0, 0].astype(jnp.float32)      # [bk]
        s = s + (1.0 - kvm)[None, :] * MASK_VALUE
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, MASK_VALUE)

        m_prev = m_scr[:, :1]                        # [bq, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, dh]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, dh]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# Backward kernels. Standard flash backward split in two passes:
#   dq : grid (B, H, nq, nk), accumulate over k-blocks
#   dkv: grid (B, H, nk, nq), accumulate over q-blocks
# p is recomputed from (q, k, lse); delta = rowsum(do * o) is precomputed.
# ---------------------------------------------------------------------------

def _recompute_p(q, k, kvm, lse, scale, causal, i, j, block_q, block_k):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # [bq, bk]
    s = s + (1.0 - kvm)[None, :] * MASK_VALUE
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, MASK_VALUE)
    return jnp.exp(s - lse[:, None])                          # [bq, bk]


def _dq_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, causal, block_q, block_k, n_k):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)                 # [bq, dh]
        lse = lse_ref[0, 0, :, 0]                             # [bq]
        delta = delta_ref[0, 0, :, 0]                         # [bq]
        kvm = kvm_ref[0, 0].astype(jnp.float32)
        p = _recompute_p(q, k, kvm, lse, scale, causal, i, j,
                         block_q, block_k)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                block_k, n_q):
    # grid = (B, H, nk, nq): program_id(2) is the k-block, (3) the q-block.
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        kvm = kvm_ref[0, 0].astype(jnp.float32)
        p = _recompute_p(q, k, kvm, lse, scale, causal, i, j,
                         block_q, block_k)                    # [bq, bk]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, dh]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params(n_seq_dims: int = 1):
    """Grid dims (B, H, outer-block) are embarrassingly parallel; only the
    innermost (accumulating) dim is order-dependent."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _fwd_call(q, k, v, kvm, scale, causal, block_q, block_k, interpret):
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    n_q, n_k = tq // block_q, tk // block_k
    grid = (b, h, n_q, n_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, i, j: (b_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm)


def _bwd_call(q, k, v, kvm, do, lse, delta, scale, causal, block_q, block_k,
              interpret):
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    n_q, n_k = tq // block_q, tk // block_k

    dq_kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k, n_k=n_k)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_dq",
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, i, j: (b_, 0, j)),
            pl.BlockSpec((1, 1, block_q, dv), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm, do, lse, delta)

    dkv_kernel = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                                   block_q=block_q, block_k=block_k, n_q=n_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_dkv",
        grid=(b, h, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, j, i: (b_, 0, j)),
            pl.BlockSpec((1, 1, block_q, dv), lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, j, i: (b_, h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk, dh), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP over the padded shapes
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kvm, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, kvm, scale, causal, block_q, block_k,
                       interpret)
    return out


def _flash_fwd(q, k, v, kvm, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, kvm, scale, causal, block_q, block_k,
                         interpret)
    out = checkpoint_name(out, RESIDUAL_OUT)
    # the statistics are kept [B,H,Tq]: a trailing 1 held across layers
    # would be tiled to 128 lanes on the chip, 128 times the bytes
    lse = checkpoint_name(lse[..., 0], RESIDUAL_LSE)
    return out, (q, k, v, kvm, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, kvm, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B,H,Tq,1]
    dq, dk, dv = _bwd_call(q, k, v, kvm, do, lse[..., None], delta, scale,
                           causal, block_q, block_k, interpret)
    return dq, dk, dv, jnp.zeros_like(kvm)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    kv_mask: Optional[jax.Array] = None,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """softmax(scale * Q K^T + mask) V, never materializing the score matrix.

    q [B,H,Tq,Dh], k [B,H,Tk,Dh], v [B,H,Tk,Dv], kv_mask [B,Tk] (1.0 =
    attend) or None; the default scale is 1/sqrt(Dh), the key width.
    Sequence dims are padded up to block multiples internally (padded keys
    are masked out; padded query rows are sliced off).
    """
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    # Default blocks 512/2048, from the r5 silicon sweep at seq 2048
    # (tok/s: 128/128 5,441 · 256/512 13,625 · 512/512 15,373 ·
    # 256/1024 15,929 · **512/2048 18,039** · 1024/2048 VMEM-OOM in the
    # dq kernel at 19.09M vs the 16M scoped stack limit). Bigger k
    # blocks cut online-softmax rescale passes; both clamp to the
    # actual sequence below, so short-seq shapes are unaffected.
    # MARIAN_FLASH_BLOCK_Q/K override at trace time for sweeps; malformed
    # values fall back to the defaults with a warning (this runs at TRACE
    # time — an uncaught ValueError here would take down a whole training
    # job over a typo'd sweep variable).
    if block_q is None:
        block_q = _env_block("MARIAN_FLASH_BLOCK_Q", 512)
    if block_k is None:
        # dq-kernel VMEM scales with block_k x dh and the sweep validated
        # 2048 only at dh=64 — the DEFAULT halves for larger heads so
        # big-head configs don't hit the 1024/2048-style VMEM OOM
        # (advisor finding). Explicit values (arg or a well-formed env
        # override) are respected verbatim — a sweep's recorded block
        # size must be the block size that actually ran.
        default_k = 2048 if dh <= 64 else 1024
        block_k = _env_block("MARIAN_FLASH_BLOCK_K", default_k)

    def _pick_block(limit: int, t: int) -> int:
        # biggest block <= limit whose grid padding wastes <= 25% of t:
        # big blocks cut online-softmax rescale passes (the r5 sweep
        # win), but a 2048 block on t=2176 would pad to 4096 and run
        # the fully-masked blocks through every kernel — padded k/q
        # blocks are NOT skipped (the causal `live` test is
        # position-only)
        b = _round_up(min(limit, _round_up(t, _LANES)), _LANES)
        while b > _LANES:
            if _round_up(t, b) - t <= max(t // 4, _LANES):
                return b
            b = (b // 2 // _LANES) * _LANES
        return _LANES

    bq = _pick_block(block_q, tq)
    bk = _pick_block(block_k, tk)
    tq_p, tk_p = _round_up(tq, bq), _round_up(tk, bk)

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

    out = _flash(q, k, v, kvm, float(scale), bool(causal), bq, bk,
                 bool(interpret))
    if tq_p != tq:
        out = out[:, :, :tq, :]
    return out
