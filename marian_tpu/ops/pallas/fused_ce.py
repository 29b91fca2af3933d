"""Fused (streaming) softmax cross-entropy over the output vocabulary as
Pallas TPU kernels — the training-side counterpart of flash attention.

The reference materializes [B,T,V] logits and a second [B,T,V] log-softmax
(src/tensors/gpu/tensor_operators.cu :: LogSoftmax + CrossEntropyPick); at
V=32k and memory-filling batches those two f32 tensors (and their gradients)
dominate HBM traffic — the round-1 profile showed the logits/CE chain as the
largest per-token cost of the train step. This module computes the output
projection and the label-smoothed CE in one pass: vocab blocks of the logits
matmul are formed in VMEM, reduced online (running max / sum-exp / label
gather / logit sum), and never written to HBM. The backward recomputes logits
blockwise (two passes: d-hidden, then d-table/d-bias) exactly like the flash
attention backward.

The VJP boundary is the per-token stats triple

    lse_i = logsumexp_v(logits_iv)      (running max + sum-exp)
    lab_i = logits_i[label_i]           (label logit)
    tot_i = sum_v logits_iv             (for the label-smoothing mean)

from which the caller composes Marian's smoothed CE
    ce_i = (1-eps) * (lse_i - lab_i) + eps * (lse_i - tot_i / V)
in plain (cheap, [N]-shaped) jnp; d logits = g_lse * softmax
+ g_lab * onehot + g_tot is formed blockwise in the backward kernels.

Shapes: x [N, E] hidden states, w [V, E] output table (tied embedding
orientation; logits = x @ w.T + b), b [V], labels [N]. Compute is f32 on the
MXU regardless of input dtype (bf16 in training), matching the dense path's
`preferred_element_type=float32` discipline.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e9       # bias for padded vocab rows: exp() == 0 in f32
STATS_INIT = -1e30
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _compiler_params():
    # Large-ish blocks (the vocab table is re-streamed once per token block,
    # so bigger token blocks cut HBM traffic) need more than the default
    # 16MB scoped-VMEM allowance; v5e/v4 have 128MB physical VMEM.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


# ---------------------------------------------------------------------------
# Forward: grid (n_n, n_v); the vocab axis is innermost and sequential, so
# the running stats live in VMEM scratch across vocab blocks.
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, labl_ref, tot_ref,
                m_scr, s_scr, g_scr, t_scr, *, block_v, n_v, v_real):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, STATS_INIT)
        s_scr[:] = jnp.zeros_like(s_scr)
        g_scr[:] = jnp.zeros_like(g_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    x = x_ref[...]                                     # [bn, E] native dtype
    w = w_ref[...]                                     # [bv, E]
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bn, bv] f32 accum
    logits = logits + b_ref[...].astype(jnp.float32)

    bn, bv = logits.shape
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    valid = cols < v_real                               # padded vocab rows
    logits = jnp.where(valid, logits, MASK_VALUE)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    s_scr[:] = jnp.broadcast_to(
        alpha * s_scr[:, :1]
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True),
        s_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    labels = lab_ref[...][:, :1]                       # [bn, 1] int32
    onehot = (cols == labels).astype(jnp.float32)
    g_scr[:] = g_scr[:] + jnp.broadcast_to(
        jnp.sum(logits * onehot, axis=1, keepdims=True), g_scr.shape)
    t_scr[:] = t_scr[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(valid, logits, 0.0), axis=1, keepdims=True),
        t_scr.shape)

    @pl.when(j == n_v - 1)
    def _finalize():
        s = s_scr[:, :1]
        s_safe = jnp.where(s == 0.0, 1.0, s)
        lse_ref[...] = m_scr[:, :1] + jnp.log(s_safe)
        labl_ref[...] = g_scr[:, :1]
        tot_ref[...] = t_scr[:, :1]


# ---------------------------------------------------------------------------
# Backward. d logits_ij = g_lse_i * P_ij + g_lab_i * onehot_ij + g_tot_i
# with P_ij = exp(logits_ij - lse_i); logits are recomputed blockwise.
# Two passes with opposite grid nesting (cf. flash attention backward):
#   dx     : grid (n_n, n_v), accumulate over vocab blocks
#   dw, db : grid (n_v, n_n), accumulate over token blocks
# ---------------------------------------------------------------------------

def _dlogits(x, w, b, labels, lse, g_lse, g_lab, g_tot, j, block_v, v_real):
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    logits = logits + b[None, :]  # b [bv]
    bn, bv = logits.shape
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    valid = cols < v_real
    p = jnp.exp(jnp.where(valid, logits, MASK_VALUE) - lse)
    onehot = (cols == labels).astype(jnp.float32)
    d = g_lse * p + g_lab * onehot + jnp.where(valid, g_tot, 0.0)
    return d                                            # [bn, bv] f32


def _dx_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, gl_ref, gg_ref, gt_ref,
               dx_ref, dx_scr, *, block_v, n_v, v_real):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    x = x_ref[...]
    w = w_ref[...]
    d = _dlogits(x, w, b_ref[...].astype(jnp.float32)[0],
                 lab_ref[...][:, :1], lse_ref[...][:, :1],
                 gl_ref[...][:, :1], gg_ref[...][:, :1], gt_ref[...][:, :1],
                 j, block_v, v_real)
    dx_scr[:] = dx_scr[:] + jax.lax.dot_general(
        d.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # [bn, E]

    @pl.when(j == n_v - 1)
    def _finalize():
        dx_ref[...] = dx_scr[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, gl_ref, gg_ref, gt_ref,
               dw_ref, db_ref, dw_scr, db_scr, *, block_v, n_n, v_real):
    # grid (n_v, n_n): program_id(0) is the vocab block, (1) the token block.
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    x = x_ref[...]
    w = w_ref[...]
    d = _dlogits(x, w, b_ref[...].astype(jnp.float32)[0],
                 lab_ref[...][:, :1], lse_ref[...][:, :1],
                 gl_ref[...][:, :1], gg_ref[...][:, :1], gt_ref[...][:, :1],
                 j, block_v, v_real)
    dw_scr[:] = dw_scr[:] + jax.lax.dot_general(
        d.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # [bv, E]
    db_scr[:] = db_scr[:] + jnp.broadcast_to(
        jnp.sum(d, axis=0)[:, None], db_scr.shape)      # [bv, LANES]

    @pl.when(i == n_n - 1)
    def _finalize():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[...] = db_scr[:, :1].astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing over padded [N, E] / [V, E]
# ---------------------------------------------------------------------------

def _fwd_call(x, w, b, labels, block_n, block_v, v_real, interpret):
    n, e = x.shape
    v = w.shape[0]
    n_n, n_v = n // block_n, v // block_v
    kernel = functools.partial(_fwd_kernel, block_v=block_v, n_v=n_v,
                               v_real=v_real)
    return pl.pallas_call(
        kernel,
        name="fused_ce_fwd",
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, e), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((block_n, _LANES), jnp.float32)
                        for _ in range(4)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(x, w, b, labels)


def _bwd_call(x, w, b, labels, lse, g_lse, g_lab, g_tot,
              block_n, block_v, v_real, interpret):
    n, e = x.shape
    v = w.shape[0]
    n_n, n_v = n // block_n, v // block_v

    tok = lambda i, j: (i, 0)        # noqa: E731
    voc = lambda i, j: (j, 0)        # noqa: E731
    in_specs = [
        pl.BlockSpec((block_n, e), tok),
        pl.BlockSpec((block_v, e), voc),
        pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
        pl.BlockSpec((block_n, 1), tok),
        pl.BlockSpec((block_n, 1), tok),
        pl.BlockSpec((block_n, 1), tok),
        pl.BlockSpec((block_n, 1), tok),
        pl.BlockSpec((block_n, 1), tok),
    ]
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_v=block_v, n_v=n_v,
                          v_real=v_real),
        name="fused_ce_dx",
        grid=(n_n, n_v),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, e), tok),
        out_shape=jax.ShapeDtypeStruct((n, e), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, e), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(x, w, b, labels, lse, g_lse, g_lab, g_tot)

    # token/vocab block roles swap in the index maps for the second pass
    tok2 = lambda j, i: (i, 0)       # noqa: E731
    voc2 = lambda j, i: (j, 0)       # noqa: E731
    in_specs2 = [
        pl.BlockSpec((block_n, e), tok2),
        pl.BlockSpec((block_v, e), voc2),
        pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        pl.BlockSpec((block_n, 1), tok2),
        pl.BlockSpec((block_n, 1), tok2),
        pl.BlockSpec((block_n, 1), tok2),
        pl.BlockSpec((block_n, 1), tok2),
        pl.BlockSpec((block_n, 1), tok2),
    ]
    dw, db = pl.pallas_call(
        functools.partial(_dw_kernel, block_v=block_v, n_n=n_n,
                          v_real=v_real),
        name="fused_ce_dw",
        grid=(n_v, n_n),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((block_v, e), voc2),
            pl.BlockSpec((block_v, 1), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((v, e), w.dtype),
            jax.ShapeDtypeStruct((v, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_v, e), jnp.float32),
                        pltpu.VMEM((block_v, _LANES), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(x, w, b, labels, lse, g_lse, g_lab, g_tot)
    return dx, dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _stats(x, w, b, labels, block_n, block_v, v_real, interpret):
    lse, lab, tot = _fwd_call(x, w, b, labels, block_n, block_v, v_real,
                              interpret)
    return lse[:, 0], lab[:, 0], tot[:, 0]


def _stats_fwd(x, w, b, labels, block_n, block_v, v_real, interpret):
    lse, lab, tot = _fwd_call(x, w, b, labels, block_n, block_v, v_real,
                              interpret)
    return (lse[:, 0], lab[:, 0], tot[:, 0]), (x, w, b, labels, lse)


def _stats_bwd(block_n, block_v, v_real, interpret, res, gs):
    x, w, b, labels, lse = res
    g_lse, g_lab, g_tot = (g[:, None] for g in gs)
    dx, dw, db = _bwd_call(x, w, b, labels, lse, g_lse, g_lab, g_tot,
                           block_n, block_v, v_real, interpret)
    return dx, dw, db[:, 0][None, :].astype(b.dtype), None


_stats.defvjp(_stats_fwd, _stats_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _pick_block_v(v: int, cap: int = 2048) -> Optional[int]:
    """Largest multiple of the lane width that divides v (no padding), else
    None (caller pads). 32000 → 1280; 32768 → 2048; 256 → 256."""
    best = None
    for bv in range(_LANES, cap + 1, _LANES):
        if v % bv == 0:
            best = bv
    return best


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def fused_available(e: int, interpret: Optional[bool] = None) -> bool:
    """Compiled-mode kernels need a lane-aligned hidden dim; interpret mode
    (CPU tests) takes anything."""
    if interpret is None:
        interpret = _interpret_default()
    return interpret or (e % _LANES == 0)


def fused_softmax_xent(x: jax.Array, w: jax.Array, b: jax.Array,
                       labels: jax.Array,
                       label_smoothing: float = 0.0,
                       block_n: int = 1024, block_v: int = 2048,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Per-token label-smoothed CE of logits = x @ w.T + b, streaming over
    vocab blocks (never materializing [N, V]).

    x [N, E] (any float dtype; matmuls accumulate f32), w [V, E], b [V],
    labels [N] int → ce [N] f32:
        ce = (1-eps) * (lse - logits[label]) + eps * (lse - mean_v logits)
    which equals ops.cross_entropy(logits, labels, eps) exactly (same
    algebra: -logP(y) = lse - logit_y; -mean_v logP(v) = lse - mean_v logit_v).

    Gradients flow to x, w, b via blockwise-recomputing backward kernels.
    """
    n, e = x.shape
    v = w.shape[0]
    if interpret is None:
        interpret = _interpret_default()

    bv = _pick_block_v(v, block_v)
    if bv is None:
        v_pad = _round_up(v, block_v)
        w = jnp.pad(w, ((0, v_pad - v), (0, 0)))
        b = jnp.pad(b, (0, v_pad - v), constant_values=MASK_VALUE)
        bv = block_v
    bn = min(block_n, _round_up(n, _LANES))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n))

    labels2 = labels.astype(jnp.int32)[:, None]
    b2 = b.reshape(1, -1).astype(jnp.float32)
    lse, lab, tot = _stats(x, w, b2, labels2, bn, bv, v, bool(interpret))

    eps = float(label_smoothing)
    nll = lse - lab
    if eps > 0.0:
        ce = (1.0 - eps) * nll + eps * (lse - tot / float(v))
    else:
        ce = nll
    return ce[:n]
