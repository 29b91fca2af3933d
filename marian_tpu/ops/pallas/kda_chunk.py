"""The state carry of the chunked delta rule (ops/kda.py) as Pallas TPU
kernels: `kda_chunk_fwd` and `kda_chunk_bwd` under one custom VJP.

A chunk of 64 positions becomes six small matrices (the WY / UT
transform: on a TPU kda_prep.py's `kda_prep_fwd` / `kda_prep_bwd` beside
this file, elsewhere ops/kda.py :: chunk_terms in XLA; either way they
reach this kernel through HBM in float32); what is left is a recurrence
over the chunks of one (row, head), each step three dependent matmuls on
a [dv, dk] float32 state:

    U   = wv - wk St^T              [C, dv]
    O   = qg St^T + p U             [C, dv]
    St' = gc * St + U^T kd          [dv, dk]

The state is kept TRANSPOSED ([dv, dk]) so that the per-channel decay gc
[1, dk] scales lanes and every product is one of the MXU's natural
forms (A B, A B^T, A^T B) with no relayout. The grid is (rows, head
groups, chunks), chunks innermost and sequential; the state lives in a
VMEM scratch across them. A grid step takes `heads` heads of one chunk
and walks them in one unrolled body: a step's chain of three dependent
matmuls is latency, and independent chains side by side hide it (the
lesson of packed_attention's tiles). Every dimension of the grid is an
exact quotient (`heads` divides H): no ragged last cell.

The forward writes the state each chunk STARTED from, [B, H, N, dv, dk]
float32, for the backward, which walks the chunks in reverse with the
cotangent of the state in scratch:

    dU   = p^T dO + kd dSt^T
    dqg  = dO St            dp  = dO U^T         dkd = U dSt
    dwv  = dU               dwk = -dU St         dgc = sum_v St * dSt
    dSt' = dO^T qg + gc * dSt - dU^T wk

Both calls sit under a jit of their own, so that the step's trace holds
one call of each and not their bodies (PERF.md, PR 26).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_A_STEP = 4


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def heads_a_step(h: int, want: int = HEADS_A_STEP) -> int:
    """The largest divisor of h that is at most `want`."""
    return max(d for d in range(1, min(h, want) + 1) if h % d == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_AB = ((1,), (0,))       # a @ b
_ABT = ((1,), (1,))      # a @ b.T
_ATB = ((0,), (0,))      # a.T @ b


def _fwd_kernel(qg_ref, wk_ref, wv_ref, kd_ref, gc_ref, p_ref,
                o_ref, s_ref, st_scr, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[:] = jnp.zeros_like(st_scr)

    for i in range(heads):
        st = st_scr[i]                                   # [dv, dk]
        s_ref[0, i, 0] = st
        u = wv_ref[0, i, 0] - _dot(wk_ref[0, i, 0], st, _ABT)
        o_ref[0, i, 0] = _dot(qg_ref[0, i, 0], st, _ABT) \
            + _dot(p_ref[0, i, 0], u, _AB)
        st_scr[i] = gc_ref[0, i, 0] * st + _dot(u, kd_ref[0, i, 0], _ATB)


def _bwd_kernel(qg_ref, wk_ref, wv_ref, kd_ref, gc_ref, p_ref, s_ref, do_ref,
                dqg_ref, dwk_ref, dwv_ref, dkd_ref, dgc_ref, dp_ref,
                dst_scr, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[:] = jnp.zeros_like(dst_scr)

    for i in range(heads):
        st, dst = s_ref[0, i, 0], dst_scr[i]             # [dv, dk]
        do, wk, kd = do_ref[0, i, 0], wk_ref[0, i, 0], kd_ref[0, i, 0]
        u = wv_ref[0, i, 0] - _dot(wk, st, _ABT)
        du = _dot(p_ref[0, i, 0], do, _ATB) + _dot(kd, dst, _ABT)
        dqg_ref[0, i, 0] = _dot(do, st, _AB)
        dp_ref[0, i, 0] = _dot(do, u, _ABT)
        dkd_ref[0, i, 0] = _dot(u, dst, _AB)
        dwv_ref[0, i, 0] = du
        dwk_ref[0, i, 0] = -_dot(du, st, _AB)
        dgc_ref[0, i, 0] = jnp.sum(st * dst, axis=0, keepdims=True)
        dst_scr[i] = _dot(do, qg_ref[0, i, 0], _ATB) \
            + gc_ref[0, i, 0] * dst - _dot(du, wk, _ATB)


def _spec(heads, rows, cols, reverse_of=None):
    """One chunk of `heads` heads: block (1, heads, 1, rows, cols) of a
    [B, H, N, rows, cols] array; `reverse_of` = N walks the chunks from
    the last to the first."""
    if reverse_of is None:
        return pl.BlockSpec((1, heads, 1, rows, cols),
                            lambda b, h, n: (b, h, n, 0, 0))
    return pl.BlockSpec((1, heads, 1, rows, cols),
                        lambda b, h, n: (b, h, reverse_of - 1 - n, 0, 0))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _fwd_call(qg, wk, wv, kd, gc, p, heads, interpret):
    bsz, h, n, c, dk = qg.shape
    dv = wv.shape[-1]
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        name="kda_chunk_fwd",
        grid=(bsz, h // heads, n),
        in_specs=[_spec(heads, c, dk), _spec(heads, c, dk),
                  _spec(heads, c, dv), _spec(heads, c, dk),
                  _spec(heads, 1, dk), _spec(heads, c, c)],
        out_specs=[_spec(heads, c, dv), _spec(heads, dv, dk)],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, n, c, dv), f32),
                   jax.ShapeDtypeStruct((bsz, h, n, dv, dk), f32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), f32)],
        interpret=interpret,
        compiler_params=None if interpret else _params(),
    )(qg, wk, wv, kd, gc, p)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _bwd_call(qg, wk, wv, kd, gc, p, states, do, heads, interpret):
    bsz, h, n, c, dk = qg.shape
    dv = wv.shape[-1]
    f32 = jnp.float32
    back = functools.partial(_spec, heads, reverse_of=n)
    shape = lambda rows, cols: jax.ShapeDtypeStruct(   # noqa: E731
        (bsz, h, n, rows, cols), f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        name="kda_chunk_bwd",
        grid=(bsz, h // heads, n),
        in_specs=[back(c, dk), back(c, dk), back(c, dv), back(c, dk),
                  back(1, dk), back(c, c), back(dv, dk), back(c, dv)],
        out_specs=[back(c, dk), back(c, dk), back(c, dv), back(c, dk),
                   back(1, dk), back(c, c)],
        out_shape=[shape(c, dk), shape(c, dk), shape(c, dv), shape(c, dk),
                   shape(1, dk), shape(c, c)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), f32)],
        interpret=interpret,
        compiler_params=None if interpret else _params(),
    )(qg, wk, wv, kd, gc, p, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _carry(qg, wk, wv, kd, gc, p, heads, interpret):
    return _fwd_call(qg, wk, wv, kd, gc, p, heads, interpret)[0]


def _carry_fwd(qg, wk, wv, kd, gc, p, heads, interpret):
    o, states = _fwd_call(qg, wk, wv, kd, gc, p, heads, interpret)
    return o, (qg, wk, wv, kd, gc, p, states)


def _carry_bwd(heads, interpret, res, do):
    return tuple(_bwd_call(*res, do, heads, interpret))


_carry.defvjp(_carry_fwd, _carry_bwd)


def kda_state_carry(qg, wk, wv, kd, gc, p, heads=None, interpret=None):
    """ops/kda.py :: state_carry on the chip: the six chunk terms
    [B, H, N, ...] float32 -> O [B, H, N, C, dv] float32."""
    if interpret is None:
        interpret = _interpret_default()
    terms = tuple(x.astype(jnp.float32) for x in (qg, wk, wv, kd, gc, p))
    return _carry(*terms, heads_a_step(qg.shape[1], heads or HEADS_A_STEP),
                  bool(interpret))
