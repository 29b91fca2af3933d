"""Fused beam-gather + cache-update + attention read for incremental decode.

The r5 standard-decoder step decomposition (docs/PERFORMANCE.md,
DECODE_ROOFLINE.md r5) put the beam-6 step at ~11.3 ms against a ~1 ms
roofline, dominated by software: the per-layer beam reorder (a flat row
gather of every K/V cache leaf, ~3.1 ms), the single-position
dynamic_update_slice cache writes (~1.1 ms), the attention read over the
cache (~2.1 ms), and a ~690-small-op while body at ~4 us dispatch each.
Three of those four are the SAME cache traffic done three times: gather
(read+write), DUS (read+write), attention (read).

This kernel collapses the sequence into one pass per (row, head): the
beam backpointer gather is folded into the cache READ side (the block
index map reads source row `src_rows[r]` via scalar prefetch), the new
step's K/V is inserted at `pos` in-register, the reordered+updated cache
is written back out ONCE, and the masked attention over positions <= pos
runs on the in-register block. Per layer the while body loses the
separate gather ops (2 leaves), the 2 DUS writes, and the separate
score/softmax/apply chain — the op-COUNT lever the r5 falsification
identified as the real small-batch bottleneck (bench_decode.py reports
the compiled while-body op count to track it).

The beam loop contract moves with it (translator/beam_search.py): the
self-attention caches are no longer reordered after top-k; the chosen
backpointers ride the carry as flat source rows and are applied by the
NEXT step's kernel. Caches lag the beam by one step by construction and
every read goes through the pending map, so the fixpoint is identical.
src_rows=None runs the identity gather — but with nothing to fold, the
full-cache write-back is pure extra HBM traffic vs the unfused
single-position DUS, so 'auto' fuses only when a beam reorder exists
(beam_src passed); greedy/scoring decode takes the kernel only under an
explicit --transformer-fused-decode-attention on (A/Bs, tests).

Shapes: q/k_new/v_new [R,H,1,Dh], cache_k/v [R,H,L,Dh], src_rows [R]
int32, pos scalar int32 -> (out [R,H,1,Dh], new_k, new_v [R,H,L,Dh]).
Inference-only (no VJP). Compute is f32; caches keep their dtype.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import MASK_VALUE, _interpret_default


def _kernel(src_ref, pos_ref, q_ref, kn_ref, vn_ref, ck_ref, cv_ref,
            o_ref, nk_ref, nv_ref, *, scale, max_len):
    # pos is per-row ([R] scalar-prefetch vector — scalar callers are
    # broadcast before the call): rows of different ages can share one
    # step, the contract the paged iteration path (kv_pool.py) relies on
    pos = pos_ref[pl.program_id(0)]
    # the gathered source row arrived via the block index map; fold the
    # new position in and materialize the reordered cache in one write.
    # The insert is a select on a row iota (the TPU lowering has no
    # dynamic_update_slice, and the whole block is rewritten anyway)
    at_pos = jax.lax.broadcasted_iota(jnp.int32, ck_ref.shape[2:], 0) == pos
    kc = jnp.where(at_pos, kn_ref[0, 0].astype(ck_ref.dtype), ck_ref[0, 0])
    vc = jnp.where(at_pos, vn_ref[0, 0].astype(cv_ref.dtype), cv_ref[0, 0])
    nk_ref[0, 0] = kc
    nv_ref[0, 0] = vc
    qv = q_ref[0, 0].astype(jnp.float32)              # [1, dh]
    s = jax.lax.dot_general(
        qv, kc.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [1, L]
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1)
    s = jnp.where(steps <= pos, s, MASK_VALUE)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=1, keepdims=True)         # pos 0 always live
    o = jax.lax.dot_general(
        p, vc.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [1, dh]
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _reference(q, k_new, v_new, cache_k, cache_v, pos, src_rows, scale):
    """Pure-jnp fallback (oversized caches past the VMEM cap): the exact
    unfused sequence the kernel replaces — flat row gather, DUS at pos,
    masked softmax read.
    ``pos`` may be a scalar or a per-row [R] vector."""
    if src_rows is not None:
        cache_k = cache_k[src_rows]
        cache_v = cache_v[src_rows]
    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim == 0:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k_new.astype(cache_k.dtype), (0, 0, pos, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v_new.astype(cache_v.dtype), (0, 0, pos, 0))
        pos_b = pos
    else:
        def dus(c, n, p):
            return jax.lax.dynamic_update_slice(c, n.astype(c.dtype),
                                                (0, p, 0))
        cache_k = jax.vmap(dus)(cache_k, k_new, pos_arr)
        cache_v = jax.vmap(dus)(cache_v, v_new, pos_arr)
        pos_b = pos_arr[:, None, None, None]
    s = jnp.einsum("rhqd,rhkd->rhqk", q.astype(jnp.float32),
                   cache_k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    steps = jnp.arange(cache_k.shape[2])[None, None, None, :]
    s = jnp.where(steps <= pos_b, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rhqk,rhkd->rhqd", p, cache_v.astype(jnp.float32),
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out, cache_k, cache_v


def decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     pos, src_rows: Optional[jax.Array] = None,
                     scale: Optional[float] = None,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused decode-attention step; see module docstring.

    `pos` may be a traced scalar (the decode loop's time index) or a
    per-row [R] vector (iteration-level decoding: rows of different ages
    share one step — the dense comparator for the paged pool path);
    `src_rows` is the pending beam backpointer map as FLAT source rows
    (None = identity, the greedy/scoring case). Returns
    (context [R,H,1,Dh], new_cache_k, new_cache_v).
    """
    r, h, _, dh = q.shape
    max_len = cache_k.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    from ..auto_tuner import decode_attention_max_len
    if max_len > decode_attention_max_len(dh):
        # degrade, don't OOM: a [L, dh] block per grid cell must fit the
        # VMEM budget (auto_tuner scales the cap down for wide heads)
        return _reference(q, k_new, v_new, cache_k, cache_v, pos,
                          src_rows, float(scale))

    if src_rows is None:
        src_rows = jnp.arange(r, dtype=jnp.int32)
    # per-row positions in the scalar-prefetch slot; scalar callers
    # broadcast (bitwise-identical: the kernel reads pos_ref[row])
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (r,))

    import functools
    kernel = functools.partial(_kernel, scale=float(scale),
                               max_len=max_len)
    new_spec = pl.BlockSpec((1, 1, max_len, dh),
                            lambda r_, h_, s, p: (r_, h_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, s, p: (r_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, s, p: (r_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, s, p: (r_, h_, 0, 0)),
            # the fused gather: cache blocks come from the SOURCE row
            pl.BlockSpec((1, 1, max_len, dh),
                         lambda r_, h_, s, p: (s[r_], h_, 0, 0)),
            pl.BlockSpec((1, 1, max_len, dh),
                         lambda r_, h_, s, p: (s[r_], h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, s, p: (r_, h_, 0, 0)),
            new_spec,
            new_spec,
        ],
    )
    out, new_k, new_v = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, h, 1, dh), q.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        interpret=bool(interpret),
    )(src_rows.astype(jnp.int32), pos_arr, q, k_new, v_new,
      cache_k, cache_v)
    return out, new_k, new_v
