"""Sequence/context parallelism over the 'seq' mesh axis: ring attention and
Ulysses-style all-to-all attention.

The reference has NO sequence parallelism (SURVEY.md §5: max-length 512,
dense O(L²) attention) — this is the TPU-native extension that makes
long-context first-class. Two strategies, both differentiable end-to-end
(JAX transposes ppermute/all_to_all automatically, emitting the reverse
collectives in the backward pass):

- **ring attention** (papers: Ring Attention arXiv:2310.01889; blockwise
  attention arXiv:2305.19370 — PAPERS.md): Q stays put, K/V blocks rotate
  around the 'seq' ring via ppermute; each hop's partial scores fold into a
  running (max, sum, out) flash-style accumulator, so the full [L, L] score
  matrix never materializes and K/V transfers overlap compute hop-by-hop on
  the ICI torus.
- **Ulysses / all-to-all** (DeepSpeed-Ulysses arXiv:2309.14509): all_to_all
  swaps the sharded axis seq↔heads, each device runs dense attention on the
  FULL sequence for H/n heads, then swaps back. Fewer, bigger collectives;
  needs heads % seq_parallelism == 0.

Both take per-device shards (call inside shard_map over a Mesh with a 'seq'
axis); `*_sharded` wrappers handle the shard_map plumbing for full arrays.
Local shapes: q [B, H, Tq/n, Dh], k/v [B, H, Tk/n, Dh], kv_mask [B, Tk/n].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_BIG_NEG = -1e30


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   kv_mask: Optional[jax.Array] = None,
                   causal: bool = False,
                   axis_name: str = "seq") -> jax.Array:
    """Blockwise ring attention over `axis_name`. Exact (same numerics as
    dense softmax attention up to fp error); masked rows return zeros."""
    n = jax.lax.psum(1, axis_name)          # ring size (static at trace time)
    my = jax.lax.axis_index(axis_name)
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qf = q.astype(jnp.float32) * scale

    q_pos = my * tq + jnp.arange(tq)                       # global q positions
    perm = [(i, (i + 1) % n) for i in range(n)]            # rotate K/V blocks

    o = jnp.zeros((b, h, tq, dh), jnp.float32)
    m = jnp.full((b, h, tq), _BIG_NEG, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    blk_mask = (jnp.ones((b, tk), jnp.float32) if kv_mask is None
                else kv_mask.astype(jnp.float32))
    k_blk, v_blk = k, v

    for step in range(n):
        src = (my - step) % n                              # owner of this block
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            k_blk.astype(jnp.float32))     # [B,H,Tq,Tk]
        pmask = blk_mask[:, None, None, :]                 # [B,1,1,Tk]
        if causal:
            k_pos = src * tk + jnp.arange(tk)
            pmask = pmask * (k_pos[None, :] <= q_pos[:, None]
                             ).astype(jnp.float32)[None, None, :, :]
        scores = scores * pmask + (1.0 - pmask) * _BIG_NEG
        blk_max = jnp.max(scores, axis=-1)                 # [B,H,Tq]
        m_new = jnp.maximum(m, blk_max)
        # p <= 1 always (scores <= m_new); multiply by the 0/1 mask so fully
        # masked blocks (where scores == m_new == _BIG_NEG) contribute nothing
        p = jnp.exp(scores - m_new[..., None]) * pmask
        alpha = jnp.exp(m - m_new)                         # rescale old acc
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        m = m_new
        if step < n - 1:                                   # rotate the ring
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            blk_mask = jax.lax.ppermute(blk_mask, axis_name, perm)

    # Fully-masked rows (batch-padding sentences whose mask is all zero) have
    # l == 0; a plain o/max(l,eps) makes the backward compute (1/l)^2 = inf
    # and inf*0 = NaN. Double-where keeps both passes finite: masked rows
    # divide by 1 and are then zeroed, so no inf ever enters the VJP.
    has_mass = (l > 0.0)[..., None]
    safe_l = jnp.where(has_mass, l[..., None], 1.0)
    return jnp.where(has_mass, o / safe_l, 0.0).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      kv_mask: Optional[jax.Array] = None,
                      causal: bool = False,
                      axis_name: str = "seq") -> jax.Array:
    """All-to-all sequence parallelism: reshard seq→heads, dense attention on
    the full sequence per head group, reshard back. heads % n must be 0."""
    from ..ops.attention import dense_attention

    n = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by seq axis ({n})")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    qg = a2a(q, split_axis=1, concat_axis=2)               # [B, H/n, T, Dh]
    kg = a2a(k, split_axis=1, concat_axis=2)
    vg = a2a(v, split_axis=1, concat_axis=2)
    tq = qg.shape[2]
    mask = None
    if kv_mask is not None:
        full = jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
        mask = full[:, None, None, :]                      # [B,1,1,T]
    if causal:
        cm = jnp.tril(jnp.ones((tq, kg.shape[2]), qg.dtype))[None, None]
        mask = cm if mask is None else mask * cm
    out = dense_attention(qg, kg, vg, mask)
    return a2a(out, split_axis=2, concat_axis=1)           # [B, H, T/n, Dh]


def sequence_attention(q, k, v, kv_mask=None, causal=False,
                       axis_name: str = "seq", mode: str = "ring"):
    """Dispatcher used inside shard_map'd model code."""
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[mode]
    return fn(q, k, v, kv_mask=kv_mask, causal=causal, axis_name=axis_name)


# ---------------------------------------------------------------------------
# shard_map wrappers over full (unsharded-view) arrays
# ---------------------------------------------------------------------------

def ring_attention_sharded(mesh: Mesh, q, k, v, kv_mask=None,
                           causal: bool = False, mode: str = "ring"):
    """Run ring/ulysses attention on full [B,H,T,Dh] arrays over `mesh`'s
    'seq' axis (the entry point for long-context encoders; jit-compatible)."""
    if kv_mask is None:
        kv_mask = jnp.ones((k.shape[0], k.shape[2]), jnp.float32)
    # batch rides 'data', heads ride 'model' (TP), time rides 'seq' — all
    # three compose; ring collectives only ever touch the 'seq' axis.
    qkv = P("data", "model", "seq", None)

    def run(q_, k_, v_, mask_):
        return sequence_attention(q_, k_, v_, kv_mask=mask_, causal=causal,
                                  mode=mode)

    return jax.shard_map(
        run, mesh=mesh, in_specs=(qkv, qkv, qkv, P("data", "seq")),
        out_specs=qkv, check_vma=False)(q, k, v, kv_mask)
