"""Blockwise (flash) attention as Pallas TPU kernels.

The reference computes attention as two strided-batched cuBLAS GEMMs with a
materialized [B,H,Tq,Tk] score tensor in between (src/tensors/gpu/prod.cpp ::
ProdBatched + gpu::Softmax); fine for NMT sentence lengths, but the O(L^2)
score tensor becomes the HBM-bandwidth bottleneck for doc-level contexts.
This module computes the same masked softmax(QK^T)V with the online-softmax
recurrence, streaming K/V blocks through VMEM so the score matrix never
touches HBM, with a matching blockwise backward (custom VJP).

Masks the kernels take (any other pattern, e.g. an arbitrary dense
[Tq, Tk] mask, goes through the dense path):
  - kv_mask [B, Tk]: key padding mask (1.0 = attend), and/or
  - a RULE over (query index, key index), the `causal` argument:
      True               the future mask, key index <= query index
      BlockDiffusion(T, block)   block-diffusion training over a doubled
                         row [noised ; clean] of 2T positions (see the
                         class)
      Window(W)          a sliding window: the last W keys up to the
                         query's own, i - W < key index <= i (see the
                         class)
    From a rule the three kernels take which tiles are live and the mask
    inside a live tile. A dead tile is neither computed nor fetched: a
    dead step's block index is clamped to a resident live tile, so the
    pipeline fetches nothing new for it.
Attention-weight dropout and returned weights are NOT supported here; the
dispatcher (ops/attention.py :: attention) falls back to the dense path for
those cases.

Shapes: q [B, H, Tq, Dh], k [B, Hkv, Tk, Dh], v [B, Hkv, Tk, Dv] -> out
[B, H, Tq, Dv]: the value width may differ from the key width (latent
attention: keys of 128 + 64, values of 128), and H may be a multiple of
Hkv (grouped-query heads: query head h reads key/value head
h // (H / Hkv); the dkv kernel sums a group's query heads inside the
kernel). Compute is f32 on the MXU regardless of input dtype (bf16 in
training).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import obs

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
# The rule over (query index, key index): which pairs see each other, which
# tiles hold such a pair, and which tile stays resident over a dead step.
# ---------------------------------------------------------------------------

class BlockDiffusion(NamedTuple):
    """Block-diffusion training (arXiv:2503.09573) over a doubled row of
    2 * `length` positions: index p < length is the NOISED copy of position
    p, index length + p its CLEAN copy; a position's block is p // block.
    A query sees a key iff
      both are noised and in the same block, or
      the query is noised, the key clean and of an EARLIER block, or
      both are clean and the key's block is the query's or earlier;
    a clean query sees no noised key. Queries and keys are the same 2 *
    length indices (self-attention)."""
    length: int
    block: int


class Window(NamedTuple):
    """A sliding window over a row (self-attention, Tq = Tk): the query at
    index i sees the key at index j iff i - window < j <= i, the `window`
    keys that end at its own. Two edges cut tiles, the diagonal and the
    trailing edge `window` behind it; the live key tiles of a query tile
    are ONE run that starts behind the tile, not at 0, with dead tiles on
    both sides. A row no longer than the window is a causal row, and
    `flash_attention` runs it as one."""
    window: int


Rule = Union[bool, BlockDiffusion, Window]


def _block_of(pos, block: int):
    shift = block.bit_length() - 1
    return pos >> shift if block == 1 << shift else pos // block


def rule_mask(rule: Union[BlockDiffusion, Window], qpos, kpos):
    """Boolean: may the query at index qpos see the key at index kpos.
    int32 arrays that broadcast against each other: a column of queries
    against a row of keys keeps all but two compares and an `or` (an
    `and` under a window) off the square. (Written as compares of
    per-index codes: Mosaic has no select between vectors of booleans.)"""
    if isinstance(rule, Window):
        return (kpos <= qpos) & (kpos > qpos - rule.window)
    t, b = rule
    q_noised, k_noised = qpos < t, kpos < t
    qb = _block_of(jnp.where(q_noised, qpos, qpos - t), b)
    kb = _block_of(jnp.where(k_noised, kpos, kpos - t), b)
    # a noised key is seen by the noised queries of its block ...
    same = jnp.where(k_noised, kb, -1) == jnp.where(q_noised, qb, -2)
    # ... a clean one from an earlier block by a noised query, from the
    # query's own block too by a clean one
    earlier = jnp.where(k_noised, 2 * t, kb) \
        < jnp.where(q_noised, qb, qb + 1)
    return same | earlier


def _kv_spans(rule: BlockDiffusion, i, block_q: int, block_k: int):
    """The key tiles that hold a key some query of query tile i sees:
    two runs of tile indices (lo, hi inclusive; lo > hi = none), one
    among the noised keys and one among the clean. Python ints, numpy
    arrays or traced scalars."""
    t, b = rule
    q0 = i * block_q
    q1 = jnp.minimum(q0 + block_q, 2 * t) - 1        # its last real row
    qn1 = jnp.minimum(q1, t - 1)                     # ... last noised one
    has_noised, has_clean = q0 < t, q1 >= t
    a_lo = (q0 // b * b) // block_k
    a_hi = jnp.where(
        has_noised,
        jnp.minimum(qn1 // b * b + b - 1, t - 1) // block_k, a_lo - 1)
    # the last clean position seen: before its block by a noised row, to
    # the end of its block by a clean one
    last = jnp.maximum(
        jnp.where(has_clean, jnp.minimum(
            jnp.maximum(q1 - t, 0) // b * b + b - 1, t - 1), -1),
        jnp.where(has_noised, qn1 // b * b - 1, -1))
    b_lo = t // block_k
    b_hi = jnp.where(last >= 0, (t + jnp.maximum(last, 0)) // block_k,
                     b_lo - 1)
    return a_lo, a_hi, b_lo, b_hi


def _q_spans(rule: BlockDiffusion, j, block_q: int, block_k: int):
    """The query tiles that hold a query which sees some key of key tile
    j: a run among the noised queries and one among the clean."""
    t, b = rule
    k0 = j * block_k
    k1 = jnp.minimum(k0 + block_k, 2 * t) - 1
    kn1 = jnp.minimum(k1, t - 1)
    has_noised, has_clean = k0 < t, k1 >= t
    none_lo, none_hi = 2 * t // block_q + 1, -1
    # noised keys: the noised queries of their blocks
    n_lo = jnp.where(has_noised, (k0 // b * b) // block_q, none_lo)
    n_hi = jnp.where(
        has_noised,
        jnp.minimum(kn1 // b * b + b - 1, t - 1) // block_q, none_hi)
    # clean keys: the noised queries of every LATER block, and the clean
    # queries from the first key's block on
    kc0 = jnp.maximum(k0, t) - t
    later = (kc0 // b + 1) * b
    seen_later = has_clean & (later <= t - 1)
    c_lo = jnp.where(seen_later, later // block_q, none_lo)
    c_hi = jnp.where(seen_later, (t - 1) // block_q, none_hi)
    b_lo = (t + kc0 // b * b) // block_q
    b_hi = jnp.where(has_clean, (2 * t - 1) // block_q, b_lo - 1)
    return (jnp.minimum(n_lo, c_lo), jnp.maximum(n_hi, c_hi), b_lo, b_hi)


def _window_keys(rule: Window, i, block_q: int, block_k: int, n_k: int):
    """The key tiles that hold a key some query of query tile i sees under
    a window: one run (lo, hi inclusive), from the tile of the first
    query's oldest key to the tile of the last query, clipped at 0 and at
    the row's end. `lax`, as under `causal` in `_key_tile`."""
    q0 = i * block_q
    lo = jax.lax.div(jax.lax.max(q0 - (rule.window - 1), 0), block_k)
    hi = jax.lax.div(q0 + (block_q - 1), block_k)
    return jax.lax.min(lo, n_k - 1), jax.lax.min(hi, n_k - 1)


def _window_queries(rule: Window, j, block_q: int, block_k: int, n_q: int):
    """Its twin: the query tiles that hold a query which sees some key of
    key tile j, from the tile of the first key's own query to the tile of
    the last query that still reaches the last key."""
    k0 = j * block_k
    lo = jax.lax.div(k0, block_q)
    hi = jax.lax.div(k0 + (block_k - 1 + rule.window - 1), block_q)
    return jax.lax.min(lo, n_q - 1), jax.lax.min(hi, n_q - 1)


def _in_spans(x, spans):
    a_lo, a_hi, b_lo, b_hi = spans
    return ((x >= a_lo) & (x <= a_hi)) | ((x >= b_lo) & (x <= b_hi))


def _resident(x, spans, n: int):
    """x where tile x is live; else the live tile before it, which the
    pipeline still holds (the first live one before any): a dead step
    asks for no new block."""
    a_lo, a_hi, b_lo, b_hi = spans
    r = jnp.where(a_lo <= a_hi, a_lo, b_lo)
    r = jnp.where((a_lo <= a_hi) & (x >= a_lo), jnp.minimum(x, a_hi), r)
    r = jnp.where((b_lo <= b_hi) & (x >= b_lo), jnp.minimum(x, b_hi), r)
    return jnp.clip(r, 0, n - 1)


def _key_tile(rule: Rule, i, j, block_q: int, block_k: int, n_k: int):
    """The key tile that step (i, j) of the forward and dq grids asks
    for: j where tile (i, j) is live, else a live tile of query tile i
    that the pipeline still holds, so a dead step fetches nothing.
    (`lax` and not `jnp` under `causal`: an index map is traced and
    lowered for every block of every kernel call, and a `jnp` function
    there is a function of its own each time: half again the lowering
    time of an attention layer.)"""
    if rule is True:
        return jax.lax.min(j, jax.lax.div(i * block_q + (block_q - 1),
                                          block_k))
    if isinstance(rule, Window):
        # before the run its first tile, fetched at step 0 and held;
        # after it its last
        lo, hi = _window_keys(rule, i, block_q, block_k, n_k)
        return jax.lax.min(jax.lax.max(j, lo), hi)
    if rule:
        return _resident(j, _kv_spans(rule, i, block_q, block_k), n_k)
    return j


def _query_tile(rule: Rule, i, j, block_q: int, block_k: int, n_q: int):
    """Its twin for the dkv grid: the query tile that step (j, i) asks
    for."""
    if rule is True:
        first = jax.lax.div(j * block_k, block_q)
        return jax.lax.min(jax.lax.max(i, first), n_q - 1)
    if isinstance(rule, Window):
        lo, hi = _window_queries(rule, j, block_q, block_k, n_q)
        return jax.lax.min(jax.lax.max(i, lo), hi)
    if rule:
        return _resident(i, _q_spans(rule, j, block_q, block_k), n_q)
    return i


def _live(rule: Rule, i, j, block_q: int, block_k: int):
    """Does tile (query tile i, key tile j) hold a pair that sees?"""
    if rule is True:
        # skip k-blocks that are entirely in the future of this q-block
        return j * block_k <= i * block_q + block_q - 1
    if isinstance(rule, Window):
        # not wholly in the future, and its last key still inside the
        # first query's window
        return (j * block_k <= i * block_q + block_q - 1) \
            & (j * block_k + block_k - 1 > i * block_q - rule.window)
    if rule:
        return _in_spans(j, _kv_spans(rule, i, block_q, block_k))
    return True


def _whole(rule: Rule, i, j, block_q: int, block_k: int):
    """Does every pair of tile (i, j) see, by the rule alone (the key
    padding mask is no part of it)? Sufficient, not necessary. Counted
    for the plan's event only: the kernels mask a whole tile like a cut
    one, because on the chip the mask's passes hide behind the MXU and
    a second, unmasked body cost set-up time for nothing (PERF.md 6, PR
    36)."""
    if rule is True:
        return j * block_k + block_k - 1 <= i * block_q
    if isinstance(rule, Window):
        # neither edge cuts it: its last key is no later than the first
        # query, its first key inside the last query's window
        return (j * block_k + block_k - 1 <= i * block_q) \
            & (j * block_k > i * block_q + block_q - 1 - rule.window)
    if rule:
        # clean keys only, before queries of one kind: a clean query sees
        # up to its own block, a noised one up to the block before
        t, b = rule
        q0, k0 = i * block_q, j * block_k
        last = _block_of(k0 + block_k - 1 - t, b)
        clean = (q0 >= t) & (last <= _block_of(q0 - t, b))
        noised = (q0 + block_q <= t) & (last < _block_of(q0, b))
        return (k0 >= t) & (clean | noised)
    return True


def _masked(rule: Rule, s, i, j, block_q: int, block_k: int):
    """The scores of live tile (i, j) with the pairs that do not see set
    to MASK_VALUE: a column of query indices against a row of key
    indices, so one compare and one select run on the square."""
    if not rule:
        return s
    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    sees = qpos >= kpos if rule is True else rule_mask(rule, qpos, kpos)
    return jnp.where(sees, s, MASK_VALUE)


def _rule_name(rule: Rule) -> str:
    if isinstance(rule, BlockDiffusion):
        return f"block_diffusion({rule.length},{rule.block})"
    if isinstance(rule, Window):
        return f"window({rule.window})"
    return "causal" if rule else "none"


def _count_tiles(test, rule: Rule, n_q: int, n_k: int, block_q: int,
                 block_k: int) -> int:
    with jax.ensure_compile_time_eval():
        i = jnp.arange(n_q, dtype=jnp.int32)[:, None]
        j = jnp.arange(n_k, dtype=jnp.int32)[None, :]
        return int(jnp.sum(jnp.broadcast_to(
            test(rule, i, j, block_q, block_k), (n_q, n_k))))


def live_tiles(rule: Rule, n_q: int, n_k: int, block_q: int,
               block_k: int) -> int:
    """How many of the n_q x n_k tiles the kernels compute."""
    return _count_tiles(_live, rule, n_q, n_k, block_q, block_k)


def whole_tiles(rule: Rule, n_q: int, n_k: int, block_q: int,
                block_k: int) -> int:
    """How many of them no edge of the rule cuts; of the others' pairs
    about half are computed and masked."""
    return _count_tiles(_whole, rule, n_q, n_k, block_q, block_k)


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

    @pl.when(_live(causal, i, j, block_q, block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        kvm = kvm_ref[0, 0].astype(jnp.float32)      # [bk]
        s = s + (1.0 - kvm)[None, :] * MASK_VALUE
        s = _masked(causal, s, i, j, block_q, block_k)

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
    s = _masked(causal, s, i, j, block_q, block_k)
    return jnp.exp(s - lse[:, None])                          # [bq, bk]


def _dq_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, causal, block_q, block_k, n_k):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_live(causal, i, j, block_q, block_k))
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
                block_k, n_q, group):
    # grid = (B, Hkv, nk, group * nq): program_id(2) is the k-block, (3)
    # runs over the q-blocks of each query head that reads this key/value
    # head, one head after another.
    j, step = pl.program_id(2), pl.program_id(3)
    i = step if group == 1 else step % n_q

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_live(causal, i, j, block_q, block_k))
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

    @pl.when(step == group * n_q - 1)
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


def _index_maps(causal, group, block_q, block_k, n_q, n_k):
    """The block index maps of the grids (b, h, i, j) of the forward and
    dq kernels: (queries' side, keys' side, key mask). A query head reads
    key/value head h // group; under a rule a dead step keeps the key
    tile of a live one."""
    def kv_head(h_):
        return h_ if group == 1 else h_ // group

    def k_tile(i, j):
        return _key_tile(causal, i, j, block_q, block_k, n_k)
    return (lambda b_, h_, i, j: (b_, h_, i, 0),
            lambda b_, h_, i, j: (b_, kv_head(h_), k_tile(i, j), 0),
            lambda b_, h_, i, j: (b_, 0, k_tile(i, j)))


def _fwd_call(q, k, v, kvm, scale, causal, block_q, block_k, interpret):
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    n_q, n_k = tq // block_q, tk // block_k
    grid = (b, h, n_q, n_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    at_q, at_k, at_mask = _index_maps(causal, h // k.shape[1], block_q,
                                      block_k, n_q, n_k)
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), at_q),
            pl.BlockSpec((1, 1, block_k, dh), at_k),
            pl.BlockSpec((1, 1, block_k, dv), at_k),
            pl.BlockSpec((1, 1, block_k), at_mask),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), at_q),
            pl.BlockSpec((1, 1, block_q, 1), at_q),
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
    h_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // h_kv
    n_q, n_k = tq // block_q, tk // block_k

    dq_kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k, n_k=n_k)
    at_q, at_k, at_mask = _index_maps(causal, group, block_q, block_k,
                                      n_q, n_k)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_dq",
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), at_q),
            pl.BlockSpec((1, 1, block_k, dh), at_k),
            pl.BlockSpec((1, 1, block_k, dv), at_k),
            pl.BlockSpec((1, 1, block_k), at_mask),
            pl.BlockSpec((1, 1, block_q, dv), at_q),
            pl.BlockSpec((1, 1, block_q, 1), at_q),
            pl.BlockSpec((1, 1, block_q, 1), at_q),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), at_q),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
    )(q, k, v, kvm, do, lse, delta)

    # grid (b, key/value head, k tile, the group's query heads x q tiles):
    # the last axis is sequential, so one key tile's dk and dv gather every
    # query head of the group in VMEM
    def q_side(b_, h_, j, step):
        if group == 1:
            head, i = h_, step
        else:
            head, i = h_ * group + step // n_q, step % n_q
        return b_, head, _query_tile(causal, i, j, block_q, block_k, n_q), 0

    def k_side(b_, h_, j, step):
        return b_, h_, j, 0
    dkv_kernel = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                                   block_q=block_q, block_k=block_k, n_q=n_q,
                                   group=group)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_dkv",
        grid=(b, h_kv, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), q_side),
            pl.BlockSpec((1, 1, block_k, dh), k_side),
            pl.BlockSpec((1, 1, block_k, dv), k_side),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, j, step: (b_, 0, j)),
            pl.BlockSpec((1, 1, block_q, dv), q_side),
            pl.BlockSpec((1, 1, block_q, 1), q_side),
            pl.BlockSpec((1, 1, block_q, 1), q_side),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, dh), k_side),
            pl.BlockSpec((1, 1, block_k, dv), k_side),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h_kv, tk, dh), k.dtype),
            jax.ShapeDtypeStruct((b, h_kv, tk, dv), v.dtype),
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


def _pick_block(limit: int, t: int) -> int:
    # biggest block <= limit whose grid padding wastes <= 25% of t:
    # big blocks cut online-softmax rescale passes (the r5 sweep
    # win), but a 2048 block on t=2176 would pad to 4096 and run
    # the fully-masked blocks through every kernel — a tile of
    # padded keys is skipped only where the rule kills it (a rule's
    # `_live` test reads indices, not the key mask)
    b = _round_up(min(limit, _round_up(t, _LANES)), _LANES)
    while b > _LANES:
        if _round_up(t, b) - t <= max(t // 4, _LANES):
            return b
        b = (b // 2 // _LANES) * _LANES
    return _LANES


def pick_blocks(tq: int, tk: int, dh: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> Tuple[int, int]:
    """The (query, key) block sizes `flash_attention` runs Tq queries on
    Tk keys of width dh at; the sequence dims are padded up to their
    multiples."""
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
    return _pick_block(block_q, tq), _pick_block(block_k, tk)


def _checked(rule: Rule, tq: int, tk: int) -> Rule:
    """The rule as the kernels take it over Tq queries and Tk keys, or a
    ValueError where it is not written for them."""
    if isinstance(rule, BlockDiffusion):
        if not (tq == tk == 2 * rule.length and rule.block >= 1):
            raise ValueError(f"{rule} over {tq} queries and {tk} keys: "
                             f"want twice its length of both")
        return rule
    if isinstance(rule, Window):
        if not (tq == tk and rule.window >= 1):
            raise ValueError(f"{rule} over {tq} queries and {tk} keys: "
                             f"want a window of 1 or more over one row")
        # no key is behind the window: a causal row
        return True if rule.window >= tk else rule
    return bool(rule)


def tile_plan(rule: Rule, tq: int, tk: int, dh: int,
              block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> Dict[str, int]:
    """What the kernels do with a rule over Tq x Tk, from shapes alone:
    the blocks, the tiles of the padded grid, those computed (`tiles_live`)
    and those no edge cuts (`tiles_whole`). The `flash_attention.plan`
    event's numbers; whoever counts a step's pairs reads the same."""
    rule = _checked(rule, tq, tk)
    bq, bk = pick_blocks(tq, tk, dh, block_q, block_k)
    n_q, n_k = _round_up(tq, bq) // bq, _round_up(tk, bk) // bk
    return dict(block_q=bq, block_k=bk, rule=_rule_name(rule),
                tiles_live=live_tiles(rule, n_q, n_k, bq, bk),
                tiles_whole=whole_tiles(rule, n_q, n_k, bq, bk),
                tiles=n_q * n_k)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    kv_mask: Optional[jax.Array] = None,
                    causal: Rule = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """softmax(scale * Q K^T + mask) V, never materializing the score matrix.

    q [B,H,Tq,Dh], k [B,Hkv,Tk,Dh], v [B,Hkv,Tk,Dv] with H a multiple of
    Hkv, kv_mask [B,Tk] (1.0 = attend) or None, `causal` a rule (False,
    True, a BlockDiffusion over Tq = Tk = 2 * its length, or a Window over
    Tq = Tk); the default scale is 1/sqrt(Dh), the key width. Sequence
    dims are padded up to block multiples internally (padded keys are
    masked out; padded query rows are sliced off).
    """
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    if h % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{h} query heads on {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    causal = _checked(causal, tq, tk)
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    bq, bk = pick_blocks(tq, tk, dh, block_q, block_k)
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

    if obs.enabled():
        obs.event("flash_attention.plan", tq=tq, tk=tk,
                  kv_group=h // k.shape[1],
                  **tile_plan(causal, tq, tk, dh, bq, bk))
    out = _flash(q, k, v, kvm, float(scale), causal, bq, bk,
                 bool(interpret))
    if tq_p != tq:
        out = out[:, :, :tq, :]
    return out
