"""A dropless expert layer that is told which experts it holds.

The router scores a token against ALL experts and picks its top k; this
chip holds the experts [first, first + count) and computes their part of
the result for the tokens routed to them. What the other experts would
have added is left out: it is the other chips' part, and the sum of all
parts (with what every chip computes alike counted once) is the whole
layer. On one chip the layer runs without its exchange.

No [tokens, experts, capacity] one-hots and nothing dropped: the
assignments that landed here are bucketed by expert, bucket after
bucket, and that list is computed in three parts, whichever experts
its rows name.

  the FIRST POOL, its first `FIRST_SHARES` even shares (`pool_rows`):
      one gather, three grouped matmuls (`jax.lax.ragged_dot`:
      consecutive groups of rows, each against its own expert's matrix)
      and one scatter-add, all of a fixed shape and ALWAYS run; rows
      past the end of the list weigh zero. An even share is what even
      routing would send here, and the pool is shared: one crowded
      expert uses what the others leave. Every list the records hold
      fits (the evidence is over FIRST_SHARES), so this part is the
      whole layer, at the matmuls' own speed and at a cost that does not
      follow the routing.
  the SECOND POOL, the next rows of the list up to `POOL_SHARES` shares
      in all, run only when the list reaches them: the SAME batch once
      more, one step further down the list. Both are one loop of one or
      two trips, so the program holds the batch's code once, a list that
      ends in the first pool runs nothing for the second, forward or
      backward, and no branch hands back zero gradients to add.
  what arrived beyond both pools, expert by expert: blocks of `block`
      rows in a loop with a DYNAMIC trip count, the number of blocks
      that arrived. Work follows what arrived, at any imbalance: every
      token on one expert is more blocks, never a dropped token.

All three are `_held`, whose backward is written out (a while loop has
no reverse mode): it runs a pool's batch again for its gradients, and
recomputes a block's activations, instead of keeping either. Under a
checkpoint that costs nothing (the forward run again there feeds
nothing and is dropped) UNLESS the caller's checkpoint must give back
the layer's OUTPUT, which a norm on that output asks for: the forward
then runs a third time to make it. models/layer_plan.py::_layer keeps
that output by name across the backward, so the pool runs twice there
too. Without a checkpoint it is one more forward of the layer for
activations never held.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256
# both pools, in shares of the assignments that even routing sends to
# the held experts; a block of the loop costs ~4x its matmuls, so the
# loop is for what no deployment should see
POOL_SHARES = 3
# the first pool, which always runs. Sized from the counters: over every
# line the records hold, a span's mean list (`moe.assignments_held` over
# `moe.assignments`, in even shares) was 0.4 to 1.42 shares: 0.45-1.2 by
# seed on a fresh router (PERF.md 6, PR 28), 1.42 at most (ledger, PR
# 32), 0.4-1.35 where the pool is the whole feed-forward half (PRs
# 34-38). What lies beyond is the second pool's, and conditional.
FIRST_SHARES = 1.5
_POOL_ROWS = 512          # a pool is whole tiles of the grouped matmul
COUNTERS = ("moe.assignments", "moe.assignments_held", "moe.load_max",
            "moe.load_mean", "moe.dropped", "moe.pool_calls",
            "moe.second_pool", "moe.loop_rows")

SCORES = ("sigmoid", "softmax")


def route(x, w_router, top_k: int, scale: float, score: str = "sigmoid"):
    """Scores over all experts in float32 (`score`: a sigmoid of each
    logit, or a softmax over them), the top k of them, renormalised to
    sum 1 and scaled: x [T, d], w_router [d, E] -> (idx [T, k] int32,
    weights [T, k] float32)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(s, top_k)
    return idx, vals / jnp.sum(vals, axis=-1, keepdims=True) * scale


def _arrivals(idx, mask, first: int, count: int):
    """Bucket the (token, slot) assignments that name a held expert by
    expert, in their own order inside a bucket. Returns order [T*k] (the
    flat assignment ids, bucket after bucket; what follows the last
    bucket is never read) and per held expert its bucket's size and
    start in `order`. No sort: an assignment's place is its bucket's
    start plus its rank among that expert's assignments, a running
    count."""
    local = (idx - first).reshape(-1)
    held = (local >= 0) & (local < count) & (jnp.repeat(mask, idx.shape[1])
                                             > 0)
    hot = (held[:, None] & (local[:, None] == jnp.arange(count)[None, :])
           ).astype(jnp.int32)                             # [T*k, count]
    sizes = jnp.sum(hot, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    place = jnp.sum(hot * (starts + jnp.cumsum(hot, axis=0) - hot), axis=1)
    n = local.shape[0]
    order = jnp.zeros((n,), jnp.int32).at[jnp.where(held, place, n)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return order, sizes, starts


def pool_rows(tokens: int, top_k: int, held: int,
              experts: int) -> Tuple[int, int]:
    """(first, second): the rows of the pool that always runs,
    `FIRST_SHARES` times the `tokens * top_k * held / experts`
    assignments of even routing, and of the one that runs when the list
    reaches it, the rest of `POOL_SHARES` shares; both in whole tiles."""
    share = -(-tokens * top_k * held // experts)
    first, both = (math.ceil(shares * share / _POOL_ROWS) * _POOL_ROWS
                   for shares in (FIRST_SHARES, POOL_SHARES))
    return first, both - first


def _block_rows(e, j, order, sizes, starts, k, block, pooled):
    """Block j of what expert e's bucket holds beyond its `pooled[e]`
    rows in the pool: its tokens [block], their slots, and which rows
    are real."""
    row = pooled[e] + j * block + jnp.arange(block, dtype=jnp.int32)
    flat = order[jnp.clip(starts[e] + row, 0, order.shape[0] - 1)]
    return flat // k, flat % k, row < sizes[e]


def _blocks(sizes, e, block, pooled):
    return (sizes[e] - pooled[e] + block - 1) // block


def _pooled(sizes, starts, lo, hi):
    """Per expert, the rows of its bucket among rows [lo, hi) of the
    list."""
    return jnp.clip(starts + sizes, lo, hi) - jnp.clip(starts, lo, hi)


def _batch_groups(sizes, starts, rows, off, end):
    """The group sizes of the batch over rows [off, off + rows) of a list
    that is computed up to row `end`: per expert its rows in the batch,
    the last group with the rows past the end besides, whose weight is
    zero. Every row of the batch is in some group: they sum to `rows`."""
    pooled = _pooled(sizes, starts, off, jnp.clip(end, off, off + rows))
    return pooled.at[-1].add(rows - jnp.sum(pooled))


def _pool_part(y0, x, w, wg, wu, wd, order, sizes, starts, k, rows, off, end):
    """Rows [off, off + rows) of the bucketed list, as far as they lie
    before row `end`, in one batch of a fixed shape, added to y0 [T, d]
    float32. Plain autodiff."""
    f32 = jnp.float32
    pos = off + jnp.arange(rows, dtype=jnp.int32)
    flat = order[jnp.minimum(pos, order.shape[0] - 1)]
    tok, slot = flat // k, flat % k
    end = jnp.minimum(end, jnp.sum(sizes))
    dot = functools.partial(
        jax.lax.ragged_dot, preferred_element_type=f32,
        group_sizes=_batch_groups(sizes, starts, rows, off, end))
    xb = x[tok]                                            # [rows, d]
    h = (jax.nn.silu(dot(xb, wg)) * dot(xb, wu)).astype(x.dtype)
    wt = jnp.where(pos < end, w[tok, slot], 0.0)
    return y0.at[tok].add(wt[:, None] * dot(h, wd))


def _expert_block(xb, wg, wu, wd):
    a = jnp.dot(xb, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(xb, wu, preferred_element_type=jnp.float32)
    sg = jax.nn.sigmoid(a)
    h = (a * sg * u).astype(xb.dtype)
    return a, u, sg, h, jnp.dot(h, wd, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _held(x, w, wg, wu, wd, order, sizes, starts, pools, k, block, pool):
    return _held_fwd(x, w, wg, wu, wd, order, sizes, starts, pools, k, block,
                     pool)[0]


# jitted for its trace cache alone: the primal, the forward rule and every
# layer of a plan with the same shapes share ONE trace of it (set-up of a
# plan's thirteen step programs is mostly tracing: PERF.md 7)
@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _held_fwd(x, w, wg, wu, wd, order, sizes, starts, pools, k, block, pool):
    """The held experts' part of the output, [T, d]: `pools` (an int32
    scalar, 1 or 2) batches of the first pool's shape over the first
    rows of the list, the second as far as the second pool goes, and
    what the buckets hold beyond both in the loop; with it the rows
    computed."""
    first, both = pool[0], sum(pool)
    y = jnp.zeros(x.shape, jnp.float32)
    if first:
        y = jax.lax.fori_loop(
            0, pools, lambda i, y: _pool_part(
                y, x, w, wg, wu, wd, order, sizes, starts, k, first,
                i * first, both), y)
    # both pools are prefixes of the list, so of every bucket
    pooled = _pooled(sizes, starts, 0, both)

    def expert(e, carry):
        def body(j, carry):
            y, done = carry
            tok, slot, valid = _block_rows(e, j, order, sizes, starts, k,
                                           block, pooled)
            yb = _expert_block(x[tok], wg[e], wu[e], wd[e])[-1]
            wt = jnp.where(valid, w[tok, slot], 0.0)
            return (y.at[tok].add(wt[:, None] * yb),
                    done + jnp.sum(valid, dtype=jnp.int32))
        return jax.lax.fori_loop(0, _blocks(sizes, e, block, pooled), body,
                                 carry)

    y, done = jax.lax.fori_loop(0, wg.shape[0], expert,
                                (y, jnp.sum(pooled, dtype=jnp.int32)))
    return (y.astype(x.dtype), done), (x, w, wg, wu, wd, order, sizes,
                                       starts, pools)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _held_bwd(k, block, pool, res, cts):
    x, w, wg, wu, wd, order, sizes, starts, pools = res
    first, both = pool[0], sum(pool)
    dy = cts[0].astype(jnp.float32)
    f32 = jnp.float32
    pooled = _pooled(sizes, starts, 0, both)
    # dx and dw gather in float32; the stacks of weight gradients are the
    # weights' own type, as autodiff's sum of the parts' gradients is
    acc = (jnp.zeros(x.shape, f32), jnp.zeros(w.shape, f32),
           jnp.zeros_like(wg), jnp.zeros_like(wu), jnp.zeros_like(wd))
    if first:
        # a pool's batch again, and its gradients into the accumulators
        # that the loop below goes on with
        def batch(i, acc):
            grads = jax.vjp(
                lambda *a: _pool_part(jnp.zeros(x.shape, f32), *a, order,
                                      sizes, starts, k, first, i * first,
                                      both),
                x, w, wg, wu, wd)[1](dy)
            return tuple(a + g.astype(a.dtype) for a, g in zip(acc, grads))
        acc = jax.lax.fori_loop(0, pools, batch, acc)

    def expert(e, carry):
        # an expert's weight gradients gather in accumulators of their
        # own and join the stacks once, after its last block
        def body(j, carry):
            dx, dw, dwg, dwu, dwd = carry
            tok, slot, valid = _block_rows(e, j, order, sizes, starts, k,
                                           block, pooled)
            xb = x[tok]
            a, u, sg, h, yb = _expert_block(xb, wg[e], wu[e], wd[e])
            dyb = dy[tok]
            dw = dw.at[tok, slot].add(
                jnp.where(valid, jnp.sum(dyb * yb, axis=-1), 0.0))
            dyb = (jnp.where(valid, w[tok, slot], 0.0)[:, None]
                   * dyb).astype(x.dtype)
            dh = jnp.dot(dyb, wd[e].T, preferred_element_type=f32)
            da = (dh * u * sg * (1.0 + a * (1.0 - sg))).astype(x.dtype)
            du = (dh * a * sg).astype(x.dtype)
            dxb = jnp.dot(da, wg[e].T, preferred_element_type=f32) \
                + jnp.dot(du, wu[e].T, preferred_element_type=f32)
            return (dx.at[tok].add(jnp.where(valid[:, None], dxb, 0.0)), dw,
                    dwg + jnp.dot(xb.T, da, preferred_element_type=f32),
                    dwu + jnp.dot(xb.T, du, preferred_element_type=f32),
                    dwd + jnp.dot(h.T, dyb, preferred_element_type=f32))

        dx, dw, dwg, dwu, dwd = carry
        zeros = lambda a: jnp.zeros(a.shape, f32)      # noqa: E731
        dx, dw, *own = jax.lax.fori_loop(
            0, _blocks(sizes, e, block, pooled), body,
            (dx, dw, zeros(wg[0]), zeros(wu[0]), zeros(wd[0])))
        return (dx, dw) + tuple(
            stack.at[e].add(g.astype(stack.dtype))
            for stack, g in zip((dwg, dwu, dwd), own))

    dx, dw, dwg, dwu, dwd = jax.lax.fori_loop(0, wg.shape[0], expert, acc)
    ints = tuple(np.zeros(a.shape, jax.dtypes.float0)  # mtlint: ok -- an integer input's cotangent IS a host float0 array; jnp has none
                 for a in (order, sizes, starts, pools))
    return (dx.astype(x.dtype), dw.astype(w.dtype), dwg, dwu, dwd) + ints


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, mask, idx, weights, wg, wu, wd, first: int,
                 block: int = BLOCK, pool: Tuple[int, int] = (0, 0)):
    """The held experts' part of the layer's output: the first `pool[0]`
    assignments that arrived in one fixed-shape batch, the next
    `pool[1]` in another where the list reaches them, the rest in the
    loop (pool (0, 0): all of it in the loop).

    x [T, d]; mask [T] (0 = padding, routed nowhere); idx, weights [T, k]
    from `route`; wg, wu [count, d, f], wd [count, f, d]: the gated MLPs
    W_d(SiLU(W_g x) * W_u x) of experts first .. first + count - 1.
    Returns (y [T, d], counters [8] float32 in the order of COUNTERS:
    assignments of real tokens, those that named a held expert, the
    largest and the mean group of a held expert, assignments that named
    a held expert and were not computed — always 0 —, this call, whether
    its second pool ran, and the assignments its loop computed)."""
    if pool[1] > pool[0]:
        raise ValueError(f"the second pool runs as one more batch of the "
                         f"first's shape and cannot be larger: {pool}")
    count = wg.shape[0]
    k = idx.shape[1]
    order, sizes, starts = _arrivals(idx, mask, first, count)
    arrived = jnp.sum(sizes)
    second = (arrived > pool[0]) & (pool[1] > 0)
    y, done = _held(x, weights, wg, wu, wd, order, sizes, starts,
                    1 + second.astype(jnp.int32), k, block, tuple(pool))
    counters = jnp.stack([
        jnp.sum(mask > 0) * k, arrived, jnp.max(sizes), arrived / count,
        arrived - done, 1, second,
        done - jnp.minimum(arrived, sum(pool))]).astype(jnp.float32)
    return y, counters


def gated_mlp(x, wg, wu, wd):
    """W_d(SiLU(W_g x) * W_u x) on every token: the dense feed-forward
    and the shared expert."""
    a = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * u).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32).astype(x.dtype)
