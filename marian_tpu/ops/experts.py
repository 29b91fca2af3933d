"""A dropless expert layer that is told which experts it holds.

The router scores a token against ALL experts and picks its top k; this
chip holds the experts [first, first + count) and computes their part of
the result for the tokens routed to them. What the other experts would
have added is left out: it is the other chips' part, and the sum of all
parts (with what every chip computes alike counted once) is the whole
layer. On one chip the layer runs without its exchange.

No [tokens, experts, capacity] one-hots and nothing dropped: the
assignments that landed here are bucketed by expert, bucket after
bucket, and that list is computed in two parts.

  its first `pool` rows, whichever experts they name: one gather, three
      grouped matmuls (`jax.lax.ragged_dot`: consecutive groups of rows,
      each against its own expert's matrix) and one scatter-add, all of a
      fixed shape; rows past the end of the list weigh zero. `pool` is a
      few times the share of the assignments that even routing would
      send here (`pool_rows`), and it is shared: one crowded expert uses
      what the others leave. While the list fits, this part is the whole
      layer, at the matmuls' own speed and at a cost that does not
      follow the routing.
  what arrived beyond the pool, expert by expert: blocks of `block`
      rows in a loop with a DYNAMIC trip count — the number of blocks
      that arrived. Work follows what arrived, at any imbalance: every
      token on one expert is more blocks, never a dropped token. The
      loop's backward is written out (a while loop has no reverse mode)
      and recomputes a block's activations instead of keeping them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256
# the pool, in shares of the assignments that even routing sends to the
# held experts: a fresh, unbalanced router sent 0.45 to 1.2 shares here,
# by seed (PERF.md 6, PR 28), and a block of the loop costs ~4x its
# matmuls, so the loop is for what no deployment should see
POOL_SHARES = 3
_POOL_ROWS = 512          # the pool is whole tiles of the grouped matmul
COUNTERS = ("moe.assignments", "moe.assignments_held", "moe.load_max",
            "moe.load_mean", "moe.dropped")


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


def pool_rows(tokens: int, top_k: int, held: int, experts: int) -> int:
    """`POOL_SHARES` times the `tokens * top_k * held / experts`
    assignments of even routing, up to whole tiles."""
    share = -(-tokens * top_k * held // experts)
    return -(-POOL_SHARES * share // _POOL_ROWS) * _POOL_ROWS


def _block_rows(e, j, order, sizes, starts, k, block, pooled):
    """Block j of what expert e's bucket holds beyond its `pooled[e]`
    rows in the pool: its tokens [block], their slots, and which rows
    are real."""
    row = pooled[e] + j * block + jnp.arange(block, dtype=jnp.int32)
    flat = order[jnp.clip(starts[e] + row, 0, order.shape[0] - 1)]
    return flat // k, flat % k, row < sizes[e]


def _blocks(sizes, e, block, pooled):
    return (sizes[e] - pooled[e] + block - 1) // block


def _pool_part(x, w, wg, wu, wd, order, sizes, starts, k, rows):
    """The first `rows` assignments of the bucketed list in one batch of
    a fixed shape: (y [T, d] float32, per expert the rows of its bucket
    computed here). Plain autodiff."""
    f32 = jnp.float32
    pos = jnp.arange(rows, dtype=jnp.int32)
    flat = order[jnp.minimum(pos, order.shape[0] - 1)]
    tok, slot = flat // k, flat % k
    pooled = jnp.minimum(starts + sizes, rows) - jnp.minimum(starts, rows)
    # every row of the pool is in some group: the last takes the rows
    # past the end of the list, whose weight is zero
    groups = pooled.at[-1].add(rows - jnp.sum(pooled))
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=groups,
                            preferred_element_type=f32)
    xb = x[tok]                                            # [rows, d]
    h = (jax.nn.silu(dot(xb, wg)) * dot(xb, wu)).astype(x.dtype)
    wt = jnp.where(pos < jnp.sum(sizes), w[tok, slot], 0.0)
    y = jnp.zeros(x.shape, f32).at[tok].add(wt[:, None] * dot(h, wd))
    return y, pooled


def _expert_block(xb, wg, wu, wd):
    a = jnp.dot(xb, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(xb, wu, preferred_element_type=jnp.float32)
    sg = jax.nn.sigmoid(a)
    h = (a * sg * u).astype(xb.dtype)
    return a, u, sg, h, jnp.dot(h, wd, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _held(y0, x, w, wg, wu, wd, order, sizes, starts, pooled, k, block):
    return _held_fwd(y0, x, w, wg, wu, wd, order, sizes, starts, pooled, k,
                     block)[0]


def _held_fwd(y0, x, w, wg, wu, wd, order, sizes, starts, pooled, k, block):
    """What the buckets hold beyond their `pooled` rows, added to y0
    [T, d] float32; with it the rows computed, the pool's and these."""
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
                                (y0, jnp.sum(pooled, dtype=jnp.int32)))
    return (y.astype(x.dtype), done), (x, w, wg, wu, wd, order, sizes,
                                       starts, pooled)


def _held_bwd(k, block, res, cts):
    x, w, wg, wu, wd, order, sizes, starts, pooled = res
    dy = cts[0].astype(jnp.float32)
    f32 = jnp.float32
    zeros = lambda a: jnp.zeros(a.shape, f32)      # noqa: E731

    def expert(e, carry):
        # an expert's weight gradients gather in accumulators of their
        # own and land in the stacks once, after its last block
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
        dx, dw, *own = jax.lax.fori_loop(
            0, _blocks(sizes, e, block, pooled), body,
            (dx, dw, zeros(wg[0]), zeros(wu[0]), zeros(wd[0])))
        return (dx, dw) + tuple(
            stack.at[e].set(g) for stack, g in zip((dwg, dwu, dwd), own))

    dx, dw, dwg, dwu, dwd = jax.lax.fori_loop(
        0, wg.shape[0], expert,
        (zeros(x), zeros(w), zeros(wg), zeros(wu), zeros(wd)))
    ints = tuple(np.zeros(a.shape, jax.dtypes.float0)
                 for a in (order, sizes, starts, pooled))
    return (dy, dx.astype(x.dtype), dw.astype(w.dtype),
            dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype)) + ints


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, mask, idx, weights, wg, wu, wd, first: int,
                 block: int = BLOCK, pool: int = 0):
    """The held experts' part of the layer's output: the first `pool`
    assignments that arrived in one fixed-shape batch, the rest in the
    loop (pool 0: all of it in the loop).

    x [T, d]; mask [T] (0 = padding, routed nowhere); idx, weights [T, k]
    from `route`; wg, wu [count, d, f], wd [count, f, d]: the gated MLPs
    W_d(SiLU(W_g x) * W_u x) of experts first .. first + count - 1.
    Returns (y [T, d], counters [5] float32 in the order of COUNTERS:
    assignments of real tokens, those that named a held expert, the
    largest and the mean group of a held expert, and assignments that
    named a held expert and were not computed — always 0)."""
    count = wg.shape[0]
    k = idx.shape[1]
    order, sizes, starts = _arrivals(idx, mask, first, count)
    y0, pooled = jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(sizes)
    if pool:
        y0, pooled = _pool_part(x, weights, wg, wu, wd, order, sizes, starts,
                                k, pool)
    y, done = _held(y0, x, weights, wg, wu, wd, order, sizes, starts, pooled,
                    k, block)
    arrived = jnp.sum(sizes)
    counters = jnp.stack([
        jnp.sum(mask > 0) * k, arrived, jnp.max(sizes),
        arrived / count, arrived - done]).astype(jnp.float32)
    return y, counters


def gated_mlp(x, wg, wu, wd):
    """W_d(SiLU(W_g x) * W_u x) on every token: the dense feed-forward
    and the shared expert."""
    a = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * u).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32).astype(x.dtype)
