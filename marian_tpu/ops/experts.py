"""A dropless expert layer that is told which experts it holds.

The router scores a token against ALL experts and picks its top k; this
chip holds the experts [first, first + count) and computes their part of
the result for the tokens routed to them. What the other experts would
have added is left out: it is the other chips' part, and the sum of all
parts (with what every chip computes alike counted once) is the whole
layer. On one chip the layer runs without its exchange.

No [tokens, experts, capacity] one-hots and nothing dropped: the
assignments that landed here are bucketed by expert, bucket after
bucket, and that list is computed in ONE loop of equal batches whose
trip count is the list's own length, whichever experts its rows name.

  a BATCH is `rows` rows of the list (`pool_rows`: half of what even
      routing would send here, from the call's shapes): one gather,
      three grouped matmuls (`jax.lax.ragged_dot`: consecutive groups of
      rows, each against its own expert's matrix) and one scatter-add,
      all of a fixed shape, so the program holds the batch's code once.
  the LOOP runs it ceil(arrived / rows) times, a trip count the step
      computes from its routing. Work follows what arrived: a short list
      is fewer trips, a crowded expert uses what the others leave, every
      token on one expert is more trips of a matmul whose one group
      holds every row, and nothing is ever dropped or run for nothing
      but the last trip's rows past the end of the list, which weigh
      zero and stay in the last group (the chip's grouped matmul reads
      past its operand otherwise).

The loop is `_held`, whose backward is written out (a while loop has no
reverse mode): it runs the loop again, a trip's batch for its gradients,
which go INTO the accumulators the loop carries (the tokens' gradient is
scattered into in place, never built a trip at a time), and keeps no
activations. Under a checkpoint that costs nothing (the forward run
again there feeds nothing and is dropped) UNLESS the caller's checkpoint
must give back the layer's OUTPUT, which a norm on that output asks for:
the forward then runs a third time to make it. models/layer_plan.py::
_layer keeps that output by name across the backward, so the loop runs
twice there too. Without a checkpoint it is one more forward of the
layer for activations never held.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_POOL_ROWS = 512          # a batch is whole tiles of the grouped matmul
COUNTERS = ("moe.assignments", "moe.assignments_held", "moe.load_max",
            "moe.load_mean", "moe.dropped", "moe.pool_calls",
            "moe.pool_trips", "moe.pool_rows")

SCORES = ("sigmoid", "softmax")


def route(x, w_router, top_k: int, scale: float, score: str = "sigmoid",
          bias=None):
    """Scores over all experts in float32 (`score`: a sigmoid of each
    logit, or a softmax over them), the top k of them, renormalised to
    sum 1 and scaled: x [T, d], w_router [d, E] -> (idx [T, k] int32,
    weights [T, k] float32). With a `bias` [.., E] the top k are chosen
    by score + bias and weighted by their scores WITHOUT it: the bias
    says where a token goes, never how much of an expert it gets."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        vals, idx = jax.lax.top_k(s, top_k)
    else:
        idx = jax.lax.top_k(s + bias.reshape(-1).astype(jnp.float32),
                            top_k)[1]
        vals = jnp.take_along_axis(s, idx, axis=-1)
    return idx, vals / jnp.sum(vals, axis=-1, keepdims=True) * scale


def loads(idx, mask, experts: int):
    """How many of the real tokens' choices named each expert of ALL the
    router scores, held here or not: idx [T, k], mask [T] -> [experts]
    float32."""
    real = jnp.repeat(mask, idx.shape[1]) > 0
    hot = real[:, None] & (idx.reshape(-1, 1) == jnp.arange(experts)[None, :])
    return jnp.sum(hot, axis=0, dtype=jnp.float32)


@jax.custom_vjp
def load_signal(y, bias, load):
    """`y` as it is. What the backward hands `bias` is NOT a derivative:
    it is `load` less its mean, how far over (+) or under (-) the even
    load each expert stood in this call, in bias's shape. It rides the
    gradients' road because that road already does what the signal
    needs: it leaves the checkpointed step, adds up over micro-batches
    and over the chips that share the data. Whoever updates the
    parameters takes it OUT of the gradients before they are normed and
    moves the bias against its sign (parallel/zero.py::take_load_signals,
    move_by_load); to an optimizer it must never look like a gradient."""
    return y


def _load_signal_fwd(y, bias, load):
    return y, (bias, load)


def _load_signal_bwd(res, dy):
    bias, load = res
    over = (load - jnp.mean(load)).reshape(bias.shape).astype(bias.dtype)
    return dy, over, jnp.zeros_like(load)


load_signal.defvjp(_load_signal_fwd, _load_signal_bwd)


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
    """The rows of one batch of the loop: HALF of the `tokens * top_k *
    held / experts` assignments that even routing sends here, in whole
    tiles. A trip costs its rows and, whatever they are, one pass over
    the held experts' matrices, the stacks of their gradients and the
    tokens' accumulator (on a v5e what ~1500 to ~2800 rows cost, 8 and
    16 held: PERF.md 6, PR 50), so a batch much under half a share
    spends on trips what it saves in rows past the list's end, and one
    much over it computes them."""
    share = -(-tokens * top_k * held // experts)
    return max(1, math.ceil(share / 2 / _POOL_ROWS)) * _POOL_ROWS


def _batch_groups(sizes, starts, rows, off, end):
    """The group sizes of the batch over rows [off, off + rows) of a list
    that is computed up to row `end`: per expert its rows in the batch,
    the last group with the rows past the end besides, whose weight is
    zero. Every row of the batch is in some group: they sum to `rows`."""
    hi = jnp.clip(end, off, off + rows)
    mine = jnp.clip(starts + sizes, off, hi) - jnp.clip(starts, off, hi)
    return mine.at[-1].add(rows - jnp.sum(mine))


def _trips(sizes, rows: int):
    return (jnp.sum(sizes) + rows - 1) // rows


def _batch_rows(i, order, sizes, starts, k, rows):
    """Batch i of the list: its rows' tokens and slots [rows], which of
    them lie before the list's end (the others read a token that is
    there and weigh zero), and its group sizes."""
    end = jnp.sum(sizes)
    pos = i * rows + jnp.arange(rows, dtype=jnp.int32)
    flat = order[jnp.minimum(pos, order.shape[0] - 1)]
    return (flat // k, flat % k, pos < end,
            _batch_groups(sizes, starts, rows, i * rows, end))


def _batch(xb, w_rows, wg, wu, wd, live, groups):
    """A batch's rows through their experts, each at its weight:
    xb [rows, d], w_rows [rows] -> [rows, d] float32. Plain autodiff."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=groups,
                            preferred_element_type=jnp.float32)
    h = (jax.nn.silu(dot(xb, wg)) * dot(xb, wu)).astype(xb.dtype)
    return jnp.where(live, w_rows, 0.0)[:, None] * dot(h, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _held(x, w, wg, wu, wd, order, sizes, starts, k, rows):
    return _held_fwd(x, w, wg, wu, wd, order, sizes, starts, k, rows)[0]


# jitted for its trace cache alone: the primal, the forward rule and every
# layer of a plan with the same shapes share ONE trace of it (set-up of a
# plan's thirteen step programs is mostly tracing: PERF.md 7)
@functools.partial(jax.jit, static_argnums=(8, 9))
def _held_fwd(x, w, wg, wu, wd, order, sizes, starts, k, rows):
    """The held experts' part of the output, [T, d], and the assignments
    computed: the list in batches of `rows`, as many as it is long."""
    def batch(i, carry):
        y, done = carry
        tok, slot, live, groups = _batch_rows(i, order, sizes, starts, k,
                                              rows)
        yb = _batch(x[tok], w[tok, slot], wg, wu, wd, live, groups)
        return y.at[tok].add(yb), done + jnp.sum(live, dtype=jnp.int32)

    y, done = jax.lax.fori_loop(
        0, _trips(sizes, rows), batch,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    return (y.astype(x.dtype), done), (x, w, wg, wu, wd, order, sizes, starts)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _held_bwd(k, rows, res, cts):
    x, w, wg, wu, wd, order, sizes, starts = res
    f32 = jnp.float32
    dy = cts[0]

    def batch(i, acc):
        # the batch again, and its gradients into the accumulators: the
        # rows' own into their tokens' places, the stacks' added whole
        dx, dw, *stacks = acc
        tok, slot, live, groups = _batch_rows(i, order, sizes, starts, k,
                                              rows)
        dxb, dw_rows, *grads = jax.vjp(
            lambda *a: _batch(*a, live, groups),
            x[tok], w[tok, slot], wg, wu, wd)[1](dy[tok].astype(f32))
        return (dx.at[tok].add(dxb), dw.at[tok, slot].add(dw_rows),
                *(s + g for s, g in zip(stacks, grads)))

    # every accumulator is its input's own type, as autodiff's sum of the
    # batches' gradients is (the tokens' [T, d] scattered into in x's: a
    # float32 one costs a fifth more a row and a pass to convert)
    dx, dw, *stacks = jax.lax.fori_loop(
        0, _trips(sizes, rows), batch,
        tuple(jnp.zeros_like(a) for a in (x, w, wg, wu, wd)))
    ints = tuple(np.zeros(a.shape, jax.dtypes.float0)  # mtlint: ok -- an integer input's cotangent IS a host float0 array; jnp has none
                 for a in (order, sizes, starts))
    return (dx, dw, *stacks) + ints


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, mask, idx, weights, wg, wu, wd, first: int, rows: int):
    """The held experts' part of the layer's output: the assignments
    that arrived, bucketed, in ceil(arrived / rows) batches of `rows`
    (`pool_rows`; whole tiles of the grouped matmul on the chip).

    x [T, d]; mask [T] (0 = padding, routed nowhere); idx, weights [T, k]
    from `route`; wg, wu [count, d, f], wd [count, f, d]: the gated MLPs
    W_d(SiLU(W_g x) * W_u x) of experts first .. first + count - 1.
    Returns (y [T, d], counters [8] float32 in the order of COUNTERS:
    assignments of real tokens, those that named a held expert, the
    largest and the mean group of a held expert, assignments that named
    a held expert and were not computed — always 0 —, this call, the
    batches it ran and the rows they computed)."""
    count = wg.shape[0]
    k = idx.shape[1]
    order, sizes, starts = _arrivals(idx, mask, first, count)
    arrived = jnp.sum(sizes)
    y, done = _held(x, weights, wg, wu, wd, order, sizes, starts, k, rows)
    trips = _trips(sizes, rows)
    counters = jnp.stack([
        jnp.sum(mask > 0) * k, arrived, jnp.max(sizes), arrived / count,
        arrived - done, 1, trips, trips * rows]).astype(jnp.float32)
    return y, counters


def gated_mlp(x, wg, wu, wd):
    """W_d(SiLU(W_g x) * W_u x) on every token: the dense feed-forward
    and the shared expert."""
    a = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * u).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32).astype(x.dtype)
