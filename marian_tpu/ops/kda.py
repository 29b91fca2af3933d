"""The delta rule with a per-channel decay (KDA; Kimi Linear, arXiv
2510.26692, and the gated delta rule of arXiv 2412.06464): a linear-
attention mixing layer whose state per head is a [dk, dv] matrix

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = scale * S_t^T q_t

with a_t in (0, 1]^dk given as its logarithm g_t <= 0 and b_t in [0, 1].

Three forms of the same function, q/k/g [B, H, T, dk], v [B, H, T, dv],
b [B, H, T] -> o [B, H, T, dv]:

  kda_recurrent   the recurrence as written, token by token: the oracle,
                  in tests only
  kda_chunked     chunks of 64: inside a chunk the WY / UT transform
                  (`chunk_terms`: pairwise decays, a triangular solve),
                  between chunks a state carry (`state_carry`, a
                  lax.scan); in jnp, what runs wherever there is no TPU
                  (tier-1, the benchmark's rehearsals) and what the
                  kernels are held against
  kda_chunked with `terms=` and `carry=` from ops/pallas/   the same two
                  steps as Pallas TPU kernel pairs, what
                  models/layer_plan.py picks on a TPU: kda_prep.py
                  (`kda_prep_fwd`, `kda_prep_bwd`) for the preparation,
                  kda_chunk.py (`kda_chunk_fwd`, `kda_chunk_bwd`) for the
                  carry; the six terms cross HBM in float32 between them

Everything is float32 and the state accumulates in float32. No
exponential of a positive number is ever taken: a decay between two
positions of a chunk is exp(G_r - G_i) with r >= i, formed either
directly (inside a 16-row sub-block) or as exp(G_r - ref) exp(ref - G_i)
around a reference row between the two, so a channel that forgets
everything in a few steps underflows to 0 and nothing overflows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16                 # rows whose pairwise decays are formed directly
assert CHUNK == 4 * SUB
_HI = jax.lax.Precision.HIGHEST
# the chunk's own small products (pairwise decays, the triangular solve):
# three bfloat16 passes on the MXU, ~16 bits, at half HIGHEST's cost
_MID = jax.lax.Precision.HIGH


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                               + eps)


def kda_recurrent(q, k, v, g, b, scale):
    """The recurrence, one token at a time (float32)."""
    q, k, v, g, b = (x.astype(jnp.float32) for x in (q, k, v, g, b))

    def step(s, x):
        qt, kt, vt, gt, bt = x                   # [B,H,dk] .. [B,H]
        s = s * jnp.exp(gt)[..., None]           # Diag(a_t) S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, scale * jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    bsz, h, _, dk = k.shape
    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, b))
    _, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)


def _pairwise_direct(xs, ks, gs):
    """The SUB x SUB blocks on the diagonal, decays formed directly:
    [..., n, SUB, d] -> [..., n, SUB, SUB]."""
    low = jnp.tril(jnp.ones((SUB, SUB), bool))
    diff = gs[..., :, None, :] - gs[..., None, :, :]       # [.., n, r, i, d]
    decay = jnp.exp(jnp.where(low[..., None], diff, -jnp.inf))
    return jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * decay,
                   axis=-1)


def _block_masks(c):
    """[c, c] masks of the SUB-row blocks: on the block diagonal, and
    strictly below it."""
    blk = jnp.arange(c) // SUB
    return blk[:, None] == blk[None, :], blk[:, None] > blk[None, :]


def _pairwise(x, k, gc):
    """[..., C, C] lower triangle (diagonal included) of
    M[r, i] = sum_c x[r, c] k[i, c] exp(gc[r, c] - gc[i, c]), r >= i,
    zeros above; x, k, gc [..., C, d], gc the inclusive cumulative log
    decay of the chunk (non-increasing along C). Blocks on the diagonal
    form their decays directly; a block below it splits the decay around
    its first row's, exp(gc_r - ref) exp(ref - gc_i), both <= 1."""
    *lead, c, d = x.shape
    n = c // SUB
    xs, ks, gs = (a.reshape(*lead, n, SUB, d) for a in (x, k, gc))
    diag = _pairwise_direct(xs, ks, gs)                    # [.., n, S, S]
    on_diag = jnp.einsum("...nri,nm->...nrmi", diag,
                         jnp.eye(n, dtype=x.dtype)).reshape(*lead, c, c)
    ref = gs[..., :, :1, :]                                # [.., n, 1, d]
    left = xs * jnp.exp(gs - ref)                          # [.., n, S, d]
    # every column against every row block's reference; the columns of a
    # row block's own and later blocks (exponent > 0) are masked out
    right = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref - gc[..., None, :, :], 0.0))                   # [.., n, C, d]
    below = jnp.einsum("...nrd,...nid->...nri", left, right,
                       precision=_MID).reshape(*lead, c, c)
    return on_diag + jnp.where(_block_masks(c)[1], below, 0.0)


def _solve_unit_lower(a, rhs):
    """(I + a)^-1 rhs for strictly lower triangular a [..., C, C], in
    whole-chunk matmuls. With D the blocks on the diagonal (nilpotent:
    D^SUB = 0) and L the rest, I + a = (I + D)(I + M), M = (I + D)^-1 L,
    and M, block strictly lower, has M^(C/SUB) = 0:
      (I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8)
      (I + M)^-1 = (I - M)(I + M^2)                     (C / SUB = 4)"""
    c = a.shape[-1]
    assert c // SUB == 4 and SUB == 16
    on_diag, _ = _block_masks(c)
    eye = jnp.eye(c, dtype=a.dtype)
    mm = lambda x, y: jnp.matmul(x, y, precision=_MID)      # noqa: E731
    d = jnp.where(on_diag, a, 0.0)
    inv, power = eye - d, d
    for _ in range(3):                                     # D^2, D^4, D^8
        power = mm(power, power)
        inv = mm(inv, eye + power)
    m = mm(inv, a - d)
    y = mm(inv, rhs)
    y = y - mm(m, y)
    return y + mm(mm(m, m), y)


def chunk_terms(q, k, v, g, b, scale, chunk=CHUNK):
    """What the state carry consumes, per chunk n of `chunk` positions
    (T a multiple of it), all float32:

      qg [B,H,N,C,dk]  scale * q decayed from the chunk's start
      wk [B,H,N,C,dk]  T (b * k * decay from the start),  T = (I + A)^-1
      wv [B,H,N,C,dv]  T (b * v)
      kd [B,H,N,C,dk]  k decayed to the chunk's end
      gc [B,H,N,1,dk]  the chunk's whole decay
      p  [B,H,N,C,C]   scale * (q k^T with the decay between the two),
                       lower triangle with its diagonal

    with A[r, i] = b_r * (k_r k_i^T with the decay between), i < r."""
    bsz, h, t, dk = k.shape
    n = t // chunk
    q, k, v, g = (x.astype(jnp.float32).reshape(bsz, h, n, chunk, -1)
                  for x in (q, k, v, g))
    b = b.astype(jnp.float32).reshape(bsz, h, n, chunk, 1)
    gcum = jnp.cumsum(g, axis=3)
    gend = gcum[..., -1:, :]
    strict = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    a = b * _pairwise(k, k, gcum) * strict
    solved = _solve_unit_lower(a, jnp.concatenate(
        [b * k * jnp.exp(gcum), b * v], axis=-1))
    return (scale * q * jnp.exp(gcum), solved[..., :dk], solved[..., dk:],
            k * jnp.exp(gend - gcum), jnp.exp(gend),
            scale * _pairwise(q, k, gcum))


def state_carry(qg, wk, wv, kd, gc, p):
    """The recurrence between chunks, in jnp (the kernel's oracle). The
    state is kept transposed, St [dv, dk], as the kernel keeps it:

      U = wv - wk St^T;  O = qg St^T + p U;  St' = gc * St + U^T kd."""
    def step(st, x):
        qg_, wk_, wv_, kd_, gc_, p_ = x
        u = wv_ - jnp.einsum("bhck,bhvk->bhcv", wk_, st, precision=_HI)
        o = jnp.einsum("bhck,bhvk->bhcv", qg_, st, precision=_HI) \
            + jnp.einsum("bhci,bhiv->bhcv", p_, u, precision=_HI)
        st = gc_ * st + jnp.einsum("bhcv,bhck->bhvk", u, kd_, precision=_HI)
        return st, o

    bsz, h, _, _, dk = qg.shape
    st0 = jnp.zeros((bsz, h, wv.shape[-1], dk), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (qg, wk, wv, kd, gc, p))
    _, o = jax.lax.scan(step, st0, xs)
    return jnp.moveaxis(o, 0, 2)


def kda_chunked(q, k, v, g, b, scale, chunk=CHUNK, carry=state_carry,
                terms=chunk_terms):
    """The chunked form. T is padded to a multiple of `chunk` with
    positions that change nothing (g = 0, b = 0, zero q, k, v). `terms`
    is the preparation inside a chunk and `carry` the recurrence between
    chunks: `chunk_terms` and `state_carry`, or the Pallas kernels'
    (ops/pallas/kda_prep.py :: kda_chunk_terms, ops/pallas/kda_chunk.py
    :: kda_state_carry)."""
    t = k.shape[2]
    pad = -t % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for x in (q, k, v, g))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad)))
    o = carry(*terms(q, k, v, g, b, scale, chunk))
    bsz, h = o.shape[:2]
    return o.reshape(bsz, h, t + pad, -1)[:, :, :t]
