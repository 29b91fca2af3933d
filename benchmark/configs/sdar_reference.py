"""Plain float32 reference of configs/sdar-30b-a3b.json: the forward pass
and the per-token cost of a Qwen3-style sparse decoder trained by
diffusion over blocks (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat;
layer equations from the config's keys and the Qwen3 family's, arXiv:
2505.09388; the objective is Block Diffusion's training form,
arXiv:2503.09573, with MDLM's / LLaDA's per-row noise level), written from
the equations, sharing no code with marian_tpu/. No kernel, no cache, no
batching tricks:

  attention  q = x W_q as 32 heads of 128, k = x W_k and v = x W_v as 4;
             q and k RMS-normed over a head's 128 channels with a learned
             scale; q and k turned at their position p, the channel pair
             (i, i + 64) by the angle p theta^(-2i/128); query head h
             reads key/value head h // 8; a dense softmax over the
             [2T, 2T] scores at scale 128^-0.5 under the mask below
  feed-forward  softmax over the whole router, the top k, renormalised to
             sum 1 (norm_topk_prob), a loop over the HELD experts, each
             applied to every token and masked by its routing weight; no
             shared expert
  objective  a row's real positions are each replaced by the mask token
             with the row's probability t; the stack runs over [noised ;
             clean], 2T indices, index p < T the noised copy of position
             p and index T + p its clean copy, both halves turned at
             positions 0..T-1 and NOT shifted; position p's block is
             p // block_length. `visibility` writes the mask out, index
             by index: a query sees a key iff both are noised and in the
             same block, or the query is noised, the key clean and of an
             EARLIER block, or both are clean and the key's block is the
             query's or earlier; padded keys are seen by nobody. The cost
             of the gold token at a MASKED position p is the noised
             half's cross-entropy at p over t, and 0 elsewhere.

Departures from the published description, each also under the file's
`assumed`: the block length, the noise (a level per row, t = eps +
(1 - eps) u; Block Diffusion draws one per block) and the mask token's id
are this file's; the program's convention of embeddings times sqrt(d); the
absent experts' part is left out (one chip's share of the layer) and a
share passes no gradient to its router; no auxiliary balancing loss.

`dims` is the configuration file (with a rehearsal's overrides); `params`
are the program's parameters under the program's names.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.reshape(-1)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mlp(x, wg, wu, wd):
    return (_silu(x @ wg) * (x @ wu)) @ wd


def visibility(width, block):
    """[2 width, 2 width] numpy bool: may the query at index q see the
    key at index k, from the three sentences of the head."""
    index = np.arange(2 * width)
    noised = index < width
    blocks = np.where(noised, index, index - width) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_block, k_block = blocks[:, None], blocks[None, :]
    return ((q_noised & k_noised & (k_block == q_block))
            | (q_noised & ~k_noised & (k_block < q_block))
            | (~q_noised & ~k_noised & (k_block <= q_block)))


def _turn(x, positions, theta):
    """x [B, L, heads, dim] at `positions` [L]: the pair (i, i + dim/2)
    turned by position theta^(-2i/dim)."""
    half = x.shape[-1] // 2
    rate = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(rate, jnp.float32)[None, :]            # [L, half]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, lp, dims, x, see, positions):
    bsz, length, _ = x.shape
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    q = (x @ p[f"{lp}_gqa_Wq"]).reshape(bsz, length, h, dh)
    k = (x @ p[f"{lp}_gqa_Wk"]).reshape(bsz, length, hk, dh)
    v = (x @ p[f"{lp}_gqa_Wv"]).reshape(bsz, length, hk, dh)
    q = _turn(_rms(q, p[f"{lp}_gqa_q_norm_scale"], eps), positions, theta)
    k = _turn(_rms(k, p[f"{lp}_gqa_k_norm_scale"], eps), positions, theta)
    # query head h = g * (h / hk) + r reads key/value head g
    q = q.reshape(bsz, length, hk, h // hk, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(dh)
    w = jax.nn.softmax(
        jnp.where(see[:, None, None], scores, -1e30), axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w, v)
    return o.reshape(bsz, length, h * dh) @ p[f"{lp}_gqa_Wo"]


def _experts(p, lp, dims, x):
    scores = jax.nn.softmax(x @ p[f"{lp}_experts_router"], axis=-1)
    top, idx = jax.lax.top_k(scores, dims["num_experts_per_tok"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True)
    if dims["num_experts"] < dims["router_width"]:
        # one share's part of the router's gradient is not the router's
        # gradient: a share does not train the router (`assumed`)
        weight = jax.lax.stop_gradient(weight)
    y = jnp.zeros_like(x)
    for i in range(dims["num_experts"]):               # the held ones
        mine = jnp.sum(jnp.where(idx == dims["experts_first"] + i,
                                 weight, 0.0), axis=-1)
        y = y + mine[..., None] * _mlp(
            x, p[f"{lp}_experts_Wg"][i], p[f"{lp}_experts_Wu"][i],
            p[f"{lp}_experts_Wd"][i])
    return y


def evaluation_noise(width):
    """(masked [width] bool, t): the rule without a key, `assumed`'s
    `evaluation`."""
    return jax.random.uniform(jax.random.key(0), (width,)) < 0.5, 0.5


def noised_costs(params, dims, ids, mask, masked, t):
    """[B, T]: masked[b, p] x CE(the noised half's logits at p, ids[b, p])
    / t[b], for `masked` [B, T] (1 where the position holds the mask
    token; real positions only) and the rows' noise levels `t` [B]."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    d, eps = dims["hidden_size"], dims["rms_norm_eps"]
    ids = jnp.asarray(ids)
    mask = jnp.asarray(mask, jnp.float32)
    masked = jnp.asarray(masked, jnp.float32) * mask
    width = ids.shape[1]
    noised = jnp.where(masked > 0, dims["mask_token_id"], ids)
    both = jnp.concatenate([noised, ids], axis=1)
    real = jnp.concatenate([mask, mask], axis=1) > 0
    see = jnp.asarray(visibility(width, dims["block_length"]))[None] \
        & real[:, None, :]
    positions = np.concatenate([np.arange(width), np.arange(width)])
    h = p["decoder_Wemb"][both] * math.sqrt(d)
    for l in range(1, dims["num_hidden_layers"] + 1):
        lp = f"decoder_l{l}"
        h = h + _attention(p, lp, dims,
                           _rms(h, p[f"{lp}_mix_norm_scale"], eps), see,
                           positions)
        h = h + _experts(p, lp, dims,
                         _rms(h, p[f"{lp}_ffn_norm_scale"], eps))
    logits = _rms(h[:, :width], p["decoder_top_norm_scale"], eps) \
        @ p["decoder_ff_logit_out_W"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    return masked * ce / jnp.asarray(t, jnp.float32).reshape(-1, 1)


def token_costs(params, dims, _src_ids, _src_mask, trg_ids, trg_mask):
    """[B, T]: what the gold token at p costs under the evaluation rule:
    twice its cross-entropy where `evaluation_noise` masks p, else 0."""
    with jax.default_matmul_precision("highest"):
        rows, width = np.shape(trg_ids)
        masked, t = evaluation_noise(width)
        return noised_costs(
            params, dims, trg_ids, trg_mask,
            jnp.broadcast_to(masked[None], (rows, width)),
            jnp.full((rows,), t, jnp.float32))
