"""Plain reference of the Vaswani et al. 2017 encoder-decoder as Marian
builds it: straightforward jax.numpy in float32, no kernels, no cache, no
batching tricks. Written from the published description; it reads the
program's parameter dictionary by name and shares no code with it.

Marian's departures from the paper, all followed here: sinusoidal
positions with the sines in the first half and the cosines in the second;
token embeddings scaled by sqrt(d); the decoder's input is the target
embeddings shifted right with a zero vector at position 0 (no BOS token);
post-norm sublayers ("dan": dropout, add, normalise) with LayerNorm
eps 1e-9; ReLU feed-forward; one embedding table tied to source, target
and output projection (no output bias unless the checkpoint has one).

On a TPU a float32 matmul runs in lower precision unless asked, so every
entry point runs under jax.default_matmul_precision("highest").
"""

import math

import jax
import jax.numpy as jnp


def _positions(length, dim):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    half = dim // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * (math.log(10000.0) / max(half - 1, 1)))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-9) * scale + bias


def _attention(p, prefix, q_in, kv_in, mask, heads):
    """mask [B, Tq, Tk] of 0/1; returns [B, Tq, d]."""
    def proj(x, n):
        return x @ p[f"{prefix}_W{n}"] + p[f"{prefix}_b{n}"]
    b, tq, d = q_in.shape
    dh = d // heads

    def split(x):
        return x.reshape(b, x.shape[1], heads, dh).transpose(0, 2, 1, 3)
    q, k, v = split(proj(q_in, "q")), split(proj(kv_in, "k")), \
        split(proj(kv_in, "v"))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    s = jnp.where(mask[:, None, :, :] > 0, s, -1e9)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, tq, d)
    return o @ p[f"{prefix}_Wo"] + p[f"{prefix}_bo"]


def _sublayer(p, ln_prefix, x, out):
    return _layer_norm(x + out, p[f"{ln_prefix}_ln_scale"],
                       p[f"{ln_prefix}_ln_bias"])


def _ffn(p, prefix, x):
    h = jax.nn.relu(x @ p[f"{prefix}_W1"] + p[f"{prefix}_b1"])
    return h @ p[f"{prefix}_W2"] + p[f"{prefix}_b2"]


def forward_logits(params, dims, src_ids, src_mask, trg_ids, trg_mask):
    """Teacher-forced float32 logits [B, Tt, V] for gold target ids."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    d, heads = dims["dim_emb"], dims["heads"]
    table = p["Wemb"]
    ts, tt = src_ids.shape[1], trg_ids.shape[1]
    x = table[src_ids] * math.sqrt(d) + _positions(ts, d)[None]
    enc_mask = jnp.broadcast_to(src_mask[:, None, :], (x.shape[0], ts, ts))
    for l in range(1, dims["enc_depth"] + 1):
        lp = f"encoder_l{l}"
        x = _sublayer(p, f"{lp}_self_Wo", x,
                      _attention(p, f"{lp}_self", x, x, enc_mask, heads))
        x = _sublayer(p, f"{lp}_ffn_ffn", x, _ffn(p, f"{lp}_ffn", x))
    enc = x
    y = table[trg_ids] * math.sqrt(d)
    y = jnp.pad(y, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
    y = y + _positions(tt, d)[None]
    causal = jnp.tril(jnp.ones((tt, tt), jnp.float32))
    self_mask = causal[None] * trg_mask[:, None, :]
    cross_mask = jnp.broadcast_to(src_mask[:, None, :],
                                  (y.shape[0], tt, ts))
    for l in range(1, dims["dec_depth"] + 1):
        lp = f"decoder_l{l}"
        y = _sublayer(p, f"{lp}_self_Wo", y,
                      _attention(p, f"{lp}_self", y, y, self_mask, heads))
        y = _sublayer(p, f"{lp}_context_Wo", y,
                      _attention(p, f"{lp}_context", y, enc, cross_mask,
                                 heads))
        y = _sublayer(p, f"{lp}_ffn_ffn", y, _ffn(p, f"{lp}_ffn", y))
    logits = y @ table.T
    if "decoder_ff_logit_out_b" in p:
        logits = logits + p["decoder_ff_logit_out_b"]
    return logits


def token_costs(params, dims, src_ids, src_mask, trg_ids, trg_mask):
    """Label-smoothed cross-entropy of each gold target token [B, Tt]
    under the reference, as the training cost counts it (Marian's
    smoothing: (1 - eps) * -log p(gold) - eps * mean over the vocabulary
    of log p), eps = dims["label_smoothing"]. Padded positions are not
    masked out here: the caller weighs them."""
    eps = float(dims["label_smoothing"])
    with jax.default_matmul_precision("highest"):
        logits = forward_logits(params, dims, jnp.asarray(src_ids),
                                jnp.asarray(src_mask, jnp.float32),
                                jnp.asarray(trg_ids),
                                jnp.asarray(trg_mask, jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        gold = jnp.take_along_axis(logp, jnp.asarray(trg_ids)[..., None],
                                   axis=-1)[..., 0]
        return -(1.0 - eps) * gold - eps * logp.mean(axis=-1)
