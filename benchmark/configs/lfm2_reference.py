"""Plain float32 reference of configs/lfm2-24b-a2b.json: the forward pass
and the per-token cost of a decoder whose layers are mostly doubly gated
short convolutions with a grouped-query attention layer every fourth
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, `lfm2_moe`; layer equations
from the config's keys and, where the keys are silent, from the family's
public modelling code as the file's `assumed` retells it), written from the
equations, sharing no code with marian_tpu/. No kernel, no cache, no
batching tricks:

  block      a = x + Op(N_op(x));  y = a + FF(N_ffn(a))      two RMSNorms
  conv       [B, C, h] = u W_in split three ways along the channels, in
             this order; z = B * h; c_t = w_0 z_{t-2} + w_1 z_{t-1} +
             w_2 z_t channel by channel, what lies before the row's first
             position counting as zero (`conv_L_cache` 3 taps, no bias);
             Op = (C * c) W_out. Written as its three shifted products
  full_attention  q = u W_q as 32 heads of 64, k = u W_k and v = u W_v as
             8; q and k RMS-normed over a head's 64 channels with a
             learned scale, THEN turned at their position p, the channel
             pair (i, i + 32) by the angle p theta^(-2i/64); query i sees
             key j iff j <= i; query head h reads key/value head h // 4; a
             dense softmax over the [T, T] scores at scale 64^-0.5;
             Op = o W_o. No gate
  feed-forward  the source's first `num_dense_layers` layers
             W_2(SiLU(W_1 z) * W_3 z) at `intermediate_size`; the others
             s = sigmoid(z W_r) over the whole router, the top k by
             s + expert_bias (the parameters' `_experts_bias`, a buffer
             no gradient reaches; by s where they hold none), w = s[top]
             / (sum s[top] + 1e-6) * routed_scaling_factor with the
             scores WITHOUT the bias, a loop over the HELD
             experts, each applied to every token and masked by its
             routing weight; no shared expert

One final RMSNorm, then the output table, which IS the input table
(`assumed.tied`). `layers_built` names the source's layers that are built,
in order: layer l of the stack has the source's
`layer_types[layers_built[l]]` and is dense iff
`layers_built[l] < num_dense_layers`.

Departures from the published description, each also under the file's
`assumed`: the program's conventions for positions (position t of a row
sees the gold tokens BEFORE t, behind a zero vector, and predicts y_t; the
rotation's positions are the shifted row's 0..T-1) and embeddings times
sqrt(d); the absent experts' part is left out (one chip's share of the
layer) and a share passes no gradient to its router. The selection bias
is READ here; moving it by the load is the trainer's, between updates,
and no part of a forward pass (it is zero at the check, before any update).

`dims` is the configuration file (with a rehearsal's overrides); `params`
are the program's parameters under the program's names.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# a row's [heads, T, T] float32 scores above this many bytes are computed
# a head at a time
_SCORES_AT_ONCE = 2 ** 30


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.reshape(-1)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _mlp(x, w1, w3, w2):
    return (_silu(x @ w1) * (x @ w3)) @ w2


def layer_kinds(dims):
    """[(the source's layer type, is its feed-forward dense)] of the
    layers that are built."""
    return [(dims["layer_types"][l], l < dims["num_dense_layers"])
            for l in dims["layers_built"]][:dims["num_hidden_layers"]]


def _conv(p, lp, dims, u):
    """The doubly gated short convolution over u [B, T, d]."""
    d, taps = dims["hidden_size"], p[f"{lp}_conv_taps"]
    assert taps.shape[0] == dims["conv_L_cache"] == 3
    bch = u @ p[f"{lp}_conv_Win"]
    b, c, h = bch[..., :d], bch[..., d:2 * d], bch[..., 2 * d:]
    z = b * h

    def back(n):                         # z_{t-n}, zero before the row
        return jnp.pad(z, ((0, 0), (n, 0), (0, 0)))[:, :z.shape[1]]
    conv = taps[0] * back(2) + taps[1] * back(1) + taps[2] * z
    return (c * conv) @ p[f"{lp}_conv_Wout"]


def _turn(x, theta):
    """x [B, T, heads, dim] at positions 0..T-1: the pair (i, i + dim/2)
    turned by position theta^(-2i/dim); float32 angles."""
    half = x.shape[-1] // 2
    rate = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(rate, jnp.float32)[None, :]            # [T, half]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, lp, dims, x, mask):
    bsz, t, _ = x.shape
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    eps = dims["norm_eps"]
    q = (x @ p[f"{lp}_gqa_Wq"]).reshape(bsz, t, h, dh)
    k = (x @ p[f"{lp}_gqa_Wk"]).reshape(bsz, t, hk, dh)
    v = (x @ p[f"{lp}_gqa_Wv"]).reshape(bsz, t, hk, dh)
    # assumed.qk_norm: per head, before the rotation
    q = _rms(q, p[f"{lp}_gqa_q_norm_scale"], eps)
    k = _rms(k, p[f"{lp}_gqa_k_norm_scale"], eps)
    theta = dims["rope_parameters"]["rope_theta"]
    q, k = _turn(q, theta), _turn(k, theta)
    see = jnp.asarray(np.tril(np.ones((t, t), bool)))[None] \
        & (mask[:, None, :] > 0)                             # [B, T, T]
    # query head h = g * (h / hk) + r reads key/value head g
    q = q.reshape(bsz, t, hk, h // hk, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(dh)
    w = jax.nn.softmax(jnp.where(see[:, None, None], scores, -1e30), axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w, v).reshape(bsz, t, h * dh)
    return o @ p[f"{lp}_gqa_Wo"]


def _attention_by_head(p, lp, dims, x, mask):
    """`_attention`, one query head at a time: head h alone is the same
    layer with W_q's and W_o's slices for h and W_k's and W_v's for
    h // 4, and the layer is the sum over its heads. For rows whose
    [T, T] scores do not fit 32 heads at once (8.6 GB at width 8192)."""
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    d = x.shape[-1]
    one = dict(dims, num_attention_heads=1, num_key_value_heads=1)
    shared = jnp.arange(h) // (h // hk)

    def per_head(name, heads):
        return jnp.moveaxis(p[f"{lp}_gqa_{name}"].reshape(d, heads, dh), 1, 0)
    names = ("Wq", "Wk", "Wv", "Wo")
    slices = (per_head("Wq", h), per_head("Wk", hk)[shared],
              per_head("Wv", hk)[shared],
              p[f"{lp}_gqa_Wo"].reshape(h, dh, d))

    def head(out, w):
        mine = {f"{lp}_gqa_{n}": a for n, a in zip(names, w)}
        for name in ("q_norm_scale", "k_norm_scale"):
            mine[f"{lp}_gqa_{name}"] = p[f"{lp}_gqa_{name}"]
        return out + _attention(mine, lp, one, x, mask), None
    return jax.lax.scan(head, jnp.zeros_like(x), slices)[0]


def _operator(p, lp, dims, u, mask, layer_type, by_head=None):
    if layer_type == "conv":
        return _conv(p, lp, dims, u)
    if layer_type != "full_attention":
        raise ValueError(f"layer type {layer_type!r}")
    bsz, t, _ = u.shape
    if by_head is None:
        by_head = 4 * bsz * dims["num_attention_heads"] * t * t \
            > _SCORES_AT_ONCE
    return (_attention_by_head if by_head else _attention)(
        p, lp, dims, u, mask)


def _scores(p, lp, dims, x, precision=None):
    """The router's scores over all experts, and which the top k name:
    a sigmoid of each logit; the top k by score + expert_bias, each at
    its score without the bias (assumed.expert_bias)."""
    s = _sigmoid(jnp.matmul(x, p[f"{lp}_experts_router"],
                            precision=precision))
    bias = p.get(f"{lp}_experts_bias")
    if bias is None:
        return jax.lax.top_k(s, dims["num_experts_per_tok"])
    idx = jax.lax.top_k(s + bias.reshape(-1), dims["num_experts_per_tok"])[1]
    return jnp.take_along_axis(s, idx, axis=-1), idx


def _experts(p, lp, dims, x):
    top, idx = _scores(p, lp, dims, x)
    # norm_topk_prob, with the family's 1e-6 (assumed.route_norm)
    weight = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6) \
        * dims["routed_scaling_factor"]
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


def _feed_forward(p, lp, dims, z, dense):
    return _mlp(z, p[f"{lp}_ffn_Wg"], p[f"{lp}_ffn_Wu"], p[f"{lp}_ffn_Wd"]) \
        if dense else _experts(p, lp, dims, z)


def _block(p, lp, dims, x, mask, layer_type, dense):
    eps = dims["norm_eps"]
    a = x + _operator(p, lp, dims, _rms(x, p[f"{lp}_mix_norm_scale"], eps),
                      mask, layer_type)
    return a + _feed_forward(
        p, lp, dims, _rms(a, p[f"{lp}_ffn_norm_scale"], eps), dense)


def _input(p, dims, ids):
    """assumed.positions: embeddings times sqrt(d), shifted right behind
    a zero vector."""
    e = p["decoder_Wemb"][ids] * math.sqrt(dims["hidden_size"])
    return jnp.pad(e, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def token_costs(params, dims, _src_ids, _src_mask, trg_ids, trg_mask):
    """[B, T]: the cross-entropy of the gold token y_t at position t; no
    label smoothing. The output table is the input table (assumed.tied)."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        ids = jnp.asarray(trg_ids)
        mask = jnp.asarray(trg_mask, jnp.float32)
        h = _input(p, dims, ids)
        for l, (layer_type, dense) in enumerate(layer_kinds(dims), 1):
            h = _block(p, f"decoder_l{l}", dims, h, mask, layer_type, dense)
        logits = _rms(h, p["decoder_top_norm_scale"], dims["norm_eps"]) \
            @ p["decoder_Wemb"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


def routed_layers(params, dims, batches):
    """The stack walked layer by layer for whoever PLACES the held experts
    (benchmark/drivers/train.py::place_held_experts), a generator over
    `batches`, a list of (ids [B, T], mask [B, T]): before each expert
    layer it yields (the router's name, arrivals [len(batches),
    router_width]: how many of a batch's real positions' top-k choices
    named each expert) and is SENT the router to go on with (its columns
    permuted), since a later layer's input holds the placed experts'
    part. The arrays are float32 and the matmuls run at the device's
    default precision (routing is counted here, no cost is read); the
    router's logits at full precision; attention a head at a time."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = dims["norm_eps"]

    def half(layer_type, dense):
        @jax.jit
        def mix(q, x, mask):
            a = x + _operator(q, "l", dims, _rms(x, q["l_mix_norm_scale"],
                                                 eps), mask, layer_type,
                              by_head=True)
            return a, _rms(a, q["l_ffn_norm_scale"], eps)

        @jax.jit
        def feed_forward(q, a, z):
            return a + _feed_forward(q, "l", dims, z, dense)
        return mix, feed_forward

    @jax.jit
    def arrivals(q, z, mask):
        idx = _scores(q, "l", dims, z, jax.lax.Precision.HIGHEST)[1]
        hot = jax.nn.one_hot(idx, dims["router_width"],
                             dtype=jnp.float32).sum(axis=-2)
        return jnp.einsum("bt,bte->e", mask, hot,
                          precision=jax.lax.Precision.HIGHEST)[None]

    walks = [[_input(p, dims, jnp.asarray(ids)),
              jnp.asarray(mask, jnp.float32), None] for ids, mask in batches]
    for l, (layer_type, dense) in enumerate(layer_kinds(dims), 1):
        lp = f"decoder_l{l}"
        q = {"l" + k[len(lp):]: v for k, v in p.items()
             if k.startswith(lp + "_")}
        mix, feed_forward = half(layer_type, dense)
        for w in walks:
            w[0], w[2] = mix(q, w[0], w[1])
        if not dense:
            router = yield f"{lp}_experts_router", jnp.concatenate(
                [arrivals(q, w[2], w[1]) for w in walks])
            q["l_experts_router"] = jnp.asarray(router, jnp.float32)
        for w in walks:
            w[0] = feed_forward(q, w[0], w[2])
