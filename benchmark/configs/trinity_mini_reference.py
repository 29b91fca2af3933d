"""Plain float32 reference of configs/trinity-mini.json: the forward pass
and the per-token cost of a decoder that mixes sliding-window and global
attention layers (https://huggingface.co/arcee-ai/Trinity-Mini, `afmoe`;
layer equations from the config's keys and, where the keys are silent,
from the family's public modelling code as the file's `assumed` retells
it), written from the equations, sharing no code with marian_tpu/. No
kernel, no cache, no batching tricks:

  block      a = x + N_post_attn(Attn(N_in(x)))
             y = a + N_post_mlp(FF(N_pre_mlp(a)))      four RMSNorms
  attention  q = u W_q as 32 heads of 128, k = u W_k and v = u W_v as 4,
             g = u W_gate as 32 x 128; q and k RMS-normed over a head's
             128 channels with a learned scale; in a `sliding_attention`
             layer q and k turned at their position p, the channel pair
             (i, i + 64) by the angle p theta^(-2i/128), and query i sees
             key j iff i - sliding_window < j <= i; in a `full_attention`
             layer q and k are NOT turned and query i sees key j iff
             j <= i. `visibility` writes either mask out as a [T, T]
             boolean from the layer's entry of `layer_types`. Query head
             h reads key/value head h // 8; a dense softmax over the
             [T, T] scores at scale 128^-0.5; Attn = W_o(o * sigmoid(g))
  feed-forward  the source's first `num_dense_layers` layers
             W_d(SiLU(W_g z) * W_u z) at `intermediate_size`; the others
             s = sigmoid(z W_r) over the whole router, the top k by
             s + expert_bias (a buffer at zero), w = s[top] / (sum s[top]
             + 1e-20) * route_scale, a loop over the HELD experts, each
             applied to every token and masked by its routing weight,
             plus the shared expert

`layers_built` names the source's layers that are built, in order: layer
l of the stack has the source's `layer_types[layers_built[l]]` and is
dense iff `layers_built[l] < num_dense_layers`.

Departures from the published description, each also under the file's
`assumed`: the program's conventions for positions (position t of a row
sees the gold tokens BEFORE t, behind a zero vector, and predicts y_t;
the rotation's positions are the shifted row's 0..T-1) and embeddings
times sqrt(d) (the source's mup_enabled); the absent experts' part is
left out (one chip's share of the layer) and a share passes no gradient
to its router; the balancing bias is a zero buffer and its update is not
run.

`dims` is the configuration file (with a rehearsal's overrides); `params`
are the program's parameters under the program's names (a window layer's
attention weights stand under `gqa`, as a global layer's).
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


def _mlp(x, wg, wu, wd):
    return (_silu(x @ wg) * (x @ wu)) @ wd


def layer_kinds(dims):
    """[(the source's layer type, is its feed-forward dense)] of the
    layers that are built."""
    return [(dims["layer_types"][l], l < dims["num_dense_layers"])
            for l in dims["layers_built"]][:dims["num_hidden_layers"]]


def visibility(width, layer_type, window):
    """[width, width] numpy bool: may the query at index q see the key at
    index k in a layer of this type."""
    q, k = np.arange(width)[:, None], np.arange(width)[None, :]
    if layer_type == "full_attention":
        return k <= q
    if layer_type != "sliding_attention":
        raise ValueError(f"layer type {layer_type!r}")
    return (k <= q) & (q - window < k)


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


def _attention(p, lp, dims, x, mask, layer_type):
    bsz, t, _ = x.shape
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    eps = dims["rms_norm_eps"]
    q = (x @ p[f"{lp}_gqa_Wq"]).reshape(bsz, t, h, dh)
    k = (x @ p[f"{lp}_gqa_Wk"]).reshape(bsz, t, hk, dh)
    v = (x @ p[f"{lp}_gqa_Wv"]).reshape(bsz, t, hk, dh)
    # assumed.gate: as wide as the heads' output, a sigmoid, before W_o
    gate = _sigmoid(x @ p[f"{lp}_gqa_Wgate"])
    # assumed.qk_norm: per head, before the rotation
    q = _rms(q, p[f"{lp}_gqa_q_norm_scale"], eps)
    k = _rms(k, p[f"{lp}_gqa_k_norm_scale"], eps)
    if layer_type == "sliding_attention":
        # assumed.rotation: the window layers alone
        q, k = _turn(q, dims["rope_theta"]), _turn(k, dims["rope_theta"])
    see = jnp.asarray(visibility(t, layer_type, dims["sliding_window"])
                      )[None] & (mask[:, None, :] > 0)       # [B, T, T]
    # query head h = g * (h / hk) + r reads key/value head g
    q = q.reshape(bsz, t, hk, h // hk, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(dh)
    w = jax.nn.softmax(jnp.where(see[:, None, None], scores, -1e30), axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w, v).reshape(bsz, t, h * dh)
    return (o * gate) @ p[f"{lp}_gqa_Wo"]


def _attention_by_head(p, lp, dims, x, mask, layer_type):
    """`_attention`, one query head at a time: head h alone is the same
    layer with W_q's, W_gate's and W_o's slices for h and W_k's and W_v's
    for h // 8, and the layer is the sum over its heads. For rows whose
    [T, T] scores do not fit 32 heads at once (34 GB at width 16384,
    1.07 GB a head)."""
    h, hk, dh = dims["num_attention_heads"], dims["num_key_value_heads"], \
        dims["head_dim"]
    d = x.shape[-1]
    one = dict(dims, num_attention_heads=1, num_key_value_heads=1)
    shared = jnp.arange(h) // (h // hk)

    def per_head(name, heads):
        return jnp.moveaxis(p[f"{lp}_gqa_{name}"].reshape(d, heads, dh), 1, 0)
    names = ("Wq", "Wk", "Wv", "Wgate", "Wo")
    slices = (per_head("Wq", h), per_head("Wk", hk)[shared],
              per_head("Wv", hk)[shared], per_head("Wgate", h),
              p[f"{lp}_gqa_Wo"].reshape(h, dh, d))

    def head(out, w):
        mine = {f"{lp}_gqa_{n}": a for n, a in zip(names, w)}
        for name in ("q_norm_scale", "k_norm_scale"):
            mine[f"{lp}_gqa_{name}"] = p[f"{lp}_gqa_{name}"]
        return out + _attention(mine, lp, one, x, mask, layer_type), None
    return jax.lax.scan(head, jnp.zeros_like(x), slices)[0]


def _attend(p, lp, dims, x, mask, layer_type):
    bsz, t, _ = x.shape
    at_once = 4 * bsz * dims["num_attention_heads"] * t * t
    return (_attention if at_once <= _SCORES_AT_ONCE
            else _attention_by_head)(p, lp, dims, x, mask, layer_type)


def _scores(p, lp, dims, x, precision=None):
    """The router's scores over all experts, and which the top k name:
    a sigmoid of each logit; the top k by score + expert_bias, a buffer
    at zero (assumed.expert_bias), so by score."""
    s = _sigmoid(jnp.matmul(x, p[f"{lp}_experts_router"],
                            precision=precision))
    top, idx = jax.lax.top_k(s, dims["num_experts_per_tok"])
    return top, idx


def _experts(p, lp, dims, x):
    top, idx = _scores(p, lp, dims, x)
    # route_norm, with the family's 1e-20 (assumed.route_norm)
    weight = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * dims["route_scale"]
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
    if dims["num_shared_experts"]:
        y = y + _mlp(x, p[f"{lp}_shared_Wg"], p[f"{lp}_shared_Wu"],
                     p[f"{lp}_shared_Wd"])
    return y


def _feed_forward(p, lp, dims, z, dense):
    return _mlp(z, p[f"{lp}_ffn_Wg"], p[f"{lp}_ffn_Wu"], p[f"{lp}_ffn_Wd"]) \
        if dense else _experts(p, lp, dims, z)


def _block(p, lp, dims, x, mask, layer_type, dense):
    """assumed.norms: a norm before each branch and one on its output."""
    eps = dims["rms_norm_eps"]
    a = x + _rms(_attend(p, lp, dims,
                         _rms(x, p[f"{lp}_mix_norm_scale"], eps), mask,
                         layer_type),
                 p[f"{lp}_mix_post_norm_scale"], eps)
    return a + _rms(_feed_forward(
        p, lp, dims, _rms(a, p[f"{lp}_ffn_norm_scale"], eps), dense),
        p[f"{lp}_ffn_post_norm_scale"], eps)


def _input(p, dims, ids):
    """assumed.positions: embeddings times sqrt(d), shifted right behind
    a zero vector."""
    e = p["decoder_Wemb"][ids] * math.sqrt(dims["hidden_size"])
    return jnp.pad(e, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def token_costs(params, dims, _src_ids, _src_mask, trg_ids, trg_mask):
    """[B, T]: the cross-entropy of the gold token y_t at position t; no
    label smoothing."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        ids = jnp.asarray(trg_ids)
        mask = jnp.asarray(trg_mask, jnp.float32)
        h = _input(p, dims, ids)
        for l, (layer_type, dense) in enumerate(layer_kinds(dims), 1):
            h = _block(p, f"decoder_l{l}", dims, h, mask, layer_type, dense)
        logits = _rms(h, p["decoder_top_norm_scale"], dims["rms_norm_eps"]) \
            @ p["decoder_ff_logit_out_W"]
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
    eps = dims["rms_norm_eps"]

    def half(layer_type, dense):
        @jax.jit
        def mix(q, x, mask):
            a = x + _rms(_attention_by_head(
                q, "l", dims, _rms(x, q["l_mix_norm_scale"], eps), mask,
                layer_type), q["l_mix_post_norm_scale"], eps)
            return a, _rms(a, q["l_ffn_norm_scale"], eps)

        @jax.jit
        def feed_forward(q, a, z):
            return a + _rms(_feed_forward(q, "l", dims, z, dense),
                            q["l_ffn_post_norm_scale"], eps)
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
