"""Plain float32 reference of configs/kimi-linear-48b-a3b.json: the forward
pass and the per-token cost of a decoder-only stack of the Kimi Linear
family (https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct;
arXiv 2510.26692), written from the layer equations, sharing no code with
marian_tpu/. No kernel, no chunking, no cache:

  KDA      the delta rule with a per-channel decay as the recurrence it
           is, one token at a time:
           S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
           o_t = S_t^T q_t / sqrt(dk)
  MLA      a dense masked softmax over [T, T] scores; the key of a head
           is [its own 128 channels from the latent, 64 channels shared
           by all heads], none of them rotated (mla_use_nope)
  experts  sigmoid scores over the whole router, top k, renormalised,
           scaled; then a loop over the HELD experts, each applied to
           every token and masked by its routing weight; the shared
           expert on every token. What the absent experts would add is
           left out, as in the program: the configuration is one chip's
           share of a layer (its `deployment`), and a share passes no
           gradient to the router.

`dims` is the configuration file (with a rehearsal's overrides); `params`
are the program's parameters under the program's names. Departures from
the published description are the file's `assumed`.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.reshape(-1)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mlp(x, wg, wu, wd):
    return (_silu(x @ wg) * (x @ wu)) @ wd


def _causal_conv(x, w):
    """y_t = sum_j w[j] x_{t - (K - 1) + j}: a depthwise filter over the
    current and the K - 1 previous positions, zeros before the first."""
    taps = w.shape[0]
    y = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
        y = y + shifted * w[j]
    return y


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(p, lp, dims, x):
    bsz, t, _ = x.shape
    h, dh = dims["kda_heads"], dims["kda_head_dim"]

    def branch(n):
        y = _silu(_causal_conv(x @ p[f"{lp}_kda_W{n}"],
                               p[f"{lp}_kda_conv_{n}"]))
        return y.reshape(bsz, t, h, dh)
    q, k, v = _unit(branch("q")), _unit(branch("k")), branch("v")
    rate = jnp.exp(p[f"{lp}_kda_A_log"]).reshape(1, 1, h, 1)
    step = jax.nn.softplus((x @ p[f"{lp}_kda_Wf1"]) @ p[f"{lp}_kda_Wf2"]
                           + p[f"{lp}_kda_dt_bias"]).reshape(bsz, t, h, dh)
    a = jnp.exp(-rate * step)                         # per key channel
    beta = jax.nn.sigmoid(x @ p[f"{lp}_kda_Wb"])      # [B, T, h]

    def token(s, xs):
        qt, kt, vt, at, bt = xs                       # [B,h,dh] .. [B,h]
        s = at[..., :, None] * s                      # Diag(a_t) S
        kts = jnp.einsum("bhk,bhkv->bhv", kt, s)      # k^T S
        s = s - bt[..., None, None] * kt[..., :, None] * kts[..., None, :] \
            + bt[..., None, None] * kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt) / math.sqrt(dh)

    s0 = jnp.zeros((bsz, h, dh, dh), jnp.float32)
    _, o = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, a, beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), p[f"{lp}_kda_out_norm_scale"],
             dims["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ p[f"{lp}_kda_Wg1"]) @ p[f"{lp}_kda_Wg2"])
    return (o.reshape(bsz, t, h * dh) * gate) @ p[f"{lp}_kda_Wo"]


def _mla(p, lp, dims, x, mask):
    bsz, t, _ = x.shape
    h, dn = dims["num_attention_heads"], dims["qk_nope_head_dim"]
    ds, dv, r = dims["qk_rope_head_dim"], dims["v_head_dim"], \
        dims["kv_lora_rank"]
    q = (x @ p[f"{lp}_mla_Wq"]).reshape(bsz, t, h, dn + ds)
    kva = x @ p[f"{lp}_mla_Wkva"]
    latent = _rms(kva[..., :r], p[f"{lp}_mla_kv_norm_scale"],
                  dims["rms_norm_eps"])
    shared = kva[..., r:]                              # [B, T, ds]
    kv = (latent @ p[f"{lp}_mla_Wkvb"]).reshape(bsz, t, h, dn + dv)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
              + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], shared)) \
        / math.sqrt(dn + ds)
    see = jnp.tril(jnp.ones((t, t), bool))[None, None] \
        & (mask[:, None, None, :] > 0)
    w = jax.nn.softmax(jnp.where(see, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., dn:])
    return o.reshape(bsz, t, h * dv) @ p[f"{lp}_mla_Wo"]


def _experts(p, lp, dims, x):
    scores = jax.nn.sigmoid(x @ p[f"{lp}_experts_router"])
    top, idx = jax.lax.top_k(scores, dims["num_experts_per_token"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True) \
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
    if dims["num_shared_experts"]:
        y = y + _mlp(x, p[f"{lp}_shared_Wg"], p[f"{lp}_shared_Wu"],
                     p[f"{lp}_shared_Wd"])
    return y


def forward_logits(params, dims, ids, mask):
    """Teacher-forced float32 logits [B, T, V]: position t sees the gold
    tokens before t (the embeddings shifted right behind a zero vector,
    scaled by sqrt(d): the program's conventions, `assumed`)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    d, eps = dims["hidden_size"], dims["rms_norm_eps"]
    x = p["decoder_Wemb"][ids] * math.sqrt(d)
    x = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    for l, kinds in enumerate(dims["layer_plan"], 1):
        mix, ffn = kinds.split(":")
        lp = f"decoder_l{l}"
        pre = _rms(x, p[f"{lp}_mix_norm_scale"], eps)
        x = x + (_kda(p, lp, dims, pre) if mix == "kda"
                 else _mla(p, lp, dims, pre, mask))
        pre = _rms(x, p[f"{lp}_ffn_norm_scale"], eps)
        x = x + (_mlp(pre, p[f"{lp}_ffn_Wg"], p[f"{lp}_ffn_Wu"],
                      p[f"{lp}_ffn_Wd"]) if ffn == "dense"
                 else _experts(p, lp, dims, pre))
    return _rms(x, p["decoder_top_norm_scale"], eps) \
        @ p["decoder_ff_logit_out_W"]


def token_costs(params, dims, _src_ids, _src_mask, trg_ids, trg_mask):
    """Cross-entropy of each gold token [B, T] (no label smoothing)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(trg_ids)
        logits = forward_logits(params, dims, ids,
                                jnp.asarray(trg_mask, jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
