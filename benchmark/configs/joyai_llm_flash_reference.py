"""Plain float32 reference of configs/joyai-llm-flash.json: the forward pass
and the per-token cost of a DeepSeek-V3-style decoder with its
multi-token-prediction module
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash; layer equations from
the config's keys and arXiv:2412.19437, 2.1.1 and 2.2), written from the
equations, sharing no code with marian_tpu/. No kernel, no cache, no
batching tricks:

  attention  c_q = RMSNorm(x W_qa), q = c_q W_qb, a head's query
             [128 unrotated | 64 rotated]; [c_kv | k_r] = x W_kva,
             c_kv = RMSNorm(c_kv), [k_n | v] = c_kv W_kvb per head, k_r
             ONE 64-channel vector for all heads; the rotated channels
             turn pair by pair, (2i, 2i + 1) at position t by the angle
             t theta^(-2i/64) (rope_interleave; rope_scaling null, so no
             further scale); a dense masked softmax over [T, T] scores at
             scale 192^-0.5
  feed-forward  the first `first_k_dense_replace` layers W_d(SiLU(W_g x)
             * W_u x) at `intermediate_size`; the others sigmoid scores
             over the whole router, top k, renormalised, scaled, a loop
             over the HELD experts, each applied to every token and
             masked by its routing weight, plus the shared expert
  prediction module k (arXiv:2412.19437, eq. 21-24)  u_t = W_eh
             [RMSNorm(e(y_{t+k-1})) ; RMSNorm(h_t)] with h the stack's
             output BEFORE its last norm (module k - 1's for k > 1), one
             block, a norm of its own, the SHARED output table; its cost
             of the gold y_{t+k}

Departures from the published description, each also under the file's
`assumed`: the program's conventions for positions (position t of a row
sees the gold tokens BEFORE t, behind a zero vector, and predicts y_t, so
module 1 at t is given e(y_t) and predicts y_{t+1}), embeddings times
sqrt(d); the absent experts' part is left out (one chip's share of the
layer) and a share passes no gradient to its router; the balancing bias
is a zero buffer; the costs are sums, main + lambda x module, lambda 0.3,
and `token_costs` books a module's cost on the token it predicts.

`dims` is the configuration file (with a rehearsal's overrides); `params`
are the program's parameters under the program's names.
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


def _turn(x, theta):
    """x [B, T, ..., dim]: the pair (2i, 2i + 1) of position t turned by
    t theta^(-2i/dim), pair by pair."""
    t, dim = x.shape[1], x.shape[-1]
    pos = jnp.arange(t, dtype=jnp.float32).reshape(
        (1, t) + (1,) * (x.ndim - 3))
    out = []
    for i in range(dim // 2):
        angle = pos * (float(theta) ** (-2.0 * i / dim))
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out += [a * jnp.cos(angle) - b * jnp.sin(angle),
                a * jnp.sin(angle) + b * jnp.cos(angle)]
    return jnp.stack(out, axis=-1)


def _attention(p, lp, dims, x, mask):
    bsz, t, _ = x.shape
    h, dn = dims["num_attention_heads"], dims["qk_nope_head_dim"]
    dr, dv, r = dims["qk_rope_head_dim"], dims["v_head_dim"], \
        dims["kv_lora_rank"]
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    cq = _rms(x @ p[f"{lp}_mla_Wqa"], p[f"{lp}_mla_q_norm_scale"], eps)
    q = (cq @ p[f"{lp}_mla_Wqb"]).reshape(bsz, t, h, dn + dr)
    kva = x @ p[f"{lp}_mla_Wkva"]
    ckv = _rms(kva[..., :r], p[f"{lp}_mla_kv_norm_scale"], eps)
    kv = (ckv @ p[f"{lp}_mla_Wkvb"]).reshape(bsz, t, h, dn + dv)
    q_r = _turn(q[..., dn:], theta)                    # [B, T, h, dr]
    k_r = _turn(kva[..., r:], theta)                   # [B, T, dr]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r)) \
        / math.sqrt(dn + dr)
    see = jnp.tril(jnp.ones((t, t), bool))[None, None] \
        & (mask[:, None, None, :] > 0)
    w = jax.nn.softmax(jnp.where(see, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., dn:])
    return o.reshape(bsz, t, h * dv) @ p[f"{lp}_mla_Wo"]


def _experts(p, lp, dims, x):
    scores = jax.nn.sigmoid(x @ p[f"{lp}_experts_router"])
    top, idx = jax.lax.top_k(scores, dims["num_experts_per_tok"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True) \
        * dims["routed_scaling_factor"]
    if dims["n_routed_experts"] < dims["router_width"]:
        # one share's part of the router's gradient is not the router's
        # gradient: a share does not train the router (`assumed`)
        weight = jax.lax.stop_gradient(weight)
    y = jnp.zeros_like(x)
    for i in range(dims["n_routed_experts"]):          # the held ones
        mine = jnp.sum(jnp.where(idx == dims["experts_first"] + i,
                                 weight, 0.0), axis=-1)
        y = y + mine[..., None] * _mlp(
            x, p[f"{lp}_experts_Wg"][i], p[f"{lp}_experts_Wu"][i],
            p[f"{lp}_experts_Wd"][i])
    if dims["n_shared_experts"]:
        y = y + _mlp(x, p[f"{lp}_shared_Wg"], p[f"{lp}_shared_Wu"],
                     p[f"{lp}_shared_Wd"])
    return y


def _block(p, lp, dims, x, mask, dense):
    eps = dims["rms_norm_eps"]
    x = x + _attention(p, lp, dims, _rms(x, p[f"{lp}_mix_norm_scale"], eps),
                       mask)
    pre = _rms(x, p[f"{lp}_ffn_norm_scale"], eps)
    return x + (_mlp(pre, p[f"{lp}_ffn_Wg"], p[f"{lp}_ffn_Wu"],
                     p[f"{lp}_ffn_Wd"]) if dense
                else _experts(p, lp, dims, pre))


def _ahead(a, k):
    """a[:, t + k] at t, zeros where the row ends."""
    return jnp.pad(a[:, k:], ((0, 0), (0, k)) + ((0, 0),) * (a.ndim - 2))


def _costs(logits, ids):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


def head_costs(params, dims, ids, mask):
    """(main [B, T], [module k's [B, T]]): the main head's cost of y_t at
    t, and module k's cost of y_{t+k} at t (whatever stands there past
    the row's end: the caller masks)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    d, eps = dims["hidden_size"], dims["rms_norm_eps"]
    table = p["decoder_ff_logit_out_W"]
    e = p["decoder_Wemb"][ids] * math.sqrt(d)
    h = jnp.pad(e, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    for l in range(1, dims["num_hidden_layers"] + 1):
        h = _block(p, f"decoder_l{l}", dims, h, mask,
                   dense=l <= dims["first_k_dense_replace"])
    main = _costs(_rms(h, p["decoder_top_norm_scale"], eps) @ table, ids)
    modules = []
    for k in range(1, dims["num_nextn_predict_layers"] + 1):
        lp = f"decoder_mtp{k}"
        u = jnp.concatenate(
            [_rms(_ahead(e, k - 1), p[f"{lp}_emb_norm_scale"], eps),
             _rms(h, p[f"{lp}_hidden_norm_scale"], eps)], axis=-1)
        h = _block(p, lp, dims, u @ p[f"{lp}_Weh"], mask, dense=False)
        modules.append(_costs(
            _rms(h, p[f"{lp}_top_norm_scale"], eps) @ table, _ahead(ids, k)))
    return main, modules


def token_costs(params, dims, _src_ids, _src_mask, trg_ids, trg_mask):
    """[B, T]: what the gold token y_t costs, the main head's
    cross-entropy plus `mtp_loss_weight` times each module's cost of
    predicting it (module k from position t - k); no label smoothing."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(trg_ids)
        main, modules = head_costs(params, dims, ids,
                                   jnp.asarray(trg_mask, jnp.float32))
        for k, ce in enumerate(modules, 1):
            main = main + dims["mtp_loss_weight"] * jnp.pad(
                ce[:, :-k], ((0, 0), (k, 0)))
        return main
