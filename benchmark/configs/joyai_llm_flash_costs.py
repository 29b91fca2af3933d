"""Cost functions of configs/joyai-llm-flash.json, named
`configs.joyai_llm_flash_costs:<function>`: operations the ALGORITHM
needs, from shapes alone, in kernel_costs.py's conventions (a matmul
[m,k]x[k,n] costs 2mkn; real tokens only for model FLOPs). `dims` is the
configuration file. The three flash kernels of its six latent-attention
layers are costed by `configs.kimi_linear_costs:mla_attention_train`,
which counts the `mla` entries of `layer_plan`, the module's among them:
the cell joins the accepted `mla_flash_roofline`."""


def _blocks(dims):
    """(dense blocks, expert blocks, prediction modules): every one of
    them holds a latent-attention layer; a module's block is an expert
    block."""
    dense = min(dims["first_k_dense_replace"], dims["num_hidden_layers"])
    return dense, dims["num_hidden_layers"] - dense, \
        dims["num_nextn_predict_layers"]


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step (3 x forward) on REAL tokens:
    every weight a token meets costs 2 (the query through its two
    low-rank factors); a routed expert is met with the probability that
    a pick lands on a held one (top k x held / router width assignments
    a token, in expectation); attention charges each real token half the
    padded width (causal); the rotation is no matmul and is not counted.
    A prediction module is one more expert block behind a [2d, d]
    projection and one more pass over the output table. It has one label
    a row fewer than the main head (under 0.4 % of a row of 256 and
    more), which the signature cannot tell and which is not taken off.
    Recomputation (--gradient-checkpointing) is not model work."""
    d, h = float(dims["hidden_size"]), dims["num_attention_heads"]
    dq = dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    dv, lat, rq = dims["v_head_dim"], dims["kv_lora_rank"], \
        dims["q_lora_rank"]
    mla_w = (d * rq + rq * h * dq + d * (lat + dims["qk_rope_head_dim"])
             + lat * h * (dims["qk_nope_head_dim"] + dv) + h * dv * d)
    mla = 2 * mla_w + h * (2 * dq + 2 * dv) * trg_width / 2.0
    dense = 6 * d * dims["intermediate_size"]
    one = 6 * d * dims["moe_intermediate_size"]
    held = dims["num_experts_per_tok"] * dims["n_routed_experts"] \
        / float(dims["router_width"])
    experts = 2 * d * dims["router_width"] \
        + (held + dims["n_shared_experts"]) * one
    table = 2 * d * dims["vocab"]
    n_dense, n_experts, n_modules = _blocks(dims)
    per_token = (n_dense * (mla + dense) + n_experts * (mla + experts)
                 + table
                 + n_modules * (2 * (2 * d) * d + mla + experts + table))
    return 3.0 * trg_tokens * per_token

