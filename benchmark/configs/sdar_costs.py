"""Cost functions of configs/sdar-30b-a3b.json, named
`configs.sdar_costs:<function>`: operations the ALGORITHM needs, from
shapes alone, in kernel_costs.py's conventions (a matmul [m,k]x[k,n]
costs 2mkn; real tokens only for model FLOPs). `dims` is the
configuration file.

Block-diffusion training runs a row of T positions as [noised ; clean],
2T indices, under the rule of the file's `assumed.block_length`: a noised
query sees its own block's noised keys (block_length of them) and the
clean keys of every earlier block, a clean query the clean keys up to the
end of its block. Over a row that is T^2 / 2 - T b / 2 + T^2 / 2 + T b / 2
+ T b = T^2 + b T (query, key) pairs a query head, b the block length."""

BF16 = 2


def _pairs(t, block):
    """(query, key) pairs that see each other, a row of width t and a
    query head."""
    return t * t + block * t


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step (3 x forward) on REAL document
    tokens: a token stands in the row twice (its noised and its clean
    copy), so it meets every weight of the stack TWICE and the output
    table once (the noised half alone is projected); a routed expert is
    met with the probability that a pick lands on a held one (top k x
    held / router width assignments a position, in expectation);
    attention charges a token its two copies' share of the padded row's
    pairs, trg_width + block_length a query head, at 2 dh (score) + 2 dh
    (apply); norms, the rotation and the noise are no matmuls and are not
    counted. Recomputation (--gradient-checkpointing) is not model
    work."""
    d, h = float(dims["hidden_size"]), dims["num_attention_heads"]
    hk, dh = dims["num_key_value_heads"], dims["head_dim"]
    attn_w = d * h * dh + 2 * d * hk * dh + h * dh * d
    held = dims["num_experts_per_tok"] * dims["num_experts"] \
        / float(dims["router_width"])
    experts_w = d * dims["router_width"] \
        + held * 3 * d * dims["moe_intermediate_size"]
    pairs = _pairs(trg_width, dims["block_length"]) / float(trg_width)
    layer = 2 * 2 * (attn_w + experts_w) + h * 4 * dh * pairs
    per_token = dims["num_hidden_layers"] * layer + 2 * d * dims["vocab"]
    return 3.0 * trg_tokens * per_token


def block_diffusion_attention_train(work, dims):
    """flash_attention_fwd, _dq and _dkv of the grouped-query layers under
    the block rule: T^2 + b T pairs a row and query head (padding
    counted, as the kernels compute it); a pair costs 2 dh (score) + 2 dh
    (apply) forward and 6 dh + 4 dh backward (the score again, dp, dq,
    dk, dv): 512 and 1280 at dh 128. Bytes: q and out forward, q, do and
    dq backward over the query heads; k and v forward, k, v, dk and dv
    backward over the key/value heads; all at 2T indices."""
    h, hk = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh, n = dims["head_dim"], dims["num_hidden_layers"]
    flops = nbytes = 0.0
    for w in work:
        b, t = w["rows"], w["trg_width"]
        flops += n * b * h * _pairs(t, dims["block_length"]) * 14 * dh
        nbytes += n * BF16 * b * 2 * t * dh * (5 * h + 6 * hk)
    return flops, nbytes
