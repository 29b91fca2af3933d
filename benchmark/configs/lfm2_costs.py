"""Cost functions of configs/lfm2-24b-a2b.json, named
`configs.lfm2_costs:<function>`: operations the ALGORITHM needs, from
shapes alone, in kernel_costs.py's conventions (a matmul [m,k]x[k,n]
costs 2mkn; real tokens only for model FLOPs). `dims` is the
configuration file.

A layer's kind is its entry of `layer_types` (the built layers are
`layers_built`): a `conv` layer is two projections around an element-wise
core, a `full_attention` layer's queries see T (T + 1) / 2 keys a query
head over a row of T positions."""

BF16 = 2


def pairs(t):
    """(query, key) pairs that see each other, a causal row of width t and
    a query head."""
    return t * (t + 1) // 2


def _layers(dims):
    """[(layer type, is its feed-forward dense)] of the built layers."""
    return [(dims["layer_types"][l], l < dims["num_dense_layers"])
            for l in dims["layers_built"]][:dims["num_hidden_layers"]]


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step (3 x forward) on REAL tokens:
    every weight a token meets costs 2 (a `conv` layer's W_in [d, 3d] and
    W_out [d, d]; an attention layer's q, k, v, o; the TIED table once,
    as the output projection: the input side is a gather); a `conv`
    layer's core costs 2 a tap and 1 a gate product, channel by channel
    (8 at 3 taps); a routed expert is met with the probability that a pick
    lands on a held one (top k x held / router width assignments a token,
    in expectation); attention charges a token its share of the padded
    row's causal pairs, at 2 dh (score) + 2 dh (apply) a query head;
    norms, the rotation and the gather are not counted. Recomputation
    (--gradient-checkpointing) is not model work."""
    d, h = float(dims["hidden_size"]), dims["num_attention_heads"]
    hk, dh = dims["num_key_value_heads"], dims["head_dim"]
    conv = 2 * (3 * d * d + d * d) + (2 * dims["conv_L_cache"] + 2) * d
    attn_w = 2 * (2 * d * h * dh + 2 * d * hk * dh)        # q, o; k, v
    seen = pairs(trg_width) / float(trg_width)
    dense = 6 * d * dims["intermediate_size"]
    held = dims["num_experts_per_tok"] * dims["num_experts"] \
        / float(dims["router_width"])
    experts = 2 * d * dims["router_width"] \
        + held * 6 * d * dims["moe_intermediate_size"]
    per_token = 2 * d * dims["vocab"]
    for layer_type, is_dense in _layers(dims):
        per_token += (conv if layer_type == "conv"
                      else attn_w + h * 4 * dh * seen) \
            + (dense if is_dense else experts)
    return 3.0 * trg_tokens * per_token


def causal_attention_train(work, dims):
    """flash_attention_fwd, _dq and _dkv of every built `full_attention`
    layer under the plain causal rule (padding counted, as the kernels
    compute it): a pair costs 2 dh (score) + 2 dh (apply) forward and
    6 dh + 4 dh backward (the score again, dp, dq, dk, dv): 14 dh, 896 at
    heads of 64. Bytes, each layer (sdar_costs' convention): q and out
    forward, q, do and dq backward over the query heads; k and v forward,
    k, v, dk and dv backward over the key/value heads."""
    h, hk = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh = dims["head_dim"]
    n = sum(layer_type == "full_attention" for layer_type, _ in _layers(dims))
    flops = nbytes = 0.0
    for w in work:
        b, t = w["rows"], w["trg_width"]
        flops += n * b * h * pairs(t) * 14 * dh
        nbytes += n * BF16 * b * t * dh * (5 * h + 6 * hk)
    return flops, nbytes
