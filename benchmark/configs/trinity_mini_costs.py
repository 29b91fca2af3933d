"""Cost functions of configs/trinity-mini.json, named
`configs.trinity_mini_costs:<function>`: operations the ALGORITHM needs,
from shapes alone, in kernel_costs.py's conventions (a matmul [m,k]x[k,n]
costs 2mkn; real tokens only for model FLOPs). `dims` is the
configuration file.

A layer's rule is its entry of `layer_types` (the built layers are
`layers_built`): over a row of T positions a `full_attention` layer's
queries see T (T + 1) / 2 keys a query head, a `sliding_attention`
layer's the last W = `sliding_window` keys up to their own,
T W - W (W - 1) / 2 for T >= W and T (T + 1) / 2 under it."""

BF16 = 2


def pairs(t, layer_type, window):
    """(query, key) pairs that see each other, a row of width t and a
    query head."""
    w = min(window, t) if layer_type == "sliding_attention" else t
    return t * w - w * (w - 1) // 2


def _layers(dims):
    """[(layer type, is its feed-forward dense)] of the built layers."""
    return [(dims["layer_types"][l], l < dims["num_dense_layers"])
            for l in dims["layers_built"]][:dims["num_hidden_layers"]]


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step (3 x forward) on REAL tokens:
    every weight a token meets costs 2 (the gate's projection among the
    attention's; the shared expert; the output table once); a routed
    expert is met with the probability that a pick lands on a held one
    (top k x held / router width assignments a token, in expectation);
    attention charges a token its share of the padded row's pairs by the
    layer's rule, at 2 dh (score) + 2 dh (apply) a query head; norms, the
    rotation, the gate's product and the input table's gather are no
    matmuls and are not counted. Recomputation
    (--gradient-checkpointing) is not model work."""
    d, h = float(dims["hidden_size"]), dims["num_attention_heads"]
    hk, dh = dims["num_key_value_heads"], dims["head_dim"]
    attn_w = 2 * (3 * d * h * dh + 2 * d * hk * dh)       # q, gate, o; k, v
    dense = 6 * d * dims["intermediate_size"]
    one = 6 * d * dims["moe_intermediate_size"]
    held = dims["num_experts_per_tok"] * dims["num_experts"] \
        / float(dims["router_width"])
    experts = 2 * d * dims["router_width"] \
        + (held + dims["num_shared_experts"]) * one
    per_token = 2 * d * dims["vocab"]
    for layer_type, is_dense in _layers(dims):
        seen = pairs(trg_width, layer_type, dims["sliding_window"]) \
            / float(trg_width)
        per_token += attn_w + h * 4 * dh * seen \
            + (dense if is_dense else experts)
    return 3.0 * trg_tokens * per_token


def window_attention_train(work, dims):
    """flash_attention_fwd, _dq and _dkv of every built layer, each under
    its own rule (padding counted, as the kernels compute it): a pair
    costs 2 dh (score) + 2 dh (apply) forward and 6 dh + 4 dh backward
    (the score again, dp, dq, dk, dv): 14 dh. Bytes, each layer: q and
    out forward, q, do and dq backward over the query heads; k and v
    forward, k, v, dk and dv backward over the key/value heads."""
    h, hk = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh = dims["head_dim"]
    flops = nbytes = 0.0
    for w in work:
        b, t = w["rows"], w["trg_width"]
        for layer_type, _ in _layers(dims):
            flops += b * h * pairs(t, layer_type, dims["sliding_window"]) \
                * 14 * dh
            nbytes += BF16 * b * t * dh * (5 * h + 6 * hk)
    return flops, nbytes
