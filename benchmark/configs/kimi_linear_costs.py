"""Cost functions of configs/kimi-linear-48b-a3b.json, named
`configs.kimi_linear_costs:<function>`: operations and bytes the
ALGORITHM needs, from shapes alone, in kernel_costs.py's conventions
(a matmul [m,k]x[k,n] costs 2mkn; each operand read once and each result
written once at the compute type's width; padding counts for a kernel's
roofline, real tokens only for model FLOPs; what a kernel does beyond the
algorithm is not counted). `dims` is the configuration file."""

BF16 = 2
CHUNK = 64                     # the delta rule's chunk (ops/kda.py)


def _layers(dims, kind, slot):
    return sum(1 for entry in dims["layer_plan"]
               if entry.split(":")[slot] == kind)


def _kda_chunk_flops_per_head_token(dk, dv):
    """Per token and head, forward: the two pairwise products of a chunk
    (k k^T below the diagonal, q k^T with it: C/2 pairs each, 2 dk a
    pair), the triangular solve on [C, dk + dv] (C/2 rows back, 2 a
    channel), the carry's three state products (2 dk dv each) and the
    lower triangle of P times U (C/2 rows, 2 dv)."""
    c = CHUNK
    return 2 * c * dk + c * (dk + dv) + 6 * dk * dv + c * dv


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step (3 x forward) on REAL tokens:
    every weight a token meets costs 2; a routed expert is met with the
    probability that a pick lands on a held one (top k x held / router
    width assignments a token, in expectation); latent attention charges
    each real token half the padded width (causal); the delta rule its
    chunked algorithm. Recomputation (--gradient-checkpointing) is not
    model work."""
    d, h = float(dims["hidden_size"]), dims["num_attention_heads"]
    dk, r = dims["kda_head_dim"], dims["kda_low_rank"]
    kda_w = (4 * d * h * dk + 2 * (d * r + r * h * dk) + d * h
             + 3 * dims["kda_conv"] * h * dk)
    kda = 2 * kda_w + h * _kda_chunk_flops_per_head_token(dk, dk)
    dq = dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    dv, lat = dims["v_head_dim"], dims["kv_lora_rank"]
    mla_w = (d * h * dq + d * (lat + dims["qk_rope_head_dim"])
             + lat * h * (dims["qk_nope_head_dim"] + dv) + h * dv * d)
    mla = 2 * mla_w + h * (2 * dq + 2 * dv) * trg_width / 2.0
    dense = 6 * d * dims["intermediate_size"]
    one = 6 * d * dims["moe_intermediate_size"]
    held = dims["num_experts_per_token"] * dims["num_experts"] \
        / float(dims["router_width"])
    experts = 2 * d * dims["router_width"] \
        + (held + dims["num_shared_experts"]) * one
    per_token = (_layers(dims, "kda", 0) * kda + _layers(dims, "mla", 0) * mla
                 + _layers(dims, "dense", 1) * dense
                 + _layers(dims, "experts", 1) * experts
                 + 2 * d * dims["vocab"])
    return 3.0 * trg_tokens * per_token


def kda_train(work, dims):
    """kda_chunk_fwd and kda_chunk_bwd, every call of the traced steps,
    with recomputation under --gradient-checkpointing NOT counted (the
    forward kernel then runs twice a layer; the second run is the loss a
    roofline share shows). Per chunk and head, C = 64:
      forward   U, qg St^T, U^T kd: 2 C dk dv each; P U below the
                diagonal: C C dv
      backward  U again, kd dSt^T, dO St, U dSt, dU St, dO^T qg, dU^T wk:
                2 C dk dv each; P^T dO and dO U^T (its lower triangle):
                C C dv each
    Bytes: the six chunk terms and dO read, O and the six cotangents
    written, at the compute type's width; the states the forward keeps
    for the backward are the implementation's, not the algorithm's."""
    h, dk = dims["num_attention_heads"], dims["kda_head_dim"]
    dv, c, n = dk, CHUNK, _layers(dims, "kda", 0)
    terms = 3 * c * dk + c * dv + dk + c * c
    flops = nbytes = 0.0
    for w in work:
        chunks = w["rows"] * h * (-(-w["trg_width"] // c))
        flops += n * chunks * ((6 + 14) * c * dk * dv + 3 * c * c * dv)
        nbytes += n * chunks * BF16 * ((terms + c * dv)
                                       + (2 * terms + c * dv))
    return flops, nbytes


def mla_attention_train(work, dims):
    """flash_attention_fwd, _dq and _dkv of the latent-attention layers:
    causal, so T (T + 1) / 2 (query, key) pairs a row and head; a pair
    costs 2 dqk (score) + 2 dv (apply) forward and 6 dqk + 4 dv backward
    (the score again, dp, dq, dk, dv). Bytes as kernel_costs.py's
    attention: q, out and k, v forward; q, do, dq and k, v, dk, dv
    backward."""
    h = dims["num_attention_heads"]
    dq = dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    dv, n = dims["v_head_dim"], _layers(dims, "mla", 0)
    flops = nbytes = 0.0
    for w in work:
        b, t = w["rows"], w["trg_width"]
        pairs = b * h * t * (t + 1) / 2.0
        flops += n * pairs * ((2 * dq + 2 * dv) + (6 * dq + 4 * dv))
        nbytes += n * BF16 * b * h * t * (
            (2 * dq + 2 * dv) + (2 * dq + dv) + (2 * dq + 2 * dv))
    return flops, nbytes
