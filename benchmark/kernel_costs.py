"""Operations and bytes the ALGORITHM needs for each kernel call and each
model step, from shapes alone — the numerators of roofline shares and MFU.
Kept with the benchmark so no later PR can move them.

Conventions: a matmul [m,k]x[k,n] costs 2mkn; bytes are each operand read
once and each result written once at the compute type's width; padding
counts (the call's shapes are what the chip is asked to do) for a
kernel's roofline, real tokens only for MFU. What a kernel does beyond
the algorithm (zero blocks of a packed tile) is NOT counted: that is the
loss the share exists to show.

`work` is the list of records the driver kept for the traced span (one
per step); `dims` is the configuration file.
"""

BF16 = 2


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_c, t_m), ("compute" if t_c >= t_m else "memory")


def _attn_fwd(b, h, tq, tk, dh):
    flops = 4.0 * b * h * tq * tk * dh               # scores + apply
    nbytes = BF16 * b * h * dh * (2 * tq + 2 * tk)   # q, out; k, v
    return flops, nbytes


def _attn_bwd(b, h, tq, tk, dh):
    flops = 10.0 * b * h * tq * tk * dh    # recomputed scores, dp, dq, dk, dv
    nbytes = BF16 * b * h * dh * (3 * tq + 4 * tk)   # q, do, dq; k, v, dk, dv
    return flops, nbytes


def packed_attention_train(work, dims):
    """Every packed-attention call of the traced train steps: encoder
    self, decoder self and decoder cross attention, forward and backward,
    in each layer. work: [{rows, src_width, trg_width}] per step, rows and
    widths as padded."""
    h, dh = dims["heads"], dims["dim_head"]
    flops = nbytes = 0.0
    for w in work:
        b, ts, tt = w["rows"], w["src_width"], w["trg_width"]
        for n, tq, tk in ((dims["enc_depth"], ts, ts),
                          (dims["dec_depth"], tt, tt),
                          (dims["dec_depth"], tt, ts)):
            for f in (_attn_fwd, _attn_bwd):
                fl, by = f(b, h, tq, tk, dh)
                flops += n * fl
                nbytes += n * by
    return flops, nbytes


def train_step_flops(dims, src_tokens, trg_tokens, src_width, trg_width):
    """Model FLOPs of one fwd+bwd step on REAL tokens (copy of
    common/flops.py::transformer_train_flops: matmuls only, attention over
    the padded width each real token attends to, train = 3x forward)."""
    d, f = float(dims["dim_emb"]), float(dims["dim_ffn"])
    enc_tok = 8 * d * d + 4 * d * f + 4 * src_width * d
    enc = dims["enc_depth"] * src_tokens * enc_tok
    dec_tok = (8 * d * d + 4 * trg_width * d
               + 4 * d * d + 4 * src_width * d
               + 4 * d * f)
    dec = dims["dec_depth"] * (trg_tokens * dec_tok
                               + 4 * d * d * src_tokens)
    logits = 2 * d * float(dims["vocab"]) * trg_tokens
    return 3.0 * (enc + dec + logits)
