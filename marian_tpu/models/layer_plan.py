"""A decoder-only stack driven by a per-layer PLAN.

`--type transformer-lm --transformer-layer-plan kda:dense kda:experts
mla:experts ...` names, for each layer in turn, its token-mixing kind and
its feed-forward kind, as data:

  mixing        kda      the delta rule with a per-channel decay
                         (ops/kda.py, ops/pallas/kda_chunk.py)
                mla      latent attention: keys and values expanded from
                         one low-rank latent, plus key channels shared
                         by all heads; causal softmax. Two of its sizes
                         are optional: a low-rank query with a norm of
                         its own (--plan-mla-q-rank; 0 = one full-rank
                         W_q) and a rotation of the shared key channels
                         and their query channels by position
                         (--plan-mla-rope-theta; 0 = not rotated)
                gqa      grouped-query attention: --transformer-heads
                         query heads on --plan-gqa-kv-heads key/value
                         heads, queries and keys RMS-normed per head and
                         rotated whole, in half-split pairs (i, i + d/2),
                         at --plan-gqa-rope-theta (0 = not rotated);
                         causal softmax
                swa      `gqa` (its sizes, its parameters' names) under a
                         SLIDING WINDOW: a query sees the last
                         --plan-swa-window keys up to its own
                         (ops/pallas/flash_attention.py::Window), rotated
                         at a theta of its own, --plan-swa-rope-theta
                         (0 = not rotated). A plan may hold both kinds:
                         window layers that are rotated beside global
                         `gqa` layers that are not
                         With --plan-gqa-gate both kinds multiply the
                         heads' output by sigmoid(W_gate x), channel by
                         channel, before W_o
                conv     a doubly gated short convolution: one projection
                         of the normed input to three streams of the
                         model's width, [B, C, h] = split(W_in x); a gate,
                         z = B * h; a depthwise causal convolution of
                         --plan-conv-taps taps along time (`short_conv`
                         of ops/ops.py, the one `kda` runs on q, k, v);
                         a second gate and one projection back,
                         W_out(C * conv(z)). No softmax, no activation,
                         no norm inside, no state beyond the taps' reach
  feed-forward  dense    gated MLP, W_d(SiLU(W_g x) * W_u x)
                experts  a router over all experts (sigmoid or softmax
                         scores, --plan-experts-score), the held ones
                         computed without dropping (ops/experts.py),
                         plus shared experts on every token, if any.
                         Under --plan-experts-bias-rate the top k are
                         chosen by score + a per-expert bias and weighed
                         by the scores without it; no gradient reaches
                         the bias: an update moves it by the rate against
                         the sign of its expert's load less the mean
                         load (`load_moved`, parallel/zero.py)

The block is pre-norm with RMSNorm (scale only) and a residual add,
x + mixing(norm(x)) then x + feed-forward(norm(x)); with --plan-post-norms
each branch's OUTPUT is normed too before it is added (four norms a
block: x + norm(mixing(norm(x)))). Input and output tables are two
matrices, or ONE under --tied-embeddings (the output table is the input
table's transpose, and its gradient arrives from both ends through
transformer.py's tied path); a final RMSNorm before the output
projection. The only positional signal is the rotation inside `mla`,
`gqa` and `swa`, each where its theta asks for it (and, under `swa`, how
far back a query sees): the delta rule has none, a `conv` layer none but
its taps' order, a `gqa` layer at theta 0 none, and a plan without a
rotated layer has none anywhere.

Under --gradient-checkpointing each half of a block is rematerialised in
the backward on its own, and keeps by name what that would run again on
the MXU for nothing (`_layer`, `_keeps`; bytes a token in the compute
type): an `mla`, `gqa` or `swa` half the flash kernel's output and row
statistics; a `gqa` or `swa` half the outputs of its k, v and gate
projections too (2 x (heads + 2 kv heads) x dim_head; q's, the widest
where there is no gate, is run again: holding it cost a plan of doubled
rows the memory its step programs need); either half of a block with
output norms the branch's output (2 x dim_emb), which the norm's
backward reads; a `conv` half the output of W_in (6 x dim_emb: the one
matmul its backward would run again, the gates and taps after it are
element-wise). A `kda` or `mla` half keeps no projection (low rank:
cheap to run again, as dear to hold). What is kept follows the layer's
kind and `post_norms`, which the plan states; there is no knob.

The objective is next-token prediction (the input shifted right), or,
with --plan-diffusion-block N and a plan of `gqa` layers, DIFFUSION OVER
BLOCKS (arXiv:2503.09573): every real position of a row is replaced by
the mask token with the row's own probability t (`diffusion_noise`), the
stack runs over [noised copy ; clean copy], 2T positions, both halves at
positions 0..T-1 and not shifted, under the rule
ops/pallas/flash_attention.py::BlockDiffusion(T, N), and the cost of a
row is its masked positions' cross-entropy, read off the noised half,
over t. The label count stays the row's tokens.

The last --plan-mtp-modules entries of the plan are not layers of the
stack but PREDICTION MODULES that run after it (`_predict_ahead`):
module k joins the normed hidden state of what came before it with the
normed embedding of the gold token k places on, projects the pair back
to the model's width, runs its one block and, through a norm of its own
and the SHARED output table, predicts the token after that one. Each is
one more weighted head of the cost (models/encoder_decoder.py), trained
and counted beside the main head and never part of the label count.

These are the families of Kimi Linear
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct), of
DeepSeek-V3 (arXiv:2412.19437, 2.1.1 latent attention, 2.2 multi-token
prediction), of Qwen3's sparse models trained as block-diffusion
models (arXiv:2505.09388; arXiv:2503.09573), of decoders that mix
window and global attention layers behind an output gate and output
norms, and of hybrids whose layers are mostly doubly gated short
convolutions with a grouped-query attention layer every few; the sizes
come from flags, nothing here knows a model's name.

The module is one more function family behind models/encoder_decoder.py
(`init_params`, `encode`, `decode_train`, `output_logits`), next to
transformer.py and s2s.py, and reuses transformer.py's embedding, output
and fused-CE code through a config that extends TransformerConfig.
Training only: there is no incremental `decode_step` for these layers
yet.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .. import obs
from ..layers import initializers as inits
from ..ops import experts as X
from ..ops import kda as K
from ..ops.attention import attention, causal_mask
from ..ops.ops import rms_norm, short_conv
from ..ops.pallas.flash_attention import (RESIDUAL_LSE, RESIDUAL_OUT,
                                          BlockDiffusion, Window, tile_plan)
from . import transformer as T

Params = Dict[str, jax.Array]

MIXINGS = ("kda", "mla", "gqa", "swa", "conv")
# grouped-query attention, causal or under a window: one set of parameters
# (`_gqa_*`), one function; with `mla` the mixings that are softmax
# attention through ops/attention.py
_GROUPED = ("gqa", "swa")
_ATTENTION = ("mla",) + _GROUPED
FEED_FORWARDS = ("dense", "experts")
# what the step carries out beside the loss, summed over the layers
COUNTERS = X.COUNTERS
# kept in the optimizer's float32 whatever the compute type: the router
# and its selection bias decide WHICH experts run, the decay's rate sits
# in an exponent, and a `conv` half's few taps multiply float32 products
_FLOAT32_SUFFIXES = ("_experts_router", "_experts_bias", "_kda_A_log",
                     "_kda_dt_bias", "_conv_taps")
# the one leaf no gradient reaches: the step moves it by its experts' load
# (`load_moved`)
_LOAD_MOVED = "_experts_bias"
# What a checkpointed half keeps across the backward beside its input, by
# NAME (`_keeps`). An `mla`, `gqa` or `swa` half: the flash kernel's output
# and row statistics
_FLASH_KEEPS = (RESIDUAL_OUT, RESIDUAL_LSE)
# a `gqa` or `swa` half besides: three of its four projections' outputs, k
# before its norm, v, and the gate's before its sigmoid (not q's: `_layer`)
_PROJECTION_KEEPS = ("gqa_k", "gqa_v", "gqa_gate")
# a `conv` half: the output of W_in, the three streams before their gates
_CONV_KEEPS = ("conv_bcx",)
# either half under `post_norms`: the branch's output, which the output
# norm's backward reads
BRANCH_OUT = "plan_branch_out"
# diffusion over blocks: what the step counts beside COUNTERS (their
# quotient is the realised noise level), the least noise level of a row,
# and the token a masked position holds (the vocabulary's <unk>)
DIFFUSION_COUNTERS = ("diffusion.masked", "diffusion.labels")
# a plan with a window layer: what the step counts of its attention, from
# shapes alone (`_attention_pairs`)
ATTENTION_COUNTERS = ("attn.pairs_seen", "attn.pairs_tiled")
DIFFUSION_EPS = 1e-3
MASK_TOKEN = 1


@dataclasses.dataclass(frozen=True)
class PlanConfig(T.TransformerConfig):
    plan: Tuple[Tuple[str, str], ...] = ()
    norm_eps: float = 1e-5
    # kda
    kda_dim_head: int = 128
    kda_conv: int = 4
    kda_low_rank: int = 128
    kda_head_groups: int = 1          # heads mixed in this many turns
    # mla
    mla_dim_nope: int = 128           # per-head key channels from the latent
    mla_dim_shared: int = 64          # key channels shared by all heads
    mla_dim_v: int = 128
    mla_latent: int = 512
    mla_q_rank: int = 0               # 0: one full-rank W_q
    mla_rope_theta: float = 0.0       # 0: the shared channels not rotated
    # prediction modules: the plan's last entries, after dec_depth layers
    mtp_modules: int = 0
    mtp_weight: float = 0.3
    # gqa
    gqa_kv_heads: int = 0             # 0: as many as query heads
    gqa_dim_head: int = 128
    gqa_rope_theta: float = 1e6       # 0: `gqa` layers not rotated
    gqa_gate: bool = False            # o * sigmoid(W_gate x) before W_o
    # swa: `gqa` under a window, at a theta of its own
    swa_window: int = 0
    swa_rope_theta: float = 1e4       # 0: not rotated
    post_norms: bool = False          # a norm on each branch's output
    # conv: taps of the depthwise causal convolution between its two gates
    conv_taps: int = 3
    # diffusion over blocks of this many positions; 0: next-token training
    diffusion_block: int = 0
    # experts
    experts: int = 0                  # the router's width
    experts_top_k: int = 8
    experts_dim_ffn: int = 1024
    experts_shared: int = 1
    experts_scale: float = 1.0
    experts_score: str = "sigmoid"
    experts_first: int = 0            # the held set: first, count
    experts_held: int = 0
    # > 0: a selection bias per expert, moved by this much an update
    # against the sign of (its load - the mean load); 0: no such leaf
    experts_bias_rate: float = 0.0


def _blocks(cfg: PlanConfig):
    """(parameter prefix, (mixing, feed-forward)) of every block of the
    plan: the stack's dec_depth layers, then the prediction modules' one
    block each."""
    n = cfg.dec_depth
    return [(f"decoder_l{l}", kinds)
            for l, kinds in enumerate(cfg.plan[:n], 1)] \
        + [(f"decoder_mtp{k}", kinds)
           for k, kinds in enumerate(cfg.plan[n:], 1)]


def parse_plan(spec) -> Tuple[Tuple[str, str], ...]:
    plan = []
    for item in spec:
        mix, _, ffn = str(item).partition(":")
        if mix not in MIXINGS or ffn not in FEED_FORWARDS:
            raise ValueError(
                f"--transformer-layer-plan entry {item!r}: want "
                f"<mixing>:<feed-forward> with mixing one of {MIXINGS} "
                f"and feed-forward one of {FEED_FORWARDS}")
        plan.append((mix, ffn))
    return tuple(plan)


def config_from_options(options, src_vocab, trg_vocab, for_inference=False,
                        **kw) -> PlanConfig:
    g = options.get
    base = T.config_from_options(options, src_vocab, trg_vocab,
                                 for_inference, **kw)
    plan = parse_plan(g("transformer-layer-plan", []) or [])
    n_experts = int(g("plan-experts", 0) or 0)
    held = [int(v) for v in (g("plan-experts-held", []) or [0, n_experts])]
    if len(held) != 2 or (any(f == "experts" for _, f in plan) and not (
            0 <= held[0] and held[1] >= 1 and sum(held) <= n_experts)):
        raise ValueError(f"--plan-experts-held {held}: want FIRST COUNT, a "
                         f"part of --plan-experts {n_experts}")
    first, count = held
    groups = int(g("plan-kda-head-groups", 1))
    if groups < 1 or base.heads % groups:
        raise ValueError(f"--plan-kda-head-groups {groups} does not divide "
                         f"--transformer-heads {base.heads}")
    ahead = int(g("plan-mtp-modules", 0) or 0)
    if not 0 <= ahead < max(len(plan), 1):
        raise ValueError(f"--plan-mtp-modules {ahead}: the last entries of "
                         f"a plan of {len(plan)}, less than all of them")
    theta = float(g("plan-mla-rope-theta", 0.0) or 0.0)
    if theta and int(g("plan-mla-dim-shared", 64)) % 2:
        raise ValueError("--plan-mla-rope-theta rotates channel pairs: "
                         "--plan-mla-dim-shared must be even")
    kv_heads = int(g("plan-gqa-kv-heads", 0) or 0) or base.heads
    if base.heads % kv_heads or int(g("plan-gqa-dim-head", 128)) % 2:
        raise ValueError(f"--plan-gqa-kv-heads {kv_heads} must divide "
                         f"--transformer-heads {base.heads}, and "
                         f"--plan-gqa-dim-head be even (rotated pairs)")
    window = int(g("plan-swa-window", 0) or 0)
    if window < 1 and any(m == "swa" for m, _ in plan):
        raise ValueError(f"--plan-swa-window {window}: a `swa` layer sees "
                         f"1 key or more")
    taps = int(g("plan-conv-taps", 3))
    if taps < 1:
        raise ValueError(f"--plan-conv-taps {taps}: a `conv` layer's "
                         f"convolution has 1 tap or more (the last weighs "
                         f"the current token)")
    score = str(g("plan-experts-score", "sigmoid") or "sigmoid")
    if score not in X.SCORES:
        raise ValueError(f"--plan-experts-score {score!r}: one of "
                         f"{X.SCORES}")
    bias_rate = float(g("plan-experts-bias-rate", 0.0) or 0.0)
    if bias_rate < 0:
        raise ValueError(f"--plan-experts-bias-rate {bias_rate}: the "
                         f"selection bias moves AGAINST its expert's "
                         f"excess load by this much an update (0: a plan "
                         f"without the bias)")
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(base)}
    fields.update(
        lm=True, dec_depth=len(plan) - ahead,
        # `tied_embeddings` stays the base's (--tied-embeddings: the output
        # table is the input table); there is no source side to tie
        tied_embeddings_all=False, tied_embeddings_src=False,
        output_omit_bias=True)
    return PlanConfig(
        **fields, plan=plan,
        norm_eps=float(g("plan-norm-eps", 1e-5)),
        kda_dim_head=int(g("plan-kda-dim-head", 128)),
        kda_conv=int(g("plan-kda-conv", 4)),
        kda_low_rank=int(g("plan-kda-low-rank", 128)),
        kda_head_groups=groups,
        mla_dim_nope=int(g("plan-mla-dim-nope", 128)),
        mla_dim_shared=int(g("plan-mla-dim-shared", 64)),
        mla_dim_v=int(g("plan-mla-dim-v", 128)),
        mla_latent=int(g("plan-mla-latent", 512)),
        mla_q_rank=int(g("plan-mla-q-rank", 0) or 0),
        mla_rope_theta=theta,
        mtp_modules=ahead, mtp_weight=float(g("plan-mtp-weight", 0.3)),
        gqa_kv_heads=kv_heads,
        gqa_dim_head=int(g("plan-gqa-dim-head", 128)),
        gqa_rope_theta=float(g("plan-gqa-rope-theta", 1e6) or 0.0),
        gqa_gate=bool(g("plan-gqa-gate", False)),
        swa_window=window,
        swa_rope_theta=float(g("plan-swa-rope-theta", 1e4) or 0.0),
        post_norms=bool(g("plan-post-norms", False)),
        conv_taps=taps,
        diffusion_block=int(g("plan-diffusion-block", 0) or 0),
        experts=n_experts,
        experts_top_k=int(g("plan-experts-top-k", 8)),
        experts_dim_ffn=int(g("plan-experts-dim-ffn", 1024)),
        experts_shared=int(g("plan-experts-shared", 1)),
        experts_scale=float(g("plan-experts-scale", 1.0)),
        experts_score=score,
        experts_first=first, experts_held=count,
        experts_bias_rate=bias_rate)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: PlanConfig, key: jax.Array) -> Params:
    p: Params = {}
    blocks = _blocks(cfg)
    keys = iter(jax.random.split(key, 64 * max(len(blocks), 1) + 8))
    d, h = cfg.dim_emb, cfg.heads

    def glorot(*shape, **kw):
        return inits.glorot_uniform(next(keys), shape, **kw)

    def ones(n):
        return inits.ones((1, n))

    p["decoder_Wemb"] = glorot(cfg.trg_vocab, d)
    if not cfg.tied_embeddings:
        p["decoder_ff_logit_out_W"] = glorot(d, cfg.trg_vocab)
    p["decoder_top_norm_scale"] = ones(d)
    for lp, (mix, ffn) in blocks:
        p[f"{lp}_mix_norm_scale"] = ones(d)
        p[f"{lp}_ffn_norm_scale"] = ones(d)
        if cfg.post_norms:
            p[f"{lp}_mix_post_norm_scale"] = ones(d)
            p[f"{lp}_ffn_post_norm_scale"] = ones(d)
        if mix == "kda":
            dh, r = cfg.kda_dim_head, cfg.kda_low_rank
            for n in "qkv":
                p[f"{lp}_kda_W{n}"] = glorot(d, h * dh)
                # a short filter that starts near the identity
                p[f"{lp}_kda_conv_{n}"] = jax.random.uniform(
                    next(keys), (cfg.kda_conv, h * dh), jnp.float32,
                    -0.5, 0.5).at[-1].add(1.0)
            p[f"{lp}_kda_Wf1"] = glorot(d, r)
            p[f"{lp}_kda_Wf2"] = glorot(r, h * dh)
            # decay rates exp(A_log) in [1, 16) and steps dt in
            # [1e-3, 1e-1), dt_bias = softplus^-1(dt): a channel's
            # memory starts between a few and a thousand positions
            p[f"{lp}_kda_A_log"] = jnp.log(jax.random.uniform(
                next(keys), (1, h), jnp.float32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(
                next(keys), (1, h * dh), jnp.float32,
                math.log(1e-3), math.log(1e-1)))
            p[f"{lp}_kda_dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            p[f"{lp}_kda_Wb"] = glorot(d, h)
            p[f"{lp}_kda_Wg1"] = glorot(d, r)
            p[f"{lp}_kda_Wg2"] = glorot(r, h * dh)
            p[f"{lp}_kda_out_norm_scale"] = ones(dh)
            p[f"{lp}_kda_Wo"] = glorot(h * dh, d)
        elif mix in _GROUPED:
            dh, hk = cfg.gqa_dim_head, cfg.gqa_kv_heads
            p[f"{lp}_gqa_Wq"] = glorot(d, h * dh)
            p[f"{lp}_gqa_Wk"] = glorot(d, hk * dh)
            p[f"{lp}_gqa_Wv"] = glorot(d, hk * dh)
            p[f"{lp}_gqa_q_norm_scale"] = ones(dh)
            p[f"{lp}_gqa_k_norm_scale"] = ones(dh)
            p[f"{lp}_gqa_Wo"] = glorot(h * dh, d)
            if cfg.gqa_gate:
                p[f"{lp}_gqa_Wgate"] = glorot(d, h * dh)
        elif mix == "conv":
            # three streams of the model's width out of one matrix, each
            # scaled as a [d, d] projection of its own
            p[f"{lp}_conv_Win"] = glorot(d, 3 * d, fan_in=d, fan_out=d)
            # a short filter that starts near the identity, as `kda`'s
            p[f"{lp}_conv_taps"] = jax.random.uniform(
                next(keys), (cfg.conv_taps, d), jnp.float32,
                -0.5, 0.5).at[-1].add(1.0)
            p[f"{lp}_conv_Wout"] = glorot(d, d)
        else:
            dq = cfg.mla_dim_nope + cfg.mla_dim_shared
            if cfg.mla_q_rank:
                p[f"{lp}_mla_Wqa"] = glorot(d, cfg.mla_q_rank)
                p[f"{lp}_mla_q_norm_scale"] = ones(cfg.mla_q_rank)
                p[f"{lp}_mla_Wqb"] = glorot(cfg.mla_q_rank, h * dq)
            else:
                p[f"{lp}_mla_Wq"] = glorot(d, h * dq)
            p[f"{lp}_mla_Wkva"] = glorot(d, cfg.mla_latent
                                         + cfg.mla_dim_shared)
            p[f"{lp}_mla_kv_norm_scale"] = ones(cfg.mla_latent)
            p[f"{lp}_mla_Wkvb"] = glorot(
                cfg.mla_latent, h * (cfg.mla_dim_nope + cfg.mla_dim_v))
            p[f"{lp}_mla_Wo"] = glorot(h * cfg.mla_dim_v, d)
        if ffn == "dense":
            f = cfg.dim_ffn
            p[f"{lp}_ffn_Wg"] = glorot(d, f)
            p[f"{lp}_ffn_Wu"] = glorot(d, f)
            p[f"{lp}_ffn_Wd"] = glorot(f, d)
        else:
            f, n = cfg.experts_dim_ffn, cfg.experts_held
            p[f"{lp}_experts_router"] = glorot(d, cfg.experts)
            if cfg.experts_bias_rate:
                p[f"{lp}{_LOAD_MOVED}"] = inits.zeros((1, cfg.experts))
            p[f"{lp}_experts_Wg"] = glorot(n, d, f, fan_in=d, fan_out=f)
            p[f"{lp}_experts_Wu"] = glorot(n, d, f, fan_in=d, fan_out=f)
            p[f"{lp}_experts_Wd"] = glorot(n, f, d, fan_in=f, fan_out=d)
            if cfg.experts_shared:
                fs = f * cfg.experts_shared
                p[f"{lp}_shared_Wg"] = glorot(d, fs)
                p[f"{lp}_shared_Wu"] = glorot(d, fs)
                p[f"{lp}_shared_Wd"] = glorot(fs, d)
    for lp, _ in blocks[cfg.dec_depth:]:
        p[f"{lp}_emb_norm_scale"] = ones(d)
        p[f"{lp}_hidden_norm_scale"] = ones(d)
        p[f"{lp}_Weh"] = glorot(2 * d, d)
        p[f"{lp}_top_norm_scale"] = ones(d)
    return p


def load_moved(cfg: PlanConfig) -> Tuple[str, float]:
    """(suffix, rate): the leaves of that suffix are moved by `rate` an
    update against the sign of the load signal that the backward leaves
    in their gradient's place (ops/experts.py::load_signal), and by
    nothing else; rate 0: the plan has no such leaf."""
    return _LOAD_MOVED, cfg.experts_bias_rate


def cast_params(params: Params, dtype) -> Params:
    """transformer.cast_params, less the few leaves that stay float32."""
    keep = {k: v for k, v in params.items()
            if k.endswith(_FLOAT32_SUFFIXES)}
    return {**T.cast_params(params, dtype), **keep}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

_heads = T._split_heads            # [B, T, h * d] -> [B, h, T, d]


def _kda_kernels():
    """The preparation inside a chunk and the state carry between
    chunks: the Pallas kernel pairs on a TPU, the jnp forms they are
    tested against elsewhere."""
    if jax.default_backend() != "tpu":
        return {"terms": K.chunk_terms, "carry": K.state_carry}
    from ..ops.pallas.kda_chunk import kda_state_carry
    from ..ops.pallas.kda_prep import kda_chunk_terms
    return {"terms": kda_chunk_terms, "carry": kda_state_carry}


def _kda(cfg: PlanConfig, p: Params, lp: str, x):
    """q, k, v = SiLU(conv(W x)), q and k L2-normalised per head; decay
    g = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias) per key channel,
    b = sigmoid(W_b x) per head; the delta rule; RMSNorm per head gated
    by sigmoid(W_g2 W_g1 x); W_o.

    The heads are mixed in `kda_head_groups` groups, one after another
    (a scan over the groups' slices of the weights, its body
    rematerialised in the backward when there is more than one): every
    intermediate is then [B, T, H / groups * dh] and only one group's
    chunk terms, states and cotangents are alive at a time. The groups'
    outputs meet in W_o's float32 accumulator."""
    h, dh, n = cfg.heads, cfg.kda_dim_head, cfg.kda_head_groups
    hg = h // n
    f32 = jnp.float32

    def by_group(name, per_head=dh, axis=-1):
        w = p[f"{lp}_kda_{name}"]
        axis %= w.ndim
        shape = w.shape[:axis] + (n, hg * per_head) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    weights = {name: by_group(name) for name in (
        "Wq", "Wk", "Wv", "conv_q", "conv_k", "conv_v", "Wf2", "dt_bias",
        "Wg2")}
    weights.update(A_log=by_group("A_log", 1), Wb=by_group("Wb", 1),
                   Wo=by_group("Wo", axis=0))
    bsz, t, _ = x.shape
    # everything between the projections and W_o is float32: the decay
    # sits in an exponent that is summed over thousands of positions, and
    # the short filter and the normalisations are cheap at a group's width
    low_f = jnp.dot(x, p[f"{lp}_kda_Wf1"], preferred_element_type=f32)
    low_g = jnp.dot(x, p[f"{lp}_kda_Wg1"], preferred_element_type=f32)

    def wide(low, w):
        return jnp.dot(low, w.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)

    def group(acc, w):
        def branch(n_):
            y = short_conv(
                jnp.dot(x, w[f"W{n_}"], preferred_element_type=f32),
                w[f"conv_{n_}"].astype(f32))
            return _heads(jax.nn.silu(y), hg)
        q, k = K.l2_normalize(branch("q")), K.l2_normalize(branch("k"))
        step = jax.nn.softplus(wide(low_f, w["Wf2"])
                               + w["dt_bias"].astype(f32))
        g = -jnp.exp(w["A_log"].astype(f32)).reshape(1, hg, 1, 1) \
            * _heads(step, hg)
        b = jax.nn.sigmoid(jnp.dot(
            x, w["Wb"], preferred_element_type=f32)).transpose(0, 2, 1)
        o = K.kda_chunked(q, k, branch("v"), g, b, dh ** -0.5,
                          **_kda_kernels())
        o = rms_norm(o.transpose(0, 2, 1, 3),
                     p[f"{lp}_kda_out_norm_scale"], eps=cfg.norm_eps)
        gate = jax.nn.sigmoid(wide(low_g, w["Wg2"]))
        o = (o.reshape(bsz, t, hg * dh) * gate).astype(x.dtype)
        return acc + jnp.dot(o, w["Wo"], preferred_element_type=f32), None

    with jax.named_scope("kda"):
        out, _ = jax.lax.scan(jax.checkpoint(group) if n > 1 else group,
                              jnp.zeros((bsz, t, cfg.dim_emb), f32),
                              weights)
        return out.astype(x.dtype)


def rope_angles(length: int, dim: int, theta: float,
                pairing: str = "interleaved"):
    """[length, dim] float32: position t times theta^(-2i / dim) for the
    channel pair i, each pair's angle under both of its channels: the
    pair (2i, 2i + 1) where `pairing` is "interleaved", (i, i + dim / 2)
    where it is "half". Integer positions times float32 rates: past 256
    positions a bfloat16 angle is no longer its position's."""
    rate = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * rate[None, :]
    if pairing == "half":
        return jnp.concatenate([angles, angles], axis=-1)
    return jnp.repeat(angles, 2, axis=-1)


def _pair_matrix(dim: int, pairing: str, start: int) -> np.ndarray:
    """[dim, dim] float32 with one +-1 a column from `start` on and zero
    columns under it: x @ M holds, under each turned channel, its pair's
    other channel with the sign the turn wants (-b under a, +a under b)."""
    j = np.arange(dim - start)
    if pairing == "half":
        half = (dim - start) // 2
        other, first = (j + half) % (2 * half), j < half
    else:
        other, first = j ^ 1, j % 2 == 0
    m = np.zeros((dim, dim), np.float32)
    m[start + other, start + j] = np.where(first, -1.0, 1.0)
    return m


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rotate(x, angles, pairing: str = "interleaved", start: int = 0):
    """x [..., T, dim] with each channel pair from channel `start` on, at
    position t, turned by its angle (angles[t] [dim - start], as
    rope_angles lays them out for the same `pairing`), in float32:
    (a, b) -> (a cos - b sin, a sin + b cos), one rounding to x's type;
    the channels under `start` come back as they are (cos 1, sin 0), so
    a tensor whose last channels turn is turned IN PLACE, at its full
    width, with no slice and no concatenate.

    The pair's other channel is x @ M, M a constant signed permutation
    (`_pair_matrix`), accumulated in float32: exact, on the MXU, and one
    fusion with the turn, where a roll along the channels was slices that
    wrote float32 pieces of the whole tensor to HBM (PERF.md 6, PR 42).
    The backward is the same pass at the negated angles."""
    # each output is ONE input times +-1, so the product is exact in x's
    # type (float32 operands would be rounded to bfloat16 on the chip
    # under the default precision)
    other = jnp.dot(
        x, jnp.asarray(_pair_matrix(x.shape[-1], pairing, start), x.dtype),
        precision=jax.lax.Precision.HIGHEST
        if x.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if start:
        still = ((0, 0),) * (angles.ndim - 1) + ((start, 0),)
        cos = jnp.pad(cos, still, constant_values=1.0)
        sin = jnp.pad(sin, still)
    return (x.astype(jnp.float32) * cos + other * sin).astype(x.dtype)


def _rotate_fwd(x, angles, pairing, start):
    return _rotate(x, angles, pairing, start), angles


def _rotate_bwd(pairing, start, angles, g):
    # the turn is orthogonal: its transpose is the turn back, the same
    # pass (float32 inside, one rounding); the angles take no cotangent
    return _rotate(g, -angles, pairing, start), jnp.zeros_like(angles)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def _mla(cfg: PlanConfig, p: Params, lp: str, x, mask):
    """Latent attention: per-head keys and values expanded from one
    normed low-rank latent, `mla_dim_shared` more key channels shared by
    all heads, a full-rank or low-rank query (`mla_q_rank`); causal
    softmax; W_o. Where `mla_rope_theta` is set, the shared key channels
    and each head's last `mla_dim_shared` query channels turn by their
    position: the query IN PLACE at its full width (`_rotate` from
    channel `mla_dim_nope` on; nothing is sliced off and joined again),
    the shared key before it is broadcast to the heads."""
    h, dn, dv = cfg.heads, cfg.mla_dim_nope, cfg.mla_dim_v
    with jax.named_scope("mla"):
        if cfg.mla_q_rank:
            low = rms_norm(jnp.dot(x, p[f"{lp}_mla_Wqa"]),
                           p[f"{lp}_mla_q_norm_scale"], eps=cfg.norm_eps)
            q = _heads(jnp.dot(low, p[f"{lp}_mla_Wqb"]), h)
        else:
            q = _heads(jnp.dot(x, p[f"{lp}_mla_Wq"]), h)
        kva = jnp.dot(x, p[f"{lp}_mla_Wkva"])
        latent = rms_norm(kva[..., :cfg.mla_latent],
                          p[f"{lp}_mla_kv_norm_scale"], eps=cfg.norm_eps)
        shared = kva[..., cfg.mla_latent:]
        t = x.shape[1]
        if cfg.mla_rope_theta:
            with jax.named_scope("mla.rope"):
                angles = rope_angles(t, cfg.mla_dim_shared,
                                     cfg.mla_rope_theta)
                q = _rotate(q, angles, start=dn)
                shared = _rotate(shared, angles)
        kv = _heads(jnp.dot(latent, p[f"{lp}_mla_Wkvb"]), h)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                shared[:, None], (*kv.shape[:3], cfg.mla_dim_shared))],
            axis=-1)
        o, _ = attention(q, k, kv[..., dn:],
                         mask=causal_mask(t) * mask[:, None, None, :],
                         kv_mask=mask, causal=True,
                         flash=cfg.flash_attention, packed="off")
        o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], t, h * dv)
        return jnp.dot(o, p[f"{lp}_mla_Wo"])


def _gate(cfg: PlanConfig, p: Params, lp: str, x, o):
    """o * sigmoid(W_gate x), channel by channel over [B, T, heads x
    dim_head]: the gate's matmul in the compute type, its sigmoid and the
    product in float32, one rounding."""
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(checkpoint_name(
            jnp.dot(x, p[f"{lp}_gqa_Wgate"]), "gqa_gate").astype(jnp.float32))
        return (o.astype(jnp.float32) * g).astype(o.dtype)


def _gqa(cfg: PlanConfig, p: Params, lp: str, x, mask, rule=None,
         kind: str = "gqa"):
    """q, k, v = W x as heads of gqa_dim_head, q and k RMS-normed per head
    and, where the kind's theta is not 0, rotated whole at their position;
    query head h reads key/value head h // (heads / kv heads); softmax;
    the gate, where asked for; W_o. Next-token training: x [B, T, d]
    under the causal rule (`gqa`) or under Window(swa_window) (`swa`).
    Under a BlockDiffusion `rule` x is the doubled row [B, 2T, d], `mask`
    [B, 2T], and both halves stand at positions 0..T-1."""
    h, hk, dh = cfg.heads, cfg.gqa_kv_heads, cfg.gqa_dim_head
    bsz, t, _ = x.shape
    theta = cfg.swa_rope_theta if kind == "swa" else cfg.gqa_rope_theta
    if kind == "swa":
        rule = Window(cfg.swa_window)
    with jax.named_scope(kind):
        q = rms_norm(_heads(jnp.dot(x, p[f"{lp}_gqa_Wq"]), h),
                     p[f"{lp}_gqa_q_norm_scale"], eps=cfg.norm_eps)
        k = rms_norm(_heads(checkpoint_name(
            jnp.dot(x, p[f"{lp}_gqa_Wk"]), "gqa_k"), hk),
            p[f"{lp}_gqa_k_norm_scale"], eps=cfg.norm_eps)
        v = _heads(checkpoint_name(jnp.dot(x, p[f"{lp}_gqa_Wv"]), "gqa_v"),
                   hk)
        if theta:
            with jax.named_scope(f"{kind}.rope"):
                doubled = isinstance(rule, BlockDiffusion)
                angles = rope_angles(rule.length if doubled else t, dh,
                                     theta, "half")
                if doubled:
                    angles = jnp.tile(angles, (2, 1))
                q, k = _rotate(q, angles, "half"), _rotate(k, angles, "half")
        # the dense path builds a rule's mask itself; the causal one is
        # handed to it, as `_mla` does
        o, _ = attention(
            q, k, v, mask=causal_mask(t) * mask[:, None, None, :]
            if rule is None else None, kv_mask=mask,
            causal=True if rule is None else rule,
            flash=cfg.flash_attention, packed="off")
        o = o.transpose(0, 2, 1, 3).reshape(bsz, t, h * dh)
        if cfg.gqa_gate:
            o = _gate(cfg, p, lp, x, o)
        return jnp.dot(o, p[f"{lp}_gqa_Wo"])


def _conv(cfg: PlanConfig, p: Params, lp: str, x):
    """[B, C, h] = W_in x split three ways along the channels, in that
    order; z = B * h; c_t = sum_j taps[j] * z_{t - (K - 1) + j}, channel
    by channel, positions before the row's first counting as zero
    (`short_conv`: the last tap weighs the current token); W_out(C * c).
    The two matmuls in the compute type; the core between them (two
    products, K taps) in float32 with one rounding, as `_gate`'s. Rows
    are padded on the right and a position reads nothing after itself, so
    no mask is asked for: a padded tail changes no real position."""
    d = cfg.dim_emb
    with jax.named_scope("conv"):
        bcx = checkpoint_name(jnp.dot(x, p[f"{lp}_conv_Win"]), "conv_bcx")
        with jax.named_scope("conv.core"):
            b, c, h = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
                       for i in range(3))
            y = c * short_conv(b * h,
                               p[f"{lp}_conv_taps"].astype(jnp.float32))
        return jnp.dot(y.astype(x.dtype), p[f"{lp}_conv_Wout"])


def _experts(cfg: PlanConfig, p: Params, lp: str, x, mask):
    bsz, t, d = x.shape
    flat = x.reshape(bsz * t, d)
    bias = p.get(f"{lp}{_LOAD_MOVED}")
    with jax.named_scope("experts.route"):
        idx, weights = X.route(flat, p[f"{lp}_experts_router"],
                               cfg.experts_top_k, cfg.experts_scale,
                               cfg.experts_score, bias)
        if cfg.experts_held < cfg.experts:
            # A share of the layer's output carries a share of the
            # router's gradient, and that share alone teaches the router
            # to send tokens elsewhere (fewer fresh experts in the sum is
            # less noise). The whole gradient is the sum over all shares,
            # which takes their exchange: until then the router of a
            # share is not trained through its part.
            weights = jax.lax.stop_gradient(weights)
    with jax.named_scope("experts.compute"):
        y, counters = X.held_experts(
            flat, mask.reshape(-1), idx, weights, p[f"{lp}_experts_Wg"],
            p[f"{lp}_experts_Wu"], p[f"{lp}_experts_Wd"],
            cfg.experts_first,
            X.pool_rows(bsz * t, cfg.experts_top_k, cfg.experts_held,
                        cfg.experts))
    if bias is not None:
        # the bias is moved by the load over ALL experts of this chip's
        # tokens: the term this chip adds to the sum over the chips that
        # share the layer (the gradients' sum over `data`)
        with jax.named_scope("experts.route"):
            y = X.load_signal(y, bias, X.loads(idx, mask.reshape(-1),
                                               cfg.experts))
    if cfg.experts_shared:
        with jax.named_scope("experts.shared"):
            y = y + X.gated_mlp(flat, p[f"{lp}_shared_Wg"],
                                p[f"{lp}_shared_Wu"], p[f"{lp}_shared_Wd"])
    return y.reshape(bsz, t, d), counters


def _mix(cfg: PlanConfig, kind: str, lp: str, p: Params, x, mask,
         rule=None):
    pre = rms_norm(x, p[f"{lp}_mix_norm_scale"], eps=cfg.norm_eps)
    if kind in _GROUPED:
        out = _gqa(cfg, p, lp, pre, mask, rule, kind)
    elif kind == "conv":
        out = _conv(cfg, p, lp, pre)
    else:
        out = _kda(cfg, p, lp, pre) if kind == "kda" \
            else _mla(cfg, p, lp, pre, mask)
    if cfg.post_norms:
        out = rms_norm(checkpoint_name(out, BRANCH_OUT),
                       p[f"{lp}_mix_post_norm_scale"], eps=cfg.norm_eps)
    return x + out


def _feed_forward(cfg: PlanConfig, kind: str, lp: str, p: Params, x, mask):
    pre = rms_norm(x, p[f"{lp}_ffn_norm_scale"], eps=cfg.norm_eps)
    if kind == "dense":
        with jax.named_scope("ffn"):
            out = X.gated_mlp(pre, p[f"{lp}_ffn_Wg"], p[f"{lp}_ffn_Wu"],
                              p[f"{lp}_ffn_Wd"])
        counters = jnp.zeros((len(COUNTERS),), jnp.float32)
    else:
        out, counters = _experts(cfg, p, lp, pre, mask)
    if cfg.post_norms:
        out = rms_norm(checkpoint_name(out, BRANCH_OUT),
                       p[f"{lp}_ffn_post_norm_scale"], eps=cfg.norm_eps)
    return x + out, counters


def _named_bytes(f, names, *args) -> int:
    """Bytes of the values that f's differentiated forward gives one of
    `names` (jax.ad_checkpoint.checkpoint_name): what a checkpoint of f
    under save_only_these_names(*names) holds across the backward, and 0
    where the code that names them is not the path taken."""
    forward = jax.make_jaxpr(lambda *a: jax.vjp(f, *a)[0])(*args)

    def named(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name" and eqn.params["name"] in names:
                yield from (v.aval for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from named(sub)
    return sum(a.size * a.dtype.itemsize for a in named(forward.jaxpr))


def _keeps(cfg: PlanConfig, kind: str) -> Tuple[str, ...]:
    """The names a checkpointed half of this kind keeps (see `_layer`)."""
    return (_FLASH_KEEPS if kind in _ATTENTION else ()) \
        + (_PROJECTION_KEEPS if kind in _GROUPED else ()) \
        + (_CONV_KEEPS if kind == "conv" else ()) \
        + ((BRANCH_OUT,) if cfg.post_norms else ())


def _checkpointed(f, lp: str, half: str, names, *args):
    """jax.checkpoint(f), keeping across the backward what f's forward
    gives one of `names`; a half that keeps a name says so once a traced
    call (`plan.remat_keep`), with the tracer on."""
    if not names:
        return jax.checkpoint(f)
    if obs.enabled():
        obs.event("plan.remat_keep", layer=lp, half=half, names=names,
                  bytes=_named_bytes(f, names, *args))
    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(*names))


def _layer(cfg: PlanConfig, kinds, lp: str, p: Params, x, mask, remat,
           rule=None):
    """One block: x + mixing(norm(x)), then x + feed-forward(norm(x)),
    each branch's output normed first under `post_norms`.
    With `remat` (--gradient-checkpointing, training) each half is
    rematerialised in the backward on its own, so what stays alive
    between the passes is a layer's input and its middle, and what a half
    keeps BY NAME (`_keeps`; bytes a token in the compute type, d the
    model's width):

      an `mla`, `gqa` or `swa` half   what only the flash kernel can
          produce (_FLASH_KEEPS: heads x (dim_v x 2 + 4)): the kernel
          does not run again;
      a `gqa` or `swa` half besides   the outputs of its k, v and gate
          projections (_PROJECTION_KEEPS: (heads + 2 kv heads) x dim_head
          x 2 with the gate, 10 KB at 32 on 4 heads of 128; 2 KB without
          one): their matmuls do not run again, while q's, the per-head
          norms, the rotation (its own float32 dot is NOT kept: by name,
          never `dots_saveable`), the transposes, the sigmoid and the
          product do. q's output is NOT kept: as dear a FLOP as the
          others', it is four fifths of the bytes where there is no gate,
          and held over a plan of doubled rows it left the widest step
          under a gigabyte of headroom (PERF.md 6, PR 47). An `mla` or
          `kda` half keeps none of its projections: they are low-rank,
          cheap to run again and as dear to hold;
      a `conv` half                   the output of W_in (_CONV_KEEPS:
          6 x d, 12 KB at width 2048): W_in, three quarters of the
          half's matmul work, does not run again; the gates and the taps
          do, and W_out's forward is read by no backward;
      either half under `post_norms`  the branch's output (BRANCH_OUT:
          d x 2), which the output norm's backward reads: without it the
          backward regenerates the whole branch to get it (W_o, the dense
          and the shared W_d, and the held experts' forward, which
          ops/experts.py's own backward then runs once more for its
          gradients). The name exists only where a backward reads it:
          x + out needs no `out`, and without `post_norms` nothing is
          named and the feed-forward half is the plain checkpoint.

    Where the dense path runs (short rows, the CPU) nothing bears the
    kernel's names and they keep nothing. KDA mixed in head groups
    rematerialises itself group by group and is not wrapped again: a
    second wrap would run its forward a third time. `rule`: the
    BlockDiffusion rule of a `gqa` half over a doubled row, None under
    next-token training."""
    mix, ffn = kinds
    f_mix = partial(_mix, cfg, mix, lp)
    if rule is not None:
        f_mix = partial(f_mix, rule=rule)
    f_ffn = partial(_feed_forward, cfg, ffn, lp)
    if remat and (mix != "kda" or cfg.kda_head_groups == 1):
        f_mix = _checkpointed(f_mix, lp, "mixing", _keeps(cfg, mix),
                              p, x, mask)
    x = f_mix(p, x, mask)
    if remat:
        f_ffn = _checkpointed(f_ffn, lp, "feed-forward", _keeps(cfg, ffn),
                              p, x, mask)
    return f_ffn(p, x, mask)


# ---------------------------------------------------------------------------
# the function family models/encoder_decoder.py closes over
# ---------------------------------------------------------------------------

def encode(cfg, params, src_ids, src_mask, train=False, key=None):
    return None


class Head(NamedTuple):
    """One more head of the cost, as data: `hidden` [B, T, d] goes
    through the output table like the main head's, against `ids` under
    `mask`; its summed cost joins the main head's times `weight` and is
    counted as `<name>.ce_sum` / `<name>.labels`. `shift` says how far
    its labels were rolled left (a per-token weight follows its label)."""
    name: str
    weight: float
    hidden: jax.Array
    ids: jax.Array
    mask: jax.Array
    shift: int


def head_names(cfg: PlanConfig) -> Tuple[str, ...]:
    return ("mtp",) if cfg.mtp_modules else ()


def counter_names(cfg: PlanConfig) -> Tuple[str, ...]:
    """What the step's one lazy vector counts, in its order."""
    return COUNTERS + (DIFFUSION_COUNTERS if cfg.diffusion_block else ()) \
        + (ATTENTION_COUNTERS if _has_window(cfg) else ())


def _has_window(cfg: PlanConfig) -> bool:
    return any(mix == "swa" for mix, _ in cfg.plan)


def _attention_pairs(cfg: PlanConfig, rows: int, width: int):
    """[2] float32, ATTENTION_COUNTERS of one step over [rows, width],
    from shapes alone: the (query, key) pairs that the layers' rules admit
    in the padded rows, over all `gqa` and `swa` layers and query heads,
    and the pairs of the tiles that the flash kernels compute for them
    (`tile_plan`: live tiles x block_q x block_k, whichever path runs).
    Their quotient is the share of the tiles' work that the rules admit;
    the rest is what the diagonal and the window's trailing edge cut off
    inside a tile, and the rows' padding to whole tiles."""
    seen = tiled = 0
    for kind, rule, w in (("swa", Window(cfg.swa_window),
                           min(cfg.swa_window, width)),
                          ("gqa", True, width)):
        layers = sum(mix == kind for mix, _ in cfg.plan)
        if layers:
            plan = tile_plan(rule, width, width, cfg.gqa_dim_head)
            seen += layers * (width * w - w * (w - 1) // 2)
            tiled += layers * plan["tiles_live"] * plan["block_q"] \
                * plan["block_k"]
    return jnp.asarray([rows * cfg.heads * seen, rows * cfg.heads * tiled],
                       jnp.float32)


def diffusion_noise(key: Optional[jax.Array], mask):
    """(masked [B, T] float32, t [B] float32) for a batch's mask [B, T]
    (1 = a real token): a noise level t = eps + (1 - eps) u, u uniform on
    [0, 1), per ROW, and each real position masked independently with
    probability t. Without a key (validation; a check that runs rows in
    chunks) nothing may depend on the batch but its width: t = 1/2 for
    every row, and position p is masked iff
    jax.random.uniform(jax.random.key(0), (T,))[p] < 1/2."""
    rows, width = mask.shape
    if key is None:
        t = jnp.full((rows,), 0.5, jnp.float32)
        draw = jnp.broadcast_to(
            jax.random.uniform(jax.random.key(0), (width,)), (rows, width))
    else:
        k_level, k_draw = jax.random.split(key)
        t = DIFFUSION_EPS + (1.0 - DIFFUSION_EPS) * jax.random.uniform(
            k_level, (rows,))
        draw = jax.random.uniform(k_draw, (rows, width))
    return (draw < t[:, None]).astype(jnp.float32) * mask, t


def _predict_ahead(cfg: PlanConfig, params: Params, x, emb, trg_ids, mask,
                   remat):
    """The prediction modules, one after another: module k's position t
    has seen y_<t+k-1 through `x` (the stack's output before its top
    norm, or the module's before it) and is GIVEN the gold y_{t+k-1},
    u = W_eh [RMSNorm(e(y_{t+k-1})) ; RMSNorm(x_t)]; one block over u; a
    norm of its own; its label is y_{t+k}. Everything keeps the stack's
    width T: the gold tokens are rolled left, and what rolled round the
    end, or lies past a row's last token, is masked out of the module's
    attention and of its labels. Returns ([Head], counters)."""
    heads = []
    counters = jnp.zeros((len(COUNTERS),), jnp.float32)
    for k, (lp, kinds) in enumerate(_blocks(cfg)[cfg.dec_depth:], 1):
        # position t is given y_{t+k-1} = emb[t+k-1] and keeps what can
        # still have a label k places on
        live = jnp.roll(mask, -k, axis=1).at[:, -k:].set(0.0)
        given = jnp.roll(emb, 1 - k, axis=1)
        u = jnp.concatenate(
            [rms_norm(given, params[f"{lp}_emb_norm_scale"],
                      eps=cfg.norm_eps),
             rms_norm(x, params[f"{lp}_hidden_norm_scale"],
                      eps=cfg.norm_eps)], axis=-1)
        x, c = _layer(cfg, kinds, lp, params,
                      jnp.dot(u, params[f"{lp}_Weh"]), live, remat)
        counters = counters + c
        heads.append(Head(
            "mtp", cfg.mtp_weight,
            rms_norm(x, params[f"{lp}_top_norm_scale"], eps=cfg.norm_eps),
            jnp.roll(trg_ids, -k, axis=1), live, k))
    return heads, counters


def _decode_diffusion(cfg: PlanConfig, params: Params, trg_ids, trg_mask,
                      train: bool, key, return_hidden: bool, noise):
    """decode_train under --plan-diffusion-block: the stack over [noised ;
    clean], 2T indices under BlockDiffusion(T, block), not shifted. Returns
    (the NOISED half's [B, T, ...] logits or hidden states, counters
    [len(counter_names(cfg))], the main head's per-token weights [B, T],
    masked / t)."""
    mask = trg_mask.astype(jnp.float32)
    remat = cfg.gradient_checkpointing and train
    width = trg_ids.shape[1]
    with jax.named_scope("diffusion.noise"):
        masked, level = noise if noise is not None \
            else diffusion_noise(key, mask)
        noised = jnp.where(masked > 0, MASK_TOKEN, trg_ids)
        weights = masked / level[:, None]
    with jax.named_scope("embed"):
        x = T._embed_words(cfg, params, jnp.concatenate(
            [noised, trg_ids], axis=1), "trg")
    both = jnp.concatenate([mask, mask], axis=1)
    rule = BlockDiffusion(width, cfg.diffusion_block)
    counters = jnp.zeros((len(COUNTERS),), jnp.float32)
    for lp, kinds in _blocks(cfg):
        x, c = _layer(cfg, kinds, lp, params, x, both, remat, rule)
        counters = counters + c
    x = rms_norm(x[:, :width], params["decoder_top_norm_scale"],
                 eps=cfg.norm_eps)
    counters = jnp.concatenate(
        [counters, jnp.stack([jnp.sum(masked), jnp.sum(mask)])])
    return (x if return_hidden else T.output_logits(cfg, params, x),
            jax.lax.stop_gradient(counters),
            jax.lax.stop_gradient(weights))


def decode_train(cfg: PlanConfig, params: Params, enc_out, src_mask,
                 trg_ids, trg_mask, train: bool = True,
                 key: Optional[jax.Array] = None,
                 return_alignment: bool = False,
                 return_hidden: bool = False, noise=None):
    """Teacher-forced: [B, T] gold ids -> ([B, T, V] logits, or the
    hidden states before the output projection when return_hidden;
    counters [len(counter_names(cfg))]; with prediction modules in the
    plan, the list of their Heads last, hidden states whatever
    return_hidden says). The input is the gold embeddings shifted right
    with a zero vector first, as transformer.decode_train's.

    Under --plan-diffusion-block the row is not shifted and the result is
    `_decode_diffusion`'s, the main head's per-token weights last; `noise`
    = (masked, t) then replaces what diffusion_noise(key, mask) draws
    (tests hand the reference the same)."""
    if return_alignment:
        raise ValueError("a layer plan has no cross attention to align")
    if cfg.diffusion_block:
        return _decode_diffusion(cfg, params, trg_ids, trg_mask, train, key,
                                 return_hidden, noise)
    with jax.named_scope("embed"):
        emb = T._embed_words(cfg, params, trg_ids, "trg")
        x = T.shift_right_embeddings(emb)
    mask = trg_mask.astype(jnp.float32)
    remat = cfg.gradient_checkpointing and train
    counters = jnp.zeros((len(COUNTERS),), jnp.float32)
    for lp, kinds in _blocks(cfg)[:cfg.dec_depth]:
        x, c = _layer(cfg, kinds, lp, params, x, mask, remat=remat)
        counters = counters + c
    heads = ()
    if cfg.mtp_modules:
        with jax.named_scope("mtp"):
            heads, c = _predict_ahead(cfg, params, x, emb, trg_ids, mask,
                                      remat)
        counters = counters + c
    if _has_window(cfg):
        counters = jnp.concatenate(
            [counters, _attention_pairs(cfg, *trg_ids.shape)])
    x = rms_norm(x, params["decoder_top_norm_scale"], eps=cfg.norm_eps)
    out = (x if return_hidden else T.output_logits(cfg, params, x),
           jax.lax.stop_gradient(counters))
    return out + (heads,) if cfg.mtp_modules else out


output_logits = T.output_logits
_plain_output_table = T._plain_output_table
