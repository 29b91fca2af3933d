"""Transformer encoder-decoder as pure JAX functions over a flat param dict.

Rebuild of reference src/models/transformer.h :: TransformerEncoder /
TransformerDecoder / MultiHead. The reference builds a fresh expression-graph
tape per batch and interprets it node-by-node; here the model is a pure
function jit-compiled once per input shape (SURVEY.md §2.3's central point).

Design notes:
- The parameter tree is a FLAT dict keyed by Marian's parameter names
  (``encoder_l1_self_Wq``, ``Wemb``, ``decoder_ff_logit_out_b``, …) so
  upstream Marian ``.npz`` checkpoints map 1:1 (symbol names recalled from
  upstream marian-dev; re-verify against a real checkpoint when available —
  see SURVEY.md provenance caveat). Weights are stored [in, out] like Marian
  and applied as ``x @ W``; all params f32, cast to the compute dtype (bf16)
  inside the forward pass.
- Pre/post-process strings follow Marian semantics: each sublayer wraps its
  core op with ``preprocess`` ops applied to the input and ``postprocess``
  ops applied to (output, input): 'd'=dropout, 'a'=residual add,
  'n'=layer-norm. Default "dan" = post-norm; --task *-prenorm sets pre="n",
  post="da", top="n".
- Incremental decoding keeps per-layer K/V caches as fixed-size
  [B, H, max_len, Dh] buffers updated with dynamic_update_slice — static
  shapes under jit (the reference appends to growing tensors instead).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..layers import initializers as inits
from ..ops.ops import (activation, affine, dropout, layer_norm,
                       logits_matmul, named_scope)
from ..ops.attention import (attention, causal_mask,
                             dense_attention_with_weights)

Params = Dict[str, jax.Array]

# decode-state keys with these suffixes are per-beam and must be reordered
# by backpointers in beam search (self-attention K/V caches); cross K/V and
# 'pos' are beam-invariant.
BEAM_CARRIED_SUFFIXES = ("_self_k", "_self_v", "_aan_sum", "_rnn_c")

_AUTOREG_MODES = ("self-attention", "average-attention", "rnn")


def _tied(cfg: "TransformerConfig", l: int) -> int:
    """Parameter-owning layer for physical layer l (1-based) under
    --transformer-tied-layers; identity without tying."""
    if cfg.tied_layers and l <= len(cfg.tied_layers):
        t = cfg.tied_layers[l - 1]
        if not 1 <= t <= l:
            raise ValueError(
                f"--transformer-tied-layers: layer {l} cannot share layer "
                f"{t} (must reference an earlier or same layer)")
        return t
    return l


def _check_autoreg(mode: str) -> str:
    if mode not in _AUTOREG_MODES:
        raise ValueError(
            f"--transformer-decoder-autoreg '{mode}' is not implemented "
            f"(supported: {', '.join(_AUTOREG_MODES)})")
    return mode


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static model hyperparameters (closed over by the jitted functions)."""
    src_vocab: int
    trg_vocab: int
    dim_emb: int = 512
    heads: int = 8
    dim_ffn: int = 2048
    dec_dim_ffn: int = 0            # 0 → dim_ffn
    ffn_depth: int = 2
    dec_ffn_depth: int = 0          # 0 → ffn_depth
    enc_depth: int = 6
    dec_depth: int = 6
    ffn_activation: str = "relu"
    preprocess: str = ""
    postprocess: str = "dan"
    postprocess_emb: str = "d"
    postprocess_top: str = ""
    tied_embeddings: bool = False       # tie trg emb ↔ output
    tied_embeddings_src: bool = False   # tie src ↔ trg emb
    tied_embeddings_all: bool = True    # tie all three
    train_position_embeddings: bool = False
    max_length: int = 512               # positional table length
    dropout: float = 0.0                # between-layer (pre/post 'd')
    attention_dropout: float = 0.0
    ffn_dropout: float = 0.0
    dropout_src: float = 0.0            # whole-word dropout
    dropout_trg: float = 0.0
    depth_scaling: bool = False
    no_projection: bool = False
    decoder_autoreg: str = "self-attention"   # or "average-attention", "rnn"
    output_approx_knn: Tuple[int, ...] = ()   # --output-approx-knn (k, nbits)
    dim_aan: int = 2048                       # AAN FFN size (--transformer-dim-aan)
    aan_depth: int = 2                        # --transformer-aan-depth
    aan_activation: str = "swish"             # --transformer-aan-activation
    aan_nogate: bool = False                  # --transformer-aan-nogate
    output_omit_bias: bool = False            # --output-omit-bias
    # --transformer-tied-layers: 1-based map, entry i = the layer whose
    # parameters layer i+1 SHARES (e.g. (1,1,1,1,1,1) = ALBERT-style all
    # layers share layer 1). Applies to encoder and decoder stacks; runtime
    # state (KV caches) stays per-physical-layer. Empty = no tying.
    tied_layers: Tuple[int, ...] = ()
    factor_weight: float = 1.0                # --factor-weight
    # --factors-combine concat (--factors-dim-emb f): each factor group
    # contributes an f-dim embedding CONCATENATED after an (emb - G*f)-dim
    # lemma embedding instead of summing same-width vectors (reference:
    # src/layers/embedding.cpp concatenative composition). Embedding-side
    # only; the factored output stays the unit-axis softmax.
    factors_combine: str = "sum"              # "sum" | "concat"
    factors_dim_emb: int = 0
    # --lemma-dim-emb L: soft lemma re-embedding in the factored output
    # (reference: src/layers/output.cpp :: Output::applyAsLogits, the
    # lemma-conditioned factor prediction): lemma distribution → expected
    # L-dim lemma embedding → projected and added to the decoder state
    # BEFORE the factor-group logits, so factor predictions condition on
    # the predicted lemma. L = -1 uses dim-emb.
    lemma_dim_emb: int = 0
    # decoder-only language model (--type transformer-lm; reference:
    # src/models/model_factory.cpp 'transformer' DecoderOnly assembly used
    # by marian-scorer for LM scoring / R2L reranking): no encoder stack,
    # no cross-attention sublayers — just the autoregressive decoder
    lm: bool = False
    # ULR (--ulr): fixed query/key tables are carried here as host arrays
    # for init_params only; the forward pass reads them from params (so
    # checkpoints are self-contained and decode needs no vector files)
    ulr: bool = False
    ulr_temperature: float = 1.0
    ulr_dropout: float = 0.0
    ulr_queries: Any = None                   # np [V_src, dq] or None
    ulr_keys: Any = None                      # np [V_u, dq] or None
    rnn_projection: bool = False              # --transformer-rnn-projection
    # --scan-layers: run the layer stack as one lax.scan over stacked
    # [L, ...] params (compile time O(1) in depth — the dominant TPU
    # cold-start cost). Default OFF since r4: the v5e bench A/B measured
    # the scanned stack 25-33% slower per step than unrolled (XLA cannot
    # schedule/fuse across the while-loop boundary); scan remains the
    # right call for very deep stacks and compile-time-bound jobs. Falls
    # back to the unrolled stack for tied layers, alignment extraction,
    # and quantized (QTensor) layer weights
    scan_layers: bool = False
    # --transformer-moe-experts (TPU extension; the reference has no MoE):
    # the FFN sublayer becomes a top-k-routed Mixture of Experts in the
    # GShard dispatch/combine-einsum formulation — expert tables [E, ...]
    # shard over the 'expert' mesh axis and XLA inserts the all-to-alls.
    # Tokens beyond an expert's capacity fall through the residual stream.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    flash_attention: str = "auto"             # auto | on | off (Pallas kernel)
    # head-packed short-sequence attention kernel (ops/pallas/
    # packed_attention.py): packs g = 128//dh heads per 128x128 MXU
    # tile pass. Only "on" selects it: it lost to XLA's dense einsum
    # at every short shape measured on a v5e, so auto = dense (PR 52).
    packed_attention: str = "auto"            # auto | on | off
    # fused beam-gather + cache-update + attention decode step
    # (ops/pallas/decode_attention.py): folds the beam reorder into the
    # kernel's cache read and collapses the reorder/DUS/attention op
    # chain in the decode while body. auto = TPU backend only.
    fused_decode_attention: str = "auto"      # auto | on | off
    gradient_checkpointing: bool = False      # jax.checkpoint per layer
    # sequence/context parallelism over the mesh 'seq' axis (TPU extension,
    # parallel/sequence.py): "none" | "ring" | "ulysses". seq_mesh is the
    # device mesh the shard_map'd attention runs on (closed over, not traced).
    sequence_parallel: str = "none"
    seq_mesh: Any = None
    # size of the 'model' (tensor-parallel) mesh axis, parsed from the
    # --mesh spec itself: seq_mesh is only built when --sequence-parallel
    # is active, so a plain TP run must not rely on it (the fused-QKV gate
    # must see the Megatron column split either way)
    n_model_tp: int = 1
    compute_dtype: Any = jnp.bfloat16
    guided_alignment_layer: str = "last"
    # factored-vocab metadata (layers/logits.py FactorTables): one entry per
    # encoder for the source side (None entry = plain vocab for that stream)
    src_factors: Tuple[Any, ...] = (None,)
    trg_factors: Any = None
    # multi-source (reference: model_factory.cpp assembling N encoders for
    # --type multi-transformer; doc-level context, config #4): encoder i
    # gets param prefix 'encoder' / 'encoder2' / ...; every decoder layer
    # stacks one cross-attention sublayer per encoder, in order.
    n_encoders: int = 1
    src_vocabs: Tuple[int, ...] = ()          # per-encoder vocab sizes

    @property
    def dim_head(self) -> int:
        return self.dim_emb // self.heads

    @property
    def dec_ffn(self) -> int:
        return self.dec_dim_ffn or self.dim_ffn

    @property
    def dec_ffn_d(self) -> int:
        return self.dec_ffn_depth or self.ffn_depth


def _mesh_axis_size(g, axis: str) -> int:
    """Axis size straight from the --mesh spec strings (``model:4`` etc.),
    via the ONE canonical parser (parallel.mesh.parse_mesh_spec).
    Deliberately independent of seq_mesh, which only exists under
    --sequence-parallel: config gates (fused QKV vs the Megatron column
    split) need the axis size on EVERY mesh run."""
    from ..parallel.mesh import parse_mesh_spec
    return max(1, parse_mesh_spec(g("mesh", []) or []).get(axis, 1))


def _resolve_scan_layers(g) -> bool:
    """--stacked-params and pipeline ('pipe') meshes structurally require
    the scanned stack (the forward consumes depth-stacked [L, ...]
    leaves), so they imply scan-layers on — announced with a log line,
    since scan costs 25-33%/step vs unrolled (r4 v5e A/B) and the user
    may have scan off (the default, or explicitly)."""
    scan = bool(g("scan-layers", False))
    implied = bool(g("stacked-params", False)) or any(
        str(s).startswith("pipe:") and int(str(s).split(":")[1]) > 1
        for s in (g("mesh", []) or []))
    if implied and not scan:
        from ..common import logging as log
        log.info("--stacked-params / pipe-sharded mesh requires the "
                 "scanned layer stack: implying --scan-layers on "
                 "(~25-33% slower per step than unrolled on TPU)")
    return scan or implied


def config_from_options(options, src_vocab, trg_vocab: int,
                        for_inference: bool = False,
                        src_factors=None, trg_factors=None,
                        seq_mesh=None) -> TransformerConfig:
    """Map Marian flags → TransformerConfig (reference: transformer.h reads
    the same option names). `src_vocab` may be a tuple of sizes
    (multi-source: one encoder per entry)."""
    g = options.get
    if isinstance(src_vocab, (tuple, list)):
        src_vocabs = tuple(int(v) for v in src_vocab)
    else:
        src_vocabs = (int(src_vocab),)
    if len(src_vocabs) > 1 and str(g("type", "transformer")) not in (
            "multi-transformer",):
        raise ValueError(
            f"--type {g('type', 'transformer')} is a single-encoder model; "
            f"multiple source streams need --type multi-transformer")
    # normalize src_factors to one entry per encoder
    if not isinstance(src_factors, (tuple, list)):
        src_factors = (src_factors,)
    src_factors = (tuple(src_factors)
                   + (None,) * (len(src_vocabs) - len(src_factors)))
    precision = g("precision", ["float32"])
    compute = precision[0] if isinstance(precision, list) else precision
    # the reference's float16 path maps to bf16 on TPU (MXU-native)
    dtype = {"float32": jnp.float32, "float16": jnp.bfloat16,
             "bfloat16": jnp.bfloat16}.get(str(compute), jnp.float32)
    drop = 0.0 if for_inference else float(g("transformer-dropout", 0.0))
    return TransformerConfig(
        src_vocab=src_vocabs[0],
        trg_vocab=trg_vocab,
        n_encoders=len(src_vocabs),
        src_vocabs=src_vocabs,
        dim_emb=int(g("dim-emb", 512)),
        heads=int(g("transformer-heads", 8)),
        dim_ffn=int(g("transformer-dim-ffn", 2048)),
        dec_dim_ffn=int(g("transformer-decoder-dim-ffn", 0)),
        ffn_depth=int(g("transformer-ffn-depth", 2)),
        dec_ffn_depth=int(g("transformer-decoder-ffn-depth", 0)),
        enc_depth=int(g("enc-depth", 6)),
        dec_depth=int(g("dec-depth", 6)),
        ffn_activation=str(g("transformer-ffn-activation", "relu")),
        preprocess=str(g("transformer-preprocess", "")),
        postprocess=str(g("transformer-postprocess", "dan")),
        postprocess_emb=str(g("transformer-postprocess-emb", "d")),
        postprocess_top=str(g("transformer-postprocess-top", "")),
        tied_embeddings=bool(g("tied-embeddings", False)),
        tied_embeddings_src=bool(g("tied-embeddings-src", False)),
        tied_embeddings_all=bool(g("tied-embeddings-all", False)),
        train_position_embeddings=bool(g("transformer-train-position-embeddings", False)),
        max_length=max(int(g("max-length", 50)) * 2, 512),
        dropout=drop,
        attention_dropout=0.0 if for_inference else float(g("transformer-dropout-attention", 0.0)),
        ffn_dropout=0.0 if for_inference else float(g("transformer-dropout-ffn", 0.0)),
        dropout_src=0.0 if for_inference else float(g("dropout-src", 0.0)),
        dropout_trg=0.0 if for_inference else float(g("dropout-trg", 0.0)),
        depth_scaling=bool(g("transformer-depth-scaling", False)),
        no_projection=bool(g("transformer-no-projection", False)),
        decoder_autoreg=_check_autoreg(
            str(g("transformer-decoder-autoreg", "self-attention"))),
        output_approx_knn=tuple(
            int(v) for v in (g("output-approx-knn", []) or [])),
        tied_layers=tuple(int(v) for v in
                          (g("transformer-tied-layers", []) or [])),
        lm=str(g("type", "transformer")) in ("transformer-lm",
                                             "lm-transformer", "lm"),
        # training-loss weighting only (reference: applyLossFunction scales
        # factor losses; getLogits sums unweighted — decode parity)
        factor_weight=1.0 if for_inference
        else float(g("factor-weight", 1.0) or 1.0),
        ulr=bool(g("ulr", False)),
        ulr_temperature=float(g("ulr-softmax-temperature", 1.0) or 1.0),
        ulr_dropout=0.0 if for_inference else float(g("ulr-dropout", 0.0)
                                                    or 0.0),
        dim_aan=int(g("transformer-dim-aan", 2048)),
        aan_depth=int(g("transformer-aan-depth", 2)),
        aan_activation=str(g("transformer-aan-activation", "swish")),
        aan_nogate=bool(g("transformer-aan-nogate", False)),
        output_omit_bias=bool(g("output-omit-bias", False)),
        rnn_projection=bool(g("transformer-rnn-projection", False)),
        scan_layers=_resolve_scan_layers(g),
        moe_experts=int(g("transformer-moe-experts", 0) or 0),
        moe_top_k=_check_moe(int(g("transformer-moe-experts", 0) or 0),
                             int(g("transformer-moe-top-k", 2) or 2)),
        moe_capacity_factor=float(
            1.25 if g("moe-capacity-factor", None) is None
            else g("moe-capacity-factor")),
        moe_aux_weight=float(
            0.01 if g("moe-aux-weight", None) is None
            else g("moe-aux-weight")),
        flash_attention=str(g("transformer-flash-attention", "auto")),
        packed_attention=str(g("transformer-packed-attention", "auto")),
        fused_decode_attention=str(
            g("transformer-fused-decode-attention", "auto")),
        gradient_checkpointing=(not for_inference
                                and bool(g("gradient-checkpointing", False))),
        sequence_parallel=str(g("sequence-parallel", "none") or "none"),
        seq_mesh=seq_mesh,
        n_model_tp=_mesh_axis_size(g, "model"),
        compute_dtype=dtype,
        guided_alignment_layer=str(g("transformer-guided-alignment-layer", "last")),
        src_factors=src_factors,
        trg_factors=trg_factors,
        factors_combine=_check_factors_combine(
            str(g("factors-combine", "sum") or "sum"),
            int(g("factors-dim-emb", 0) or 0), int(g("dim-emb", 512)),
            src_factors, trg_factors,
            bool(g("tied-embeddings-all", False))
            or bool(g("tied-embeddings", False))
            or bool(g("tied-embeddings-src", False))),
        factors_dim_emb=int(g("factors-dim-emb", 0) or 0),
        lemma_dim_emb=_check_lemma_dim(int(g("lemma-dim-emb", 0) or 0),
                                       int(g("dim-emb", 512)), trg_factors),
    )


def _check_factors_combine(mode: str, f_dim: int, d: int, src_factors,
                           trg_factors, tied: bool) -> str:
    if mode not in ("sum", "concat"):
        raise ValueError(f"--factors-combine '{mode}' (sum or concat)")
    if mode == "sum" and f_dim > 0:
        raise ValueError(
            "--factors-dim-emb only applies with --factors-combine concat "
            "(sum combination uses full-width dim-emb factor vectors)")
    if mode == "concat":
        if f_dim <= 0:
            raise ValueError("--factors-combine concat requires "
                             "--factors-dim-emb > 0")
        if tied:
            raise ValueError(
                "--factors-combine concat is incompatible with tied "
                "embeddings: the lemma table is narrower than dim-emb and "
                "cannot double as the unit-axis output matrix")
        for ft in tuple(src_factors or ()) + (trg_factors,):
            if ft is None:
                continue
            groups = len(ft.group_slices) - 1
            if d - groups * f_dim < 1:
                raise ValueError(
                    f"--factors-dim-emb {f_dim}: {groups} factor groups "
                    f"leave no room for the lemma embedding at dim-emb {d}")
    return mode


def _check_moe(experts: int, top_k: int) -> int:
    if experts > 0 and not (1 <= top_k <= experts):
        raise ValueError(
            f"--transformer-moe-top-k {top_k}: must be between 1 and the "
            f"number of experts ({experts})")
    return top_k


def _check_lemma_dim(val: int, d: int, trg_factors) -> int:
    if val == -1:
        val = d
    if val < 0:
        raise ValueError(f"--lemma-dim-emb {val}: use 0 (off), -1 "
                         f"(= dim-emb) or a positive dimension")
    if val > 0 and trg_factors is None:
        raise ValueError("--lemma-dim-emb needs a factored target vocab")
    return val


def _src_rows(cfg: TransformerConfig, i: int = 0) -> int:
    ft = cfg.src_factors[i] if i < len(cfg.src_factors) else None
    return ft.n_units if ft else cfg.src_vocabs[i]


def _trg_rows(cfg: TransformerConfig) -> int:
    return cfg.trg_factors.n_units if cfg.trg_factors else cfg.trg_vocab


def _enc_prefix(i: int) -> str:
    """Param prefix of encoder i (multi-source: encoder, encoder2, ...)."""
    return "encoder" if i == 0 else f"encoder{i + 1}"


def _ctx_suffix(i: int) -> str:
    """Suffix of the decoder cross-attention block for encoder i."""
    return "" if i == 0 else str(i + 1)


def _as_tuple(x) -> tuple:
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


# ---------------------------------------------------------------------------
# Initialization (param names follow upstream Marian's transformer.h)
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    p: Params = {}
    k = iter(jax.random.split(key, 4096))
    d = cfg.dim_emb

    def glorot(shape, depth_layer: int = 0):
        scale = 1.0
        if cfg.depth_scaling and depth_layer > 0:
            scale = 1.0 / math.sqrt(depth_layer)
        return inits.glorot_uniform(next(k), shape, scale=scale)

    # embeddings (row count = factor units for factored vocabs; concat
    # combination splits each factored table into a narrower lemma table
    # plus an f-wide factor table — see layers/logits.py)
    def emb_tables(name: str, ft, rows: int):
        if ft is not None and cfg.factors_combine == "concat":
            groups = len(ft.group_slices) - 1
            p[name] = glorot((ft.n_lemmas,
                              d - groups * cfg.factors_dim_emb))
            p[name + "_factors"] = glorot((ft.n_units - ft.n_lemmas,
                                           cfg.factors_dim_emb))
        else:
            p[name] = glorot((rows, d))

    if cfg.lm:
        emb_tables("Wemb" if (cfg.tied_embeddings_all or cfg.tied_embeddings)
                   else "decoder_Wemb", cfg.trg_factors, _trg_rows(cfg))
    elif cfg.tied_embeddings_all or cfg.tied_embeddings_src:
        if any(_src_rows(cfg, i) != _trg_rows(cfg)
               for i in range(cfg.n_encoders)):
            raise ValueError("tied src embeddings require equal vocab sizes")
        p["Wemb"] = glorot((_trg_rows(cfg), d))
    else:
        for i in range(cfg.n_encoders):
            emb_tables(f"{_enc_prefix(i)}_Wemb",
                       cfg.src_factors[i] if i < len(cfg.src_factors)
                       else None, _src_rows(cfg, i))
        emb_tables("decoder_Wemb", cfg.trg_factors, _trg_rows(cfg))
    if cfg.train_position_embeddings:
        p["Wpos"] = glorot((cfg.max_length, d))
    if "n" in cfg.postprocess_emb:
        if not cfg.lm:
            for i in range(cfg.n_encoders):
                p[f"{_enc_prefix(i)}_emb_ln_scale"] = inits.ones((1, d))
                p[f"{_enc_prefix(i)}_emb_ln_bias"] = inits.zeros((1, d))
        p["decoder_emb_ln_scale"] = inits.ones((1, d))
        p["decoder_emb_ln_bias"] = inits.zeros((1, d))

    def attn_block(prefix: str, layer: int):
        p[f"{prefix}_Wq"] = glorot((d, d), layer)
        p[f"{prefix}_bq"] = inits.zeros((1, d))
        p[f"{prefix}_Wk"] = glorot((d, d), layer)
        p[f"{prefix}_bk"] = inits.zeros((1, d))
        p[f"{prefix}_Wv"] = glorot((d, d), layer)
        p[f"{prefix}_bv"] = inits.zeros((1, d))
        if not cfg.no_projection:
            p[f"{prefix}_Wo"] = glorot((d, d), layer)
            p[f"{prefix}_bo"] = inits.zeros((1, d))
        if "n" in cfg.preprocess or "n" in cfg.postprocess:
            p[f"{prefix}_Wo_ln_scale"] = inits.ones((1, d))
            p[f"{prefix}_Wo_ln_bias"] = inits.zeros((1, d))

    def ffn_block(prefix: str, dim_ffn: int, depth: int, layer: int):
        if cfg.moe_experts > 0:
            # MoE FFN (--transformer-moe-experts): expert-stacked tables;
            # glorot fans are the per-expert matmul dims, not the E axis
            ex = cfg.moe_experts
            base = prefix[:-4]           # strip '_ffn' → '{ep}_l{l}'
            scale = 1.0 / math.sqrt(layer) if (cfg.depth_scaling and layer)\
                else 1.0
            p[f"{base}_moe_gate"] = inits.glorot_uniform(
                next(k), (d, ex), scale=scale)
            p[f"{base}_moe_W1"] = inits.glorot_uniform(
                next(k), (ex, d, dim_ffn), fan_in=d, fan_out=dim_ffn,
                scale=scale)
            p[f"{base}_moe_b1"] = inits.zeros((ex, 1, dim_ffn))
            p[f"{base}_moe_W2"] = inits.glorot_uniform(
                next(k), (ex, dim_ffn, d), fan_in=dim_ffn, fan_out=d,
                scale=scale)
            p[f"{base}_moe_b2"] = inits.zeros((ex, 1, d))
        else:
            dims = [d] + [dim_ffn] * (depth - 1) + [d]
            for i in range(depth):
                p[f"{prefix}_W{i+1}"] = glorot((dims[i], dims[i + 1]), layer)
                p[f"{prefix}_b{i+1}"] = inits.zeros((1, dims[i + 1]))
        if "n" in cfg.preprocess or "n" in cfg.postprocess:
            p[f"{prefix}_ffn_ln_scale"] = inits.ones((1, d))
            p[f"{prefix}_ffn_ln_bias"] = inits.zeros((1, d))

    for i in range(0 if cfg.lm else cfg.n_encoders):
        ep = _enc_prefix(i)
        for l in range(1, cfg.enc_depth + 1):
            if _tied(cfg, l) != l:
                continue                 # shares an earlier layer's params
            attn_block(f"{ep}_l{l}_self", l)
            ffn_block(f"{ep}_l{l}_ffn", cfg.dim_ffn, cfg.ffn_depth, l)
        if "n" in cfg.postprocess_top or "n" in cfg.preprocess:
            p[f"{ep}_top_ln_scale"] = inits.ones((1, d))
            p[f"{ep}_top_ln_bias"] = inits.zeros((1, d))

    def aan_block_params(prefix: str, layer: int):
        """Average Attention Network sublayer (reference:
        src/models/transformer.h :: LayerAAN / AverageAttention): FFN over
        the cumulative average + a sigmoid gate mixing with the input. The
        pre/post layer-norm params keep the `_self_Wo` naming so the Marian
        process strings apply unchanged."""
        # --transformer-aan-depth: chain of `depth` dense layers
        # d → aan → … → d (activation between, none after the last)
        n = max(1, cfg.aan_depth)
        for i in range(1, n + 1):
            din = d if i == 1 else cfg.dim_aan
            dout = d if i == n else cfg.dim_aan
            p[f"{prefix}_aan_W{i}"] = glorot((din, dout), layer)
            p[f"{prefix}_aan_b{i}"] = inits.zeros((1, dout))
        if not cfg.aan_nogate:      # --transformer-aan-nogate drops these
            p[f"{prefix}_aan_Wi"] = glorot((d, d), layer)
            p[f"{prefix}_aan_bi"] = inits.zeros((1, d))
            p[f"{prefix}_aan_Wg"] = glorot((d, d), layer)
            p[f"{prefix}_aan_bg"] = inits.zeros((1, d))
        if "n" in cfg.preprocess or "n" in cfg.postprocess:
            p[f"{prefix}_self_Wo_ln_scale"] = inits.ones((1, d))
            p[f"{prefix}_self_Wo_ln_bias"] = inits.zeros((1, d))

    def rnn_block(prefix: str, layer: int):
        """SSRU decoder sublayer (reference: src/models/transformer.h ::
        DecoderLayerRNN with --dec-cell ssru; ops/rnn.py supplies the cell
        math). Param names follow the SSRU cell's x_proj contract."""
        p[f"{prefix}_rnn_W"] = glorot((d, d), layer)
        p[f"{prefix}_rnn_Wf"] = glorot((d, d), layer)
        p[f"{prefix}_rnn_bf"] = inits.zeros((1, d))
        if cfg.rnn_projection:
            p[f"{prefix}_rnn_Wo"] = glorot((d, d), layer)
            p[f"{prefix}_rnn_bo"] = inits.zeros((1, d))
        if "n" in cfg.preprocess or "n" in cfg.postprocess:
            p[f"{prefix}_self_Wo_ln_scale"] = inits.ones((1, d))
            p[f"{prefix}_self_Wo_ln_bias"] = inits.zeros((1, d))

    for l in range(1, cfg.dec_depth + 1):
        if _tied(cfg, l) != l:
            continue
        if cfg.decoder_autoreg == "average-attention":
            aan_block_params(f"decoder_l{l}", l)
        elif cfg.decoder_autoreg == "rnn":
            rnn_block(f"decoder_l{l}", l)
        else:
            attn_block(f"decoder_l{l}_self", l)
        for i in range(0 if cfg.lm else cfg.n_encoders):
            attn_block(f"decoder_l{l}_context{_ctx_suffix(i)}", l)
        ffn_block(f"decoder_l{l}_ffn", cfg.dec_ffn, cfg.dec_ffn_d, l)
    if "n" in cfg.postprocess_top or "n" in cfg.preprocess:
        p["decoder_top_ln_scale"] = inits.ones((1, d))
        p["decoder_top_ln_bias"] = inits.zeros((1, d))

    if not (cfg.tied_embeddings_all or cfg.tied_embeddings):
        p["decoder_ff_logit_out_W"] = glorot((d, _trg_rows(cfg)))
    if not cfg.output_omit_bias:    # --output-omit-bias drops the term
        p["decoder_ff_logit_out_b"] = inits.zeros((1, _trg_rows(cfg)))
    if cfg.trg_factors is not None and cfg.lemma_dim_emb > 0:
        # soft lemma re-embedding (--lemma-dim-emb; see TransformerConfig)
        p["decoder_lemma_reembed_W"] = glorot(
            (cfg.trg_factors.n_lemmas, cfg.lemma_dim_emb))
        p["decoder_lemma_reembed_Wp"] = glorot((cfg.lemma_dim_emb, d))
        p["decoder_lemma_reembed_bp"] = inits.zeros((1, d))

    if cfg.ulr:
        if cfg.ulr_queries is None or cfg.ulr_keys is None:
            raise ValueError(
                "--ulr training requires --ulr-query-vectors and "
                "--ulr-keys-vectors files matching the source vocabulary")
        q = jnp.asarray(cfg.ulr_queries, jnp.float32)
        kk_ = jnp.asarray(cfg.ulr_keys, jnp.float32)
        p["ulr_Q"] = q                           # fixed (frozen in updates)
        p["ulr_K"] = kk_                         # fixed
        p["ulr_A"] = jnp.eye(q.shape[1], dtype=jnp.float32)
        p["ulr_Wu"] = glorot((kk_.shape[0], d))  # universal value embs
    return p


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

@named_scope("pre_post")
def _pre_post(cfg: TransformerConfig, ops: str, x: jax.Array,
              residual: Optional[jax.Array], prefix: str, params: Params,
              key, train: bool) -> jax.Array:
    """Apply a Marian process string ('d','a','n') to x."""
    for i, op in enumerate(ops):
        if op == "d":
            if train and cfg.dropout > 0.0 and key is not None:
                x = dropout(x, cfg.dropout, jax.random.fold_in(key, i))
        elif op == "a":
            if residual is not None:
                x = x + residual
        elif op == "n":
            x = layer_norm(x, params[f"{prefix}_ln_scale"],
                           params[f"{prefix}_ln_bias"])
        else:
            raise ValueError(f"Unknown process op '{op}'")
    return x


def _split_heads(x: jax.Array, heads: int) -> jax.Array:
    b, t, d = x.shape
    return x.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)


_SP_FALLBACK_WARNED: set = set()


def _warn_sp_fallback(reason: str) -> None:
    """One-time (per reason) warning when --sequence-parallel is configured
    but a shape/dropout gate silently routes attention to the dense path —
    otherwise SP can be a no-op with its memory benefit lost and no signal
    (ADVICE r1). Runs at trace time, so it fires once per compiled shape."""
    if reason in _SP_FALLBACK_WARNED:
        return
    _SP_FALLBACK_WARNED.add(reason)
    from ..common.logging import log
    log.warn("sequence-parallel configured but falling back to dense "
             "attention: {}", reason)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


@jax.custom_vjp
def _bias_add_bhtd(y: jax.Array, b4: jax.Array) -> jax.Array:
    return y + b4


def _bias_add_bhtd_fwd(y, b4):
    return y + b4, None


def _bias_add_bhtd_bwd(res, g):
    # db as a dot (ones contraction over batch+time) instead of the 4-D
    # reduce XLA emits, which lowers to a slow transpose+reduce on TPU (the
    # round-1 profile showed 54 such reduces costing ~10% of the step).
    bb, h, t, d = g.shape
    ones = jnp.ones((bb, t), g.dtype)
    db = jax.lax.dot_general(ones, g, (((0, 1), (0, 2)), ((), ())),
                             preferred_element_type=jnp.float32)
    return g, db[None, :, None, :].astype(g.dtype)


_bias_add_bhtd.defvjp(_bias_add_bhtd_fwd, _bias_add_bhtd_bwd)


def _proj_heads(x: jax.Array, w, b, heads: int) -> jax.Array:
    """affine + split_heads in ONE dot: 'bte,ehd->bhtd'. The [B,T,H,Dh] →
    [B,H,T,Dh] transpose becomes the matmul's output layout instead of a
    physical copy — the round-1 profile showed those copies ("data
    formatting") costing >10% of the train step. Identical numerics to
    _split_heads(affine(...)): the weight reshape splits output columns
    head-major exactly like the activation reshape did."""
    e = w.shape[0]
    dh = w.shape[1] // heads
    y = jnp.einsum("bte,ehd->bhtd", x, w.reshape(e, heads, dh),
                   preferred_element_type=x.dtype)
    if b is not None:
        y = _bias_add_bhtd(y, b.reshape(1, heads, 1, dh).astype(y.dtype))
    return y


def _unproj_heads(x: jax.Array, w, b) -> jax.Array:
    """merge_heads + output affine in ONE dot: 'bhtd,hde->bte' (see
    _proj_heads)."""
    h, dh = x.shape[1], x.shape[3]
    e = w.shape[1]
    y = jnp.einsum("bhtd,hde->bte", x, w.reshape(h, dh, e),
                   preferred_element_type=x.dtype)
    if b is not None:
        y = y + b.reshape(1, 1, e).astype(y.dtype)
    return y


def fused_decode_active(cfg: TransformerConfig) -> bool:
    """Whether the fused gather+attention decode kernel handles the
    cached self-attention step (--transformer-fused-decode-attention).
    'auto' engages on the TPU backend only — interpret mode would just
    be a slower dense step; tests force 'on'. The beam search consults
    this (via EncoderDecoder.fused_decode_reorder) to hand the kernel
    the pending backpointers instead of reordering the caches itself."""
    mode = getattr(cfg, "fused_decode_attention", "off")
    if mode == "off" or cfg.decoder_autoreg != "self-attention":
        return False
    if getattr(cfg, "n_model_tp", 1) > 1:
        # Megatron TP shards the KV caches over heads on the 'model'
        # axis; the pallas call is opaque to GSPMD, which would
        # all-gather every layer's full cache around it each step
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


@named_scope(lambda _cfg, _params, prefix, *_a, **_kw:
         "self_attn" if prefix.endswith("_self") else "cross_attn")
def _mha(cfg: TransformerConfig, params: Params, prefix: str,
         q_in: jax.Array, kv_in: jax.Array, mask: Optional[jax.Array],
         key, train: bool,
         cache: Optional[Dict[str, jax.Array]] = None,
         cache_pos: Optional[jax.Array] = None,
         static_kv: bool = False,
         return_weights: bool = False,
         kv_mask: Optional[jax.Array] = None,
         causal: bool = False,
         beam_src: Optional[jax.Array] = None,
         fused_decode: Optional[bool] = None,
         page_table: Optional[jax.Array] = None):
    """Multi-head attention with optional decode cache.

    cache (self-attn): dict with 'k','v' [B,H,L,Dh]; new K/V written at
    cache_pos. static_kv (cross-attn): K/V precomputed in cache, reused.
    beam_src [rows] int32: pending beam backpointers (flat source rows)
    for the fused decode kernel, which folds the beam reorder into its
    cache read; None = identity (greedy/scoring, or reorder-on-the-
    outside decoding when the fused kernel is off). fused_decode
    overrides fused_decode_active(cfg) when the CALLER knows better —
    the beam search passes False under a decode mesh, where the
    GSPMD-opaque pallas call would re-replicate the sharded caches.
    page_table [rows, max_pages] int32 (iteration-level decode): cache
    is a PAGED POOL ({'k','v'} = [n_pages,H,page_len,Dh]) and cache_pos
    is a per-row [rows] position vector — the paged kernel
    (ops/pallas/kv_pool.py) owns the whole cached-attention step.
    """
    from ..ops.quantization import QTensor

    h = cfg.heads

    def proj(x, wname, bname):
        w, b = params[wname], params[bname]
        if isinstance(w, QTensor):  # int8 decode weights: affine handles them
            return _split_heads(affine(x, w, b), h)
        return _proj_heads(x, w, b, h)

    def proj_many(x, names):
        """G projections of the SAME input as ONE widened GEMM
        ('bte,eghd->gbhtd'): the r4 TPU trace showed the per-projection
        dots (54/step at ~100µs each) running far under MXU efficiency —
        tripling N amortizes the tiling. Output columns are concatenated
        per projection, so each slice is element-identical to its
        separate _proj_heads dot's contraction; biases go through the
        same _bias_add_bhtd custom-VJP as the unfused path. The runtime
        weight concat costs one 3d² read+write (~0.1 ms/step at
        transformer-big) against the GEMM win; int8 QTensor weights
        fall back to per-projection affine."""
        ws = [params[f"{prefix}_W{n}"] for n in names]
        if any(isinstance(w, QTensor) for w in ws):
            return [proj(x, f"{prefix}_W{n}", f"{prefix}_b{n}")
                    for n in names]
        g, e = len(ws), ws[0].shape[0]
        dh = ws[0].shape[1] // h
        w = jnp.concatenate(ws, axis=1).reshape(e, g, h, dh)
        y = jnp.einsum("bte,eghd->gbhtd", x, w,
                       preferred_element_type=x.dtype)
        return [_bias_add_bhtd(
                    y[i], params[f"{prefix}_b{n}"].reshape(
                        1, h, 1, dh).astype(y.dtype))
                for i, n in enumerate(names)]

    # fuse only where it wins: full-sequence shapes (the t=1 cached decode
    # step is weight-bandwidth-bound — a runtime 3d² concat would DOUBLE
    # its attention weight traffic) and no 'model' (TP) axis (the concat
    # crosses the Megatron column split, and GSPMD cannot push P(None,
    # 'model') through the (e,3,h,dh) reshape's major g dim — it would
    # replicate the weights every step)
    n_model_tp = max(cfg.n_model_tp,
                     cfg.seq_mesh.shape.get("model", 1)
                     if cfg.seq_mesh is not None else 1)
    fuse = n_model_tp <= 1 and q_in.shape[-2] > 1
    if static_kv and cache is not None:
        q = proj(q_in, f"{prefix}_Wq", f"{prefix}_bq")
        k_, v_ = cache["k"], cache["v"]
    elif fuse and q_in is kv_in:
        q, k_, v_ = proj_many(q_in, ("q", "k", "v"))    # self-attention
    elif fuse:
        q = proj(q_in, f"{prefix}_Wq", f"{prefix}_bq")
        k_, v_ = proj_many(kv_in, ("k", "v"))           # uncached cross
    else:
        q = proj(q_in, f"{prefix}_Wq", f"{prefix}_bq")
        k_ = proj(kv_in, f"{prefix}_Wk", f"{prefix}_bk")
        v_ = proj(kv_in, f"{prefix}_Wv", f"{prefix}_bv")
    fused_out = None
    # 'auto' fuses only when there is a beam reorder to fold: with the
    # identity gather (greedy/scoring pass no beam_src) the kernel still
    # collapses the DUS+attention op chain but rewrites the FULL cache
    # per step where the unfused path wrote one position in place —
    # net extra HBM traffic for no gather saved. Explicit 'on' forces it
    # either way (tests, A/Bs).
    if fused_decode is not None:
        use_fused = fused_decode
    else:
        use_fused = fused_decode_active(cfg) and (
            beam_src is not None
            or getattr(cfg, "fused_decode_attention", "") == "on")
    if not (static_kv and cache is not None):
        if cache is not None and cache_pos is not None:
            if page_table is not None:
                # paged pool (iteration-level decode): page-table read +
                # one new-token insert, per-row positions — no beam
                # reorder exists here (the page table IS row identity)
                from ..ops.pallas.kv_pool import paged_decode_attention
                fused_out, nk, nv = paged_decode_attention(
                    q, k_, v_, cache["k"], cache["v"], page_table,
                    cache_pos)
                cache["k"], cache["v"] = nk, nv
            elif use_fused:
                # fused gather + cache update + attention read: ONE
                # kernel replaces the beam reorder of this layer's two
                # cache leaves, the two single-position DUS writes, and
                # the score/softmax/apply chain (the r5 while-body
                # op-count lever; ops/pallas/decode_attention.py)
                from ..ops.pallas.decode_attention import decode_attention
                fused_out, nk, nv = decode_attention(
                    q, k_, v_, cache["k"], cache["v"], cache_pos,
                    src_rows=beam_src)
                cache["k"], cache["v"] = nk, nv
            else:
                # write this step's K/V into the fixed-size cache at
                # position pos
                k_ = jax.lax.dynamic_update_slice(
                    cache["k"], k_.astype(cache["k"].dtype),
                    (0, 0, cache_pos, 0))
                v_ = jax.lax.dynamic_update_slice(
                    cache["v"], v_.astype(cache["v"].dtype),
                    (0, 0, cache_pos, 0))
                cache["k"], cache["v"] = k_, v_
    dk = jax.random.fold_in(key, 97) if (key is not None) else None
    # sequence-parallel path: full-sequence attention (training/scoring, not
    # the cached decode step) runs ring/ulysses over the 'seq' mesh axis so
    # the time dimension stays sharded end-to-end (parallel/sequence.py)
    n_seq = cfg.seq_mesh.shape.get("seq", 1) if cfg.seq_mesh is not None else 1
    n_model = cfg.seq_mesh.shape.get("model", 1) if cfg.seq_mesh is not None else 1
    sp_wanted = (cfg.sequence_parallel != "none" and n_seq > 1
                 and cache is None and not return_weights and q.shape[-2] > 1)
    sp_fallback = None
    if sp_wanted:
        # shard_map needs even splits: time dims over 'seq', heads over
        # 'model' (length buckets guarantee this only up to seq<=8 —
        # fall back to dense/GSPMD otherwise)
        if q.shape[-2] % n_seq != 0 or k_.shape[-2] % n_seq != 0:
            sp_fallback = (f"sequence length ({q.shape[-2]}/{k_.shape[-2]}) "
                           f"not divisible by seq={n_seq}")
        elif q.shape[1] % max(n_model, 1) != 0:
            sp_fallback = f"heads ({q.shape[1]}) not divisible by model={n_model}"
        elif q.shape[0] % max(cfg.seq_mesh.shape.get("data", 1), 1) != 0:
            sp_fallback = (f"batch ({q.shape[0]}) not divisible by "
                           f"data={cfg.seq_mesh.shape.get('data', 1)}")
        elif (cfg.sequence_parallel == "ulysses"
              # ulysses swaps heads<->seq: per-device heads split over seq
              and (q.shape[1] // max(n_model, 1)) % n_seq != 0):
            sp_fallback = (f"ulysses needs per-device heads "
                           f"({q.shape[1]}//{n_model}) divisible by seq={n_seq}")
        elif cfg.attention_dropout != 0.0 and train:
            sp_fallback = "attention dropout is active in training"
        if sp_fallback is not None:
            _warn_sp_fallback(sp_fallback)
    if fused_out is not None:
        out, weights = fused_out, None
    elif sp_wanted and sp_fallback is None:
        from ..parallel.sequence import ring_attention_sharded
        out = ring_attention_sharded(cfg.seq_mesh, q, k_, v_,
                                     kv_mask=kv_mask, causal=causal,
                                     mode=cfg.sequence_parallel)
        weights = None
    else:
        out, weights = attention(
            q, k_, v_, mask, kv_mask=kv_mask, causal=causal,
            dropout_rate=cfg.attention_dropout, dropout_key=dk,
            deterministic=not train, return_weights=return_weights,
            flash=cfg.flash_attention,
            packed=getattr(cfg, "packed_attention", "auto"))
    if cfg.no_projection:
        return _merge_heads(out), weights
    wo, bo = params[f"{prefix}_Wo"], params[f"{prefix}_bo"]
    if isinstance(wo, QTensor):
        return affine(_merge_heads(out), wo, bo), weights
    return _unproj_heads(out, wo, bo), weights


def _aan_apply(cfg: TransformerConfig, params: Params, lp: str,
               x_in: jax.Array, y_avg: jax.Array) -> jax.Array:
    """FFN + sigmoid gate of the AAN sublayer applied to the cumulative
    average (reference: transformer.h LayerAAN — gate mixes the raw input
    with the transformed average: out = g⊙x + (1-g)⊙FFN(avg)).
    `lp` is the layer param prefix (e.g. 'decoder_l3')."""
    pfx = f"{lp}_aan"
    act = activation(cfg.aan_activation)
    y = y_avg
    n = max(1, cfg.aan_depth)
    for i in range(1, n + 1):       # --transformer-aan-depth dense chain
        y = affine(y, params[f"{pfx}_W{i}"], params[f"{pfx}_b{i}"])
        if i < n:
            y = act(y)
    if cfg.aan_nogate:              # --transformer-aan-nogate
        return y
    gate = jax.nn.sigmoid(
        affine(x_in, params[f"{pfx}_Wi"], params[f"{pfx}_bi"])
        + affine(y, params[f"{pfx}_Wg"], params[f"{pfx}_bg"]))
    return gate * x_in + (1.0 - gate) * y


def _aan_train(cfg: TransformerConfig, params: Params, lp: str,
               x: jax.Array) -> jax.Array:
    """Full-sequence AAN: the cumulative mean over positions is a prefix
    sum — O(T) HBM traffic instead of the T×T attention matrix (reference:
    AverageAttention on groundTruth; 'Accelerating Neural Transformer via an
    Average Attention Network', Zhang et al. 2018)."""
    t = x.shape[1]
    csum = jnp.cumsum(x.astype(jnp.float32), axis=1)
    denom = jnp.arange(1, t + 1, dtype=jnp.float32)[None, :, None]
    y = (csum / denom).astype(x.dtype)
    return _aan_apply(cfg, params, lp, x, y)


def _ssru_train(cfg: TransformerConfig, params: Params, lp: str,
                x: jax.Array) -> jax.Array:
    """Full-sequence SSRU decoder sublayer via the parallel linear-
    recurrence scan (ops/rnn.py) — O(log T) depth on TPU."""
    from ..ops.rnn import SSRU, scan_linear_recurrence
    d = cfg.dim_emb
    cell = SSRU(d, d, False)
    xp = cell.x_proj(params, f"{lp}_rnn", x)              # [B,T,2D]
    f, inp = xp[..., :d], xp[..., d:]
    c = scan_linear_recurrence(f.transpose(1, 0, 2), inp.transpose(1, 0, 2),
                               jnp.zeros_like(f[:, 0]))
    out = jax.nn.relu(c.transpose(1, 0, 2)).astype(x.dtype)
    if cfg.rnn_projection:
        out = affine(out, params[f"{lp}_rnn_Wo"],
                     params[f"{lp}_rnn_bo"])
    return out


def _autoreg_train(cfg: TransformerConfig, params: Params, lp: str,
                   pre: jax.Array, self_mask, trg_mask, lk, train):
    """The decoder's autoregressive sublayer on the full target sequence
    (--transformer-decoder-autoreg). `lp` = layer param prefix."""
    if cfg.decoder_autoreg == "average-attention":
        return _aan_train(cfg, params, lp, pre)
    if cfg.decoder_autoreg == "rnn":
        return _ssru_train(cfg, params, lp, pre)
    out, _ = _mha(cfg, params, f"{lp}_self", pre, pre, self_mask,
                  lk, train, kv_mask=trg_mask, causal=True)
    return out


@named_scope("ffn")
def _ffn(cfg: TransformerConfig, params: Params, prefix: str, x: jax.Array,
         dim_ffn: int, depth: int, key, train: bool) -> jax.Array:
    act = activation(cfg.ffn_activation)
    for i in range(depth):
        x = affine(x, params[f"{prefix}_W{i+1}"], params[f"{prefix}_b{i+1}"])
        if i < depth - 1:
            x = act(x)
            if train and cfg.ffn_dropout > 0.0 and key is not None:
                x = dropout(x, cfg.ffn_dropout, jax.random.fold_in(key, i))
    return x


@named_scope("ffn")
def _moe_ffn(cfg: TransformerConfig, params: Params, prefix: str,
             x: jax.Array, train: bool = False,
             key=None, mask: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Top-k-routed Mixture-of-Experts FFN (TPU extension; GShard
    arXiv:2006.16668 / Switch arXiv:2101.03961 dispatch-einsum form —
    PAPERS.md). Returns (out [B,T,D], aux load-balance scalar).

    Tokens flatten to S=B*T; the router picks top-k experts per token with
    renormalized gates; slot 0 of every token claims capacity before slot 1
    (GShard's priority rule). Dispatch/combine are one-hot einsums — no
    gather/scatter — so with expert tables sharded P('expert', ...) the
    SPMD partitioner lowers them to all-to-alls over the 'expert' axis.
    Over-capacity tokens get a zero update (the residual stream carries
    them). Aux loss is Switch's E * Σ_e fraction_e · mean_gate_e."""
    e, k = cfg.moe_experts, cfg.moe_top_k
    b, t, d = x.shape
    s = b * t
    xf = x.reshape(s, d)
    mf = (jnp.ones((s, 1), jnp.float32) if mask is None
          else mask.reshape(s, 1).astype(jnp.float32))
    if train:
        cap = min(max(1, int(math.ceil(
            k * s * cfg.moe_capacity_factor / e))), s)
        out, r0, ge, n = _moe_route(cfg, params, prefix, xf, mf, cap, key,
                                    True)
    else:
        # inference: NO token dropping, so routing is purely per-token —
        # teacher-forced scoring and incremental beam decode then agree
        # exactly (capacity pooling across timesteps cannot be reproduced
        # step-by-step). Chunk the token axis so the [CH, E, CH] dispatch
        # tensors stay bounded instead of O(S²·E) for long scoring batches;
        # with per-chunk capacity == chunk size nothing ever overflows, so
        # chunking cannot change any token's output.
        ch = min(s, 256)
        pad = (-s) % ch
        xp = jnp.pad(xf, ((0, pad), (0, 0)))
        mp = jnp.pad(mf, ((0, pad), (0, 0)))
        xch = xp.reshape(-1, ch, d)
        mch = mp.reshape(-1, ch, 1)

        def body(_, xm):
            xc, mc = xm
            return None, _moe_route(cfg, params, prefix, xc, mc, ch, None,
                                    False)
        _, (outs, r0s, ges, ns) = jax.lax.scan(body, None, (xch, mch))
        out = outs.reshape(-1, d)[:s]
        r0, ge, n = r0s.sum(0), ges.sum(0), ns.sum()
    n = jnp.maximum(n, 1.0)
    # load balance over REAL tokens: fraction routed to e × mean gate
    aux = e * jnp.sum((r0 / n) * (ge / n))
    return out.reshape(b, t, d), aux


def _moe_route(cfg: TransformerConfig, params: Params, prefix: str,
               xf: jax.Array, mf: jax.Array, cap: int, key, train: bool):
    """Dispatch/combine core on flat tokens [S, D] with expert capacity
    `cap`; returns (out [S, D], top1-routing counts [E], masked gate sums
    [E], real-token count) — the stats feed the load-balance aux loss."""
    e, k = cfg.moe_experts, cfg.moe_top_k
    s = xf.shape[0]
    gates = jax.nn.softmax(jnp.dot(
        xf, params[f"{prefix}_gate"].astype(xf.dtype),
        preferred_element_type=jnp.float32).astype(jnp.float32))   # [S,E]
    vals, idx = jax.lax.top_k(gates, k)                            # [S,k]
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    # padding tokens claim no expert slot, no gate mass, no aux weight —
    # otherwise identical pad embeddings pile onto one expert and displace
    # real tokens from its capacity
    oh = jax.nn.one_hot(idx, e, dtype=jnp.float32) * mf[:, :, None]
    # capacity positions: slot-major order (all slot-0 claims first)
    flat = oh.transpose(1, 0, 2).reshape(k * s, e)                 # [kS,E]
    pos = (jnp.cumsum(flat, axis=0) - 1.0) * flat                  # [kS,E]
    keep = flat * (pos < cap)
    pos_k = pos.reshape(k, s, e)
    keep_k = keep.reshape(k, s, e)
    disp = jnp.einsum("kse,ksec->sec", keep_k,
                      jax.nn.one_hot(pos_k.astype(jnp.int32), cap,
                                     dtype=jnp.float32))
    gate_se = jnp.einsum("ske,sk->se", oh, vals)                   # [S,E]
    comb = (disp * gate_se[:, :, None]).astype(xf.dtype)           # [S,E,C]
    ein = jnp.einsum("sec,sd->ecd", disp.astype(xf.dtype), xf)     # [E,C,D]
    act = activation(cfg.ffn_activation)
    h = act(jnp.einsum("ecd,edf->ecf", ein, params[f"{prefix}_W1"])
            + params[f"{prefix}_b1"])
    if train and cfg.ffn_dropout > 0.0 and key is not None:
        h = dropout(h, cfg.ffn_dropout, jax.random.fold_in(key, 91))
    y = jnp.einsum("ecf,efd->ecd", h, params[f"{prefix}_W2"]) \
        + params[f"{prefix}_b2"]
    out = jnp.einsum("sec,ecd->sd", comb, y)
    return out, oh[:, 0, :].sum(axis=0), (gates * mf).sum(axis=0), mf.sum()


def sinusoidal_positions(length: int, dim: int, start: int = 0) -> jax.Array:
    """Tensor2tensor-style timing signal (reference: transformer.h
    addPositionalEmbeddings): first half sin, second half cos."""
    pos = jnp.arange(start, start + length, dtype=jnp.float32)[:, None]
    half = dim // 2
    inv_freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                       * (math.log(10000.0) / max(half - 1, 1)))
    angles = pos * inv_freq[None, :]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def _embed_words(cfg: TransformerConfig, params: Params, ids: jax.Array,
                 side: str, enc_idx: int = 0) -> jax.Array:
    """Token embedding * sqrt(dim) (reference: transformer.h embFactor);
    factored vocabs compose emb(lemma) + Σ emb(factor) (layers/logits.py)."""
    own = _enc_prefix(enc_idx) + "_Wemb" if side == "src" else "decoder_Wemb"
    if cfg.tied_embeddings_all or (cfg.tied_embeddings_src and side == "src") \
            or ("Wemb" in params and own not in params):
        table = params["Wemb"]
    else:
        table = params[own]
    ft = cfg.src_factors[enc_idx] if side == "src" else cfg.trg_factors
    from ..ops.quantization import QTensor, int8_gather
    if ft is not None:
        from ..layers.logits import factored_embed, factored_embed_concat
        if isinstance(table, QTensor):
            table = table.dequantize(cfg.compute_dtype)
        if cfg.factors_combine == "concat":
            fac = params[own + "_factors"]     # tying is refused for concat
            if isinstance(fac, QTensor):
                fac = fac.dequantize(cfg.compute_dtype)
            x = factored_embed_concat(table, fac, ft, ids, cfg.compute_dtype)
        else:
            x = factored_embed(table, ft, ids, cfg.compute_dtype)
    elif isinstance(table, QTensor):
        x = int8_gather(table, ids, cfg.compute_dtype)
    else:
        x = table[ids].astype(cfg.compute_dtype)
    return x * jnp.asarray(math.sqrt(cfg.dim_emb), cfg.compute_dtype)


def _word_dropout(cfg: TransformerConfig, x: jax.Array, rate: float, key,
                  train: bool) -> jax.Array:
    """Whole-word dropout (reference: --dropout-src/--dropout-trg)."""
    if train and rate > 0.0 and key is not None:
        keep = jax.random.bernoulli(jax.random.fold_in(key, 11), 1.0 - rate,
                                    x.shape[:-1])
        x = x * keep[..., None].astype(x.dtype)
    return x


def _add_pos(cfg: TransformerConfig, params: Params, x: jax.Array,
             start_pos=0) -> jax.Array:
    t = x.shape[-2]
    start = jnp.asarray(start_pos)
    if start.ndim == 1:
        # per-row positions (iteration-level decode: rows of different
        # ages share one step) — x is [R, t, d], offsets are [R]
        pos_ids = (jnp.arange(t)[None, :] + start[:, None]).astype(jnp.int32)
        if cfg.train_position_embeddings:
            return x + params["Wpos"][jnp.maximum(pos_ids, 0)].astype(x.dtype)
        return x + _sinusoidal_rows(pos_ids, cfg.dim_emb).astype(x.dtype)
    if cfg.train_position_embeddings:
        pos_ids = (jnp.arange(t) + start_pos).astype(jnp.int32)
        return x + params["Wpos"][pos_ids].astype(x.dtype)
    return x + sinusoidal_positions_dynamic(t, cfg.dim_emb, start_pos).astype(x.dtype)


def _sinusoidal_rows(pos_ids: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal embeddings for an arbitrary [R, t] position grid —
    identical per-position values to sinusoidal_positions_dynamic (same
    inv_freq expression), vectorized over rows."""
    pos = pos_ids.astype(jnp.float32)[..., None]            # [R, t, 1]
    half = dim // 2
    inv_freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                       * (math.log(10000.0) / max(half - 1, 1)))
    angles = pos * inv_freq[None, None, :]                  # [R, t, half]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def _ulr_embed(cfg: TransformerConfig, params: Params, ids: jax.Array,
               key, train: bool) -> jax.Array:
    """Universal Language Representation term for source tokens
    (reference: src/layers/embedding.cpp :: ULREmbedding; Gu et al. 2018
    'Universal NMT for Extremely Low Resource Languages'): the token's
    fixed query vector attends (via a trainable transform A) over the
    fixed universal key table; the softmax mixes trainable universal
    value embeddings. Per-token computation — [B,T,Vu] scores, no
    [V_src,Vu] table materialization."""
    q = params["ulr_Q"][ids].astype(jnp.float32)         # [B,T,dq] fixed
    k = params["ulr_K"].astype(jnp.float32)              # [Vu,dq] fixed
    scores = jnp.einsum("btd,de,ve->btv", q, params["ulr_A"], k,
                        preferred_element_type=jnp.float32)
    alpha = jax.nn.softmax(scores / max(cfg.ulr_temperature, 1e-6), axis=-1)
    u = jnp.einsum("btv,vd->btd", alpha,
                   params["ulr_Wu"].astype(jnp.float32))
    if train and cfg.ulr_dropout > 0.0 and key is not None:
        u = dropout(u, cfg.ulr_dropout, jax.random.fold_in(key, 23))
    return u.astype(cfg.compute_dtype)


@named_scope("embed")
def _embed(cfg: TransformerConfig, params: Params, ids: jax.Array,
           side: str, key, train: bool, start_pos=0,
           enc_idx: int = 0) -> jax.Array:
    x = _embed_words(cfg, params, ids, side, enc_idx)
    if cfg.ulr and side == "src":
        # word and universal parts share Marian's sqrt(dim) embed factor
        x = x + _ulr_embed(cfg, params, ids, key, train) \
            * jnp.asarray(math.sqrt(cfg.dim_emb), cfg.compute_dtype)
    rate = cfg.dropout_src if side == "src" else cfg.dropout_trg
    x = _word_dropout(cfg, x, rate, key, train)
    return _add_pos(cfg, params, x, start_pos)


def shift_right_embeddings(x: jax.Array) -> jax.Array:
    """Shift target embeddings one step right, zero vector at t=0 — Marian's
    decoder-start convention: no BOS token, position 0 attends to a zero
    embedding (reference: transformer.h shiftEmbeddings)."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]


def sinusoidal_positions_dynamic(length: int, dim: int, start) -> jax.Array:
    """Like sinusoidal_positions but `start` may be a traced scalar (decode)."""
    pos = (jnp.arange(length, dtype=jnp.float32)
           + jnp.asarray(start, jnp.float32))[:, None]
    half = dim // 2
    inv_freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                       * (math.log(10000.0) / max(half - 1, 1)))
    angles = pos * inv_freq[None, :]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def layer_param_groups(cfg: TransformerConfig):
    """(prefix, depth) per layer stack: encoders (unless LM) + decoder."""
    groups = []
    if not cfg.lm:
        for i in range(cfg.n_encoders):
            groups.append((_enc_prefix(i), cfg.enc_depth))
    groups.append(("decoder", cfg.dec_depth))
    return groups


def can_stack_layers(cfg: TransformerConfig) -> Optional[str]:
    """None if depth-stacked parameter storage applies, else the reason it
    can't (pipeline-parallel 'pipe' sharding requires the scanned stack)."""
    if not cfg.scan_layers:
        return "--scan-layers off"
    if cfg.tied_layers:
        return "--transformer-tied-layers shares leaves across layers"
    if cfg.enc_depth < 2 and cfg.dec_depth < 2:
        return "layer stacks of depth 1"
    return None


def stack_layer_params(cfg: TransformerConfig, tree: Params) -> Params:
    """Depth-stacked parameter storage (pipeline-parallel memory layout):
    per-layer leaves '{prefix}_l{l}_{suffix}' are replaced by ONE
    '{prefix}_stack_{suffix}' leaf of shape [L, ...], which parallel/
    tensor.py shards P('pipe', ...) over the mesh — each pipeline stage
    holds (and Adam-updates) only its layers, and the lax.scan forward
    streams one layer's weights at a time (the TPU-era equivalent of
    pipeline-stage weight residency; compute overlap comes from XLA's
    latency-hiding scheduler). Checkpoints stay Marian-flat via
    unstack_layer_params."""
    out = dict(tree)
    for prefix, n in layer_param_groups(cfg):
        first = f"{prefix}_l1_"
        for s in [k[len(first):] for k in tree if k.startswith(first)]:
            leaves = [out.pop(f"{prefix}_l{l}_{s}") for l in range(1, n + 1)]
            out[f"{prefix}_stack_{s}"] = jnp.stack(
                [jnp.asarray(v) for v in leaves])
    return out


def unstack_layer_params(cfg: TransformerConfig, tree: Params) -> Params:
    """Inverse of stack_layer_params (checkpoint IO, validators, decode)."""
    out = dict(tree)
    for prefix, n in layer_param_groups(cfg):
        pre = f"{prefix}_stack_"
        for k in [k for k in out if k.startswith(pre)]:
            stacked = out.pop(k)
            for l in range(1, n + 1):
                out[f"{prefix}_l{l}_{k[len(pre):]}"] = stacked[l - 1]
    return out


def _stacked_layer_params(cfg: TransformerConfig, params: Params,
                          base: str, n: int):
    """--scan-layers: stack each per-layer weight into one [n, ...] leaf so
    the layer stack runs as ONE lax.scan instead of n unrolled copies —
    the compiled HLO (and XLA compile time, the dominant cold-start cost
    on TPU) stays O(1) in depth. Returns {suffix: stacked} keyed by the
    name after '{base}{l}_', or None when scanning doesn't apply: flag
    off, depth < 2, or cross-layer tying (layers share leaves). Int8
    QTensor decode weights stack too (their values/scale children stack;
    lax.scan slices them back into per-layer QTensors).

    The stack is rebuilt inside every jitted forward (one HBM copy of the
    layer weights per step, ~1ms for transformer-big — measured against
    ~100ms steps). That per-step cost is deliberate: params stay stored
    flat under Marian's per-layer names, keeping checkpoint IO, TP
    sharding specs, freezing, and quantization untouched."""
    pre = base[:-2] + "_stack_"          # base = '{prefix}_l'
    pre_stacked = {k[len(pre):]: v for k, v in params.items()
                   if k.startswith(pre)}
    if pre_stacked:
        return pre_stacked               # depth-stacked storage (pipe mode)
    if not cfg.scan_layers or n < 2 or cfg.tied_layers:
        return None
    first = f"{base}1_"
    sfxs = [k[len(first):] for k in params if k.startswith(first)]
    if not sfxs:
        return None
    from ..ops.quantization import QTensor
    out = {}
    for s in sfxs:
        leaves = []
        for l in range(1, n + 1):
            v = params.get(f"{base}{l}_{s}")
            if v is None or v.shape != params[f"{base}1_{s}"].shape:
                return None
            leaves.append(v)
        if all(isinstance(v, QTensor) for v in leaves):
            # int8 decode weights: stack the pytree children — lax.scan
            # slices them back into per-layer QTensors
            if len({v.axis for v in leaves}) != 1:
                return None
            out[s] = QTensor(jnp.stack([v.values for v in leaves]),
                             jnp.stack([v.scale for v in leaves]),
                             leaves[0].axis)
        elif all(isinstance(v, jax.Array) for v in leaves):
            out[s] = jnp.stack(leaves)
        else:
            return None
    return out


@named_scope("encoder")
def encode(cfg: TransformerConfig, params: Params, src_ids,
           src_mask, train: bool = False,
           key: Optional[jax.Array] = None, with_aux: bool = False):
    """[B, Ts] ids + mask → [B, Ts, D] encoder states (reference:
    TransformerEncoder::apply). Multi-source: pass tuples of ids/masks —
    one encoder stack per stream, returns a tuple of states.
    `with_aux` additionally returns the summed MoE load-balance loss."""
    if cfg.lm:
        return (None, jnp.zeros((), jnp.float32)) if with_aux else None
    if isinstance(src_ids, (tuple, list)):
        masks = _as_tuple(src_mask)
        res = tuple(
            _encode_one(cfg, params, ids_i, masks[i], train,
                        jax.random.fold_in(key, 1000 + i) if key is not None
                        else None, i)
            for i, ids_i in enumerate(src_ids))
        outs = tuple(r[0] for r in res)
        return (outs, sum(r[1] for r in res)) if with_aux else outs
    out, aux = _encode_one(cfg, params, src_ids, src_mask, train, key, 0)
    return (out, aux) if with_aux else out


def _ffn_or_moe(cfg: TransformerConfig, pp: Params, lp: str, pre, dim_ffn,
                depth, key, train, mask=None):
    """FFN sublayer body: dense _ffn or the routed MoE; returns (out, aux)
    with aux = 0 for the dense path (type-stable for lax.scan)."""
    if cfg.moe_experts > 0:
        return _moe_ffn(cfg, pp, f"{lp}_moe", pre, train, key, mask)
    return (_ffn(cfg, pp, f"{lp}_ffn", pre, dim_ffn, depth, key, train),
            jnp.zeros((), jnp.float32))


def _encode_one(cfg: TransformerConfig, params: Params, src_ids: jax.Array,
                src_mask: jax.Array, train: bool, key, enc_idx: int,
                emb_offset: Optional[jax.Array] = None):
    ep = _enc_prefix(enc_idx)
    kk = (lambda i: jax.random.fold_in(key, i)) if key is not None else (lambda i: None)
    x = _embed(cfg, params, src_ids, "src", kk(0), train, enc_idx=enc_idx)
    if emb_offset is not None:   # e.g. BERT sentence-type embeddings
        x = x + emb_offset.astype(x.dtype)
    x = _pre_post(cfg, cfg.postprocess_emb, x, None, f"{ep}_emb", params,
                  kk(1), train)
    attn_mask = src_mask[:, None, None, :]  # [B,1,1,Ts]

    def enc_layer(x, pp, lp, lnum):
        """One encoder layer; `pp` is the param view, `lp` the layer param
        prefix (e.g. 'encoder_l3'), `lnum` the 1-based layer number for
        dropout-key folding (may be a traced int under lax.scan)."""
        lk = kk(lnum * 10)
        # self-attention sublayer
        pre = _pre_post(cfg, cfg.preprocess, x, None,
                        f"{lp}_self_Wo", pp, lk, train)
        out, _ = _mha(cfg, pp, f"{lp}_self", pre, pre, attn_mask,
                      lk, train, kv_mask=src_mask)
        x = _pre_post(cfg, cfg.postprocess, out, x,
                      f"{lp}_self_Wo", pp, lk, train)
        # ffn sublayer (dense or MoE)
        lk2 = kk(lnum * 10 + 5)
        pre = _pre_post(cfg, cfg.preprocess, x, None,
                        f"{lp}_ffn_ffn", pp, lk2, train)
        out, aux = _ffn_or_moe(cfg, pp, lp, pre, cfg.dim_ffn,
                               cfg.ffn_depth, lk2, train, mask=src_mask)
        return _pre_post(cfg, cfg.postprocess, out, x,
                         f"{lp}_ffn_ffn", pp, lk2, train), aux

    aux_total = jnp.zeros((), jnp.float32)
    stacked = _stacked_layer_params(cfg, params, f"{ep}_l", cfg.enc_depth)
    if stacked is not None:
        def body(x, sl):
            lp_leaves, lnum = sl
            pv = {**params, **{f"{ep}_lS_{s}": v
                               for s, v in lp_leaves.items()}}
            return enc_layer(x, pv, f"{ep}_lS", lnum)
        if cfg.gradient_checkpointing and train:
            # prevent_cse=False: safe and faster under lax.scan (the loop
            # already prevents the CSE remat guards against)
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxs = jax.lax.scan(
            body, x, (stacked, jnp.arange(1, cfg.enc_depth + 1)))
        aux_total = aux_total + auxs.sum()
    else:
        for l in range(1, cfg.enc_depth + 1):
            pl = _tied(cfg, l)           # parameter-owning layer
            f = partial(enc_layer, pp=params, lp=f"{ep}_l{pl}", lnum=l)
            if cfg.gradient_checkpointing and train:
                # --gradient-checkpointing: rematerialize the layer in the
                # backward pass instead of keeping its activations in HBM
                x, aux_l = jax.checkpoint(f)(x)
            else:
                x, aux_l = f(x)
            aux_total = aux_total + aux_l
    x = _pre_post(cfg, cfg.postprocess_top, x, None, f"{ep}_top", params,
                  kk(9999), train)
    return x, aux_total


# ---------------------------------------------------------------------------
# Decoder (teacher-forced training path)
# ---------------------------------------------------------------------------

@named_scope("decoder")
def decode_train(cfg: TransformerConfig, params: Params, enc_out: jax.Array,
                 src_mask: jax.Array, trg_ids: jax.Array,
                 trg_mask: jax.Array, train: bool = True,
                 key: Optional[jax.Array] = None,
                 return_alignment: bool = False,
                 return_hidden: bool = False,
                 with_aux: bool = False):
    """Teacher-forced decoder: [B, Tt] gold target ids → [B, Tt, V] logits
    (or the pre-logits hidden states when return_hidden — the fused-CE path
    computes the output projection inside its streaming kernel).
    Input embeddings are the gold embeddings shifted right with a zero vector
    at t=0 (reference: TransformerDecoder::step on full groundTruth)."""
    kk = (lambda i: jax.random.fold_in(key, i)) if key is not None else (lambda i: None)
    with jax.named_scope("embed"):
        we = _embed_words(cfg, params, trg_ids, "trg")
        we = shift_right_embeddings(we)
        we = _word_dropout(cfg, we, cfg.dropout_trg, kk(0), train)
        x = _add_pos(cfg, params, we, 0)
    x = _pre_post(cfg, cfg.postprocess_emb, x, None, "decoder_emb", params,
                  kk(1), train)
    tt = trg_ids.shape[1]
    self_mask = causal_mask(tt) * trg_mask[:, None, None, :]
    if cfg.lm:
        enc_outs, masks, cross_masks = (), (), []
    else:
        enc_outs = _as_tuple(enc_out)
        masks = _as_tuple(src_mask)
        cross_masks = [m[:, None, None, :] for m in masks]
    align = None

    def dec_layer(x, pp, lp, lnum, want_align):
        """One decoder layer; `pp`/`lp`/`lnum` as in enc_layer."""
        lk = kk(lnum * 10)
        pre = _pre_post(cfg, cfg.preprocess, x, None,
                        f"{lp}_self_Wo", pp, lk, train)
        out = _autoreg_train(cfg, pp, lp, pre, self_mask, trg_mask,
                             lk, train)
        x = _pre_post(cfg, cfg.postprocess, out, x,
                      f"{lp}_self_Wo", pp, lk, train)

        align_l = None
        # one cross-attention sublayer per encoder (multi-source stacks them)
        for i, eo in enumerate(enc_outs):
            cname = f"{lp}_context{_ctx_suffix(i)}"
            lk2 = kk(lnum * 10 + 3 + i)
            want_w = want_align and i == 0
            pre = _pre_post(cfg, cfg.preprocess, x, None,
                            f"{cname}_Wo", pp, lk2, train)
            out, w = _mha(cfg, pp, cname, pre, eo,
                          cross_masks[i], lk2, train, return_weights=want_w,
                          kv_mask=masks[i])
            if want_w and w is not None:
                align_l = w.mean(axis=1)  # [B,Tt,Ts] head-averaged
            x = _pre_post(cfg, cfg.postprocess, out, x,
                          f"{cname}_Wo", pp, lk2, train)

        lk3 = kk(lnum * 10 + 7)
        pre = _pre_post(cfg, cfg.preprocess, x, None,
                        f"{lp}_ffn_ffn", pp, lk3, train)
        out, aux = _ffn_or_moe(cfg, pp, lp, pre, cfg.dec_ffn,
                               cfg.dec_ffn_d, lk3, train, mask=trg_mask)
        x = _pre_post(cfg, cfg.postprocess, out, x,
                      f"{lp}_ffn_ffn", pp, lk3, train)
        return x, align_l, aux

    aux_total = jnp.zeros((), jnp.float32)
    # alignment extraction needs one specific layer's attention weights —
    # scan can't surface a single iteration's side output cheaply, so the
    # guided-alignment path keeps the unrolled stack
    stacked = None if return_alignment else _stacked_layer_params(
        cfg, params, "decoder_l", cfg.dec_depth)
    if stacked is not None:
        def body(x, sl):
            lp_leaves, lnum = sl
            pv = {**params, **{f"decoder_lS_{s}": v
                               for s, v in lp_leaves.items()}}
            x, _, aux = dec_layer(x, pv, "decoder_lS", lnum, False)
            return x, aux
        if cfg.gradient_checkpointing and train:
            # prevent_cse=False: safe and faster under lax.scan (the loop
            # already prevents the CSE remat guards against)
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxs = jax.lax.scan(
            body, x, (stacked, jnp.arange(1, cfg.dec_depth + 1)))
        aux_total = aux_total + auxs.sum()
    else:
        for l in range(1, cfg.dec_depth + 1):
            want_align = return_alignment and _is_alignment_layer(cfg, l)
            pl = _tied(cfg, l)           # parameter-owning layer
            f = partial(dec_layer, pp=params, lp=f"decoder_l{pl}", lnum=l,
                        want_align=want_align)
            if cfg.gradient_checkpointing and train and not want_align:
                x, _, aux_l = jax.checkpoint(f)(x)
            else:
                x, align_l, aux_l = f(x)
                if align_l is not None:
                    align = align_l
            aux_total = aux_total + aux_l
    x = _pre_post(cfg, cfg.postprocess_top, x, None, "decoder_top", params,
                  kk(9999), train)
    out = x if return_hidden else output_logits(cfg, params, x)
    res = [out]
    if return_alignment:
        res.append(align)
    if with_aux:
        res.append(aux_total)
    return res[0] if len(res) == 1 else tuple(res)


def _is_alignment_layer(cfg: TransformerConfig, l: int) -> bool:
    gal = cfg.guided_alignment_layer
    if gal == "last":
        return l == cfg.dec_depth
    return l == int(gal)


def _plain_output_table(cfg: TransformerConfig, params: Params):
    """The [V, E] output table when it is a plain tensor (no factors, no
    int8 quantization) — the cases the LSH index supports; else None."""
    from ..ops.quantization import QTensor
    if cfg.trg_factors is not None:
        return None
    if cfg.tied_embeddings_all:
        t = params.get("Wemb")
    elif cfg.tied_embeddings:
        t = params.get("Wemb", params.get("decoder_Wemb"))
    else:
        w = params.get("decoder_ff_logit_out_W")
        if w is None or isinstance(w, QTensor):
            return None
        return w.T
    return None if (t is None or isinstance(t, QTensor)) else t


def _lemma_conditioned_units(cfg: TransformerConfig, params: Params,
                             x: jax.Array, w, b) -> jax.Array:
    """--lemma-dim-emb: unit scores with soft lemma re-embedding
    (reference: src/layers/output.cpp lemma-conditioned factor logits).
    Lemma logits come from the plain decoder state; the lemma posterior's
    expected L-dim embedding is projected back to dim-emb and added to the
    state before the factor-group logits, so factor predictions see the
    (softly) chosen lemma. Two matmuls over disjoint unit columns — same
    total FLOPs as the single fused matmul."""
    from ..ops.quantization import QTensor

    def _f32(t):
        return (t.dequantize(jnp.float32) if isinstance(t, QTensor)
                else t.astype(jnp.float32))

    ft = cfg.trg_factors
    nl = ft.n_lemmas
    w = w.astype(x.dtype)
    b = b.astype(jnp.float32)
    lemma_units = jnp.dot(x, w[:, :nl],
                          preferred_element_type=jnp.float32)
    lemma_units = lemma_units.astype(jnp.float32) + b[..., :nl]
    probs = jax.nn.softmax(lemma_units, axis=-1)
    e = jnp.dot(probs, _f32(params["decoder_lemma_reembed_W"]))
    delta = jnp.dot(e, _f32(params["decoder_lemma_reembed_Wp"])) \
        + params["decoder_lemma_reembed_bp"].astype(jnp.float32)
    x = x + delta.astype(x.dtype)
    fac_units = jnp.dot(x, w[:, nl:], preferred_element_type=jnp.float32)
    fac_units = fac_units.astype(jnp.float32) + b[..., nl:]
    return jnp.concatenate([lemma_units, fac_units], axis=-1)


@named_scope("output")
def output_logits(cfg: TransformerConfig, params: Params, x: jax.Array,
                  shortlist: Optional[jax.Array] = None) -> jax.Array:
    """Output projection with tied embeddings and optional shortlist slice
    (reference: src/layers/output.cpp :: mlp::Output). Returns f32 logits.

    Factored vocab: ONE matmul over the unit axis, then the group-wise
    log-softmax combination (reference: layers/logits.cpp; the returned
    values are word log-probs — downstream softmax/log-softmax renormalizes
    over the word axis, which only shifts scores by a constant per
    position)."""
    from ..ops.quantization import QTensor, int8_logits
    # Per-row shortlist (iteration serving, ISSUE 16): a 2-D [R, K]
    # index set — every decode row carries its OWN sentence union, so
    # the slice is a batched gather, not one [d, K] column slice. Only
    # the plain-tensor path supports it; int8 / factored decodes keep
    # the batch-wide 1-D contract.
    per_row = shortlist is not None and getattr(shortlist, "ndim", 1) == 2
    if per_row and x.ndim != 2:
        raise ValueError("per-row [R, K] shortlist needs [R, d] "
                         "activations (single decode position)")
    if cfg.tied_embeddings_all:
        table = params["Wemb"]
    elif cfg.tied_embeddings:
        table = params["Wemb"] if "Wemb" in params else params["decoder_Wemb"]
    else:
        table = None
    # --output-omit-bias: no bias param; a constant zero keeps every
    # branch below uniform and XLA folds the add away. Activation dtype:
    # an f32 zero would silently promote the [B,V] logits under bf16
    b = params.get("decoder_ff_logit_out_b")
    if b is None:
        b = jnp.zeros((1, _trg_rows(cfg)), x.dtype)
    if per_row and (cfg.trg_factors is not None
                    or isinstance(table, QTensor)
                    or (table is None and isinstance(
                        params.get("decoder_ff_logit_out_W"), QTensor))):
        raise NotImplementedError(
            "per-row shortlists are not supported with int8 or factored "
            "output layers; decode with a float, unfactored model")
    if table is not None and isinstance(table, QTensor):
        # tied quantized table [V, d], per-row scales → int8 x @ table.T
        if cfg.trg_factors is not None:
            from ..layers.logits import factored_log_probs
            if cfg.lemma_dim_emb > 0:
                raise NotImplementedError(
                    "--lemma-dim-emb with an int8-quantized tied output "
                    "table is not supported; decode with a float model")
            units = int8_logits(x, table, None) + b.astype(jnp.float32)
            return factored_log_probs(units, cfg.trg_factors, shortlist,
                                      cfg.factor_weight)
        y = int8_logits(x, table, shortlist)
        bb = b if shortlist is None else b[:, shortlist]
        return y + bb.astype(jnp.float32)
    if table is not None:
        w = table.T
    else:
        w = params["decoder_ff_logit_out_W"]
        if isinstance(w, QTensor):
            if cfg.trg_factors is None:
                from ..ops.quantization import QTensor as _QT, int8_affine
                q = w                      # [d, V], per-column (vocab) scales
                if shortlist is not None:
                    q = _QT(q.values[:, shortlist], q.scale[shortlist], 1)
                    b = b[:, shortlist]
                return int8_affine(x.astype(jnp.float32), q, b)
            w = w.dequantize(jnp.float32)
    if cfg.trg_factors is not None:
        from ..layers.logits import factored_log_probs
        if cfg.lemma_dim_emb > 0:
            units = _lemma_conditioned_units(cfg, params, x, w, b)
        else:
            units = logits_matmul(x, w.astype(x.dtype))
            units = units + b.astype(jnp.float32)
        return factored_log_probs(units, cfg.trg_factors, shortlist,
                                      cfg.factor_weight)
    if per_row:
        # [R, K, d] gather of each row's output columns, then a batched
        # row-vector matmul — the per-row twin of the [d, K] slice below
        wg = jnp.take(w.T, shortlist, axis=0).astype(x.dtype)  # [R, K, d]
        y = jnp.einsum("rd,rkd->rk", x, wg,
                       preferred_element_type=jnp.float32)
        return y + b[0, shortlist].astype(jnp.float32)
    if shortlist is not None:
        w = w[:, shortlist]
        b = b[:, shortlist]
    y = logits_matmul(x, w.astype(x.dtype))
    return y + b.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Incremental decoding (beam/greedy): startState / step
# ---------------------------------------------------------------------------

def _decode_scan_stack(cfg: TransformerConfig, params: Params):
    """Stacked decoder-layer params when the scanned decode step applies
    (self-attention autoreg only — AAN/SSRU keep tiny per-layer states and
    the unrolled path); None otherwise."""
    if cfg.decoder_autoreg != "self-attention":
        return None
    return _stacked_layer_params(cfg, params, "decoder_l", cfg.dec_depth)


def init_decode_state(cfg: TransformerConfig, params: Params,
                      enc_out, src_mask,
                      max_len: int,
                      want_alignment: bool = False) -> Dict[str, Any]:
    """Precompute cross-attention K/V; allocate fixed-size self-attn caches
    (reference: EncoderDecoder::startState + per-layer cache init).
    Multi-source: per-encoder cross K/V under suffixed keys."""
    # decoder-only LM: no cross K/V; batch size from the (dummy) source mask
    enc_outs = () if cfg.lm else _as_tuple(enc_out)
    b = src_mask.shape[0] if cfg.lm else enc_outs[0].shape[0]
    h, dh = cfg.heads, cfg.dim_head
    state: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}

    stacked = None if want_alignment else _decode_scan_stack(cfg, params)
    if stacked is not None:
        # scanned decode: ONE [L, ...] cache per kind; the step function
        # runs the layer stack as a lax.scan (same O(1)-in-depth compile
        # win as the training path). 'stack_*' keys gather on axis 1 when
        # the beam reorders (translator/beam_search.py).
        from ..ops.quantization import QTensor

        def cross_proj(kv, w, bias):
            """[B,S,d] × stacked [L,d,d] weights → [L,B,S,d]; int8 stacks
            vmap the per-layer int8 affine (same kernel as the unrolled
            decode path, so quantization numerics are identical)."""
            if isinstance(w, QTensor):
                f = jax.vmap(lambda wl, bl: affine(kv, wl, bl),
                             in_axes=(0, 0))
                return f(w, bias).astype(kv.dtype)
            return jnp.einsum("bsd,lde->lbse", kv, w) + bias[:, None]

        for i, kv in enumerate(enc_outs):
            sfx = _ctx_suffix(i)
            k_all = cross_proj(kv, stacked[f"context{sfx}_Wk"],
                               stacked[f"context{sfx}_bk"])
            v_all = cross_proj(kv, stacked[f"context{sfx}_Wv"],
                               stacked[f"context{sfx}_bv"])
            ts = kv.shape[1]
            state[f"stack_cross_kc{sfx}"] = k_all.reshape(
                -1, b, ts, h, dh).transpose(0, 1, 3, 2, 4)
            state[f"stack_cross_vc{sfx}"] = v_all.reshape(
                -1, b, ts, h, dh).transpose(0, 1, 3, 2, 4)
        state["stack_self_k"] = jnp.zeros(
            (cfg.dec_depth, b, h, max_len, dh), cfg.compute_dtype)
        state["stack_self_v"] = jnp.zeros(
            (cfg.dec_depth, b, h, max_len, dh), cfg.compute_dtype)
        # stacked decoder weights computed ONCE here (beam-invariant;
        # no param suffix collides with the beam-carried cache suffixes)
        for sname, v in stacked.items():
            state[f"stack_p_{sname}"] = v
        _maybe_lsh_state(cfg, params, state)
        return state

    proj_cache: Dict[Any, Any] = {}    # tied layers share cross projections
    for l in range(1, cfg.dec_depth + 1):
        pl = _tied(cfg, l)
        for i, kv in enumerate(enc_outs):
            cname = f"decoder_l{pl}_context{_ctx_suffix(i)}"
            sfx = _ctx_suffix(i)
            if (pl, i) not in proj_cache:
                proj_cache[(pl, i)] = (
                    _split_heads(affine(kv, params[f"{cname}_Wk"],
                                        params[f"{cname}_bk"]), h),
                    _split_heads(affine(kv, params[f"{cname}_Wv"],
                                        params[f"{cname}_bv"]), h))
            state[f"l{l}_cross_k{sfx}"], state[f"l{l}_cross_v{sfx}"] = \
                proj_cache[(pl, i)]
        if cfg.decoder_autoreg == "average-attention":
            # AAN needs only the running sum of inputs — O(D) per position
            # decode state instead of the O(L·D) KV cache
            state[f"l{l}_aan_sum"] = jnp.zeros((b, 1, cfg.dim_emb),
                                               jnp.float32)
        elif cfg.decoder_autoreg == "rnn":
            state[f"l{l}_rnn_c"] = jnp.zeros((b, 1, cfg.dim_emb),
                                             cfg.compute_dtype)
        else:
            state[f"l{l}_self_k"] = jnp.zeros((b, h, max_len, dh),
                                              cfg.compute_dtype)
            state[f"l{l}_self_v"] = jnp.zeros((b, h, max_len, dh),
                                              cfg.compute_dtype)
    _maybe_lsh_state(cfg, params, state)
    return state


def init_paged_decode_state(cfg: TransformerConfig, params: Params,
                            enc_out, src_mask, n_pages: int,
                            page_len: int, max_pages: int
                            ) -> Dict[str, Any]:
    """Decode state for iteration-level (continuous) batching: the dense
    per-row self-attention caches are replaced by per-layer PAGE POOLS
    ``[n_pages, H, page_len, dh]`` shared across all rows, one page
    table ``[rows, max_pages]`` (all layers write the same positions, so
    one table serves every layer — page 0 is the reserved trash page)
    and a per-row position vector. Cross-attention K/V stay dense
    per-row (computed once per sentence at join time). Unrolled layout
    only: rows join and leave individually, which the host-side slot
    engine (translator/iteration.py) manages between steps.
    """
    if cfg.decoder_autoreg != "self-attention":
        raise ValueError("the paged KV pool requires the self-attention "
                         "autoreg decoder (AAN/SSRU keep O(1) states — "
                         "there is no cache to page)")
    # want_alignment=True forces the UNROLLED state layout (per-layer
    # cross keys); the tiny [b,h,1,dh] dense self caches it allocates
    # are dropped below in favor of the pools
    state = init_decode_state(cfg, params, enc_out, src_mask, max_len=1,
                              want_alignment=True)
    b = src_mask.shape[0] if cfg.lm else _as_tuple(enc_out)[0].shape[0]
    h, dh = cfg.heads, cfg.dim_head
    for l in range(1, cfg.dec_depth + 1):
        del state[f"l{l}_self_k"], state[f"l{l}_self_v"]
        state[f"l{l}_pool_k"] = jnp.zeros((n_pages, h, page_len, dh),
                                          cfg.compute_dtype)
        state[f"l{l}_pool_v"] = jnp.zeros((n_pages, h, page_len, dh),
                                          cfg.compute_dtype)
    state["page_table"] = jnp.zeros((b, max_pages), jnp.int32)
    state["pos"] = jnp.zeros((b,), jnp.int32)
    return state


def fork_paged_rows(state: Dict[str, Any], src_mask: jax.Array,
                    src_slots: jax.Array, dst_slots: jax.Array
                    ) -> Tuple[Dict[str, Any], jax.Array]:
    """Beam-aware paged state fork: copy the ROW-indexed leaves of a
    paged decode state (per-layer cross-attention K/V — the per-sentence
    encoder summary) plus the source-mask row from ``src_slots`` to
    ``dst_slots``. This is how a new hypothesis row (beam fork) or a
    cross-request prefix follower acquires its sentence identity WITHOUT
    re-running the encoder: the decoder-side history travels separately
    as page-table aliases + one partial-page copy (kv_pool.py).

    Slot index arrays are int32 ``[n]``; pairs with ``src == dst`` are
    deterministic self-copies, so callers can pad to a static shape with
    ``(0, 0)``. Pool/whole leaves and the host-owned ``pos``/
    ``page_table`` pass through untouched."""
    from ..ops.pallas.kv_pool import state_key_groups
    row_keys, _, _ = state_key_groups(state)
    src = jnp.asarray(src_slots, jnp.int32)
    dst = jnp.asarray(dst_slots, jnp.int32)
    new_state = dict(state)
    for k in row_keys:
        v = state[k]
        new_state[k] = v.at[dst].set(v[src])
    new_mask = src_mask.at[dst].set(src_mask[src])
    return new_state, new_mask


def _maybe_lsh_state(cfg: TransformerConfig, params: Params,
                     state: Dict[str, Any]) -> None:
    if not cfg.output_approx_knn:
        return
    # --output-approx-knn: LSH index over the output table (ops/lsh.py).
    # Pure function of params, built once per compiled search; the
    # entries are beam-invariant so the beam reorder leaves them alone.
    table = _plain_output_table(cfg, params)
    if table is None:
        raise ValueError("--output-approx-knn requires a plain-tensor "
                         "output projection (no factored vocab, no "
                         "int8-quantized table)")
    from ..ops.lsh import build_index
    nbits = cfg.output_approx_knn[1] if len(cfg.output_approx_knn) > 1 \
        else 1024
    planes, sigs = build_index(table, nbits)
    state["lsh_planes"] = planes
    state["lsh_signatures"] = sigs


def decode_step(cfg: TransformerConfig, params: Params, state: Dict[str, Any],
                prev_ids: jax.Array, src_mask: jax.Array,
                shortlist: Optional[jax.Array] = None,
                return_alignment: bool = False,
                beam_src: Optional[jax.Array] = None,
                fused_decode: Optional[bool] = None):
    """One decode step on [B, 1] previous ids → ([B, V] logits, new state).

    All shapes static; `state['pos']` is the traced time index. The self-attn
    mask allows positions <= pos (cache beyond pos is zeros but masked out).
    `beam_src` [B] int32: pending beam backpointers for the fused decode
    kernel (see _mha); the beam search passes them instead of reordering
    the self-attention caches when fused_decode_active(cfg).
    `fused_decode=False` force-disables the kernel regardless of the
    config gate (the beam search under a decode mesh — see _mha).
    """
    pos = state["pos"]
    # paged iteration-level decode (ops/pallas/kv_pool.py): the state
    # carries a shared page table + per-layer pools instead of dense
    # per-row caches, and pos is a PER-ROW [R] vector (rows of
    # different ages share one step; pos < 0 marks an inactive slot)
    page_table = state.get("page_table")
    paged = page_table is not None
    scanned = "stack_self_k" in state
    if paged:
        if cfg.decoder_autoreg != "self-attention":
            raise ValueError("paged decode state requires the "
                             "self-attention autoreg decoder")
        if return_alignment:
            raise ValueError("alignment output is not supported with a "
                             "paged decode state")
        max_len = page_table.shape[1] * state["l1_pool_k"].shape[2]
    elif cfg.decoder_autoreg == "self-attention":
        max_len = (state["stack_self_k"].shape[3] if scanned
                   else state["l1_self_k"].shape[2])
    else:
        max_len = 0
    we = _embed_words(cfg, params, prev_ids, "trg")
    # step 0 uses the zero embedding (Marian's no-BOS decoder start);
    # per-row pos: each row applies its OWN step-0 rule (<= covers the
    # inactive pos=-1 slots with deterministic zeros)
    start0 = (pos <= 0)[:, None, None] if paged else (pos == 0)
    we = jnp.where(start0, jnp.zeros_like(we), we)
    x = _add_pos(cfg, params, we, pos)
    x = _pre_post(cfg, _strip_dropout(cfg.postprocess_emb), x, None,
                  "decoder_emb", params, None, False)
    # self mask: [1,1,1,max_len] — attend to steps 0..pos (per-row
    # [R,1,1,max_len] when pos is a vector; the paged kernel applies
    # its own equivalent mask — this one feeds any dense fallback)
    if cfg.decoder_autoreg == "self-attention":
        steps = jnp.arange(max_len)
        if paged:
            self_mask = (steps[None, :] <= pos[:, None]).astype(
                cfg.compute_dtype)[:, None, None, :]
        else:
            self_mask = (steps <= pos).astype(
                cfg.compute_dtype)[None, None, None, :]
    else:
        self_mask = None                 # AAN/SSRU need no attention mask
    cross_masks = [m[:, None, None, :] for m in _as_tuple(src_mask)]
    align = None
    new_state = dict(state)

    if scanned:
        if return_alignment:
            raise ValueError("alignment output needs the unrolled decode "
                             "state — pass want_alignment to start_state")
        n_enc = 0 if cfg.lm else cfg.n_encoders
        # stacked decoder weights precomputed ONCE in init_decode_state
        # ('stack_p_*', beam-invariant) — restacking here would copy every
        # decoder weight per generated token
        stacked = {k[len("stack_p_"):]: v for k, v in state.items()
                   if k.startswith("stack_p_")}
        caches = {"self_k": state["stack_self_k"],
                  "self_v": state["stack_self_v"]}
        for i in range(n_enc):
            sfx = _ctx_suffix(i)
            caches[f"cross_k{sfx}"] = state[f"stack_cross_kc{sfx}"]
            caches[f"cross_v{sfx}"] = state[f"stack_cross_vc{sfx}"]

        def body(x, xs):
            leaves, cc = xs
            pv = {**params, **{f"decoder_lS_{s}": v
                               for s, v in leaves.items()}}
            x, new_c, _ = _decode_layer(cfg, pv, "decoder_lS", x, pos,
                                        self_mask, cross_masks, cc, n_enc,
                                        beam_src=beam_src,
                                        fused_decode=fused_decode)
            return x, (new_c["self_k"], new_c["self_v"])

        x, (new_sk, new_sv) = jax.lax.scan(body, x, (stacked, caches))
        new_state["stack_self_k"] = new_sk
        new_state["stack_self_v"] = new_sv
        x = _pre_post(cfg, _strip_dropout(cfg.postprocess_top), x, None,
                      "decoder_top", params, None, False)
        logits = _final_logits(cfg, params, state, x, shortlist)
        new_state["pos"] = pos + 1
        return logits, new_state

    n_enc = 0 if cfg.lm else cfg.n_encoders
    for l in range(1, cfg.dec_depth + 1):
        pl = _tied(cfg, l)               # parameter-owning layer
        kinds = (("aan_sum",) if cfg.decoder_autoreg == "average-attention"
                 else ("rnn_c",) if cfg.decoder_autoreg == "rnn"
                 else ("pool_k", "pool_v") if paged
                 else ("self_k", "self_v"))
        caches_l = {kind: state[f"l{l}_{kind}"] for kind in kinds}
        for i in range(n_enc):
            sfx = _ctx_suffix(i)
            caches_l[f"cross_k{sfx}"] = state[f"l{l}_cross_k{sfx}"]
            caches_l[f"cross_v{sfx}"] = state[f"l{l}_cross_v{sfx}"]
        want_w = return_alignment and _is_alignment_layer(cfg, l)
        x, new_c, align_l = _decode_layer(
            cfg, params, f"decoder_l{pl}", x, pos, self_mask, cross_masks,
            caches_l, n_enc, want_w=want_w, beam_src=beam_src,
            fused_decode=fused_decode, page_table=page_table)
        for kind in kinds:
            new_state[f"l{l}_{kind}"] = new_c[kind]
        if align_l is not None:
            align = align_l
    x = _pre_post(cfg, _strip_dropout(cfg.postprocess_top), x, None,
                  "decoder_top", params, None, False)
    logits = _final_logits(cfg, params, state, x, shortlist)
    new_state["pos"] = pos + 1
    if return_alignment:
        return logits, new_state, align
    return logits, new_state


def _decode_layer(cfg: TransformerConfig, pv: Params, lp: str, x: jax.Array,
                  pos, self_mask, cross_masks, caches: Dict[str, jax.Array],
                  n_enc: int, want_w: bool = False,
                  beam_src: Optional[jax.Array] = None,
                  fused_decode: Optional[bool] = None,
                  page_table: Optional[jax.Array] = None):
    """One decode-step layer, shared verbatim between the scanned and the
    unrolled stacks (the training path shares dec_layer the same way).
    `caches` holds THIS layer's state leaves keyed by kind ('self_k',
    'aan_sum', 'rnn_c', 'pool_k'/'pool_v' with `page_table` (paged
    iteration-level decode; `pos` is then per-row), 'cross_k{sfx}', ...);
    returns (x, updated caches, head-averaged cross-attention row when
    want_w)."""
    new_c: Dict[str, jax.Array] = {}
    align = None
    pre = _pre_post(cfg, _strip_dropout(cfg.preprocess), x, None,
                    f"{lp}_self_Wo", pv, None, False)
    if cfg.decoder_autoreg == "average-attention":
        # running-sum cumulative average: y = (sum + x_t) / (pos+1)
        s = caches["aan_sum"] + pre.astype(jnp.float32)
        y = (s / (pos + 1).astype(jnp.float32)).astype(pre.dtype)
        out = _aan_apply(cfg, pv, lp, pre, y)
        new_c["aan_sum"] = s
    elif cfg.decoder_autoreg == "rnn":
        from ..ops.rnn import SSRU
        d = cfg.dim_emb
        cell = SSRU(d, d, False)
        xp = cell.x_proj(pv, f"{lp}_rnn", pre)
        f, inp = xp[..., :d], xp[..., d:]
        c2 = f * caches["rnn_c"].astype(f.dtype) + inp
        out = jax.nn.relu(c2).astype(pre.dtype)
        if cfg.rnn_projection:
            out = affine(out, pv[f"{lp}_rnn_Wo"], pv[f"{lp}_rnn_bo"])
        new_c["rnn_c"] = c2.astype(caches["rnn_c"].dtype)
    elif page_table is not None:
        # paged self-attention: this layer's slice of the shared pool
        cache = {"k": caches["pool_k"], "v": caches["pool_v"]}
        out, _ = _mha(cfg, pv, f"{lp}_self", pre, pre, self_mask,
                      None, False, cache=cache, cache_pos=pos,
                      page_table=page_table)
        new_c["pool_k"] = cache["k"]
        new_c["pool_v"] = cache["v"]
    else:
        cache = {"k": caches["self_k"], "v": caches["self_v"]}
        out, _ = _mha(cfg, pv, f"{lp}_self", pre, pre, self_mask,
                      None, False, cache=cache, cache_pos=pos,
                      beam_src=beam_src, fused_decode=fused_decode)
        new_c["self_k"] = cache["k"]
        new_c["self_v"] = cache["v"]
    x = _pre_post(cfg, _strip_dropout(cfg.postprocess), out, x,
                  f"{lp}_self_Wo", pv, None, False)

    for i in range(n_enc):
        sfx = _ctx_suffix(i)
        cname = f"{lp}_context{sfx}"
        pre = _pre_post(cfg, _strip_dropout(cfg.preprocess), x, None,
                        f"{cname}_Wo", pv, None, False)
        out, w = _mha(cfg, pv, cname, pre, None, cross_masks[i],
                      None, False,
                      cache={"k": caches[f"cross_k{sfx}"],
                             "v": caches[f"cross_v{sfx}"]},
                      static_kv=True, return_weights=want_w and i == 0)
        if want_w and i == 0 and w is not None:
            align = w.mean(axis=1)[:, 0, :]  # [B, Ts]
        x = _pre_post(cfg, _strip_dropout(cfg.postprocess), out, x,
                      f"{cname}_Wo", pv, None, False)

    pre = _pre_post(cfg, _strip_dropout(cfg.preprocess), x, None,
                    f"{lp}_ffn_ffn", pv, None, False)
    out, _ = _ffn_or_moe(cfg, pv, lp, pre, cfg.dec_ffn,
                         cfg.dec_ffn_d, None, False)
    x = _pre_post(cfg, _strip_dropout(cfg.postprocess), out, x,
                  f"{lp}_ffn_ffn", pv, None, False)
    return x, new_c, align


def _final_logits(cfg: TransformerConfig, params: Params, state, x,
                  shortlist):
    if cfg.output_approx_knn and shortlist is None \
            and "lsh_planes" in state:
        from ..ops.lsh import lsh_logits
        table = _plain_output_table(cfg, params)
        lsh_b = params.get("decoder_ff_logit_out_b")
        if lsh_b is None:           # --output-omit-bias (activation dtype)
            lsh_b = jnp.zeros((1, _trg_rows(cfg)), x.dtype)
        return lsh_logits(
            x[:, 0, :], table,
            lsh_b.reshape(-1),
            state["lsh_planes"], state["lsh_signatures"],
            k=int(cfg.output_approx_knn[0]))
    return output_logits(cfg, params, x[:, 0, :], shortlist)


def _strip_dropout(ops: str) -> str:
    return ops.replace("d", "")


def cast_params(params: Params, dtype) -> Params:
    """Cast float params to the compute dtype (kept f32 in the optimizer).
    Quantized (QTensor) leaves pass through — their int8 payload + f32
    scales are dtype-handled at the op sites."""
    from ..ops.quantization import QTensor
    return {k: (v.astype(dtype)
                if not isinstance(v, QTensor)
                and jnp.issubdtype(v.dtype, jnp.floating) else v)
            for k, v in params.items()}
