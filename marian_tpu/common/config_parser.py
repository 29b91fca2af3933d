"""Marian-compatible configuration surface: YAML config files + CLI overrides.

TPU-native rebuild of reference src/common/config_parser.cpp ::
ConfigParser::parseOptions and src/common/cli_wrapper.cpp. Flag NAMES and
semantics follow Marian so existing Marian command lines / config.yml files run
unmodified (north-star requirement); the implementation is plain argparse+yaml.

Precedence (same as Marian): defaults < config file(s) < CLI flags.
``--dump-config [minimal|expand]`` prints the effective config and exits.
Aliases (``--task transformer-big``) expand to canonical hyperparameter sets
(reference: src/common/aliases.cpp) before user overrides are applied.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence

import yaml

from .options import Options
from .aliases import ALIASES, expand_aliases

# ---------------------------------------------------------------------------
# Flag table. Each entry: (name, type, default, help, group)
# type: bool flags are implicit-true switches with optional value, like CLI11.
# A default of None means "unset" (Options.has() is False) unless the mode
# defaults below fill it in.
# ---------------------------------------------------------------------------

F = dataclasses.make_dataclass("F", ["name", "type", "default", "help", "group", "nargs"])


def _f(name, type_, default, help_, group, nargs=None):
    return F(name, type_, default, help_, group, nargs)


_COMMON = [
    _f("config", str, None, "Paths to YAML config file(s); later files override earlier", "general", "+"),
    _f("workspace", int, -1, "Device workspace hint in MB (XLA manages memory; kept for CLI compat)", "general"),
    _f("log", str, None, "Log to file in addition to stderr", "general"),
    _f("log-level", str, "info", "trace/debug/info/warn/error/critical/off", "general"),
    _f("log-time-zone", str, "", "Time zone for log timestamps", "general"),
    _f("quiet", bool, False, "Suppress all logging to stderr", "general"),
    _f("quiet-translation", bool, False, "Suppress logging for translation", "general"),
    _f("seed", int, 0, "RNG seed; 0 means use wall-clock", "general"),
    _f("check-nan", bool, False, "Check gradients for NaN/inf (jax_debug_nans)", "general"),
    _f("interpolate-env-vars", bool, False, "Interpolate ${ENV_VAR} in config/paths", "general"),
    _f("relative-paths", bool, False, "Paths in configs are relative to the config file", "general"),
    _f("dump-config", str, None, "Dump effective config and exit: full/minimal/expand", "general"),
    _f("sigterm", str, "save-and-exit", "SIGTERM behavior: save-and-exit or exit-immediately", "general"),
    _f("profile", str, None, "Capture a jax.profiler device trace to this directory around a training-update window (TPU extension; view with tensorboard)", "general", "?"),
    _f("profile-server", int, 0, "Start a live jax.profiler server on this port (0 = off): attach TensorBoard's profile tab or xprof to a RUNNING training job and capture on demand (TPU extension; SURVEY tracing row)", "general"),
    _f("profile-start", int, 10, "First update of the profiler trace window", "general"),
    _f("profile-updates", int, 5, "Number of updates to trace", "general"),
    _f("dump-hlo", str, None, "Write jaxpr + optimized HLO of the compiled train step to this path prefix and continue (graph-dump debugging equivalent)", "general"),
    _f("authors", bool, False, "Print list of authors and exit", "general"),
    _f("cite", bool, False, "Print citation and exit", "general"),
    _f("build-info", str, None, "Print build info and exit", "general"),
    _f("version", bool, False, "Print version and exit", "general"),
]

_MODEL = [
    _f("model", str, "model.npz", "Path prefix for model to be saved/resumed", "model"),
    _f("pretrained-model", str, None, "Initialize weights from this model", "model"),
    _f("ignore-model-config", bool, False, "Ignore the config embedded in the model file", "model"),
    _f("type", str, "amun", "Model type: transformer, s2s, nematus, amun, multi-s2s, char-s2s, multi-transformer, bert, bert-classifier, transformer-lm", "model"),
    _f("dim-vocabs", int, [0, 0], "Maximum vocabulary sizes (0 = from vocab file)", "model", "+"),
    _f("dim-emb", int, 512, "Embedding vector size", "model"),
    _f("factors-dim-emb", int, 0, "Embedding size of factors (0 = sum combine)", "model"),
    _f("factors-combine", str, "sum", "How to combine factor embeddings: sum or concat", "model"),
    _f("lemma-dim-emb", int, 0, "Re-embedding dimension of lemma in factors", "model"),
    _f("lemma-dependency", str, "", "Factor-prediction dependency mechanism (collapsed into --lemma-dim-emb re-embedding; see flag audit)", "model"),
    _f("output-omit-bias", bool, False, "Output (logits) projection without a bias term", "model"),
    _f("dim-rnn", int, 1024, "RNN state size", "model"),
    _f("char-stride", int, 5, "Width of max-pooling layer after convolution layer in char-s2s model", "model"),
    _f("char-highway", int, 4, "Number of highway network layers after max-pooling in char-s2s model", "model"),
    _f("enc-type", str, "bidirectional", "Encoder type: bidirectional, bi-unidirectional, alternating", "model"),
    _f("enc-cell", str, "gru", "Encoder cell: gru, lstm, ssru, gru-nematus", "model"),
    _f("enc-cell-depth", int, 1, "Cells per encoder transition (deep transition)", "model"),
    _f("enc-depth", int, 1, "Encoder layers", "model"),
    _f("dec-cell", str, "gru", "Decoder cell: gru, lstm, ssru, gru-nematus", "model"),
    _f("dec-cell-base-depth", int, 2, "Cells in first decoder transition (incl. attention cell)", "model"),
    _f("dec-cell-high-depth", int, 1, "Cells in higher decoder transitions", "model"),
    _f("dec-depth", int, 1, "Decoder layers", "model"),
    _f("skip", bool, False, "Residual/skip connections in RNN layers", "model"),
    _f("layer-normalization", bool, False, "Layer normalization in RNN cells", "model"),
    _f("right-left", bool, False, "Train right-to-left model", "model"),
    _f("input-types", str, [], "Input types per stream: sequence, class, alignment, weight", "model", "*"),
    _f("tied-embeddings", bool, False, "Tie target embeddings and output layer", "model"),
    _f("tied-embeddings-src", bool, False, "Tie source and target embeddings", "model"),
    _f("tied-embeddings-all", bool, False, "Tie all embeddings and output layer", "model"),
    # transformer
    _f("transformer-heads", int, 8, "Number of attention heads", "model"),
    _f("transformer-dim-ffn", int, 2048, "FFN hidden size", "model"),
    _f("transformer-decoder-dim-ffn", int, 0, "Decoder FFN hidden size (0 = transformer-dim-ffn)", "model"),
    _f("transformer-ffn-depth", int, 2, "FFN depth (number of linear layers)", "model"),
    _f("transformer-decoder-ffn-depth", int, 0, "Decoder FFN depth (0 = transformer-ffn-depth)", "model"),
    _f("transformer-ffn-activation", str, "swish", "relu, swish, gelu", "model"),
    _f("transformer-no-projection", bool, False, "Omit output projection in MHA", "model"),
    _f("transformer-pool", bool, False, "Pooler instead of self-attention (experimental)", "model"),
    _f("transformer-dim-aan", int, 2048, "AAN FFN hidden size", "model"),
    _f("transformer-aan-depth", int, 2, "Depth of the AAN position-wise FFN", "model"),
    _f("transformer-aan-activation", str, "swish", "Activation of the AAN FFN: swish | relu | gelu", "model"),
    _f("transformer-aan-nogate", bool, False, "Disable the AAN input/forget gate", "model"),
    _f("transformer-decoder-autoreg", str, "self-attention", "self-attention, average-attention, rnn", "model"),
    _f("transformer-flash-attention", str, "auto", "Pallas blockwise attention kernel: auto, on, off (TPU extension)", "model"),
    _f("transformer-packed-attention", str, "auto", "Pallas head-packed short-sequence attention kernel, 128//dim-head heads per 128x128 MXU tile pass: auto = off (below the flash threshold attention is XLA's dense einsum, which beat the kernel 2.3-5.5x on a v5e), on forces the kernel up to its length cap, off (TPU extension)", "model"),
    _f("transformer-fused-decode-attention", str, "auto", "Pallas fused beam-gather + cache-update + attention decode step: auto (TPU only), on, off (TPU extension)", "model"),
    _f("fused-ce", str, "auto", "Streaming fused softmax cross-entropy kernel (logit blocks stay in VMEM): auto (TPU only), on, off (TPU extension)", "model"),
    _f("transformer-tied-layers", int, [], "Tie decoder layers to these encoder layers", "model", "*"),
    _f("transformer-guided-alignment-layer", str, "last", "Decoder layer for guided alignment", "model"),
    _f("transformer-preprocess", str, "", "Per-sublayer preprocess ops: d=dropout, a=add(residual), n=layernorm", "model"),
    _f("transformer-postprocess", str, "dan", "Per-sublayer postprocess ops", "model"),
    _f("transformer-postprocess-emb", str, "d", "Embedding postprocess ops", "model"),
    _f("transformer-postprocess-top", str, "", "Final decoder-top postprocess ops", "model"),
    _f("transformer-train-position-embeddings", bool, False, "Learned positional embeddings", "model"),
    _f("transformer-depth-scaling", bool, False, "Depth-scaled parameter initialization", "model"),
    _f("transformer-rnn-projection", bool, False, "Projection after decoder RNN (autoreg=rnn)", "model"),
    _f("max-length", int, 50, "Maximum sentence length (training crop/skip; decode cap)", "model"),
    _f("max-length-crop", bool, False, "Crop instead of skipping over-long sentences", "model"),
    _f("bert-mask-symbol", str, "[MASK]", "BERT masking symbol", "model"),
    _f("bert-sep-symbol", str, "[SEP]", "BERT separator symbol", "model"),
    _f("bert-class-symbol", str, "[CLS]", "BERT class symbol", "model"),
    _f("bert-masking-fraction", float, 0.15, "BERT masking fraction", "model"),
    _f("bert-train-type-embeddings", bool, True, "Train sentence-type embeddings", "model"),
    _f("bert-type-vocab-size", int, 2, "Type vocab size", "model"),
    # precision
    _f("precision", str, ["float32", "float32"], "Training precisions: compute, optimizer accumulation (float16 is mapped to bfloat16 on TPU)", "model", "+"),
    _f("cost-scaling", str, [], "Dynamic loss scaling (mostly unneeded in bf16; kept for parity)", "model", "*"),
    _f("gradient-checkpointing", bool, False, "Rematerialization (jax.checkpoint) to save memory", "model"),
    # tpu-specific (new, no Marian equivalent)
    _f("attention-kernel", str, "auto", "Attention impl: auto, dense, flash (Pallas)", "model"),
    _f("auto-tune", bool, False, "Time implementation alternatives (dense vs Pallas flash attention crossover) on the current backend and bind the fastest, like the reference's AutoTuner (TPU extension)", "model"),
    _f("sequence-parallel", str, "none", "Sequence/context parallelism over the 'seq' mesh axis: none, ring (K/V blocks rotate via ppermute), ulysses (all-to-all head<->seq swap) (TPU extension)", "model"),
    _f("scan-layers", bool, False, "lax.scan over layer stack: compile time O(1) in depth, but measured 25-33% slower per step than unrolled on TPU v5e (r4 bench scan A/B — XLA schedules/fuses across unrolled layers, not across a while-loop boundary). Default off; turn on for very deep stacks or compile-time-bound jobs. Auto-falls back for tied layers/alignment/int8; implied ON by --stacked-params and pipe-sharded meshes (they consume the stacked layout)", "model"),
    _f("stacked-params", bool, False, "Store transformer layer weights depth-stacked [L,...] during training: the --scan-layers forward consumes the stack directly, removing its per-step restack (one full HBM read+write of every layer weight per micro-batch). Implied by meshes with pipe>1; checkpoints stay Marian-flat", "model"),
    _f("transformer-moe-experts", int, 0, "Mixture-of-Experts FFN: number of experts (0 = dense FFN; TPU extension, shards over the 'expert' mesh axis)", "model"),
    _f("transformer-moe-top-k", int, 2, "MoE router top-k (1 = Switch, 2 = GShard)", "model"),
    _f("moe-capacity-factor", float, 1.25, "MoE expert capacity factor (tokens beyond capacity fall through the residual)", "model"),
    _f("moe-aux-weight", float, 0.01, "Weight of the MoE load-balancing auxiliary loss", "training"),
    # a decoder-only stack whose layers differ (models/layer_plan.py; TPU extension)
    _f("transformer-layer-plan", str, [], "--type transformer-lm only: one <mixing>:<feed-forward> entry per layer, mixing kda (delta rule with per-channel decay), mla (latent attention), gqa (grouped-query attention), swa (gqa under a sliding window) or conv (a doubly gated short convolution), feed-forward dense (gated MLP of --transformer-dim-ffn) or experts; pre-norm RMSNorm, positions only through a layer's own rotation, the output table a matrix of its own or, under --tied-embeddings, the input table", "model", "*"),
    _f("plan-norm-eps", float, 1e-5, "RMSNorm epsilon of a layer plan", "model"),
    _f("plan-kda-dim-head", int, 128, "kda: key and value channels per head", "model"),
    _f("plan-kda-conv", int, 4, "kda: width of the depthwise causal convolution on q, k, v", "model"),
    _f("plan-kda-low-rank", int, 128, "kda: rank of the decay and output-gate projections", "model"),
    _f("plan-kda-head-groups", int, 1, "kda: mix the heads in this many groups, one after another, each rematerialised in the backward: every intermediate is a group wide (memory for time; must divide --transformer-heads)", "model"),
    _f("plan-mla-dim-nope", int, 128, "mla: per-head key channels expanded from the latent", "model"),
    _f("plan-mla-dim-shared", int, 64, "mla: key channels shared by all heads (rotated by position where --plan-mla-rope-theta says so)", "model"),
    _f("plan-mla-dim-v", int, 128, "mla: value channels per head", "model"),
    _f("plan-mla-latent", int, 512, "mla: width of the key-value latent", "model"),
    _f("plan-mla-q-rank", int, 0, "mla: rank of the query's projection, W_qb RMSNorm(W_qa x) (0: one full-rank W_q)", "model"),
    _f("plan-mla-rope-theta", float, 0.0, "mla: base of the rotation by position of the shared key channels and of their query channels, pairs (2i, 2i+1), float32 angles (0: not rotated, the layer has no positional signal)", "model"),
    _f("plan-mtp-modules", int, 0, "the last N entries of --transformer-layer-plan are prediction modules after the stack: each joins the hidden state with the next gold token's embedding, runs its block and predicts one token further through the shared output table", "model"),
    _f("plan-mtp-weight", float, 0.3, "weight of the prediction modules' summed cost beside the main head's (the label count stays the main head's)", "model"),
    _f("plan-experts", int, 0, "experts: the router's width (all experts of the layer, held here or not)", "model"),
    _f("plan-experts-held", int, [], "experts: FIRST COUNT, the experts this process holds and computes (default: all); the rest are other chips' part of the result", "model", "*"),
    _f("plan-experts-top-k", int, 8, "experts: experts per token", "model"),
    _f("plan-experts-dim-ffn", int, 1024, "experts: hidden width of one expert's gated MLP", "model"),
    _f("plan-experts-shared", int, 1, "experts: shared experts added to every token", "model"),
    _f("plan-experts-scale", float, 1.0, "experts: factor on the renormalised routing weights", "model"),
    _f("plan-experts-score", str, "sigmoid", "experts: the router's scores over all experts, before the top k and their renormalisation: sigmoid (of each logit) or softmax (over them)", "model"),
    _f("plan-experts-bias-rate", float, 0.0, "experts: > 0 gives every router a selection bias per expert, added to the scores for the top-k choice alone (the routing weights are the scores without it); no gradient reaches it: each update moves it by this much AGAINST the sign of (the expert's load - the mean load) over the update's tokens, summed over the chips that share the data (0: no bias)", "model"),
    _f("plan-gqa-kv-heads", int, 0, "gqa: key/value heads, each read by --transformer-heads / N query heads (0: as many as query heads)", "model"),
    _f("plan-gqa-dim-head", int, 128, "gqa: channels per head of queries, keys and values; queries and keys are RMS-normed per head with a learned scale", "model"),
    _f("plan-gqa-rope-theta", float, 1e6, "gqa: base of the rotation by position of every query and key head, whole heads in half-split pairs (i, i + dim/2), float32 angles (0: gqa layers are not rotated)", "model"),
    _f("plan-gqa-gate", bool, False, "gqa and swa: multiply the heads' output, channel by channel, by sigmoid(W_gate x) before W_o (W_gate [dim-emb, heads x dim-head]; the sigmoid in float32)", "model"),
    _f("plan-swa-window", int, 0, "swa: a gqa layer under a sliding window, a query sees the last N keys up to its own; its sizes are gqa's", "model"),
    _f("plan-swa-rope-theta", float, 1e4, "swa: base of the rotation of its query and key heads, as --plan-gqa-rope-theta is for gqa layers (0: not rotated)", "model"),
    _f("plan-conv-taps", int, 3, "conv: taps of the depthwise causal convolution between the layer's two gates, the last on the current token (at least 1); the layer's width is --dim-emb", "model"),
    _f("plan-post-norms", bool, False, "a layer plan's block norms each branch's OUTPUT too before the residual add, x + norm(mixing(norm(x))): four RMSNorms a block", "model"),
    _f("plan-diffusion-block", int, 0, "train a plan of gqa layers by diffusion over blocks of N positions: the stack runs over [noised copy ; clean copy] of each row under the block rule and the cost is the masked positions' cross-entropy over the row's noise level (0: next-token training)", "model"),
]

_TRAINING = [
    _f("task", str, None, "Shortcut for a predefined hyperparameter bundle: transformer-base, transformer-big, transformer-base-prenorm, transformer-big-prenorm", "training", "?"),
    _f("cost-type", str, "ce-sum", "ce-mean, ce-mean-words, ce-sum, perplexity", "training"),
    _f("multi-loss-type", str, "sum", "sum, scaled, mean", "training"),
    _f("unlikelihood-loss", bool, False, "Use word-level weights as indicators for unlikelihood loss", "training"),
    _f("overwrite", bool, False, "Do not create checkpoints per save, overwrite model file", "training"),
    _f("no-reload", bool, False, "Do not load existing model file before training", "training"),
    _f("train-sets", str, [], "Paths to training corpora (source target ...)", "training", "*"),
    _f("vocabs", str, [], "Paths to vocabulary files; created if missing", "training", "*"),
    _f("sentencepiece-alphas", float, [], "Subword-regularization sampling alphas per stream", "training", "*"),
    _f("sentencepiece-options", str, "", "Options passed to on-the-fly SentencePiece training", "training"),
    _f("sentencepiece-max-lines", int, 2000000, "Max lines for SentencePiece vocab training", "training"),
    _f("after-epochs", int, 0, "Stop after this many epochs (0 = no limit); same as --after Ne", "training"),
    _f("after-batches", int, 0, "Stop after this many updates (0 = no limit)", "training"),
    _f("after", str, "0e", "Stop after: e.g. 10e (epochs), 100Ku (updates), 1Gt (labels)", "training"),
    _f("disp-freq", str, "1000u", "Display information every N updates/epochs/labels", "training"),
    _f("disp-first", int, 0, "Display information for the first N updates", "training"),
    _f("disp-label-counts", bool, True, "Display label counts in progress", "training"),
    _f("save-freq", str, "10000u", "Save model every N", "training"),
    _f("normalize-gradient", bool, False, "Additionally divide the gradient by the batch's target-word count", "training"),
    _f("check-gradient-nan", bool, False, "Skip the whole update (params + optimizer state unchanged) when the gradient norm is non-finite", "training"),
    _f("dynamic-gradient-scaling", str, [], "FACTOR ['log']: scale outlier gradients down to FACTOR x the windowed average (log-)norm", "training", "*"),
    _f("gradient-norm-average-window", int, 100, "Window for the running gradient-norm average used by --dynamic-gradient-scaling", "training"),
    _f("optimizer-state-dtype", str, "float32", "Storage dtype for Adam's first moment: float32 | bfloat16 (halves m's HBM footprint and per-step traffic; math stays f32, v stays f32; beyond the reference)", "training"),
    _f("gradient-dtype", str, "float32", "Dtype gradients are produced, reduce-scattered, and stored in until the optimizer's in-register f32 upcast: float32 | bfloat16 (halves backward gradient HBM writes and ZeRO-1 collective bytes — the analogue of Marian's fp16 gradient communication; requires matching bfloat16 compute --precision, otherwise ignored with a warning). Note: the logits backward always rounds its cotangent through the COMPUTE dtype (ops/ops.py logits_matmul — the bf16 MXU-rate fix), so float32 here does NOT make bf16-compute backward passes fully f32; see docs/PERFORMANCE.md", "training"),
    _f("async-save", bool, False, "Overlap checkpoint writes with training: device snapshots on the train thread, numpy+disk IO on a background worker (beyond the reference, whose Train::save blocks the update loop). Needs transient HBM headroom for one device copy of params+EMA+optimizer state at save time", "training"),
    _f("keep-checkpoint-bundles", int, 3, "Crash-safe checkpointing: keep the last N committed checkpoint bundles under <model>.bundles/ (each bundle is the atomic, checksummed model+optimizer+progress unit restore validates and falls back across; see docs/ROBUSTNESS.md). Disk cost is ~N x checkpoint size; minimum 1 (TPU extension)", "training"),
    _f("compact-transfer", bool, True, "Ship training batches as uint16 tokens + per-row lengths instead of int32 ids + float masks (~4x less host-to-device traffic per step; ids/masks are rebuilt inside the jitted step — beyond the reference)", "training"),
    _f("tensorboard", str, None, "Write train/valid scalars (cost, words/s, learn rate, validation metrics) as TensorBoard events to this directory (beyond the reference, which logs text only)", "training", "?"),
    _f("logical-epoch", str, ["1e"], "Logical epoch spec, e.g. 1Gt", "training", "+"),
    _f("max-length-factor", float, 3.0, "Max target length factor of source length while decoding", "training"),
    _f("shuffle", str, "data", "data, batches, none", "training"),
    _f("no-shuffle", bool, False, "Disable shuffling (= --shuffle none)", "training"),
    _f("no-restore-corpus", bool, False, "Do not restore corpus position on resume", "training"),
    _f("tempdir", str, "/tmp", "Temporary directory for shuffling", "training"),
    _f("sqlite", str, None, "Keep corpus in an on-disk database for O(1) mid-epoch resume", "training", "?"),
    _f("sqlite-drop", bool, False, "Drop the SQLite corpus database (no-op; see flag audit)", "training"),
    _f("mini-batch-track-optimum", bool, False, "Track the optimal batch size (no-op; see flag audit)", "training"),
    _f("train-embedder-rank", str, [], "Margin-based embedder-rank training (refused; see flag audit)", "training", "*"),
    _f("tsv", bool, False, "Train sets are tab-separated files (one line carries all streams)", "training"),
    _f("tsv-fields", int, 0, "Number of TSV columns (0 = infer from --vocabs count)", "training"),
    _f("no-spm-encode", bool, False, "Input is already SentencePiece-encoded: skip encoding, split on whitespace", "training"),
    _f("input-reorder", int, [], "Permutation applied to TSV columns before they become streams, e.g. 1 0", "training", "*"),
    _f("throw-on-divergence", bool, False, "Raise (instead of logging) when the training cost goes non-finite, so orchestration restarts from the last checkpoint", "training"),
    _f("on-divergence", str, "", "Divergence policy: throw | warn | rollback. 'rollback' self-heals in-process: restore the last good checkpoint bundle, rewind the data pipeline to the bundle's corpus snapshot (past the poison window), apply --divergence-lr-backoff per retry, and give up loudly (raise) after --divergence-retries attempts. Empty derives from --throw-on-divergence: throw when set, else warn (TPU extension; see docs/ROBUSTNESS.md)", "training"),
    _f("divergence-retries", int, 3, "With --on-divergence rollback: in-process rollback attempts before giving up and raising like throw (TPU extension)", "training"),
    _f("divergence-lr-backoff", float, 0.5, "With --on-divergence rollback: multiply the learning-rate decay factor by this on each retry (compounds across retries and persists in the saved training state; 1.0 = no backoff) (TPU extension)", "training"),
    _f("divergence-skip-window", int, 10, "With --check-gradient-nan: treat this many CONSECUTIVE NaN-skipped updates as divergence, feeding --on-divergence without waiting for the display-boundary cost sync (0 = never; detection lags the hot loop by ~2 updates, not a display window) (TPU extension)", "training"),
    _f("train-stall-timeout", float, 0.0, "Training-step watchdog: when the update loop makes no progress for this many seconds (a step that never fences — wedged collective, hung data feed), dump a flight recording naming the stalled step, save a host-side diagnostic progress file, and exit with the distinct retriable code 75 so a supervisor restarts into the checkpoint-resume path (0 = off) (TPU extension)", "training"),
    _f("diverged-after", str, None, "fp16 divergence-recovery horizon (no-op; see flag audit)", "training", "?"),
    _f("custom-fallbacks", str, [], "fp16 fallback config list (no-op; see flag audit)", "training", "*"),
    _f("fp16-fallback-to-fp32", bool, False, "fp16 fallback (no-op; see flag audit)", "training"),
    _f("recover-from-fallback-after", str, None, "fp16 fallback recovery (no-op; see flag audit)", "training", "?"),
    _f("overwrite-checkpoint", bool, True, "Overwrite the single rolling checkpoint (no-op; see flag audit)", "training"),
    _f("clip-gemm", float, 0.0, "Legacy GEMM clipping (no-op; see flag audit)", "training"),
    _f("mini-batch", int, 64, "Minibatch size (sentences)", "training"),
    _f("mini-batch-words", int, 0, "Minibatch size in target labels (token budget)", "training"),
    _f("mini-batch-fit", bool, False, "Determine minibatch automatically from workspace (TPU: bucket table)", "training"),
    _f("mini-batch-fit-step", int, 10, "Step for mini-batch-fit search", "training"),
    _f("maxi-batch", int, 100, "Number of minibatches to preload and sort", "training"),
    _f("maxi-batch-sort", str, "trg", "Sorting within maxi-batch: trg, src, none", "training"),
    _f("batch-row-multiple", int, 8, "Rows of a padded batch snap up to a multiple of this (pad rows are masked); 1 for long rows, where 8 rows would be several times the token budget (TPU extension)", "training"),
    _f("length-buckets", int, [], "Padded widths a batch snaps up to, ascending; beyond the last, steps of 512 (default: 8 16 24 32 48 64 96 128 ... 4096) (TPU extension)", "training", "*"),
    _f("precompile-buckets", int, 0, "With --length-buckets and --mini-batch-words the train step has one shape a bucket: compile them ahead on this many host threads from the first update on, so that one shape's compile overlaps the next's (0: each compiles when its first batch arrives) (TPU extension)", "training"),
    _f("shuffle-in-ram", bool, False, "Shuffle corpus in RAM instead of temp files", "training"),
    _f("data-threads", int, 8, "Host threads for data pipeline", "training"),
    _f("all-caps-every", int, 0, "Upper-case every Nth batch (data augmentation)", "training"),
    _f("english-title-case-every", int, 0, "Title-case every Nth batch", "training"),
    _f("mini-batch-words-ref", int, 0, "Reference batch size in words for LR auto-adjustment", "training"),
    _f("mini-batch-warmup", str, "0", "Linear batch-size warmup period", "training"),
    _f("mini-batch-track-lr", bool, False, "Adjust LR for tracked batch-size ramp", "training"),
    _f("mini-batch-round-up", bool, True, "Round up batch size for warmup", "training"),
    _f("optimizer", str, "adam", "adam, adagrad, sgd", "training"),
    _f("optimizer-params", float, [], "Optimizer hyperparameters (Adam: beta1 beta2 eps)", "training", "*"),
    _f("optimizer-delay", float, 1.0, "SGD update delay (gradient accumulation): N updates or fractional", "training"),
    _f("sync-sgd", bool, False, "Synchronous SGD (the only mode on TPU; async maps to it with a warning)", "training"),
    _f("learn-rate", float, 0.0001, "Learning rate", "training"),
    _f("lr-report", bool, False, "Report learning rate in progress lines", "training"),
    _f("lr-decay", float, 0.0, "Decay factor: lr = lr * decay", "training"),
    _f("lr-decay-strategy", str, "epoch+stalled", "epoch, batches, stalled, epoch+batches, epoch+stalled", "training"),
    _f("lr-decay-start", int, [10, 1], "Decay start: [epoch, batches/stalled]", "training", "+"),
    _f("lr-decay-freq", int, 50000, "Decay frequency (strategy: batches)", "training"),
    _f("lr-decay-reset-optimizer", bool, False, "Reset optimizer state at LR decay", "training"),
    _f("lr-decay-repeat-warmup", bool, False, "Repeat warmup after decay", "training"),
    _f("lr-decay-inv-sqrt", str, ["0"], "Inverse-sqrt decay with this warmup, e.g. 16000u", "training", "+"),
    _f("lr-warmup", str, "0", "Linear LR warmup period", "training"),
    _f("lr-warmup-start-rate", float, 0.0, "Warmup start LR", "training"),
    _f("lr-warmup-cycle", bool, False, "Cyclic warmup", "training"),
    _f("lr-warmup-at-reload", bool, False, "Repeat warmup after checkpoint reload", "training"),
    _f("label-smoothing", float, 0.0, "Label smoothing epsilon", "training"),
    _f("factor-weight", float, 1.0, "Weight for loss of factors vs lemma", "training"),
    _f("clip-norm", float, 1.0, "Global gradient-norm clipping (0 = off)", "training"),
    _f("exponential-smoothing", float, 0.0, "EMA decay of parameters, e.g. 1e-4 (0 = off)", "training", "?"),
    _f("guided-alignment", str, "none", "Path to alignments or 'none'", "training"),
    _f("guided-alignment-cost", str, "ce", "ce, mse, mult", "training"),
    _f("guided-alignment-weight", float, 0.1, "Weight for guided-alignment cost", "training"),
    _f("data-weighting", str, None, "Path to per-sentence/word weight file", "training"),
    _f("data-weighting-type", str, "sentence", "sentence or word", "training"),
    _f("embedding-vectors", str, [], "Paths to pretrained embedding vectors", "training", "*"),
    _f("embedding-normalization", bool, False, "Normalize pretrained embedding vectors", "training"),
    _f("embedding-fix-src", bool, False, "Fix source embeddings", "training"),
    _f("embedding-fix-trg", bool, False, "Fix target embeddings", "training"),
    _f("quantize-bits", int, 0, "Train-time model quantization bits (0 = off)", "training"),
    _f("gradient-dropping-rate", float, 0.0, "Drop this fraction of each gradient tensor (DGC-style, with error feedback); 0 = off", "training"),
    _f("quantize-optimization-steps", int, 0, "Scale-optimization steps for quantization", "training"),
    _f("quantize-log-based", bool, False, "Log-based quantization", "training"),
    _f("quantize-biases", bool, False, "Quantize biases too", "training"),
    _f("ulr", bool, False, "Universal language representation", "training"),
    _f("ulr-query-vectors", str, "", "Path to ULR query vectors", "training"),
    _f("ulr-keys-vectors", str, "", "Path to ULR key vectors", "training"),
    _f("ulr-trainable-transformation", bool, False, "Trainable ULR transformation", "training"),
    _f("ulr-dim-emb", int, 0, "ULR embedding dim", "training"),
    _f("ulr-dropout", float, 0.0, "ULR dropout", "training"),
    _f("ulr-softmax-temperature", float, 1.0, "ULR softmax temperature", "training"),
    # dropout group
    _f("dropout-rnn", float, 0.0, "RNN state dropout", "training"),
    _f("dropout-src", float, 0.0, "Source word dropout", "training"),
    _f("dropout-trg", float, 0.0, "Target word dropout", "training"),
    _f("transformer-dropout", float, 0.0, "Dropout between transformer layers", "training"),
    _f("transformer-dropout-attention", float, 0.0, "Attention-weight dropout", "training"),
    _f("transformer-dropout-ffn", float, 0.0, "FFN dropout", "training"),
    # devices
    _f("devices", str, ["0"], "Device ids (GPU compat) or tpu:N..M mesh spec", "training", "+"),
    _f("num-devices", int, 0, "Number of devices (0 = all visible)", "training"),
    _f("no-nccl", bool, False, "(GPU compat; ignored — ICI collectives are always used)", "training"),
    _f("sharding", str, "global", "Optimizer sharding domain: global (ZeRO-1 over all devices) or local", "training"),
    _f("sync-freq", str, "200u", "Param sync frequency for local sharding", "training"),
    _f("cpu-threads", int, 0, "Use CPU with this many threads (inference)", "training", "?"),
    # multi-node
    _f("multi-node", bool, False, "Multi-host training (jax.distributed)", "training"),
    _f("multi-node-overlap", bool, True, "(compat; XLA overlaps automatically)", "training"),
    _f("coordinator-address", str, None, "jax.distributed coordinator ip:port", "training"),
    _f("num-processes", int, 1, "Number of hosts (jax.distributed)", "training"),
    _f("process-id", int, 0, "This host's rank", "training"),
    # mesh axes (TPU-native extension; absent in reference)
    _f("mesh", str, [], "Mesh axes as name:size pairs, e.g. data:8 model:4 seq:2 (default: all devices on data)", "training", "*"),
]

_VALIDATION = [
    _f("valid-sets", str, [], "Paths to validation corpora", "valid", "*"),
    _f("valid-freq", str, "10000u", "Validate every N", "valid"),
    _f("valid-metrics", str, ["cross-entropy"], "cross-entropy, ce-mean-words, perplexity, bleu, bleu-detok, bleu-segmented, chrf, valid-script, translation", "valid", "+"),
    _f("valid-reset-stalled", bool, False, "Reset stalled counts on training restart", "valid"),
    _f("valid-reset-all", bool, False, "Reset all validation state on restart", "valid"),
    _f("early-stopping", int, 10, "Stop after N consecutive non-improving validations", "valid"),
    _f("early-stopping-epsilon", float, [0.0], "Minimum required improvement per metric", "valid", "+"),
    _f("early-stopping-on", str, "first", "first, all, any of valid-metrics", "valid"),
    _f("keep-best", bool, False, "Keep best model per metric", "valid"),
    _f("valid-log", str, None, "Validation log file", "valid"),
    _f("valid-max-length", int, 1000, "Max length for validation sentences", "valid"),
    _f("valid-mini-batch", int, 32, "Validation minibatch size", "valid"),
    _f("valid-script-path", str, None, "External validation script", "valid"),
    _f("valid-script-args", str, [], "Args for external validation script", "valid", "*"),
    _f("valid-translation-output", str, None, "Print validation translations to file", "valid"),
]

_TRANSLATION = [
    _f("vocabs", str, [], "Paths to vocabulary files", "translate", "*"),
    _f("mini-batch", int, 1, "Minibatch size (sentences)", "translate"),
    _f("mini-batch-words", int, 0, "Minibatch size in words", "translate"),
    _f("maxi-batch", int, 1, "Number of minibatches to preload and sort", "translate"),
    _f("maxi-batch-sort", str, "src", "Sorting within maxi-batch: src, none", "translate"),
    _f("data-threads", int, 8, "Host threads for data pipeline", "translate"),
    _f("input", str, ["stdin"], "Input file(s) or stdin", "translate", "+"),
    _f("output", str, "stdout", "Output file or stdout", "translate"),
    _f("models", str, [], "Model file(s) to ensemble", "translate", "*"),
    _f("weights", float, [], "Ensemble scorer weights", "translate", "*"),
    _f("beam-size", int, 12, "Beam size", "translate"),
    _f("normalize", float, 0.0, "Divide score by length^alpha", "translate", "?"),
    _f("word-penalty", float, 0.0, "Subtract penalty*length from score", "translate"),
    _f("allow-unk", bool, False, "Allow <unk> in output", "translate"),
    _f("allow-special", bool, False, "Allow special symbols in output", "translate"),
    _f("n-best", bool, False, "Produce n-best lists", "translate"),
    _f("word-scores", bool, False, "Print per-word scores in n-best lists", "translate"),
    _f("n-best-feature", str, "Score", "Feature name for the n-best score column", "translate"),
    _f("alignment", str, None, "Return word alignments: 0.x threshold, soft, hard", "translate", "?"),
    _f("force-decode", bool, False, "Force-decode given prefixes", "translate"),
    _f("best-deep", bool, False, "(compat)", "translate"),
    _f("output-sampling", str, [], "Sampling instead of argmax: full [temp] / topk k [temp]", "translate", "*"),
    _f("output-approx-knn", int, [], "LSH-approximated output layer: nodes, hashes", "translate", "*"),
    _f("max-length-factor-translate", float, 3.0, "(see max-length-factor)", "translate"),
    _f("skip-cost", bool, False, "Skip costly final scoring", "translate"),
    _f("shortlist", str, [], "Lexical shortlist: path [first] [best] [prune]", "translate", "*"),
    _f("port", int, 8080, "marian-server port", "translate"),
    # serving subsystem (marian_tpu/serving/ — TPU extension, no Marian
    # equivalent): continuous batching, admission control, observability
    _f("max-queue", int, 512, "marian-server admission control: maximum queued sentences before new requests are shed with an explicit !!SERVER-OVERLOADED reply (0 = unbounded, the reference's behavior) (TPU extension)", "translate"),
    _f("request-timeout", float, 0.0, "marian-server per-request deadline in seconds: expired requests get an explicit !!SERVER-TIMEOUT reply (even while queued) instead of waiting forever (0 = no deadline) (TPU extension)", "translate"),
    _f("batch-token-budget", int, 0, "marian-server continuous batching: token budget per device batch against the bucketed static-shape table (data/batch_generator buckets, so serve-time batches hit warm jit-cache shapes). Counted as real rows x bucketed width — the same --mini-batch-words semantics training uses; the realized device batch can exceed it by the row snap-up to the batch multiple. 0 = derive from mini-batch x bucketed max-length (TPU extension)", "translate"),
    _f("batching-mode", str, "request", "marian-server batching discipline: 'request' packs whole requests into device batches between decodes (the default continuous token-budget scheduler); 'iteration' moves scheduling INSIDE the decode loop over a paged KV-cache pool — sentences join a RUNNING decode at any step and leave the step they finish, admission prices queue debt in pool pages, and the headroom gauge's queue-pressure units become pages. --beam-size 1 decodes greedily; beam > 1 decodes with copy-on-write page sharing across hypotheses (full pages alias via refcounts, only partial pages copy on fork — translator/beam_iteration.py; a sentence occupies beam-size slots). Single model only; composes with a restricted option surface (validated loudly at boot; docs/DEPLOYMENT.md) (TPU extension)", "translate"),
    _f("iteration-rows", int, 32, "With --batching-mode iteration: decode slot count — the maximum concurrently decoding sentences; the per-step compiled shape rounds the OCCUPIED slot prefix up through the row-bucket table, so idle slots cost nothing compiled (TPU extension)", "translate"),
    _f("iteration-steps", int, 1, "With --batching-mode iteration: decode steps per scheduling round, run as one jitted scan. 1 = joins possible at EVERY step (pure iteration-level); >1 amortizes per-step host dispatch on host-bound backends at the cost of up to N-1 steps of join latency and a few self-fed row-steps past each EOS. Applies at ANY beam size: beam > 1 scans too under the default fused on-device merge (EOS freezing is an in-scan mask; the COW reorder is in-graph table math), while --iteration-beam-merge host pins beam rounds to single-step (the numpy merge needs the host between steps) (TPU extension)", "translate"),
    _f("iteration-beam-merge", str, "fused", "With --batching-mode iteration and beam > 1: where the k*k candidate merge runs. 'fused' (default) merges on-device — one jitted flat top-k over every live sentence plus in-graph COW page bookkeeping, one host sync per round, composes with --iteration-steps > 1; 'host' keeps the per-step numpy merge (the pre-fused A/B baseline — single-step rounds, one sync per token). Sampling and the cow=False replication baseline always run the host path (TPU extension)", "translate"),
    _f("kv-page-len", int, 16, "With --batching-mode iteration: tokens per KV-cache page. Smaller pages waste less pool on short sentences (internal fragmentation <= page_len-1 tokens/row) but grow the page table; see docs/DECODE_ROOFLINE.md r7 for the HBM-line-size trade (TPU extension)", "translate"),
    _f("kv-pool-bytes", int, 0, "With --batching-mode iteration: byte budget for the paged KV pool across all decoder layers (K+V). 0 = size the pool so every slot can hold a full --max-length row (the pool is then never the admission constraint) (TPU extension)", "translate"),
    _f("max-queue-pages", int, 0, "With --batching-mode iteration: admission bound on queued KV-pool PAGE debt — requests are shed with !!SERVER-OVERLOADED when the queue already owes this many pages (0 = 4x the pool's allocatable pages). Beam-k requests are priced at the shared-trunk steady-state holding (one trunk + k-1 extra partial pages) — an optimistic estimate, never k-times full replication; fully divergent lineages can transiently hold more, which lazy claims cover with retriable mid-decode eviction when the pool runs dry (TPU extension)", "translate"),
    _f("prefix-cache", bool, False, "With --batching-mode iteration: cross-request prefix sharing over the paged KV pool. An exact repeat of a source decoding RIGHT NOW joins as a copy-on-write follower (aliases the leader's full KV pages via refcounts, copies only the partial page, skips the encoder); a repeat of a COMPLETED decode replays it instantly, with the finished rows' pages retained by the cache and LRU-evicted under pool pressure. Deterministic decode makes warm output bitwise-identical to cold; marian_prefix_* metrics count hits/tokens saved/pages reused (docs/DEPLOYMENT.md) (TPU extension)", "translate"),
    _f("prefix-cache-entries", int, 64, "With --prefix-cache: maximum completed decodes retained (LRU); pool pressure can evict below this (TPU extension)", "translate"),
    _f("metrics-port", int, 0, "Serve Prometheus /metrics + /healthz + /readyz on this port (0 = off): queue depth, batch fill ratio, padding waste, time-to-first-batch, end-to-end latency, shed/timeout counts; train/translate emit into the same registry (TPU extension)", "translate"),
    _f("dispatch-stall-timeout", float, 0.0, "marian-server liveness watchdog: if one device batch (translate_lines call) runs longer than this many seconds, fail its requests with an explicit retriable !!SERVER-RETRY reply and move the scheduler onto a fresh device worker instead of wedging the whole serving path behind the stuck call (0 = off; set comfortably above the worst legitimate batch decode time; see docs/ROBUSTNESS.md) (TPU extension)", "translate"),
    _f("quiesce-deadline", float, 2.0, "With --batching-mode iteration and --model-watch: drain budget in seconds for a lifecycle quiesce (swap/canary/rollback). Joins pause and active decode rows drain naturally; rows still decoding at the deadline are evicted with a retriable !!SERVER-RETRY (pages freed, counted in marian_serving_quiesce_evictions_total) so a swap is never held hostage by one long sentence; the engine is re-pointed at a step boundary with an empty join set (docs/ROBUSTNESS.md) (TPU extension)", "translate"),
    _f("brownout", bool, False, "marian-server brownout ladder: under sustained overload (capacity headroom at/below --brownout-headroom, or the SLO fast-burn threshold) step through explicit degradation levels — 1 tighten per-row decode caps, 2 evict lowest-priority/longest-remaining rows with retriable !!SERVER-RETRY, 3 shed admissions below --brownout-min-priority — so high-priority traffic keeps a bounded p99 while low lanes degrade predictably; every transition is a timeline event + marian_brownout_level move (docs/ROBUSTNESS.md) (TPU extension)", "translate"),
    _f("brownout-headroom", float, 0.1, "Brownout overload signal: escalate while marian_capacity_headroom_ratio stays at or below this floor (TPU extension)", "translate"),
    _f("brownout-burn", float, 0.0, "Brownout overload signal: escalate while the SLO engine's fast-window burn rate stays at or above this (0 = use the SLO fast-burn factor when an SLO is declared, else the burn signal is off and headroom drives the ladder alone) (TPU extension)", "translate"),
    _f("brownout-hold", float, 5.0, "Seconds the overload signal must persist before the ladder escalates one level (each rung needs its own sustained hold) (TPU extension)", "translate"),
    _f("brownout-cool", float, 15.0, "Seconds of continuous health before the ladder de-escalates one level (TPU extension)", "translate"),
    _f("brownout-cap-factor", float, 0.5, "Brownout level 1: scale factor applied to NEW rows' decode caps (shorter rows claim fewer KV pages and leave sooner; possible truncation of the longest outputs is the explicit trade) (TPU extension)", "translate"),
    _f("brownout-min-priority", int, 1, "Brownout level 3: admission sheds requests whose priority lane is below this (clients set a lane with the '#priority:N' protocol header; default lane is 0) (TPU extension)", "translate"),
    _f("model-watch", float, 0.0, "marian-server zero-downtime lifecycle: poll <model>.bundles/ every N seconds for newly committed checkpoint bundles and hot-swap to them after an off-path warmup (compat check, load, jit compile, golden smoke) with no dropped requests; in-flight batches finish on the old model (0 = off; see docs/DEPLOYMENT.md) (TPU extension)", "translate"),
    _f("canary-fraction", float, 0.0, "With --model-watch: route this fraction of device batches to a freshly warmed candidate (state 'canary') before promoting it to live; per-version error/latency metrics (marian_model_*) record both sides, and a canary whose failure rate or p99 regresses is auto-rolled-back (0 = swap immediately after warmup) (TPU extension)", "translate"),
    _f("rollback-error-rate", float, 0.5, "With --model-watch: auto-rollback threshold on the windowed device-batch failure rate — a canary (or a freshly swapped live version with a retained rollback target) exceeding this rate is rolled back to the previous live version (docs/DEPLOYMENT.md) (TPU extension)", "translate"),
    _f("rollback-p99-factor", float, 0.0, "With --model-watch: auto-rollback a canary whose p99 batch latency exceeds this factor x the live version's p99 (both over a recent-sample window; 0 = latency check off) (TPU extension)", "translate"),
    _f("canary-min-batches", int, 8, "With --model-watch and --canary-fraction > 0: promote the canary to live after this many canary batches without tripping a rollback threshold (TPU extension)", "translate"),
    _f("warmup-golden", str, "", "With --model-watch: file of golden source sentences (one per line) each candidate model must translate during off-path warmup before it can serve — forces jit compilation of the serving shapes and proves the checkpoint decodes (empty = a built-in probe set) (TPU extension)", "translate"),
    # observability (marian_tpu/obs/ — docs/OBSERVABILITY.md)
    _f("trace", bool, False, "Enable the request-scoped span tracer: every request's path (ingest, admission, queue wait, batch formation, dispatch, translate, reply write — and the trainer's data.* / train.* spans) is recorded into a bounded in-memory ring, exported as Chrome trace JSON at /tracez on the metrics port (open in Perfetto). Off = zero overhead: no ring allocation, no lock on the hot path (TPU extension)", "translate"),
    _f("trace-ring", int, 4096, "With --trace: span ring capacity — how many most-recent spans /tracez and flight-recorder dumps can see (TPU extension)", "translate"),
    _f("trace-dump", str, "", "Arm the crash flight recorder (implies --trace): on a dispatch-watchdog trip, a canary/live auto-rollback, a poison-request isolation, or an injected MARIAN_FAULTS kill, snapshot the span ring + event timeline + /metrics to a timestamped JSON file in this directory (docs/OBSERVABILITY.md runbook) (TPU extension)", "translate"),
    _f("perf-accounting", bool, True, "Live performance & capacity plane (obs/perf.py): per-batch chip-seconds/token, tokens/s, MFU-vs-analytic-roofline and capacity-headroom gauges on /metrics, plus per-shape-bucket jit-compile telemetry (boot/swap warmup vs steady-state recompiles — a steady-state recompile is a latency incident and lands on the event timeline). One counter update per device batch; `--perf-accounting false` restores the strictly lock-free batch path (TPU extension)", "translate"),
    _f("warmup-on-boot", bool, False, "marian-server: golden-warm every serving width bucket BEFORE accepting the first request (one jit compile per bucket off the serving path, reported as trigger=boot-warmup compile telemetry) instead of letting the first request of each bucket pay the compile inline (TPU extension)", "translate"),
    _f("fleet", str, "", "marian-server multi-tenant fleet serving: comma-separated <tag>=<model-path> tenants (e.g. 'en-de=/m/ende.npz,en-fr=/m/enfr.npz') served concurrently by ONE process — per-tenant lifecycle stacks (bundle watcher, canary, rollback) under the shared --fleet-hbm-budget-mb with evict-coldest + warm-on-demand; clients pick a tenant with the '#model:<tag>' protocol header. Request batching mode only; mutually exclusive with --model-watch (docs/DEPLOYMENT.md 'Fleet serving') (TPU extension)", "translate"),
    _f("fleet-hbm-budget-mb", float, 0.0, "With --fleet: shared HBM budget in MB for resident tenant executors (estimated as bundle member bytes x an overhead factor); warming a tenant past the budget evicts the coldest idle tenant's executors first (never one with in-flight batches). 0 = unbudgeted — every tenant stays resident (TPU extension)", "translate"),
    _f("fleet-default-tenant", str, "", "With --fleet: tenant tag for requests that send no '#model:' header (must name a configured tenant); empty = un-tagged requests are rejected with !!SERVER-ERROR (TPU extension)", "translate"),
    _f("fleet-watch", float, 0.0, "With --fleet: poll each RESIDENT tenant's <model>.bundles/ every N seconds and hot-swap new committed bundles through that tenant's own canary/rollback lifecycle (the per-tenant --model-watch; 0 = off, tenants still warm-on-demand) (TPU extension)", "translate"),
    _f("slo-availability", float, 0.0, "Declare an availability SLO (e.g. 0.999): the in-process burn-rate engine (obs/slo.py) evaluates ok-vs-(failure|timeout|stalled) outcomes over fast/slow windows, exports marian_slo_* gauges and GET /sloz, emits timeline events on threshold crossings and fires a flight dump on fast burn (0 = off) (TPU extension)", "translate"),
    _f("slo-p99-ms", float, 0.0, "Declare a latency SLO: 99% of requests must resolve under this many milliseconds (evaluated against the request-latency histogram buckets, conservatively rounded DOWN to a bucket edge). Same burn-rate machinery and exports as --slo-availability (0 = off) (TPU extension)", "translate"),
    _f("slo-window", float, 60.0, "SLO engine short (fast-burn) window in seconds; the slow window is 10x this (TPU extension)", "translate"),
    _f("slo-eval-interval", float, 2.0, "SLO engine evaluation cadence in seconds (its own daemon thread; nothing on the batch path) (TPU extension)", "translate"),
    _f("fuse", bool, False, "(compat; XLA always fuses)", "translate"),
    _f("gemm-type", str, "float32", "float32, bfloat16, int8 (TPU AQT path), intgemm8/packed* map to int8", "translate"),
    _f("quantize-range", float, 0.0, "Quantization clip range in stddevs (0 = absmax)", "translate"),
    _f("mini-batch-words-translate", int, 0, "(see mini-batch-words)", "translate"),
    # Decoder-compat shims live here, not in _TRAINING: translation /
    # embedding / server modes parse _COMMON+_MODEL+_TRANSLATION only and
    # SystemExit on unknown options, so Marian decoder command lines that
    # carry these must still parse in those modes (ADVICE r3). Training
    # mode also includes this list, so they remain accepted everywhere.
    _f("devices", str, ["0"], "Device ids (GPU compat; the data-parallel decode mesh uses all visible devices)", "translate", "+"),
    _f("num-devices", int, 0, "Cap the data-parallel decode mesh (0 = all visible devices; the batch dim shards over a 'data' mesh — the SPMD equivalent of per-device translator workers)", "translate"),
    _f("optimize", bool, False, "Legacy optimized int16 GEMM switch (no-op; see flag audit)", "translate"),
    _f("model-mmap", bool, False, "Memory-map model loading (no-op; .bin checkpoints are always mmap-loaded)", "translate"),
    _f("fp16", bool, False, "Half-precision shortcut: maps to bfloat16 compute on TPU (fp16's narrow exponent needs loss scaling; bf16 keeps the f32 range)", "translate"),
]

_SCORER = [
    _f("train-sets-scorer", str, [], "(scorer) corpora to score", "scorer", "*"),
    _f("n-best-feature", str, "Score", "Feature name for n-best rescoring", "scorer"),
    _f("summary", str, None, "Summary score: cross-entropy, ce-mean-words, perplexity", "scorer", "?"),
    _f("normalize-scorer", float, 0.0, "(see normalize)", "scorer"),
]

_EMBEDDER = [
    _f("train-sets", str, [], "(embedder) input text stream(s) to embed", "embedder", "*"),
    _f("compute-similarity", bool, False, "(embedder) cosine similarity of two parallel text streams' sentence embeddings instead of printing vectors", "embedder"),
]


MODE_FLAGS: Dict[str, List[Any]] = {
    # training includes the translation group: the translation validator
    # runs beam search with --beam-size/--normalize etc. (reference:
    # config_parser.cpp addOptionsTranslation in training mode)
    "training": _COMMON + _MODEL + _TRAINING + _VALIDATION + _TRANSLATION,
    "translation": _COMMON + _MODEL + _TRANSLATION,
    "scoring": _COMMON + _MODEL + _TRAINING + _SCORER + _TRANSLATION,
    "embedding": _COMMON + _MODEL + _EMBEDDER + _TRANSLATION,
    "vocab": _COMMON,
    "server": _COMMON + _MODEL + _TRANSLATION,
}


def _flag_table(mode: str) -> Dict[str, Any]:
    seen: Dict[str, Any] = {}
    for f in MODE_FLAGS[mode]:
        if f.name not in seen:
            seen[f.name] = f
    return seen


class ConfigParser:
    """parseOptions equivalent. Returns a fully-populated Options."""

    def __init__(self, mode: str = "training"):
        if mode not in MODE_FLAGS:
            raise ValueError(f"Unknown mode '{mode}'")
        self.mode = mode
        self.flags = _flag_table(mode)

    def _build_argparser(self) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(
            prog=f"marian-tpu ({self.mode})", add_help=True, allow_abbrev=False
        )
        for f in self.flags.values():
            arg = f"--{f.name}"
            kwargs: Dict[str, Any] = {"dest": f.name.replace("-", "_"), "default": None}
            if f.type is bool:
                # CLI11-style: bare flag = true, or explicit --flag true/false
                kwargs.update(nargs="?", const=True, type=_parse_bool)
            else:
                kwargs["type"] = f.type
                if f.nargs:
                    kwargs["nargs"] = f.nargs
                    if f.nargs == "?":
                        kwargs["const"] = True if f.type is bool else ""
            p.add_argument(arg, help=f.help, **kwargs)
        return p

    def defaults(self) -> Dict[str, Any]:
        return {f.name: f.default for f in self.flags.values() if f.default is not None}

    def parse(self, argv: Optional[Sequence[str]] = None) -> Options:
        argv = list(sys.argv[1:] if argv is None else argv)
        parser = self._build_argparser()
        ns, unknown = parser.parse_known_args(argv)
        if unknown:
            raise SystemExit(f"Unknown option(s): {' '.join(unknown)}")
        cli: Dict[str, Any] = {
            k.replace("_", "-"): v for k, v in vars(ns).items() if v is not None
        }

        # layer 1: defaults
        merged = self.defaults()

        # layer 2: config file(s)
        explicit = set(cli.keys())       # keys the user actually provided
        for path in _as_list(cli.get("config")):
            with open(path, "r", encoding="utf-8") as fh:
                loaded = yaml.safe_load(fh) or {}
            interp = loaded.get("interpolate-env-vars",
                                cli.get("interpolate-env-vars", False))
            if interp:
                loaded = _interpolate_env_vars(loaded)
            if loaded.get("relative-paths", cli.get("relative-paths", False)):
                loaded = _make_paths_absolute(loaded, os.path.dirname(
                    os.path.abspath(path)))
            for k, v in loaded.items():
                merged[str(k)] = v
                explicit.add(str(k))

        # layer 3: alias expansion (--task / from config), before CLI overrides
        task = cli.get("task", merged.get("task"))
        if task:
            merged = expand_aliases(task, merged)
            merged["task"] = task

        # layer 4: CLI overrides
        for k, v in cli.items():
            if k == "config":
                continue
            merged[k] = v

        if merged.get("no-shuffle"):
            merged["shuffle"] = "none"
        if merged.get("fp16"):
            # --fp16 shortcut (reference: precision float16 float32 +
            # cost-scaling defaults). On TPU fp16's 5-bit exponent would
            # need the whole loss-scaling apparatus; bf16 keeps the f32
            # range, so the shortcut maps there — same memory/matmul
            # savings, no scaling machinery. An explicit --precision wins.
            if "precision" not in explicit:
                merged["precision"] = ["bfloat16", "float32"]
        if str((merged.get("precision") or ["float32"])[0]) in (
                "float16", "fp16", "half"):
            from . import logging as _log
            _log.warn("precision float16 is mapped to bfloat16 on TPU "
                      "(same width, f32 exponent range — no loss scaling "
                      "needed)")
            merged["precision"] = ["bfloat16"] + \
                list(merged["precision"][1:])
        # bare `--output-sampling` (Marian shorthand) = full sampling, temp 1
        if cli.get("output-sampling") == []:
            merged["output-sampling"] = ["full"]
        # bare `--dynamic-gradient-scaling` = factor 2 (same default the
        # YAML `true` spelling gets)
        if cli.get("dynamic-gradient-scaling") == [] \
                or merged.get("dynamic-gradient-scaling") is True:
            merged["dynamic-gradient-scaling"] = ["2"]
        if cli.get("interpolate-env-vars") or merged.get("interpolate-env-vars"):
            merged = _interpolate_env_vars(merged)

        # mode-suffixed duplicates and synonyms → the canonical key runtime
        # code reads (the suffixed names exist because translate/scorer modes
        # share one flag registry with training); config-file values count
        # as explicit too, and the canonical key wins if the user set both
        for alias, (canon, modes, vmap) in _CANONICAL.items():
            if modes is not None and self.mode not in modes:
                continue
            if alias in explicit and canon not in explicit:
                val = merged[alias]
                if vmap is not None:
                    if str(val) not in vmap:
                        raise SystemExit(
                            f"--{alias}: unknown value '{val}' "
                            f"(expected one of {sorted(vmap)})")
                    val = vmap[str(val)]
                merged[canon] = val

        opts = Options(merged)

        for meta in ("authors", "cite", "build-info", "version"):
            if cli.get(meta):
                print(_META_TEXT[meta]())
                raise SystemExit(0)

        dump = cli.get("dump-config") or (True if "dump-config" in cli else None)
        if dump:
            self.dump(opts, mode=dump if isinstance(dump, str) else "full")
            raise SystemExit(0)
        return opts

    def dump(self, opts: Options, mode: str = "full", stream=None) -> None:
        """--dump-config: print effective config as YAML (reference:
        config_parser.cpp dumpConfig)."""
        stream = stream or sys.stdout
        data = opts.as_dict()
        if mode == "minimal":
            defaults = self.defaults()
            data = {k: v for k, v in data.items() if defaults.get(k) != v}
        data.pop("dump-config", None)
        yaml.safe_dump(data, stream, default_flow_style=False, sort_keys=True)


# Mode-suffixed duplicates / synonyms → the canonical key runtime code
# reads: alias → (canonical, applicable modes or None for all, value map or
# None for identity). The mode gate matters: in training mode the
# translate-suffixed names configure the validation decoder only and must
# NOT clobber the training-side canonical keys (e.g. the token budget).
_CANONICAL = {
    "max-length-factor-translate":
        ("max-length-factor", ("translation", "scoring"), None),
    "mini-batch-words-translate":
        ("mini-batch-words", ("translation", "scoring"), None),
    "normalize-scorer": ("normalize", ("scoring",), None),
    "train-sets-scorer": ("train-sets", ("scoring",), None),
    "attention-kernel":
        ("transformer-flash-attention", None,
         {"auto": "auto", "dense": "off", "flash": "on"}),
}

_META_TEXT = {
    "authors": lambda: "marian-tpu contributors (TPU-native rebuild of the "
                       "Marian NMT toolkit; reference authors: Junczys-"
                       "Dowmunt et al., see --cite)",
    "cite": lambda: ("@inproceedings{junczys2018marian,\n"
                     "  title={Marian: Fast Neural Machine Translation in "
                     "C++},\n  author={Junczys-Dowmunt, Marcin and others},\n"
                     "  booktitle={Proceedings of ACL 2018, System "
                     "Demonstrations},\n  year={2018}\n}"),
    "build-info": lambda: _build_info(),
    "version": lambda: "marian-tpu v0.1.0 (jax %s)" % __import__("jax").__version__,
}


def _build_info() -> str:
    import platform
    try:
        import jax
        backend = jax.default_backend()
        jv = jax.__version__
    except Exception:  # pragma: no cover
        backend, jv = "?", "?"
    return (f"marian-tpu 0.1.0; python {platform.python_version()}; "
            f"jax {jv}; backend {backend}")


_ENV_RE = re.compile(r"\$\{([A-Za-z_][A-Za-z0-9_]*)\}")


def _interpolate_env_vars(obj: Any) -> Any:
    """${ENV_VAR} substitution in string config values (reference:
    cli::interpolateEnvVars)."""
    if isinstance(obj, str):
        return _ENV_RE.sub(lambda m: os.environ.get(m.group(1), m.group(0)), obj)
    if isinstance(obj, list):
        return [_interpolate_env_vars(v) for v in obj]
    if isinstance(obj, dict):
        return {k: _interpolate_env_vars(v) for k, v in obj.items()}
    return obj


# Config keys holding filesystem paths, for --relative-paths (reference:
# cli::makeAbsolutePaths / ConfigParser's PATHS list).
_PATH_KEYS = {
    "model", "models", "pretrained-model", "train-sets", "vocabs",
    "valid-sets", "valid-script-path", "valid-translation-output",
    "valid-log", "log", "sqlite", "shortlist", "embedding-vectors",
    "guided-alignment", "data-weighting", "input", "output", "tempdir",
    "ulr-keys-vectors", "ulr-query-vectors", "train-embedder-rank",
}


def _make_paths_absolute(cfg: Dict[str, Any], base: str) -> Dict[str, Any]:
    def fix(v):
        if isinstance(v, str) and v and not os.path.isabs(v) \
                and v not in ("stdin", "stdout", "stderr", "-"):
            return os.path.normpath(os.path.join(base, v))
        return v

    out = dict(cfg)
    for k in _PATH_KEYS & set(out.keys()):
        v = out[k]
        if isinstance(v, list):
            # e.g. shortlist is [path, k, ...]: only fix path-looking strings
            out[k] = [fix(x) if isinstance(x, str) and not str(x).isdigit()
                      else x for x in v]
        else:
            out[k] = fix(v)
    return out


# ---------------------------------------------------------------------------
# Unimplemented-flag audit (reference parity rule: same behavior per flag —
# accept-and-silently-ignore is never allowed; VERDICT r1). Every flag that
# is parsed but has no runtime reader is registered here with an action:
#   warn  — the TPU design makes it unnecessary or a safe no-op; a one-line
#           rationale is logged when the user sets it to a non-default value
#   error — honoring it would require semantics we don't provide; training or
#           decoding would silently differ, so refuse to run
# Implementing a flag removes it from this table (tests assert every parsed
# flag is either read somewhere in the package or listed here).
# ---------------------------------------------------------------------------

UNIMPLEMENTED_FLAGS: Dict[str, tuple] = {
    # -- safe no-ops under the TPU/XLA design --
    "workspace": ("warn", "XLA owns device memory; batch fitting uses the "
                          "bucket table (data/batch_generator.py)"),
    "cpu-threads": ("warn", "host threading is managed by XLA/the runtime"),
    "data-threads": ("warn", "the data pipeline prefetches asynchronously; "
                             "thread count is not user-tunable"),
    "no-nccl": ("warn", "collectives are XLA GSPMD over ICI/DCN, not NCCL"),
    "sync-freq": ("warn", "parameter sync is every step under GSPMD data "
                          "parallelism (no stale local copies exist)"),
    "multi-node-overlap": ("warn", "XLA overlaps collectives with compute "
                                   "automatically"),
    "tempdir": ("warn", "corpus shuffling happens in RAM; no temp files"),
    "log-time-zone": ("warn", "log timestamps use the process-local time "
                              "zone; set TZ in the environment instead"),
    "mini-batch-fit-step": ("warn", "bucketed static shapes replace the "
                                    "binary batch-fitting search"),
    "mini-batch-round-up": ("warn", "bucket table already snaps batch sizes "
                                    "to hardware-friendly multiples"),
    "cost-scaling": ("warn", "bf16 training keeps gradients in f32 master "
                             "range; dynamic loss scaling (an fp16 "
                             "necessity) has nothing to rescue"),
    "fuse": ("warn", "XLA fuses elementwise chains into matmuls "
                     "automatically"),
    "sharding": ("warn", "optimizer state is ZeRO-1 sharded over the full "
                         "'data' mesh axis; there is no node-local NVLink "
                         "domain to restrict to on ICI"),
    "shuffle-in-ram": ("warn", "the corpus always shuffles in RAM"),
    "sqlite": ("warn", "the resumable in-RAM corpus replaces the SQLite "
                       "shuffle database; positions checkpoint in "
                       "progress.yml"),
    "best-deep": ("warn", "s2s depth/variant comes from --type and the "
                          "dim/depth flags directly"),
    "skip-cost": ("warn", "hypothesis scores fall out of the beam at no "
                          "extra cost; there is nothing to skip"),
    "bert-sep-symbol": ("warn", "sentence-pair assembly takes the token "
                                "streams as given; separators are not "
                                "re-inserted by the pipeline"),
    "bert-class-symbol": ("warn", "classifier pooling uses the first "
                                  "position; the symbol itself is not "
                                  "re-inserted by the pipeline"),
    "ulr-dim-emb": ("warn", "the ULR query dimension is taken from the "
                            "key-vectors file, not this flag"),
    "interpolate-env-vars": ("none", "handled at config load"),
    "relative-paths": ("none", "handled at config load"),
    "fp16": ("none", "handled at config load (maps to bfloat16 precision)"),
    "sqlite-drop": ("warn", "the resumable in-RAM corpus replaces the "
                            "SQLite shuffle database; there is nothing "
                            "to drop"),
    "diverged-after": ("warn", "fp16 divergence recovery does not apply: "
                               "bf16 keeps the f32 exponent range; use "
                               "--check-gradient-nan + --on-divergence "
                               "rollback (in-process self-heal) or throw"),
    "custom-fallbacks": ("warn", "fp16 fallback machinery does not apply "
                                 "to bf16 training"),
    "fp16-fallback-to-fp32": ("warn", "fp16 fallback machinery does not "
                                      "apply to bf16 training"),
    "recover-from-fallback-after": ("warn", "fp16 fallback machinery does "
                                           "not apply to bf16 training"),
    "overwrite-checkpoint": ("warn", "checkpoint rotation is governed by "
                                     "--overwrite (.iterN copies)"),
    "clip-gemm": ("warn", "legacy intgemm clipping; XLA int8 GEMMs "
                          "quantize with per-channel scales instead"),
    "optimize": ("warn", "legacy int16 GEMM switch; use an int8 "
                         "marian-conv checkpoint for quantized decode"),
    "model-mmap": ("warn", ".bin checkpoints are always mmap-loaded; "
                           ".npz loads copy (convert with marian-conv "
                           "for mmap)"),
    "mini-batch-track-optimum": ("warn", "bucketed static batch shapes "
                                         "replace dynamic batch-size "
                                         "tracking"),
    "lemma-dependency": ("warn", "factor prediction is lemma-conditioned "
                                 "via --lemma-dim-emb soft re-embedding "
                                 "(layers/logits.py); the reference's "
                                 "per-mechanism selector is collapsed "
                                 "into that one implementation"),
    # -- would silently change training/decoding semantics: refuse --
    "transformer-pool": ("error", "pooled attention variant is not "
                                  "implemented"),
    "train-embedder-rank": ("error", "margin-based embedder-rank training "
                                     "is not implemented (semantics "
                                     "unverifiable against the empty "
                                     "reference mount)"),
}


def audit_flags(opts: Options, parser: "ConfigParser") -> None:
    """Warn or refuse for parsed-but-unimplemented flags the user actually
    set (compared against the registry defaults)."""
    from . import logging as log
    for name, spec in UNIMPLEMENTED_FLAGS.items():
        f = parser.flags.get(name)
        if f is None or not opts.has(name):
            continue
        val = opts.get(name)
        if val == f.default or val in (None, [], False, "", 0, 0.0):
            continue
        action = spec[0]
        if action == "none":
            continue
        if action == "error-unless":
            allowed, why = spec[1], spec[2]
            if val == allowed:
                continue
            raise ValueError(f"--{name} {val}: {why} is supported")
        why = spec[1]
        if action == "error":
            raise ValueError(
                f"--{name} is accepted for Marian config compatibility but "
                f"its semantics are not implemented ({why}); refusing to "
                f"silently ignore it")
        log.warn("--{} has no effect on TPU: {}", name, why)


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).lower() in ("1", "true", "yes", "on")


def _as_list(v: Any) -> List[Any]:
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v]


def parse_options(argv: Optional[Sequence[str]] = None, mode: str = "training",
                  validate: bool = True) -> Options:
    """Module-level convenience mirroring ConfigParser::parseOptions."""
    parser = ConfigParser(mode)
    opts = parser.parse(argv)
    if validate:
        from .config_validator import validate_options
        validate_options(opts, mode)
        audit_flags(opts, parser)
    return opts
