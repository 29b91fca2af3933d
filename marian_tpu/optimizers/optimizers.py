"""Optimizers with Marian's exact semantics (reference:
src/optimizers/optimizers.cpp :: Adam::updateImpl, Adagrad, Sgd;
src/optimizers/exponential_smoothing.h).

Implemented as pure (state, grads) → (state, params) transforms over the
flat param dict, optax-style but hand-rolled so the update math matches the
reference line-for-line:

- Adam with bias correction (denominators 1-beta^t), epsilon INSIDE the
  sqrt-denominator addition, and optional --mini-batch-words-ref scaling of
  lr/eps (OptimizerBase::update's refMBWords logic);
- global-norm clipping computed over the FULL gradient before the shard
  update (GraphGroup order: clip → update), see training/graph_group.py;
- exponential smoothing of params (EMA swapped in for validation/decode).

State arrays are f32 regardless of compute dtype (the reference keeps
optimizer state in fp32 even for fp16 training). Under ZeRO-1 the state trees
carry PartitionSpec('data') while params are replicated (SURVEY.md §2.7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]


@dataclasses.dataclass
class OptimizerConfig:
    name: str = "adam"                 # adam | adagrad | sgd
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    clip_norm: float = 1.0             # 0 = off  (--clip-norm)
    smoothing: float = 0.0             # --exponential-smoothing
    ref_mb_words: int = 0              # --mini-batch-words-ref
    # train-time compression (optimizers/compression.py)
    quantize_bits: int = 0             # --quantize-bits (0 = off)
    quantize_log: bool = False         # --quantize-log-based
    quantize_biases: bool = False      # --quantize-biases
    quantize_opt_steps: int = 0        # --quantize-optimization-steps
    quantize_range: float = 0.0        # --quantize-range (clip at N stddevs)
    grad_drop_rate: float = 0.0        # --gradient-dropping-rate (0 = off)
    # --optimizer-state-dtype: storage dtype of Adam's FIRST moment only
    # (optax mu_dtype precedent). bfloat16 halves m's HBM footprint and
    # per-step read/write traffic; the math still runs in f32 and the
    # second moment v stays f32 (its sqrt sits in the update denominator,
    # where bf16's 8 mantissa bits would bite). Beyond the reference.
    state_dtype: str = "float32"       # float32 | bfloat16
    # --normalize-gradient: additionally divide gradients by the batch's
    # target-word count (reference: SyncGraphGroup multiplies the update
    # normalizer by updateTrgWords when the flag is set)
    normalize_gradient: bool = False
    # --check-gradient-nan: skip the ENTIRE update (params + optimizer
    # state unchanged) when the gradient norm is non-finite (reference:
    # GraphGroup checkGradientNan); metrics carry skipped=1
    check_gradient_nan: bool = False
    # --dynamic-gradient-scaling FACTOR [log]: track a windowed average
    # of the (log-)gradient norm; when a step's norm exceeds
    # factor x average, scale the gradient down to that threshold
    # (reference: costScaling/dynamic gradient scaling in
    # training/graph_group.cpp — outlier-step protection)
    dyn_scale_factor: float = 0.0      # 0 = off
    dyn_scale_log: bool = False
    norm_window: int = 100             # --gradient-norm-average-window

    @classmethod
    def from_options(cls, options) -> "OptimizerConfig":
        params = [float(x) for x in options.get("optimizer-params", []) or []]
        name = options.get("optimizer", "adam")
        cfg = cls(name=name,
                  clip_norm=float(options.get("clip-norm", 1.0) or 0.0),
                  smoothing=float(options.get("exponential-smoothing", 0.0) or 0.0),
                  ref_mb_words=int(options.get("mini-batch-words-ref", 0) or 0),
                  quantize_bits=int(options.get("quantize-bits", 0) or 0),
                  quantize_log=bool(options.get("quantize-log-based", False)),
                  quantize_biases=bool(options.get("quantize-biases", False)),
                  quantize_opt_steps=int(
                      options.get("quantize-optimization-steps", 0) or 0),
                  quantize_range=float(
                      options.get("quantize-range", 0.0) or 0.0),
                  grad_drop_rate=float(
                      options.get("gradient-dropping-rate", 0.0) or 0.0),
                  state_dtype=str(options.get("optimizer-state-dtype",
                                              "float32") or "float32"),
                  normalize_gradient=bool(
                      options.get("normalize-gradient", False)),
                  check_gradient_nan=bool(
                      options.get("check-gradient-nan", False)),
                  norm_window=int(
                      options.get("gradient-norm-average-window", 100)
                      or 100))
        dyn = options.get("dynamic-gradient-scaling", []) or []
        if dyn is True:
            dyn = ["2"]
        if isinstance(dyn, (str, int, float)):
            dyn = [dyn]
        if dyn:
            cfg.dyn_scale_factor = float(dyn[0])
            cfg.dyn_scale_log = any(str(v).lower() == "log"
                                    for v in dyn[1:])
        if cfg.state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"--optimizer-state-dtype {cfg.state_dtype}: expected "
                f"float32 or bfloat16")
        if name == "adam":
            if len(params) > 0:
                cfg.beta1 = params[0]
            if len(params) > 1:
                cfg.beta2 = params[1]
            if len(params) > 2:
                cfg.eps = params[2]
        elif name == "adagrad" and params:
            cfg.eps = params[0]
        return cfg


def init_state(cfg: OptimizerConfig, params: Params) -> Dict[str, Any]:
    zeros_like = lambda: {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    st: Dict[str, Any] = {"t": jnp.zeros((), jnp.float32)}
    if cfg.name == "adam":
        m_dtype = jnp.dtype(cfg.state_dtype)
        st["m"] = {k: jnp.zeros(v.shape, m_dtype)
                   for k, v in params.items()}
        st["v"] = zeros_like()
    elif cfg.name == "adagrad":
        st["gt"] = zeros_like()
    elif cfg.name != "sgd":
        raise ValueError(f"Unknown optimizer '{cfg.name}'")
    if cfg.smoothing > 0:
        # copy=True: astype on an f32 array is a no-op alias, and aliasing
        # params here makes jit buffer donation see the same buffer twice
        st["avg"] = {k: jnp.array(v, dtype=jnp.float32, copy=True)
                     for k, v in params.items()}
    if cfg.quantize_bits > 0:     # quantization error feedback (quantizer.cpp)
        st["qerr"] = {k: jnp.zeros(v.shape, jnp.float32)
                      for k, v in params.items()}
    if cfg.grad_drop_rate > 0:    # gradient-dropping residual (DGC)
        st["gerr"] = {k: jnp.zeros(v.shape, jnp.float32)
                      for k, v in params.items()}
    if cfg.dyn_scale_factor > 0:  # --dynamic-gradient-scaling statistics
        st["gstat"] = {"avg": jnp.zeros((), jnp.float32),
                       "n": jnp.zeros((), jnp.float32)}
    return st


def apply_update(cfg: OptimizerConfig, state: Dict[str, Any], params: Params,
                 grads: Params, lr: jax.Array,
                 mb_words: Optional[jax.Array] = None
                 ) -> Tuple[Dict[str, Any], Params]:
    """One optimizer step. `mb_words` enables Marian's reference-batch LR
    scaling (Adam::updateImpl multiplies lr and eps by T/Tref)."""
    t = state["t"] + 1.0
    new_state: Dict[str, Any] = {"t": t}
    lr = jnp.asarray(lr, jnp.float32)
    eps = cfg.eps
    if cfg.ref_mb_words and mb_words is not None:
        ratio = mb_words.astype(jnp.float32) / float(cfg.ref_mb_words)
        lr = lr * ratio
        eps = eps * ratio

    if cfg.grad_drop_rate > 0:
        # DGC-style sparsification with error feedback (reference:
        # training/gradient_dropping/; warmup ramps the rate via t)
        from .compression import drop_gradients
        grads, new_state["gerr"] = drop_gradients(
            grads, state["gerr"], cfg.grad_drop_rate)

    out: Params = {}
    if cfg.name == "adam":
        with jax.named_scope("adam"):     # metadata only (profiles)
            bc1 = 1.0 - jnp.power(cfg.beta1, t)
            bc2 = 1.0 - jnp.power(cfg.beta2, t)
            m_new, v_new = {}, {}
            m_dtype = jnp.dtype(cfg.state_dtype)
            for k, p in params.items():
                g = grads[k].astype(jnp.float32)
                m = cfg.beta1 * state["m"][k].astype(jnp.float32) \
                    + (1.0 - cfg.beta1) * g
                v = cfg.beta2 * state["v"][k] + (1.0 - cfg.beta2) * jnp.square(g)
                m_new[k], v_new[k] = m.astype(m_dtype), v
                mhat = m / bc1
                vhat = v / bc2
                out[k] = (p.astype(jnp.float32)
                          - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
        new_state["m"], new_state["v"] = m_new, v_new
    elif cfg.name == "adagrad":
        gt_new = {}
        for k, p in params.items():
            g = grads[k].astype(jnp.float32)
            gt = state["gt"][k] + jnp.square(g)
            gt_new[k] = gt
            out[k] = (p.astype(jnp.float32)
                      - lr * g / (jnp.sqrt(gt) + eps)).astype(p.dtype)
        new_state["gt"] = gt_new
    else:  # sgd
        for k, p in params.items():
            out[k] = (p.astype(jnp.float32)
                      - lr * grads[k].astype(jnp.float32)).astype(p.dtype)

    if cfg.quantize_bits > 0:
        # train-time model quantization with error feedback (quantizer.cpp);
        # runs before EMA so the smoothed params track the quantized model
        from .compression import quantize_model
        out, new_state["qerr"] = quantize_model(
            out, state["qerr"], cfg.quantize_bits, cfg.quantize_log,
            cfg.quantize_opt_steps, cfg.quantize_biases,
            qrange=cfg.quantize_range)

    if cfg.smoothing > 0:
        # reference ExponentialSmoothing: avg += tau * (p - avg), with tau
        # effectively scaled by batch size when using labels-based decay; we
        # use the plain per-update form.
        tau = cfg.smoothing
        with jax.named_scope("ema"):
            new_state["avg"] = {
                k: state["avg"][k]
                + tau * (out[k].astype(jnp.float32) - state["avg"][k])
                for k in params}
    if "gstat" in state:
        # dynamic-gradient-scaling statistics are updated by the caller
        # (zero.py step_fn, which owns the gradient norm) — pass through
        new_state["gstat"] = state["gstat"]
    return new_state, out


def smoothed_params(cfg: OptimizerConfig, state: Dict[str, Any],
                    params: Params) -> Params:
    """Return EMA params for validation/decoding (reference: swapParams)."""
    if cfg.smoothing > 0 and "avg" in state:
        return {k: state["avg"][k].astype(params[k].dtype) for k in params}
    return params
