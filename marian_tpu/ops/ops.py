"""Core tensor ops. The reference implements these as hand-written CUDA
kernels (src/tensors/gpu/tensor_operators.cu, element.cu, add_all.cu); here
each is a few lines of jnp that XLA fuses into the surrounding computation —
the per-node kernel dispatch the reference does at runtime collapses into one
compiled program (SURVEY.md §2.3/§2.4).

Numerics conventions kept from the reference:
- layer_norm uses epsilon inside sqrt(var + eps) (gpu::LayerNormalization);
- dropout uses inverted scaling (mask / keep_prob) with explicit PRNG keys
  (the reference's cuRAND bernoulli nodes become functional masks);
- masked softmax adds a large negative to masked logits pre-softmax.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # large-negative mask value; safe in bf16 (min normal ~ -3.4e38)


def named_scope(name):
    """Run the function under ``jax.named_scope(name)`` (`name` may be a
    function of the call's arguments). Metadata only: every op traced
    inside carries the scope in its HLO ``op_name``, which is how a
    profile attributes device time to encoder/decoder/ffn/...; the
    compiled program is the same. No layer index goes into a name:
    scanned layers have none, and like ops must add up."""
    def deco(fn):
        @functools.wraps(fn)
        def in_scope(*args, **kwargs):
            with jax.named_scope(name(*args, **kwargs) if callable(name)
                                 else name):
                return fn(*args, **kwargs)
        return in_scope
    return deco


def layer_norm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array] = None,
               eps: float = 1e-9) -> jax.Array:
    """LayerNorm over the last axis (reference: gpu::LayerNormalization;
    Marian's default eps is 1e-9)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array] = None,
             eps: float = 1e-9) -> jax.Array:
    """RMSNorm (reference: rmsNorm in expression_operators.cpp)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution along time: x [B, T, D], w [K, D];
    y_t = sum_j w[j] * x_{t-(K-1)+j}, so w[K-1] weighs the current token
    and positions before the first count as zero. What the layer plan's
    `kda` half runs on q, k and v, and the whole of its `conv` half's
    mixing along time (models/layer_plan.py)."""
    k = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t, :] * w[j] for j in range(k))


def dropout(x: jax.Array, rate: float, key: Optional[jax.Array],
            deterministic: bool = False) -> jax.Array:
    """Inverted dropout with explicit key (reference: dropout nodes backed by
    cuRAND bernoulli; PRNG-key discipline replaces device RNG state)."""
    if deterministic or rate <= 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x)


def swish(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


ACTIVATIONS = {
    "relu": jax.nn.relu,
    "swish": swish,
    "gelu": gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


def activation(name: str):
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"Unknown activation '{name}'") from None


@jax.custom_vjp
def logits_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w emitting f32 (softmax/CE wants f32 logits) whose BACKWARD
    GEMMs run at the bf16 MXU rate.

    Without this, the [.., V] f32 logits cotangent forces the two VJP
    transpose dots (dx and dW — the largest GEMMs in the whole step, V
    = 32k wide) to run as f32xf32 matmuls: ~1/4 the MXU rate on v5e.
    The r5 HLO audit (scripts/audit_backward_dots.py) measured exactly
    two f32xf32 dots at 10.4% of step FLOPs ≈ ~30% of ideal step time —
    the bulk of VERDICT r4's "backward GEMMs at ~20% of roofline".

    The fix: round the cotangent to the compute dtype (bf16) once,
    then both backward dots are bf16xbf16 with f32 MXU accumulation.
    One extra rounding of the gradient signal (~2^-9 relative) against
    a 4x throughput win on the step's biggest GEMMs — the standard
    mixed-precision discipline (grads round through bf16 anyway
    wherever they cross a cast_params boundary).

    NOTE: this cotangent rounding follows the COMPUTE dtype (x.dtype)
    and applies regardless of --gradient-dtype — with bf16 compute,
    ``--gradient-dtype float32`` still sees the logits cotangent round
    through bf16 here (the flag only controls the dtype gradients are
    STORED/reduced in downstream). Documented in the --gradient-dtype
    help and docs/PERFORMANCE.md.

    x: [.., d] compute dtype; w: [d, V] compute dtype. Out: [.., V] f32.
    """
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _logits_matmul_fwd(x, w):
    return logits_matmul(x, w), (x, w)


def _logits_matmul_bwd(res, g):
    x, w = res
    g16 = g.astype(x.dtype)
    dx = jax.lax.dot_general(g16, w, (((g16.ndim - 1,), (1,)), ((), ())),
                             preferred_element_type=x.dtype)
    lead = tuple(range(x.ndim - 1))
    dw = jax.lax.dot_general(x, g16, ((lead, lead), ((), ())),
                             preferred_element_type=jnp.float32)
    return dx, dw.astype(w.dtype)


logits_matmul.defvjp(_logits_matmul_fwd, _logits_matmul_bwd)


def affine(x: jax.Array, w, b: Optional[jax.Array] = None) -> jax.Array:
    """x @ w + b (reference: gpu::Affine / cublasLt fused bias). XLA fuses the
    bias add; weights stored [in, out] like Marian. Quantized (QTensor)
    weights from marian-conv run as int8×int8 MXU matmuls."""
    from .quantization import QTensor, int8_affine
    if isinstance(w, QTensor):
        return int8_affine(x, w, b)
    y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def masked_log_softmax(logits: jax.Array, mask: Optional[jax.Array] = None,
                       axis: int = -1) -> jax.Array:
    if mask is not None:
        logits = jnp.where(mask > 0, logits, NEG_INF)
    return jax.nn.log_softmax(logits, axis=axis)


def masked_softmax(logits: jax.Array, mask: Optional[jax.Array] = None,
                   axis: int = -1) -> jax.Array:
    """Softmax with additive log-mask (reference: gpu::Softmax with mask).
    The mask is pinned to the logits dtype before the arithmetic: masks
    are routinely built f32 (causal_mask's default), and an f32 mask would
    silently promote the whole bf16 softmax chain (mtlint MT-DTYPE-LITERAL;
    0/1 mask values are exact in every dtype, so the cast is lossless)."""
    if mask is not None:
        logits = logits + (1.0 - mask.astype(logits.dtype)) * NEG_INF
    return jax.nn.softmax(logits, axis=axis)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  label_smoothing: float = 0.0) -> jax.Array:
    """Per-position CE with Marian's label smoothing (reference:
    gpu::CrossEntropyPick + layers/loss.cpp):
      ce = (1-eps) * -logP(label) - eps * mean_v logP(v)
    computed in f32 regardless of logit dtype."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    if label_smoothing > 0.0:
        smooth = -jnp.mean(logp, axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return nll


def global_norm(tree) -> jax.Array:
    """L2 norm over a pytree of grads (reference: clippers.cpp norm over the
    flat gradient arena)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_by_global_norm(tree, max_norm: float, norm: Optional[jax.Array] = None):
    if max_norm <= 0:
        return tree
    if norm is None:
        norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-8))
    return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), tree)
