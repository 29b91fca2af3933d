"""Scaled dot-product attention — the MXU hot path.

The reference implements attention as strided-batched cuBLAS GEMMs +
masked-softmax kernels (src/tensors/gpu/prod.cpp :: ProdBatched,
src/models/transformer.h :: MultiHead). Here the dense path is einsum-based
(XLA maps it straight onto the MXU and fuses mask+softmax); a Pallas
flash-attention kernel (ops/pallas/flash_attention.py) takes over for long
sequences where the O(L²) score tensor would blow HBM bandwidth.

Shapes are batch-major: q [B, H, Tq, Dh], k/v [B, Hkv, Tk, Dh] with H a
multiple of Hkv (grouped-query heads), mask [B, 1, Tq, Tk] (1 = attend).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .ops import NEG_INF, dropout as _dropout


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_key: Optional[jax.Array] = None,
                    deterministic: bool = True) -> jax.Array:
    """Returns ([B, H, Tq, Dh] context, attention weights are not returned;
    use dense_attention_with_weights when alignments are needed)."""
    out, _ = dense_attention_with_weights(
        q, k, v, mask, dropout_rate, dropout_key, deterministic,
        return_weights=False)
    return out


def dense_attention_with_weights(q, k, v, mask=None, dropout_rate=0.0,
                                 dropout_key=None, deterministic=True,
                                 return_weights=True):
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32)).astype(q.dtype)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                        preferred_element_type=jnp.float32)
    if mask is not None:
        scores = scores + (1.0 - mask.astype(scores.dtype)) * NEG_INF
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and not deterministic:
        weights = _dropout(weights, dropout_rate, dropout_key)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out, (weights if return_weights else None)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              mask: Optional[jax.Array] = None,
              kv_mask: Optional[jax.Array] = None,
              causal=False,
              dropout_rate: float = 0.0,
              dropout_key: Optional[jax.Array] = None,
              deterministic: bool = True,
              return_weights: bool = False,
              flash: str = "auto",
              flash_min_len: Optional[int] = None,
              packed: str = "auto",
              packed_max_len: Optional[int] = None):
    """Attention dispatcher: dense (XLA-fused einsum) vs the two Pallas
    kernels — flash (long sequences) and head-packed (only when forced).

    `mask` is the general [B,1,Tq,Tk] dense mask; `kv_mask` [B,Tk] + `causal`
    is the structured form both Pallas kernels understand. Callers that can,
    pass both. `causal` is a RULE over (query index, key index), as
    flash_attention.py lists them: False, True (the future mask), a
    flash_attention.BlockDiffusion (block-diffusion training over a
    doubled row) or a flash_attention.Window (the last W keys up to the
    query's own). The flash kernels take the rule; for the last two the
    dense path builds the rule's mask here (`block_diffusion_mask`,
    `window_mask`), and `mask` may then be left out. Key/value heads shared by a group of
    query heads are read in place by flash and repeated for the dense path.
    A kernel is picked when it is (a) allowed (its gate = auto|on),
    (b) applicable (no returned weights, no active attention dropout, a
    structured mask describing the dense one, multi-query step), and (c) for
    "auto", worth it on its regime: flash when the sequence is long enough
    that streaming K/V blocks beats one fused dense batch matmul (crossover
    measured on v5e ~1-2k). Below it `auto` is the dense einsum on every
    backend: the packed kernel costs a tile's latency whatever it holds and
    lost to the einsum at every short shape read on a v5e, alone and in the
    train step (the note under the flash return has the readings), so
    packed="on" alone selects it, up to the auto-tuner's cap; it stays for
    its tests. Flash owns the overlap: its gate is checked first."""
    if flash_min_len is None:
        # default crossover; --auto-tune rebinds it (ops/auto_tuner.py)
        from .auto_tuner import flash_threshold
        flash_min_len = flash_threshold()
    applicable = (
        not return_weights
        and (deterministic or dropout_rate == 0.0)
        and q.shape[-2] > 1
        and (kv_mask is not None or causal or mask is None))
    if applicable and flash != "off" and (
            flash == "on" or max(q.shape[-2], k.shape[-2]) >= flash_min_len):
        from .pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, kv_mask=kv_mask, causal=causal), None
    from .pallas.flash_attention import BlockDiffusion, Window
    if isinstance(causal, (BlockDiffusion, Window)):
        packed = "off"
        see = _rule_as_mask(causal, q.shape[-2])
        if kv_mask is not None:
            see = see * kv_mask[:, None, None, :].astype(see.dtype)
        mask = combine_masks(mask, see)
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    # Short sequences, read on a v5e (PRs 31, 52; docs/PERFORMANCE.md):
    # the einsum below beat the packed kernel 2.3-5.5 x forward + backward
    # at 4096 words in widths 8-64, at 32 x 128 and at 8 heads of 32
    # (scripts/attn_microbench.py), and by 8.3 % of big.train's rate in
    # the step, so no shape class selects the kernel: packed="on" does.
    if applicable and packed == "on":
        from .auto_tuner import packed_attention_max_t
        cap = (packed_max_len if packed_max_len is not None
               else packed_attention_max_t(q.shape[-1]))
        if max(q.shape[-2], k.shape[-2]) <= cap:
            from .pallas.packed_attention import packed_attention
            return packed_attention(q, k, v, kv_mask=kv_mask,
                                    causal=causal), None
    return dense_attention_with_weights(
        q, k, v, mask, dropout_rate, dropout_key, deterministic,
        return_weights)


def causal_mask(length: int, dtype=jnp.float32) -> jax.Array:
    """[1, 1, T, T] future mask (reference: transformer.h triangle mask)."""
    m = jnp.tril(jnp.ones((length, length), dtype=dtype))
    return m[None, None, :, :]


def block_diffusion_mask(length: int, block: int, dtype=jnp.float32
                         ) -> jax.Array:
    """[1, 1, 2T, 2T]: flash_attention.BlockDiffusion(length, block) as an
    array, for the dense path and for tests (1 = the query sees the key)."""
    from .pallas.flash_attention import BlockDiffusion
    return _rule_as_mask(BlockDiffusion(length, block), 2 * length, dtype)


def window_mask(length: int, window: int, dtype=jnp.float32) -> jax.Array:
    """[1, 1, T, T]: flash_attention.Window(window) as an array, for the
    dense path and for tests (1 = the query sees the key: the `window`
    keys that end at its own)."""
    from .pallas.flash_attention import Window
    return _rule_as_mask(Window(window), length, dtype)


def _rule_as_mask(rule, n: int, dtype=jnp.float32) -> jax.Array:
    """[1, 1, n, n]: a flash_attention rule over n indices as an array."""
    from .pallas.flash_attention import rule_mask
    pos = jnp.arange(n, dtype=jnp.int32)
    return rule_mask(rule, pos[:, None], pos[None, :]).astype(dtype)[
        None, None, :, :]


def combine_masks(*masks: Optional[jax.Array]) -> Optional[jax.Array]:
    out = None
    for m in masks:
        if m is None:
            continue
        out = m if out is None else out * m
    return out
