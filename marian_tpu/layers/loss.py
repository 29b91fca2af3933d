"""Loss assembly: cross-entropy with label smoothing, cost-type
normalization, guided-alignment aux loss, data weighting.

Rebuild of reference src/layers/loss.cpp :: CrossEntropyLoss/RationalLoss/
MultiRationalLoss and src/layers/guided_alignment.cpp. A loss is carried as
(sum, label_count) — Marian's "rational loss" — so ce-sum / ce-mean /
ce-mean-words / perplexity are different finalizations of the same pair.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.ops import cross_entropy, named_scope


@dataclasses.dataclass
class RationalLoss:
    loss_sum: jax.Array   # scalar f32
    labels: jax.Array     # scalar f32 (real target labels in batch)

    def value(self, cost_type: str = "ce-sum") -> jax.Array:
        if cost_type in ("ce-sum", "ce-rescore"):
            return self.loss_sum
        if cost_type == "ce-mean-words":
            return self.loss_sum / jnp.maximum(self.labels, 1.0)
        if cost_type == "perplexity":
            return jnp.exp(self.loss_sum / jnp.maximum(self.labels, 1.0))
        if cost_type == "ce-mean":
            # per-sentence mean is handled by caller passing sentence count
            return self.loss_sum / jnp.maximum(self.labels, 1.0)
        raise ValueError(f"Unknown cost-type {cost_type}")


@named_scope("loss")
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array, label_smoothing: float = 0.0,
                       data_weights: Optional[jax.Array] = None,
                       unlikelihood: bool = False) -> RationalLoss:
    """logits [B,T,V], labels [B,T], mask [B,T] → summed CE over real tokens.

    unlikelihood (--unlikelihood-loss, reference: layers/loss.h ::
    SequenceUnlikelihoodLoss): the sign of the data weight selects the
    objective per token — weight > 0 trains likelihood (-w·log p), weight
    < 0 trains UNlikelihood (-|w|·log(1-p)), pushing probability away from
    tokens marked as negative evidence."""
    w = mask.astype(jnp.float32)
    if unlikelihood and data_weights is not None:
        dw = jnp.broadcast_to(data_weights.astype(jnp.float32), w.shape)
        pos = dw >= 0
        ce_like = cross_entropy(logits, labels, label_smoothing)      # [B,T]
        logp = -cross_entropy(logits, labels, 0.0)                    # log p
        # log(1-p) = log1p(-exp(logp)), clamped away from logp==0
        log1mp = jnp.log1p(-jnp.exp(jnp.minimum(logp, -1e-6)))
        ce = jnp.where(pos, ce_like, -log1mp)
        w = w * jnp.abs(dw)
        return RationalLoss(jnp.sum(ce * w),
                            jnp.sum(mask.astype(jnp.float32)))
    ce = cross_entropy(logits, labels, label_smoothing)  # [B,T] f32
    if data_weights is not None:
        w = w * jnp.broadcast_to(data_weights.astype(jnp.float32), w.shape)
    return RationalLoss(jnp.sum(ce * w), jnp.sum(mask.astype(jnp.float32)))


def guided_alignment_loss(attn: jax.Array, guided: jax.Array,
                          trg_mask: jax.Array, cost_type: str = "ce",
                          eps: float = 1e-6) -> jax.Array:
    """attn, guided: [B, Tt, Ts] (normalized rows); per-token CE between
    soft attention and the guided alignment (reference:
    guided_alignment.cpp :: guidedAlignmentCost)."""
    a = attn.astype(jnp.float32)
    g = guided.astype(jnp.float32)
    if cost_type == "ce":
        per_tok = -jnp.sum(g * jnp.log(a + eps), axis=-1)
    elif cost_type == "mse":
        per_tok = 0.5 * jnp.sum(jnp.square(a - g), axis=-1)
    elif cost_type == "mult":
        per_tok = -jnp.log(jnp.sum(a * g, axis=-1) + eps)
    else:
        raise ValueError(f"Unknown guided-alignment-cost {cost_type}")
    # only count target positions that have at least one alignment point
    has_pt = (jnp.sum(g, axis=-1) > 0).astype(jnp.float32) * trg_mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(has_pt), 1.0)
    return jnp.sum(per_tok * has_pt) / n
