"""Factored output combination and factored embedding composition.

Rebuild of reference src/layers/logits.cpp :: Logits (group-wise factored
softmax) and the factored path of src/layers/embedding.cpp. The reference
keeps one logits tensor per factor group and combines them lazily; under
XLA we compute the unit-axis scores in ONE matmul (all groups share the
output matrix over the unit axis), take a log-softmax per group slice, and
gather-sum back to word space — fully fused, static shapes.

Semantics (same as Marian): P(word) = P(lemma) * Π_g P(factor_g(word)),
each distribution normalized within its own group; absent factors (PAD
unit) contribute log-prob 0.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ops import named_scope


@dataclasses.dataclass(eq=False)
class FactorTables:
    """Static per-vocab factor metadata closed over by the jitted model.
    Built from data.factored_vocab.FactoredVocab."""
    n_units: int
    n_lemmas: int
    pad_unit: int
    factor_indices: np.ndarray                 # [V, K] int32 (K = 1+groups)
    group_slices: Tuple[Tuple[str, int, int], ...]

    @classmethod
    def from_vocab(cls, vocab) -> "FactorTables":
        return cls(n_units=vocab.n_units, n_lemmas=vocab.n_lemmas,
                   pad_unit=vocab.pad_unit,
                   factor_indices=np.asarray(vocab.factor_indices, np.int32),
                   group_slices=vocab.group_slices)

    @property
    def vocab_size(self) -> int:
        return self.factor_indices.shape[0]


def factored_embed(table: jax.Array, ft: FactorTables,
                   ids: jax.Array, dtype) -> jax.Array:
    """emb(word) = emb(lemma) + Σ_g emb(factor_g) (reference: factored
    embedding composition). `table` is [n_units, D]; PAD contributions are
    masked out (no trainable PAD bias)."""
    idx = jnp.asarray(ft.factor_indices)[ids]          # [..., K]
    gathered = table[idx].astype(dtype)                # [..., K, D]
    mask = (idx != ft.pad_unit)[..., None].astype(dtype)
    return (gathered * mask).sum(axis=-2)              # [..., D]


def factored_embed_concat(lemma_table: jax.Array, factor_table: jax.Array,
                          ft: FactorTables, ids: jax.Array,
                          dtype) -> jax.Array:
    """--factors-combine concat (reference: src/layers/embedding.cpp
    concatenative composition): emb(word) = [emb(lemma);
    emb(factor_1); ...; emb(factor_G)] with a (dim_emb - G*f)-wide lemma
    table and f-wide per-factor vectors. `factor_table` rows are the factor
    units in unit order with the PAD unit as its LAST row; absent factors
    contribute a zero block (masked, no trainable PAD bias)."""
    idx = jnp.asarray(ft.factor_indices)[ids]              # [..., K]
    parts = [lemma_table[idx[..., 0]].astype(dtype)]       # lemma column
    for kcol in range(1, idx.shape[-1]):
        u = idx[..., kcol] - ft.n_lemmas                   # factor-row index
        mask = (idx[..., kcol] != ft.pad_unit)[..., None].astype(dtype)
        parts.append(factor_table[u].astype(dtype) * mask)
    return jnp.concatenate(parts, axis=-1)


@named_scope("output")
def factored_log_probs(unit_logits: jax.Array, ft: FactorTables,
                       shortlist: Optional[jax.Array] = None,
                       factor_weight: float = 1.0) -> jax.Array:
    """[..., n_units] unit scores → [..., V] word log-probs.

    Per-group log-softmax over each unit slice, then for every word sum the
    log-probs of its units (reference: Logits::getLoss /
    Logits::getLogits combination). With a shortlist, only the shortlisted
    words' rows of the index table are gathered (output [..., K_sl]).
    `factor_weight` (--factor-weight) scales the non-lemma groups'
    contributions (reference: Logits applying factorWeight_)."""
    pieces = []
    for gi, (_name, start, end) in enumerate(ft.group_slices):
        lp = jax.nn.log_softmax(unit_logits[..., start:end], axis=-1)
        if gi > 0 and factor_weight != 1.0:    # group 0 is the lemma
            lp = lp * factor_weight
        pieces.append(lp)
    # PAD unit (last) gets log-prob 0 so absent factors are no-ops
    logp = jnp.concatenate(
        pieces + [jnp.zeros_like(unit_logits[..., -1:])], axis=-1)

    idx_tbl = jnp.asarray(ft.factor_indices)           # [V, K]
    if shortlist is not None:
        idx_tbl = idx_tbl[shortlist]                   # [K_sl, K]
    out = None
    # accumulate per factor column: peak memory [..., V], not [..., V, K]
    for k in range(idx_tbl.shape[1]):
        contrib = jnp.take(logp, idx_tbl[:, k], axis=-1)
        out = contrib if out is None else out + contrib
    return out
