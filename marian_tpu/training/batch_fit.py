"""Automated batch fitting (--mini-batch-fit): find the largest
--mini-batch-words token budget whose worst-case bucketed batch trains
without exhausting device memory.

Reference: src/training/graph_group.h :: GraphGroup::collectStats — Marian
binary-searches the largest sentence count per length bin that fits
--workspace by building throwaway graphs. The TPU redesign searches over
ONE number (the token budget; data/batch_generator.py turns it into
per-bucket row counts) by actually compiling + running the fused train
step on a worst-case synthetic batch and catching the allocator's
RESOURCE_EXHAUSTED. Real measurement, not a heuristic — XLA's buffer
assignment is the ground truth and is not predictable analytically.
"""

from __future__ import annotations

import re
from typing import Optional

import numpy as np

from ..common import logging as log

_WORDS_MIN = 256
_WORDS_CAP = 131072


# a Pallas kernel refused for its ON-CHIP memory is RESOURCE_EXHAUSTED too
# ("Ran out of memory in memory space vmem ... exceeded scoped vmem limit",
# "... space=smem ... prefetched SMEM operand 0")
_ON_CHIP = re.compile(r"memory space (vmem|smem)|space=(vmem|smem)|"
                      r"scoped vmem", re.IGNORECASE)


def _oom(err: Exception) -> bool:
    """Whether ``err`` says the batch does not fit DEVICE memory (HBM).
    A kernel's VMEM/SMEM refusal is a compile failure, not a fit result:
    no smaller batch cures it, so it is not read as "too big" but
    re-raised by the caller."""
    s = str(err)
    if _ON_CHIP.search(s):
        return False
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s \
        or "out of memory" in s


def _try_budget(gg, words: int, max_len: int, vocab: int) -> bool:
    """One throwaway update through the REAL GraphGroup.update path (the
    fused step for delay=1, the grad-accumulation path for delay>1 — their
    peak memories differ, and the fit must hold for the one training will
    run) on the worst-case batch: every sentence at full max_len (the
    bucket table can never produce a worse [rows, max_len] shape for the
    same budget). The caller snapshots/restores params around the search."""
    import jax

    rows = max(8, (words // max_len) // 8 * 8)
    r = np.random.RandomState(0)
    batch = {
        "src_ids": r.randint(2, vocab, (rows, max_len)).astype(np.int32),
        "src_mask": np.ones((rows, max_len), np.float32),
        "trg_ids": r.randint(2, vocab, (rows, max_len)).astype(np.int32),
        "trg_mask": np.ones((rows, max_len), np.float32),
    }
    try:
        gg.update([dict(batch)] * gg.delay, 1, jax.random.key(0))
        jax.block_until_ready(gg.params)
        return True
    except Exception as e:  # noqa: BLE001 — OOM class varies by backend
        if _oom(e):
            return False
        raise


def fit_mini_batch_words(gg, opts, vocab_size: int,
                         cap: Optional[int] = None) -> int:
    """Grow-then-bisect the token budget. Called once at startup when
    --mini-batch-fit is set; the result feeds BatchGenerator as
    mini-batch-words. Each probe is a full compile (~20-40 s on TPU), so
    the search is log-bounded (≤ ~8 probes)."""
    import jax

    max_len = int(opts.get("max-length", 50))
    start = int(opts.get("mini-batch-words", 0) or 0) or 2048
    cap = cap or _WORDS_CAP
    # probes run REAL updates (gg.update, donated buffers) — snapshot the
    # initialized params/optimizer state and restore before EVERY probe: a
    # runtime OOM mid-update leaves the donated buffers deleted, so the
    # next probe would otherwise die on 'array has been deleted' instead
    # of fitting (and the throwaway updates must leave no trace either way)
    saved_params = {k: np.asarray(v) for k, v in gg.params.items()}
    saved_opt = gg.optimizer_arrays()

    def _restore():
        import jax.numpy as jnp
        gg.params = {k: jnp.asarray(v) for k, v in saved_params.items()}
        gg.load_optimizer_arrays(saved_opt)
        gg.initialize(jax.random.key(0), gg.params)

    lo, hi = 0, None
    words = max(_WORDS_MIN, min(start, cap))
    first = True
    while True:
        if not first:
            _restore()
        first = False
        ok = _try_budget(gg, words, max_len, vocab_size)
        log.info("mini-batch-fit probe: {} words → {}", words,
                 "fits" if ok else "OOM")
        if ok:
            lo = words
            if words >= cap:
                break
            if hi is None:
                words = min(words * 2, cap)
            else:
                if hi - lo <= max(256, lo // 8):
                    break
                words = (lo + hi) // 2
        else:
            hi = words
            if lo == 0:
                words = words // 2
                if words < _WORDS_MIN:
                    raise RuntimeError(
                        "mini-batch-fit: even the minimum batch does not "
                        "fit device memory — reduce --max-length or model "
                        "size")
            else:
                if hi - lo <= max(256, lo // 8):
                    break
                words = (lo + hi) // 2
    _restore()                                    # re-place + rebuild jits
    log.info("mini-batch-fit: using mini-batch-words={} (max-length {})",
             lo, max_len)
    return lo
