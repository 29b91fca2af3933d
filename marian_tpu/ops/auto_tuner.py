"""Auto-tuner: time alternative implementations and bind the fastest
(reference: src/graph/auto_tuner.h :: AutoTuner — Marian times e.g. int16
vs fp32 GEMM per shape-hash and calls the winner thereafter).

On TPU the choice that actually matters is made OUTSIDE jit, because the
implementation choice changes the compiled program: which attention kernel
(XLA-fused dense einsum vs the Pallas flash kernel) to compile for a given
sequence-length bucket. ``calibrate_flash_attention`` measures the crossover
once per process and rebinds the threshold that ``ops.attention.attention``
consults for its "auto" mode (opt-in via --auto-tune; the static default is
the v5e-measured ~1k crossover)."""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import logging as log


class AutoTuner:
    """Generic per-key implementation chooser (reference: AutoTuner::run /
    ::start/stop timing protocol, collapsed to explicit measurement)."""

    def __init__(self, warmup: int = 1, iters: int = 3):
        self.warmup = warmup
        self.iters = iters
        self._choice: Dict[Any, str] = {}
        self._timings: Dict[Any, Dict[str, float]] = {}

    def measure(self, fn: Callable, *args) -> float:
        """Median wall time of fn(*args) with device sync (block_until_ready
        replaces the reference's cudaStreamSynchronize timing fences)."""
        for _ in range(self.warmup):
            jax.block_until_ready(fn(*args))
        times = []
        for _ in range(self.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    def pick(self, key: Any,
             candidates: Dict[str, Tuple[Callable, tuple]]) -> str:
        """Return the name of the fastest candidate for `key`, timing each
        once and caching the winner (per-shape-hash binding)."""
        if key in self._choice:
            return self._choice[key]
        timings = {name: self.measure(fn, *args)
                   for name, (fn, args) in candidates.items()}
        winner = min(timings, key=timings.get)
        self._choice[key] = winner
        self._timings[key] = timings
        return winner

    def run(self, key: Any,
            candidates: Dict[str, Tuple[Callable, tuple]]):
        """pick + call the winner (the reference AutoTuner::run shape)."""
        name = self.pick(key, candidates)
        fn, args = candidates[name]
        return fn(*args)


# ---------------------------------------------------------------------------
# Pallas kernel block/capacity registry (r6). One table, one convention:
# entries are validated at dh=64 (the NMT head width every silicon number
# was taken at) and HALVE for wider heads — per-cell VMEM scales with
# dh x the sequence-side block, so oversized heads degrade to smaller
# blocks (or the callers' fallback paths) instead of a Mosaic VMEM OOM.
# Same rule the r5 flash_attention dh>64 block_k halving established.
# ---------------------------------------------------------------------------

# per-kernel base entries at dh<=64: the sequence-side capacity each
# kernel holds per grid cell (packed: full Tq=Tk per (row, head-group)
# TILE, padded to multiples of 64 past 64 positions; up to 64 a tile is
# 64 positions filled with 64 // T rows at their own width, rows_a_tile
# — a grid cell is a block of rows x all heads that the kernel sizes
# itself from the shapes, packed_attention.py::cell_plan, down to one
# row's single head group at the cap; decode: the whole [L, dh] cache
# row per (row, head) cell)
KERNEL_BLOCKS = {
    # packed fwd tile peak ~ g*T x g*T f32 scores + operands; T=256 at
    # g=2/dh=64 is ~2.5 MB — comfortably under the kernel's VMEM budget,
    # and NMT sentence lengths (T 8-64) are far below the cap anyway
    "packed_attention": {"max_t": 256},
    # decode cell holds 2 x [L, dh] cache blocks + the [1, L] score row;
    # L=2048 at dh=64 f32 is ~1 MB/cache block
    "decode_attention": {"max_len": 2048},
    # paged-pool cell accumulates 2 x [max_pages*page_len, dh] VMEM
    # scratch rows (ops/pallas/kv_pool.py) — same per-row footprint as
    # the dense decode cell, so the same 2048-token cap applies; the
    # page-table granularity only changes WHICH HBM lines feed it
    "kv_pool": {"max_tokens": 2048},
}


def _dh_scaled(base: int, dh: int) -> int:
    """Halve a sequence-side capacity for every doubling of head width
    past the validated dh=64 (floor: one 64-wide block)."""
    v = base
    width = 64
    while width < dh:
        v //= 2
        width *= 2
    return max(v, 64)


# ---------------------------------------------------------------------------
# offline sweep overlay (ISSUE 20). The static KERNEL_BLOCKS table above
# holds hand-validated v5e numbers; scripts/kernel_sweep.py measures the
# same capacities ON a chip and records them WITH provenance (chip kind,
# device count, jax version, timestamp, per-candidate timings). Pointing
# MARIAN_KERNEL_SWEEP at that JSON overlays the table — but only when
# the recorded chip matches the running one: blocks tuned for different
# silicon are refused loudly (the provenance is the point — arxiv
# 1802.04799's autotuning loop records where numbers came from; a
# hand-edited table can't).
# ---------------------------------------------------------------------------

SWEEP_ENV = "MARIAN_KERNEL_SWEEP"
# provenance of the applied sweep (None = static table); kept for
# introspection/tests
SWEEP_PROVENANCE: Optional[Dict] = None
_sweep_checked = False


def load_kernel_sweep(path: str, chip: Optional[str] = None) -> bool:
    """Overlay ``KERNEL_BLOCKS`` from a kernel_sweep.py recording.
    Returns True when applied. Refuses (False, with a loud warning)
    when the recorded chip differs from the running one, when the file
    is malformed, or when it names unknown kernels/keys — a sweep that
    cannot be attributed must never silently change block sizes."""
    global SWEEP_PROVENANCE
    import json
    import os
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        log.warn("kernel sweep: cannot read {}: {} — keeping the "
                 "static KERNEL_BLOCKS table", path, e)
        return False
    if chip is None:
        try:
            chip = str(getattr(jax.devices()[0], "device_kind", "unknown"))
        except Exception:  # noqa: BLE001 — no backend: nothing to tune
            chip = "unknown"
    recorded = str(doc.get("chip", ""))
    if not recorded or recorded != chip:
        log.warn("kernel sweep: {} was recorded on chip '{}' but this "
                 "process runs on '{}' — REFUSING the overlay (re-run "
                 "scripts/kernel_sweep.py on this chip)",
                 path, recorded or "?", chip)
        return False
    blocks = doc.get("blocks", {})
    staged = {}
    for kernel, entries in blocks.items():
        if kernel not in KERNEL_BLOCKS:
            log.warn("kernel sweep: unknown kernel {!r} in {} — "
                     "refusing the whole overlay", kernel, path)
            return False
        for key, val in entries.items():
            if key not in KERNEL_BLOCKS[kernel] or int(val) < 64:
                log.warn("kernel sweep: bad entry {}.{}={!r} in {} — "
                         "refusing the whole overlay",
                         kernel, key, val, path)
                return False
            staged[(kernel, key)] = int(val)
    for (kernel, key), val in staged.items():
        KERNEL_BLOCKS[kernel][key] = val
    SWEEP_PROVENANCE = {k: doc.get(k) for k in
                        ("chip", "n_devices", "jax", "recorded_at",
                         "timings") if k in doc}
    SWEEP_PROVENANCE["path"] = os.path.abspath(path)
    log.info("kernel sweep: applied {} block override(s) from {} "
             "(chip '{}')", len(staged), path, recorded)
    return True


def _maybe_load_sweep_env() -> None:
    """One-shot lazy overlay from $MARIAN_KERNEL_SWEEP (checked at the
    first registry lookup, not import time — jax.devices() must not run
    on import)."""
    global _sweep_checked
    if _sweep_checked:
        return
    _sweep_checked = True
    import os
    path = os.environ.get(SWEEP_ENV, "")
    if path:
        load_kernel_sweep(path)


def kernel_block(kernel: str, key: str, dh: int) -> int:
    """Registry lookup with the dh-scaled VMEM convention applied."""
    _maybe_load_sweep_env()
    return _dh_scaled(KERNEL_BLOCKS[kernel][key], dh)


def packed_attention_max_t(dh: int) -> int:
    """Longest (padded) sequence the packed kernel takes per cell under
    packed="on"; past it the dispatcher leaves the shape to dense/flash.

    Two VMEM axes bound it: wide heads grow the [T, dh] operand blocks
    (the halving rule above), and NARROW heads grow the pack group g =
    128//dh, whose backward kernel materializes [g*T, g*T] f32 blocks —
    quadratic in g·T. So the cap bounds g*T at the validated point
    (dh=64: g=2 × T=256 = 512), not T alone: dh=32 → 128, dh=16 → 64.
    The target regime (T 48-64) stays inside the cap at every dh."""
    base = kernel_block("packed_attention", "max_t", dh)
    g = max(1, 128 // max(dh, 1))
    return max(64, min(base, 512 // g))


def decode_attention_max_len(dh: int) -> int:
    """Longest decode cache the fused kernel holds per cell; past it
    decode_attention degrades to its unfused jnp reference path."""
    return kernel_block("decode_attention", "max_len", dh)


def kv_pool_max_tokens(dh: int) -> int:
    """Longest per-row paged span (max_pages x page_len) the paged
    decode kernel assembles in VMEM scratch; past it
    paged_decode_attention degrades to its jnp gather reference."""
    return kernel_block("kv_pool", "max_tokens", dh)


# ---------------------------------------------------------------------------
# flash-attention crossover calibration
# ---------------------------------------------------------------------------

_calibrated_threshold: Optional[int] = None


def flash_threshold(default: int = 1024) -> int:
    """Sequence length above which 'auto' picks the Pallas flash kernel."""
    return _calibrated_threshold if _calibrated_threshold is not None \
        else default


def calibrate_flash_attention(heads: int = 8, dim_head: int = 64,
                              batch: int = 4,
                              lengths=(256, 512, 1024, 2048),
                              causal: bool = True) -> int:
    """Time dense vs flash attention per length bucket on the current
    backend; bind the smallest length where flash wins (--auto-tune)."""
    global _calibrated_threshold
    from .attention import dense_attention
    from .pallas.flash_attention import flash_attention

    tuner = AutoTuner()
    crossover = None
    for t in lengths:
        q = jnp.ones((batch, heads, t, dim_head), jnp.bfloat16)
        mask = (jnp.tril(jnp.ones((t, t), jnp.bfloat16))[None, None]
                if causal else None)
        dense_j = jax.jit(lambda a, m: dense_attention(a, a, a, m))
        flash_j = jax.jit(lambda a: flash_attention(a, a, a, causal=causal))
        name = tuner.pick(("attn", t), {
            "dense": (dense_j, (q, mask)),
            "flash": (flash_j, (q,)),
        })
        if name == "flash" and crossover is None:
            crossover = t
    # No crossover measured → flash lost at every tested length; disable it
    # for 'auto' outright rather than extrapolating a win past the sweep.
    _calibrated_threshold = crossover if crossover is not None \
        else sys.maxsize
    return _calibrated_threshold
