"""Tracing / profiling subsystem (SURVEY §5 row 1 — the reference ships
nvtx ranges + nvprof hooks in src/common/profiler.h; the TPU-native
equivalents are jax.profiler device traces and HLO dumps).

Two surfaces (where host wall-clock goes is the span tracer's business:
obs/trace.py, live under either of them):

- ``--profile [dir]``: capture a jax.profiler trace (TensorBoard / xprof
  format) around a window of training updates. The trace records every XLA
  op's device time — the tool the round-1 verdict flagged as missing for
  locating the throughput gap.
- ``--dump-hlo path``: write the jaxpr and the optimized HLO of the jitted
  train step (the ExpressionGraph::graphviz debugging equivalent).

``TraceWindow`` (the ``--profile`` window) lives in
``marian_tpu/obs/profiling.py``.
"""

from __future__ import annotations

import os
from typing import Optional

from . import logging as log


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def compilation_cache_dir() -> str:
    """The one place the persistent-cache location is decided: where
    $JAX_COMPILATION_CACHE_DIR says (JAX reads that variable itself), else
    the fixed ``<checkout>/.cache/xla``. Never a temporary, per-process or
    dated directory: a cache that moves is a cache that never hits."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".cache", "xla")


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process, at
    :func:`compilation_cache_dir`; returns the directory. Every entry
    point calls this (marian-train, marian-decoder, marian-server), so a
    restart — or the next process of one chip run — reloads what the last
    one compiled. With $JAX_COMPILATION_CACHE_DIR set the directory is
    JAX's own setting and this sets none. Idempotent; the persistence
    thresholds stay JAX's (JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS)."""
    import jax
    path = compilation_cache_dir()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # XLA's side caches would otherwise sit INSIDE the directory with
    # their absolute paths hashed into every cache key — entries packed
    # into a bundle (serving/lifecycle/compile_cache.py) could then never
    # hit under another directory
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    return path


def program_store_dir() -> Optional[str]:
    """Where the trainer keeps the step programs --precompile-buckets
    builds (training/program_store.py): ``programs/`` inside the
    persistent cache's directory, so one setting moves both and JAX's own
    eviction, which reads the directory's top level only, never sees an
    entry. None where this process has no persistent cache (neither
    :func:`enable_compilation_cache` nor $JAX_COMPILATION_CACHE_DIR, or
    ``jax_enable_compilation_cache`` off): then nothing is kept."""
    import jax
    path = jax.config.jax_compilation_cache_dir
    if not path or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(path, "programs")


def maybe_start_profile_server(options) -> bool:
    """--profile-server PORT: live profiler endpoint on a RUNNING job —
    TensorBoard's profile tab / xprof connect and capture on demand,
    with no pre-planned trace window (the TPU-era answer to attaching
    nvprof to a running trainer; SURVEY §5 tracing row). Returns whether
    a server was started."""
    port = int(options.get("profile-server", 0) or 0)
    if port <= 0:
        return False
    import jax
    try:
        jax.profiler.start_server(port)
    except Exception as e:  # noqa: BLE001 — diagnostics must not kill train
        log.warn("--profile-server {}: failed to start ({})", port, e)
        return False
    log.info("Profiler server listening on port {} (attach with "
             "TensorBoard's profile tab or xprof)", port)
    return True


def dump_lowered(path: str, lowered) -> None:
    """Write <path>.hlo.txt (stable HLO) and <path>.hlo_opt.txt (post-
    fusion — what actually runs on the chip) for a lowered jitted call
    (reference: ExpressionGraph::graphviz / --dump-graph debugging)."""
    base = path[:-4] if path.endswith(".txt") else path
    with open(base + ".hlo.txt", "w") as fh:
        fh.write(lowered.as_text())
    try:
        with open(base + ".hlo_opt.txt", "w") as fh:
            fh.write(lowered.compile().as_text())
    except Exception as e:  # noqa: BLE001
        log.warn("optimized-HLO dump failed: {}", e)
    log.info("Dumped train-step HLO to {}.hlo*.txt", base)
