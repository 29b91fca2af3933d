"""Warmup pipeline — turn a committed bundle into a serving-ready
executor OFF the serving path (ISSUE 5 tentpole).

Order of operations, cheapest refusal first:

1. **Compat check** (no weights touched): the candidate manifest's
   ``compat`` block (vocab sha256 + model-geometry config hash, written
   by training/bundle.py since manifest v2) must match the live
   version's. A mismatched vocabulary or geometry would serve garbage
   tokens or crash inside the jitted step mid-traffic — refuse here,
   while the refusal costs a dict comparison. v1 manifests carry no
   compat block and are accepted with a warning (documented fallback).
2. **Load**: ``executor_factory(bundle_dir, manifest)`` builds a fresh
   ``TranslationService``-style ``translate_lines`` callable against the
   bundle's members (the server's factory re-reads model.npz; tests
   inject stubs).
3. **Golden smoke**: the executor translates the golden set
   (``--warmup-golden`` file, or a built-in probe). This forces jit
   compilation of the serving shapes AND proves the model actually
   decodes — a checkpoint that loads but cannot run must never reach
   dispatch. Output arity is checked against the input (the scheduler's
   reply-routing invariant).

Everything runs on the caller's thread (the watcher thread in the real
wiring), so a multi-second model load + compile never stalls a batch.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Dict, List, Optional

from ...common import faultpoints as fp
from ...common import logging as log
from ...data.batch_generator import DEFAULT_LENGTH_BUCKETS, bucket_length
from ...obs.perf import (PERF, TRIGGER_SWAP, round_bucket_key,
                         width_bucket_key)
from ...training import bundle as bdl

# Built-in golden probe when --warmup-golden is unset: short sentences in
# the bucket widths serving traffic most commonly lands on. Unknown
# tokens are fine — warmup proves the decode path runs, not quality.
DEFAULT_GOLDEN = [
    "hello",
    "a b c d",
    "the quick brown fox jumps over the lazy dog",
]


class WarmupError(RuntimeError):
    """The candidate could not be warmed (load error, golden smoke
    failure, bad output arity)."""


class CompatMismatch(WarmupError):
    """Refused before loading weights: the candidate's compat block
    contradicts the live version's."""


def load_golden(path: Optional[str]) -> List[str]:
    """Golden source sentences from --warmup-golden (one per line, blank
    lines dropped); the built-in probe set when unset. An unreadable
    file is a hard error — a typo'd path silently warming with the
    default would void the operator's golden-set contract."""
    if not path:
        return list(DEFAULT_GOLDEN)
    with open(path, "r", encoding="utf-8") as fh:
        lines = [ln.strip() for ln in fh]
    lines = [ln for ln in lines if ln]
    if not lines:
        raise WarmupError(f"--warmup-golden {path} contains no sentences")
    return lines


def check_compat(candidate: Optional[Dict], live: Optional[Dict],
                 name: str) -> None:
    """Raise CompatMismatch on a declared mismatch; log the permissive
    v1-manifest fallback so an operator can see an unchecked swap."""
    ok, why = bdl.compat_ok(candidate, live)
    if not ok:
        raise CompatMismatch(f"bundle {name} is incompatible with the "
                             f"live model: {why}")
    if why:
        log.warn("model lifecycle: {} — swap proceeds unchecked ({})",
                 why, name)


def golden_buckets(golden: List[str],
                   length_buckets=DEFAULT_LENGTH_BUCKETS
                   ) -> "collections.OrderedDict":
    """Group golden sentences by the width bucket their whitespace
    token count (+EOS, matching the scheduler's default_length_fn)
    lands on — one group = one warmup call = one jit shape bucket
    compiled off the serving path (ISSUE 9)."""
    groups: "collections.OrderedDict[int, List[str]]" = \
        collections.OrderedDict()
    for line in golden:
        w = bucket_length(len(line.split()) + 1, length_buckets)
        groups.setdefault(w, []).append(line)
    return groups


def smoke_buckets(executor: Callable[[List[str]], List[str]],
                  golden: List[str], version: str, trigger: str,
                  where: str) -> None:
    """Per-bucket golden smoke with compile telemetry (ISSUE 9): one
    timed executor call per width bucket, reported to the perf meter as
    a warmup compilation for (version, bucket) — so steady-state
    traffic landing on a warmed bucket is provably NOT a recompile, and
    ROADMAP 5's future AOT cache has a hits-vs-misses ledger to beat.
    A combined one-call smoke would warm only the WIDEST bucket's jit
    shape (shorter sentences ride padded), so the split is also what
    makes warmup actually warm the serving shapes. Raises WarmupError
    like the single-call smoke."""
    for width, lines in golden_buckets(golden).items():
        t0 = time.perf_counter()
        try:
            with PERF.compile_context(trigger):
                out = executor(list(lines))
        except Exception as e:  # noqa: BLE001
            raise WarmupError(f"golden-set smoke translation failed for "
                              f"{where} (bucket w{width}): {e}") from e
        dt = time.perf_counter() - t0
        if not isinstance(out, (list, tuple)) or len(out) != len(lines):
            raise WarmupError(
                f"golden-set smoke returned "
                f"{len(out) if isinstance(out, (list, tuple)) else type(out).__name__} "
                f"outputs for {len(lines)} inputs ({where}, bucket "
                f"w{width}) — reply routing would misalign")
        PERF.warm_bucket(version, width_bucket_key(width), dt, trigger)


def smoke_engine_grid(executor, version: str, trigger: str,
                      where: str) -> None:
    """Iteration-mode bucket-grid smoke (ISSUE 17 satellite): when the
    warmed executor wraps a paged decode engine (EngineExecutor), drive
    the engine's FULL compile-key grid — every row bucket and every
    halving encode width (PagedDecodeEngine.warm_grid) — and register
    each (row bucket, encode width, steps) triple in the perf meter's
    warm ledger under the :func:`round_bucket_key` vocabulary the
    scheduler reports rounds with. After this, a steady-state round can
    reach NO round key that was not warmed here, so any
    ``trigger=steady-state`` compile incident on a round key is a real
    compile-cache bug (the closed-shape-set claim, asserted end-to-end
    by the jit retrace witness, common/jitwit.py). The composite grid is
    registered in full: warm_grid drives each row bucket at one width
    and each width at one row bucket, but both component jits (step and
    install) are keyed independently, so every cross pairing is warm by
    construction — the undriven pairings register at 0.0 s.

    This works unchanged for the fused-merge beam engine (ISSUE 18):
    PagedBeamEngine overrides ``row_buckets`` to beam-block multiples
    (block_bucket · beam_size) and ``steps_per_round`` to the scanned
    step count, so the cross-fill below enumerates exactly the beam
    scan's reachable round keys."""
    engine = getattr(executor, "engine", None)
    warm_grid = getattr(engine, "warm_grid", None)
    if warm_grid is None:
        return
    try:
        with PERF.compile_context(trigger):
            driven = warm_grid()
    except Exception as e:  # noqa: BLE001
        raise WarmupError(f"engine bucket-grid smoke failed for "
                          f"{where}: {e}") from e
    seen = set()
    for rb, enc_w, steps, dt in driven:
        key = round_bucket_key(rb, enc_w, steps)
        if key in seen:
            continue
        seen.add(key)
        PERF.warm_bucket(version, key, dt, trigger)
    steps = int(getattr(engine, "steps_per_round", 1))
    for rb in getattr(engine, "row_buckets", ()):
        for enc_w in engine.encode_widths():
            key = round_bucket_key(rb, enc_w, steps)
            if key not in seen:
                seen.add(key)
                PERF.warm_bucket(version, key, 0.0, trigger)
    log.info("model lifecycle: engine bucket grid warmed for {} — {} "
             "round keys registered ({} driven)", where, len(seen),
             len(driven))


def warm_executor(bundle_dir: str, manifest: Optional[Dict],
                  executor_factory: Callable[[str, Optional[Dict]],
                                             Callable[[List[str]],
                                                      List[str]]],
                  golden: List[str],
                  version: str = "", trigger: str = TRIGGER_SWAP
                  ) -> Callable[[List[str]], List[str]]:
    """Steps 2+3: build the executor and golden-smoke it. Returns the
    warmed ``translate_lines``; raises WarmupError on any failure.

    With the perf plane enabled (``--perf-accounting``), the smoke runs
    per width bucket and each bucket's compile is reported as warmup
    telemetry (:func:`smoke_buckets`); otherwise the historical single
    combined call is kept — same refusal semantics, no telemetry."""
    fp.fault_point("lifecycle.warmup")
    t0 = time.perf_counter()
    # persisted compile cache (ISSUE 20): a bundle carrying xla_cache.zip
    # whose recorded (chip, geometry, flags) key matches this process
    # turns the jit compiles below into load+verify from disk — the
    # trigger=swap-warmup compile ledger stays ~flat across the swap.
    # Any mismatch/absence degrades to the full jit, counted, never fatal.
    if manifest is not None and bundle_dir:
        from . import compile_cache as _cc
        import os as _os
        if _os.path.isdir(bundle_dir):
            # entries unpack into the process's one cache directory,
            # beside what it has compiled itself
            adopted, _why = _cc.adopt(
                bundle_dir,
                compat_hash=bdl.compat_hash(bdl.manifest_compat(manifest)))
            if adopted:
                log.info("warmup: adopted persisted compile cache from "
                         "{} — expecting cache-hit compiles only",
                         bundle_dir)
    try:
        executor = executor_factory(bundle_dir, manifest)
    except Exception as e:  # noqa: BLE001 — any load error refuses the swap
        raise WarmupError(f"executor load failed for {bundle_dir}: "
                          f"{e}") from e
    t_load = time.perf_counter()
    if PERF.enabled:
        smoke_buckets(executor, golden, version or bundle_dir, trigger,
                      bundle_dir)
        smoke_engine_grid(executor, version or bundle_dir, trigger,
                          bundle_dir)
    else:
        try:
            out = executor(list(golden))
        except Exception as e:  # noqa: BLE001
            raise WarmupError(f"golden-set smoke translation failed for "
                              f"{bundle_dir}: {e}") from e
        if not isinstance(out, (list, tuple)) or len(out) != len(golden):
            raise WarmupError(
                f"golden-set smoke returned "
                f"{len(out) if isinstance(out, (list, tuple)) else type(out).__name__} "
                f"outputs for {len(golden)} inputs ({bundle_dir}) — reply "
                f"routing would misalign")
    t_done = time.perf_counter()
    log.info("model lifecycle: warmed {} (load {:.2f}s, golden smoke of "
             "{} sentences {:.2f}s)", bundle_dir, t_load - t0,
             len(golden), t_done - t_load)
    return executor
