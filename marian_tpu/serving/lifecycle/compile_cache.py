"""Persisted XLA compilation cache as a checkpoint-bundle member
(ISSUE 20 tentpole — the warm-on-demand cold-start enabler).

Since ISSUE 17 the serving compile-key surface is ENUMERABLE: the
``# buckets:`` registries + ``warm_grid`` manifest close the shape set,
so "persist the compile cache" finally has a concrete manifest (the warm
grid IS the list of programs the cache must hold) and a ledger
(``marian_compile_backend_seconds_total{trigger=swap-warmup}`` must stay
~flat across a cache-backed swap — tests/test_compile_cache.py pins it).

Mechanism: jax's persistent compilation cache
(``jax_compilation_cache_dir``) already content-addresses compiled
executables by (computation, compile options, backend). This module adds
the bundle plumbing around it:

- The process has ONE cache directory, decided by
  common/profiling.py::enable_compilation_cache
  ($JAX_COMPILATION_CACHE_DIR, else ``<checkout>/.cache/xla``); every
  entry point enables it at start-up.
- :func:`pack_member` is a ``write_bundle``-compatible member writer
  that zips that directory plus a :func:`cache_key` record into the
  bundle (member ``xla_cache.zip`` —
  training/bundle.py :: COMPILE_CACHE_MEMBER).
- :func:`adopt` (called by warmup before the executor factory runs)
  VERIFIES a candidate bundle's recorded key against the current (chip,
  geometry, flags) and only then unpacks its entries INTO the active
  directory, beside what the process already compiled — a cache built
  for different silicon or XLA flags must never be installed (jax would
  re-key and miss anyway; the refusal makes the mismatch visible in the
  hit/miss ledger instead of silent).

The key is deliberately coarse — chip kind + device count + platform +
jax version + XLA-flags hash + the bundle compat hash. jax's own cache
key does the fine-grained content addressing; ours only answers "was
this cache produced by an equivalent process on equivalent silicon".

Everything degrades to a loud no-op when jax is unavailable (the
stub-or-gate dependency rule) or the cache member is absent — warmup
then pays the full jit exactly as before this ISSUE.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import zipfile
from typing import Callable, Dict, Optional, Tuple

from ...common import logging as log
from ...common.profiling import enable_compilation_cache
from .. import metrics as msm

# bundle member name (mirrored as training/bundle.py::COMPILE_CACHE_MEMBER
# so producers need no import of the serving tree)
CACHE_MEMBER = "xla_cache.zip"
# key record inside the zip, checked before enabling the unpacked cache
KEY_FILE = "MARIAN_CACHE_KEY.json"

_m_events = None


def _events():
    """marian_compile_cache_events_total{event}: the hit/miss ledger —
    packed / adopted / miss (no member) / key-mismatch / error."""
    global _m_events
    if _m_events is None:
        _m_events = msm.REGISTRY.counter(
            "marian_compile_cache_events_total",
            "Persisted-compile-cache lifecycle events "
            "(adopted = warm-on-demand is load+verify, not full jit)",
            labels=("event",))
        # pre-declare every event so the ledger renders at zero — an
        # operator alerting on key-mismatch needs the series to exist
        # before the first mismatch
        for ev in ("packed", "adopted", "miss", "key-mismatch", "error"):
            _m_events.labels(ev).inc(0)
    return _m_events


def _flags_sha() -> str:
    """Hash of the env-level compiler knobs that change compiled code
    without changing the computation."""
    blob = "\x1f".join(os.environ.get(k, "") for k in
                       ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "JAX_PLATFORMS"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def cache_key(compat_hash: str = "") -> Optional[Dict[str, str]]:
    """The (chip, geometry, flags) identity of caches this process can
    adopt. None when jax is unavailable."""
    try:
        import jax
        devs = jax.devices()
    except Exception as e:  # noqa: BLE001 — no backend = no cache
        log.warn("compile cache: no jax backend ({}) — cache disabled", e)
        return None
    return {
        "chip": str(getattr(devs[0], "device_kind", "unknown")),
        "platform": str(getattr(devs[0], "platform", "unknown")),
        "n_devices": str(len(devs)),
        "jax": str(getattr(jax, "__version__", "unknown")),
        "flags_sha": _flags_sha(),
        "compat": str(compat_hash or ""),
    }


def key_matches(recorded: Dict, current: Dict) -> Tuple[bool, str]:
    """Strict equality on every field; compat is compared only when both
    sides recorded one (v1 manifests carry none — documented fallback,
    same permissiveness as bundle compat_ok)."""
    for field in ("chip", "platform", "n_devices", "jax", "flags_sha"):
        r, c = str(recorded.get(field, "")), str(current.get(field, ""))
        if r != c:
            return False, f"{field} mismatch (cache '{r}' vs here '{c}')"
    r, c = str(recorded.get("compat", "")), str(current.get("compat", ""))
    if r and c and r != c:
        return False, f"compat mismatch (cache '{r}' vs here '{c}')"
    return True, ""


def active_dir() -> Optional[str]:
    """The directory this process's persistent cache writes to, or None
    while it is off."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


def pack_member(compat_hash: str = "") -> Callable[[str], None]:
    """A ``write_bundle`` member writer for ``xla_cache.zip``: zips the
    active cache directory with the current :func:`cache_key` record.
    The writer raises if no cache is enabled or the key cannot be
    derived — a producer asking to persist a cache it does not have is
    a config error, not a silent empty member."""
    def _write(path: str) -> None:
        src = active_dir()
        if not src or not os.path.isdir(src):
            raise RuntimeError(
                "compile cache: no persistent cache directory to pack "
                "(enable_compilation_cache() has not run)")
        key = cache_key(compat_hash)
        if key is None:
            raise RuntimeError("compile cache: no jax backend — cannot "
                               "record a cache key")
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr(KEY_FILE, json.dumps(key, indent=1))
            n = 0
            for root, _dirs, files in os.walk(src):
                for name in files:
                    full = os.path.join(root, name)
                    zf.write(full, os.path.relpath(full, src))
                    n += 1
        _events().labels("packed").inc()
        log.info("compile cache: packed {} cache file(s) into {}", n,
                 os.path.basename(path))
    return _write


def adopt(bundle_dir: str, compat_hash: str = "") -> Tuple[bool, str]:
    """Warm-on-demand entry point (warmup.py calls this BEFORE the
    executor factory): if the bundle carries ``xla_cache.zip`` and its
    recorded key matches this process, unpack its entries into the
    active cache directory — the subsequent jit compiles become
    load+verify from disk. Returns (adopted, why: the directory when
    adopted). Never raises: a bad/missing/mismatched member degrades to
    the pre-cache full-jit warmup, counted in the event ledger."""
    member = os.path.join(bundle_dir, CACHE_MEMBER)
    if not os.path.isfile(member):
        _events().labels("miss").inc()
        return False, "no compile-cache member in bundle"
    current = cache_key(compat_hash)
    if current is None:
        _events().labels("error").inc()
        return False, "no jax backend"
    try:
        with zipfile.ZipFile(member) as zf:
            try:
                recorded = json.loads(zf.read(KEY_FILE).decode("utf-8"))
            except KeyError:
                _events().labels("error").inc()
                return False, f"member carries no {KEY_FILE}"
            ok, why = key_matches(recorded, current)
            if not ok:
                _events().labels("key-mismatch").inc()
                log.warn("compile cache: NOT adopting {} ({}) — warmup "
                         "pays the full jit", member, why)
                return False, why
            dest = enable_compilation_cache()
            for info in zf.infolist():
                if info.filename == KEY_FILE or info.is_dir():
                    continue
                # path-traversal guard: members must unpack INSIDE dest
                target = os.path.realpath(os.path.join(dest, info.filename))
                if not target.startswith(os.path.realpath(dest) + os.sep):
                    raise RuntimeError(
                        f"compile cache: refusing member path "
                        f"{info.filename!r} (escapes the unpack dir)")
                os.makedirs(os.path.dirname(target), exist_ok=True)
                with zf.open(info) as src, open(target, "wb") as out:
                    shutil.copyfileobj(src, out)
    except (OSError, zipfile.BadZipFile, RuntimeError) as e:
        _events().labels("error").inc()
        log.warn("compile cache: could not adopt {}: {}", member, e)
        return False, str(e)
    _events().labels("adopted").inc()
    log.info("compile cache: adopted {} — swap warmup is load+verify "
             "(chip {}, {} device(s))", member, current["chip"],
             current["n_devices"])
    return True, dest
