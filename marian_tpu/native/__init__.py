"""Native (C++) runtime components and their ctypes bindings.

The reference is 100% native (SURVEY.md §2: C++/CUDA throughout); here the
DEVICE side is XLA's domain, and one host-side hot path beside it is native
C++ like the reference's:

- bpe_encoder.cpp — the subword tokenization hot path for in-repo BPE
  models (reference: vendored C++ SentencePiece), bound below as
  NativeBPEEncoder. Deterministic greedy path only; BPE-dropout sampling
  stays in Python. data/bpe_vocab.py uses it where the library builds and
  its own Python encoder where it does not.

The shared library builds on demand with g++ (no pybind11 in this image;
plain C ABI + ctypes). Build artifacts land next to the sources.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List

from ..common import lockdep

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = lockdep.make_lock("marian_tpu.native._LOCK")


def _build_so(src: str, so: str, force: bool = False) -> str:
    """Compile one native component → .so (g++ -O3, on demand)."""
    if not force and os.path.exists(so) and \
            os.path.getmtime(so) >= os.path.getmtime(src):
        return so
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", so, src]
    proc = subprocess.run(cmd, capture_output=True, text=True)  # mtlint: ok -- one-time lazy g++ build; _LOCK exists to serialize exactly this
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed: {proc.stderr[-2000:]}")
    return so


_BPE_SO = os.path.join(_DIR, "libmarian_bpe.so")
_BPE_SRC = os.path.join(_DIR, "bpe_encoder.cpp")
_BPE_LIB = None


def build_bpe_library(force: bool = False) -> str:
    return _build_so(_BPE_SRC, _BPE_SO, force)


def _bpe_lib():
    global _BPE_LIB
    with _LOCK:
        if _BPE_LIB is None:
            lib = ctypes.CDLL(build_bpe_library())
            lib.bpe_create.restype = ctypes.c_void_p
            lib.bpe_destroy.argtypes = [ctypes.c_void_p]
            lib.bpe_add_piece.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_int32]
            lib.bpe_add_merge.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_char_p, ctypes.c_int32]
            lib.bpe_encode.restype = ctypes.c_int32
            # (handle, utf8 bytes, byte len, add_eos, out, max_out) —
            # explicit length so embedded NULs stay data, like Python
            lib.bpe_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int32, ctypes.c_int32,
                                       ctypes.POINTER(ctypes.c_int32),
                                       ctypes.c_int32]
            _BPE_LIB = lib
        return _BPE_LIB


class NativeBPEEncoder:
    """ctypes wrapper over one loaded BPE model. Produces ids identical
    to bpe_vocab.BPEVocab's Python encoder (pinned by
    tests/test_bpe_fallback.py::TestNativeEncoder)."""

    def __init__(self, pieces, merges):
        self._lib = _bpe_lib()
        self._h = self._lib.bpe_create()
        for i, p in enumerate(pieces):
            self._lib.bpe_add_piece(self._h, p.encode("utf-8"), i)
        for r, (a, b) in enumerate(merges):
            self._lib.bpe_add_merge(self._h, a.encode("utf-8"),
                                    b.encode("utf-8"), r)

    def __del__(self):
        try:
            self._lib.bpe_destroy(self._h)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def encode(self, line: str, add_eos: bool = True) -> List[int]:
        data = line.encode("utf-8")
        # per-call buffer: encode() is called concurrently (prefetch
        # thread + validators share the vocab, and ctypes releases the
        # GIL during the C call) — a shared buffer would race
        cap = max(256, 4 * len(data) + 8)
        while True:
            buf = (ctypes.c_int32 * cap)()
            n = self._lib.bpe_encode(self._h, data, len(data),
                                     1 if add_eos else 0, buf, cap)
            if n >= 0:
                return list(buf[:n])
            cap *= 2
