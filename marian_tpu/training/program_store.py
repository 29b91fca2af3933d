"""The step programs --precompile-buckets builds, kept between starts.

JAX's persistent cache keeps compiled code under a hash of the LOWERED
program, so a warm start still traces and lowers every step program,
seconds each and one at a time under the interpreter lock, only to learn
the key under which its executable waits. This store keeps the
executables themselves (`jax.experimental.serialize_executable`) under a
fingerprint that needs no trace: a sha256 over what shapes a step
program,

- the contents of every ``.py`` file of this package (any edit to the
  program is a miss: a restart across versions is a cold start by design);
- jax, jaxlib, the backend's build, the device kind and count, the mesh,
  the XLA and MARIAN_* environment, the JAX flags that reach a lowering;
- every parsed option EXCEPT those in DENIED_OPTIONS, each there with
  its reason: a new option is in the key until somebody argues it out;
- what the trainer bakes into the trace beside the options (the
  schedule's decay factor and warm-up offset, the optimizer's
  configuration, the model's);
- the abstract value and sharding of every argument, and the donation.

An entry is ONE file, ``<cache>/programs/<fingerprint>.exe`` (the cache
is common/profiling.py::compilation_cache_dir): a line of JSON an
operator can read (`head -n 1`: the fingerprint's inputs, the program's
name, what `memory_analysis()` said of it), then the pickled argument
and result trees, the program's StableHLO and the serialized executable
(both packed: zstd where it is installed, else zlib). It is written under a temporary name and renamed, so a
reader sees a whole file or none. An entry that cannot be read, that was
written for other inputs, or that the backend will not load is REFUSED:
the caller compiles as if there were none and writes over it. Only bytes
this program wrote are unpickled: the directory is the trainer's own,
as the persistent cache's is JAX's.

Nothing is ever deleted here: `rm -rf <cache>/programs` clears the store
(docs/DEPLOYMENT.md says what it costs on disk).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import threading
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..common import logging as log

try:                    # as JAX's own cache: zstd where it is installed
    import zstandard
except ImportError:
    zstandard = None

FORMAT = "marian-tpu step program 1"
# Platforms whose programs are not kept. XLA:CPU's serialized executables
# do not all come back whole (jaxlib 0.9.0: a loaded step of a plan with
# loops dies at run time, "Function compare_reduce_fusion not found"), and
# the CPU is nobody's deployment: a trainer there compiles as it always did.
OFF_PLATFORMS = ("cpu",)

_WHERE = "names a file or a directory: what the file holds reaches the " \
    "step through the arguments' shapes"
_SHOWN = "what is logged or displayed, and when"
_SAVED = "when and how checkpoints are written"
_STOPS = "when training stops"
_VALID = "validation runs programs of its own"
_ASKS = "prints and exits: no step runs"

# Options that cannot reach a step program's text, each with the reason.
# Everything else config_parser knows is part of the fingerprint, so a new
# option is IN the key by default; leaving one out is an edit here, and
# tests/test_program_store.py walks every option against this list.
DENIED_OPTIONS: Dict[str, str] = {
    # -- the seed ---------------------------------------------------------
    "seed": "reaches the step as an argument (the key), never as text",
    # -- paths and names of files ------------------------------------------
    "config": "names the files the other options were read from",
    "interpolate-env-vars": "how paths in a config file are read",
    "relative-paths": "how paths in a config file are read",
    "model": _WHERE,
    "pretrained-model": _WHERE,
    "train-sets": _WHERE + " (and the number of streams through the "
                           "batch's leaves)",
    "vocabs": _WHERE + " (the vocabulary sizes are the tables' shapes)",
    "tempdir": _WHERE,
    "sqlite": _WHERE,
    "sqlite-drop": "the corpus database's lifetime",
    # -- logging and display -----------------------------------------------
    "log": _SHOWN,
    "log-level": _SHOWN,
    "log-time-zone": _SHOWN,
    "quiet": _SHOWN,
    "quiet-translation": _SHOWN,
    "disp-freq": _SHOWN,
    "disp-first": _SHOWN,
    "disp-label-counts": _SHOWN,
    "lr-report": _SHOWN,
    "tensorboard": _SHOWN,
    "profile": "a profiler's window around updates, not the updates",
    "profile-server": "a profiler's port",
    "profile-start": "a profiler's window around updates, not the updates",
    "profile-updates": "a profiler's window around updates, not the updates",
    "dump-hlo": "writes the step's text somewhere; changes none of it",
    "dump-config": _ASKS,
    "authors": _ASKS,
    "cite": _ASKS,
    "build-info": _ASKS,
    "version": _ASKS,
    # -- saving and stopping -------------------------------------------------
    "save-freq": _SAVED,
    "async-save": _SAVED,
    "keep-checkpoint-bundles": _SAVED,
    "overwrite": _SAVED,
    "overwrite-checkpoint": _SAVED,
    "no-reload": "whether a checkpoint is read at start: the state's "
                 "values, not its shapes",
    "sigterm": "what a signal does to the process",
    "after-epochs": _STOPS,
    "after-batches": _STOPS,
    "after": _STOPS,
    # -- validation ------------------------------------------------------------
    "valid-sets": _VALID,
    "valid-freq": _VALID,
    "valid-metrics": _VALID,
    "valid-reset-stalled": _VALID,
    "valid-reset-all": _VALID,
    "valid-log": _VALID,
    "valid-max-length": _VALID,
    "valid-mini-batch": _VALID,
    "valid-script-path": _VALID,
    "valid-script-args": _VALID,
    "valid-translation-output": _VALID,
    "early-stopping": _VALID + "; the stalls it counts stop the training",
    "early-stopping-epsilon": _VALID + "; the stalls it counts stop the "
                                       "training",
    "early-stopping-on": _VALID + "; the stalls it counts stop the training",
    "keep-best": _VALID + "; which checkpoints are kept",
}

# host-side switches of the tracing: turning the spans on must not cost
# the warm start they are there to watch
_ENV_DENIED = ("MARIAN_TRACE", "MARIAN_TRACE_DUMP", "MARIAN_PERF")
# the compiler's own options, wherever they are set
_ENV_NAMED = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
# JAX flags that change what a function lowers to
_JAX_FLAGS = ("jax_enable_x64", "jax_default_matmul_precision",
              "jax_default_prng_impl", "jax_threefry_partitionable",
              "jax_numpy_dtype_promotion", "jax_debug_nans",
              "jax_traceback_in_locations_limit")


class Refused(Exception):
    """An entry that is there and cannot be used."""


@dataclasses.dataclass
class Entry:
    """A step program taken from the store."""
    exe: Any                    # jax.stages.Compiled, loaded
    costs: Dict[str, int]       # memory_analysis() as it was written
    bytes: int                  # the file's size


def package_digest() -> str:
    """sha256 over the path and contents of every ``.py`` file of the
    package, in order: ~40 k lines, milliseconds."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for where, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(where, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as fh:
                    h.update(fh.read() + b"\0")
    return h.hexdigest()


def versions() -> Dict[str, str]:
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def environment(mesh) -> Dict[str, Any]:
    """What the machine and the process add to a step program: the
    libraries, the backend's build (libtpu's), the devices, the mesh."""
    devices = list(mesh.devices.flat)
    env = {k: v for k, v in os.environ.items()
           if k in _ENV_NAMED
           or k.startswith("MARIAN_") and k not in _ENV_DENIED}
    return dict(
        versions(),
        platform=devices[0].client.platform,
        platform_version=devices[0].client.platform_version,
        device_kind=devices[0].device_kind,
        devices=jax.device_count(),
        mesh={"axes": list(mesh.axis_names),
              "shape": list(mesh.devices.shape),
              "ids": [d.id for d in devices]},
        env=env,
        flags={name: getattr(jax.config, name) for name in _JAX_FLAGS})


def option_inputs(options) -> Dict[str, Any]:
    """Every option but the denied ones."""
    return {k: v for k, v in options.as_dict().items()
            if k not in DENIED_OPTIONS}


def describe(tree) -> List[list]:
    """Abstract value and sharding of every leaf, by path."""
    return [[jax.tree_util.keystr(path), list(a.shape), str(a.dtype),
             str(getattr(a, "sharding", None))]
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _pack(data: bytes) -> bytes:
    """A step program's code is mostly padding: it packs to a sixth."""
    if zstandard is not None:
        return zstandard.ZstdCompressor().compress(data)
    return zlib.compress(data, 1)


def _unpack(codec: str, data: bytes) -> bytes:
    if codec == "zlib":
        return zlib.decompress(data)
    if codec == "zstd" and zstandard is not None:
        return zstandard.ZstdDecompressor().decompress(data)
    raise Refused(f"packed with {codec!r}, which this process cannot read")


def _plain(value):
    """`value` as JSON would give it back: what a file's header is
    compared with."""
    return json.loads(json.dumps(value, sort_keys=True, default=str))


class ProgramStore:
    """One directory of step programs and the inputs every program of one
    trainer shares. `baked` is what the caller traces into the step
    beside the options."""

    def __init__(self, directory: str, mesh, options, baked: Dict[str, Any]):
        self.directory = directory
        self.devices = list(mesh.devices.flat)
        self.common = _plain({"package": package_digest(),
                              "environment": environment(mesh),
                              "options": option_inputs(options),
                              "baked": baked})

    def fingerprint(self, args: Dict[str, List[list]],
                    donate: Tuple[int, ...]) -> Tuple[str, Dict[str, Any]]:
        """(fingerprint, its inputs) of the program for `args`: the
        step's arguments by name, each as `describe` gives it."""
        inputs = dict(self.common, donate=list(donate), args=_plain(args))
        text = json.dumps(inputs, sort_keys=True)
        return hashlib.sha256(text.encode()).hexdigest(), inputs

    def path(self, fingerprint: str) -> str:
        return os.path.join(self.directory, fingerprint + ".exe")

    def holds(self, fingerprint: str) -> bool:
        return os.path.exists(self.path(fingerprint))

    def load(self, fingerprint: str, inputs: Dict[str, Any]
             ) -> Optional[Entry]:
        """The entry's executable, loaded onto this trainer's devices;
        None where there is no entry. Raises Refused for one that is
        there and cannot be used. A loaded program's text goes where
        JAX_DUMP_IR_TO says, as a lowering's would have."""
        path = self.path(fingerprint)
        try:
            with open(path, "rb") as fh:
                line = fh.readline()
                header = json.loads(line)
                if header.get("format") != FORMAT \
                        or header.get("inputs") != inputs:
                    raise Refused("written for other inputs")
                sizes = [int(n) for n in header["sizes"]]
                costs = {k: int(v) for k, v in header["costs"].items()}
                name, codec = str(header["name"]), str(header["codec"])
                size = os.fstat(fh.fileno()).st_size
                if size != len(line) + sum(sizes):
                    raise Refused(f"{size} bytes where its header says "
                                  f"{len(line) + sum(sizes)}")
                trees, text, payload = (fh.read(n) for n in sizes)
        except FileNotFoundError:
            return None
        except (OSError, ValueError, KeyError, TypeError) as e:
            raise Refused(f"unreadable ({e!r})") from e
        from jax.experimental import serialize_executable
        try:
            in_tree, out_tree = pickle.loads(trees)
            exe = serialize_executable.deserialize_and_load(
                _unpack(codec, payload), in_tree, out_tree,
                backend=self.devices[0].client,
                execution_devices=self.devices)
            _dump_text(name, fingerprint, codec, text)
        except Refused:
            raise
        except Exception as e:  # noqa: BLE001 — whatever the backend says
            raise Refused(f"the backend will not load it ({e!r})") from e
        return Entry(exe, costs, size)

    def save(self, fingerprint: str, inputs: Dict[str, Any], name: str,
             exe, text: str, costs: Dict[str, int]) -> int:
        """Write (or write over) the entry; returns its bytes, 0 where
        it could not be written, which is logged and costs the next
        start its trace and nothing else."""
        from jax.experimental import serialize_executable
        path = self.path(fingerprint)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(exe)
            parts = [pickle.dumps((in_tree, out_tree)),
                     _pack(text.encode()), _pack(payload)]
            header = json.dumps({
                "format": FORMAT, "fingerprint": fingerprint, "name": name,
                "codec": "zlib" if zstandard is None else "zstd",
                "costs": costs, "sizes": [len(p) for p in parts],
                "inputs": inputs}, sort_keys=True).encode() + b"\n"
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp, "wb") as fh:
                for part in [header] + parts:
                    fh.write(part)
            os.replace(tmp, path)
            return len(header) + sum(len(p) for p in parts)
        except Exception as e:  # noqa: BLE001 — the trainer runs without it
            log.warn("Step program {} was not kept in {}: {!r}", name,
                     self.directory, e)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return 0


def _dump_text(name: str, fingerprint: str, codec: str,
               packed: bytes) -> None:
    """JAX_DUMP_IR_TO promises the text of every program that runs; JAX
    writes it where a program is handed to the compiler, which a loaded
    one never is."""
    where = jax.config.read("jax_dump_ir_to")
    if not where:
        return
    try:
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(
                where, f"jax_ir_stored_{name}_{fingerprint[:16]}.mlir"),
                "wb") as fh:
            fh.write(_unpack(codec, packed))
    except OSError as e:
        log.warn("Step program {}: its text was not dumped to {}: {!r}",
                 name, where, e)
