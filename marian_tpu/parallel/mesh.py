"""Device-mesh construction — the TPU-native replacement for the reference's
device lists + NCCL communicators (src/training/communicator.h,
communicator_nccl.h; SURVEY.md §2.7).

``--devices 0 1 2 3`` (GPU-style) or ``--mesh data:8 model:2 seq:2`` map to a
``jax.sharding.Mesh``. The default is all visible devices on a single 'data'
axis (Marian's only parallelism). Axis names are fixed: 'data' (batch/DP +
ZeRO-1 shard domain), 'model' (tensor parallel), 'seq' (sequence/context
parallel) — present-but-size-1 axes cost nothing and let the same sharded
program scale without refactoring.

Multi-host: jax.distributed.initialize (reference: MPIWrapper + NCCL uniqueId
broadcast) — see initialize_distributed().
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as obs_trace

AXES = ("data", "model", "seq", "pipe", "expert")


def initialize_distributed(options) -> None:
    """Process-group init for multi-host training (reference: initMPI in
    src/training/communicator.cpp; rank/size from mpirun env)."""
    if not options.get("multi-node", False):
        return
    coord = options.get("coordinator-address", None)
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(options.get("num-processes", 1)),
        process_id=int(options.get("process-id", 0)))


def parse_mesh_spec(spec: Sequence[str]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for item in spec:
        name, _, size = str(item).partition(":")
        if name not in AXES:
            raise ValueError(f"Unknown mesh axis '{name}' (known: {AXES})")
        out[name] = int(size)
    return out


def make_mesh(options=None, devices: Optional[List] = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if options is not None and options.get("devices", None):
            # GPU-style --devices 0 1 2 3: device *identity* is meaningless
            # under the TPU runtime, but the requested parallel width isn't
            n = len(options.get("devices", []))
            if n > len(devices):
                raise RuntimeError(
                    f"--devices requests {n} devices but only "
                    f"{len(devices)} are visible — refusing to silently "
                    f"under-provision")
            devices = devices[:n]
        if options is not None:
            n = int(options.get("num-devices", 0) or 0)
            if n:
                devices = devices[:n]
    sizes = {"data": len(devices), "model": 1, "seq": 1, "pipe": 1,
             "expert": 1}
    if options is not None and options.get("mesh", []):
        sizes.update(parse_mesh_spec(options.get("mesh")))
        unset = [a for a in AXES if a not in parse_mesh_spec(options.get("mesh"))]
        # any axis not mentioned gets the remaining devices (data by default)
        spec_prod = int(np.prod([sizes[a] for a in AXES if a not in unset]))
        rest = len(devices) // spec_prod
        for a in unset:
            sizes[a] = rest if a == "data" else 1
    total = int(np.prod([sizes[a] for a in AXES]))
    if total != len(devices):
        raise ValueError(
            f"Mesh {sizes} needs {total} devices, have {len(devices)}")
    arr = np.array(devices).reshape([sizes[a] for a in AXES])
    return Mesh(arr, AXES)


# -- canonical shardings ----------------------------------------------------

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_leaf_spec(name: str, ndim: int) -> P:
    """Per-leaf batch sharding by NAME: token id/mask streams [B, T] shard
    (data, seq); other leaves — 'guided' alignment [B, Tt, Ts] and
    'data_weights' [B, Tt] or [B, 1] — shard only the batch dim (their
    trailing dims are not bucket-padded, so 'seq' divisibility isn't
    guaranteed)."""
    if (name.endswith("_ids") or name.endswith("_mask")
            or name.endswith("_tok")) and ndim == 2:
        return P("data", "seq")
    # compact per-row lengths ([B]) and other 1D+ leaves shard batch-only
    return P("data") if ndim >= 1 else P()


def zero1_leaf_spec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-1 sharding of one optimizer-state leaf: shard the first axis
    divisible by the data-axis size; replicate scalars/small leaves.

    This is the GSPMD expression of the reference's sharded Adam
    (SyncGraphGroup: each device owns 1/N of the flat parameter arena and
    Adam-updates only that shard — communicator_nccl.h scatterReduce /
    allGather over contiguous shard ranges). Sharding dim0 per-tensor keeps
    tensors whole-rowed (friendly to XLA layouts) at a small imbalance cost
    vs Marian's flat-arena split.
    """
    n = mesh.shape["data"]
    if n <= 1 or not shape:
        return P()
    for axis, dim in enumerate(shape):
        if dim % n == 0 and dim >= n:
            return P(*([None] * axis + ["data"]))
    return P()


def replicate_tree(tree, mesh: Mesh):
    return jax.device_put(tree, replicated(mesh))


def shard_batch(batch, mesh: Mesh):
    """Place batch leaves on the mesh with name-aware specs."""
    with obs_trace.span("train.h2d") as sp:
        if sp:
            sp.set_attrs(bytes=sum(int(getattr(v, "nbytes", 0))
                                   for v in batch.values()))
        return {k: jax.device_put(v, NamedSharding(
                    mesh, batch_leaf_spec(k, getattr(v, "ndim", 2))))
                for k, v in batch.items()}
