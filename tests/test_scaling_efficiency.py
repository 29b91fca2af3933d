"""Scaling-efficiency telemetry on the virtual mesh (VERDICT r3 #6).

Correctness tests can't see an accidental host sync or a re-replication
regression in the sharded step — the numbers stay right while every
update quietly pays N× compute or an extra device round-trip. This
measures what those regressions inflate: per-step wall time at 1 vs 8
virtual devices at FIXED per-device batch, plus the compiled collective
footprint. On one CPU core the 8 virtual devices serialize, so the ideal
wall-clock ratio is ~8×; a replicated-optimizer regression pushes it
well past that (8× compute + 8× optimizer math + resharding traffic),
and a host sync shows up as a constant floor per step.

Measured numbers are recorded in docs/PERFORMANCE.md (round 4).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.optimizers.optimizers import OptimizerConfig, init_state
from marian_tpu.optimizers.schedule import LRSchedule
from marian_tpu.parallel import mesh as M
from marian_tpu.parallel.zero import build_train_step, place

DIM = 64
PER_DEV_B = 8
T = 16


def _opts():
    return Options({
        "type": "transformer", "dim-emb": DIM, "transformer-heads": 4,
        "transformer-dim-ffn": 2 * DIM, "enc-depth": 2, "dec-depth": 2,
        "tied-embeddings-all": True, "precision": ["float32", "float32"],
        "max-length": T, "label-smoothing": 0.1,
        "cost-type": "ce-mean-words", "learn-rate": 1e-3,
        "optimizer": "adam", "clip-norm": 1.0,
    })


def _timed_step(n_dev, vocab=64, n_steps=6):
    o = _opts()
    mesh = M.make_mesh(None, jax.devices()[:n_dev])
    model = create_model(o, vocab, vocab)
    params = model.init(jax.random.key(0))
    cfg = OptimizerConfig.from_options(o)
    st = init_state(cfg, params)
    params, st = place(params, st, mesh)
    step = build_train_step(model, cfg, LRSchedule.from_options(o),
                            "ce-mean-words", mesh, params, st,
                            donate=False)
    rs = np.random.RandomState(0)
    b = M.shard_batch({
        "src_ids": jnp.asarray(rs.randint(2, vocab, (PER_DEV_B * n_dev, T)),
                               jnp.int32),
        "src_mask": jnp.ones((PER_DEV_B * n_dev, T), jnp.float32),
        "trg_ids": jnp.asarray(rs.randint(2, vocab, (PER_DEV_B * n_dev, T)),
                               jnp.int32),
        "trg_mask": jnp.ones((PER_DEV_B * n_dev, T), jnp.float32)}, mesh)
    args = (b, jnp.asarray(1.0, jnp.float32), jax.random.key(1))
    p, s = params, st
    for _ in range(2):                      # compile + settle
        p, s, m = step(p, s, *args)
    jax.block_until_ready((p, s))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        p, s, m = step(p, s, *args)
    jax.block_until_ready((p, s))
    per_step = (time.perf_counter() - t0) / n_steps
    lowered = step.lower(params, st, *args).compile().as_text()
    return per_step, lowered, len(params)


@pytest.mark.slow
def test_scaling_overhead_bound_and_collective_budget():
    assert len(jax.devices()) >= 8
    t1, _, _ = _timed_step(1)
    t8, hlo8, n_leaves = _timed_step(8)
    ratio = t8 / t1
    # the N cores share the 8 virtual devices' serialized compute →
    # ideal wall ratio is 8 / min(8, cores) at fixed per-device batch
    # (8.0 on the usual 1-core box). Bounds leave headroom for timer
    # noise and in-process collective scheduling; a replicated-Adam or
    # re-replication regression lands well above, a vanished shard
    # (under-provisioned mesh) well below.
    import os
    ideal = 8.0 / min(8, os.cpu_count() or 1)
    assert ideal * 0.45 < ratio < ideal * 2.0 + 2.0, \
        f"8-dev/1-dev wall ratio {ratio:.2f} (ideal {ideal:.1f})"

    from marian_tpu.parallel.collectives import (collective_stats,
                                                 format_stats)
    stats = collective_stats(hlo8)
    # collective BUDGET at fixed model: one reduce-scatter and one
    # all-gather per param leaf per step, nothing param-sized in
    # all-reduce (the pattern test pins presence; this pins absence of
    # growth — e.g. a second all-gather per leaf from an EMA reshard)
    assert stats["reduce-scatter"]["count"] == n_leaves
    assert stats["all-gather"]["count"] == n_leaves
    assert stats.get("all-reduce", {"count": 0})["count"] <= 4
    print(f"\nscaling telemetry: t1={t1 * 1e3:.1f}ms "
          f"t8={t8 * 1e3:.1f}ms ratio={ratio:.2f} (ideal 8.0)\n"
          + format_stats(stats))
