"""Multi-host initialization (reference: src/training/communicator.cpp ::
initMPI / MPIWrapper; here jax.distributed over a localhost coordinator —
VERDICT r1 #7 'exercise multi-host init').

Two OS processes each expose 4 virtual CPU devices and form one 8-device
jax.distributed world; both run ONE identical data-parallel ZeRO-1 train
step through parallel/zero.py on a global mesh and must agree on the loss
to the last bit (the psum'd metrics are world-global)."""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    coord, pid = sys.argv[1], int(sys.argv[2])
    from marian_tpu.common.options import Options
    from marian_tpu.parallel.mesh import initialize_distributed
    initialize_distributed(Options({
        "multi-node": True, "coordinator-address": coord,
        "num-processes": 2, "process-id": pid}))
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    import jax.numpy as jnp
    import numpy as np
    from marian_tpu.models.encoder_decoder import create_model
    from marian_tpu.optimizers.optimizers import OptimizerConfig, init_state
    from marian_tpu.optimizers.schedule import LRSchedule
    from marian_tpu.parallel import mesh as M
    from marian_tpu.parallel.zero import build_train_step, place

    opts = Options({
        "type": "transformer", "dim-emb": 16, "transformer-heads": 2,
        "transformer-dim-ffn": 32, "enc-depth": 1, "dec-depth": 1,
        "tied-embeddings-all": True, "precision": ["float32", "float32"],
        "learn-rate": 0.01, "optimizer": "adam", "clip-norm": 1.0,
        "cost-type": "ce-mean-words",
    })
    mesh = M.make_mesh(None, jax.devices())
    model = create_model(opts, 31, 31)
    params = model.init(jax.random.key(0))
    opt_cfg = OptimizerConfig.from_options(opts)
    opt_state = init_state(opt_cfg, params)
    params, opt_state = place(params, opt_state, mesh)
    step = build_train_step(model, opt_cfg, LRSchedule.from_options(opts),
                            "ce-mean-words", mesh, params, opt_state,
                            donate=False)
    r = np.random.RandomState(5)
    host = {
        "src_ids": r.randint(2, 31, (8, 6)).astype("int32"),
        "src_mask": np.ones((8, 6), "float32"),
        "trg_ids": r.randint(2, 31, (8, 7)).astype("int32"),
        "trg_mask": np.ones((8, 7), "float32"),
    }
    # every process holds the full global batch; shard_batch lays it out
    # over the global mesh (jax.make_array_from_process-local data is
    # handled inside shard_batch via device_put on addressable shards)
    batch = M.shard_batch({k: jnp.asarray(v) for k, v in host.items()}, mesh)
    p2, o2, metrics = step(params, opt_state, batch,
                           jnp.asarray(1.0, jnp.float32), jax.random.key(1))
    jax.block_until_ready(p2)

    # data-parallel DECODE under multiprocess: the translator's mesh must
    # use only this process's ADDRESSABLE devices (4 of the 8 global) —
    # per-host independent decode, the reference's per-worker translator
    # decomposition. Both processes decode the same rows and must agree
    # exactly (placement-independent beam search).
    from marian_tpu.translator.beam_search import BeamSearch
    imodel = create_model(opts, 31, 31, inference=True)
    bs = BeamSearch(imodel, [params], None,
                    opts.with_(**{"beam-size": 2, "max-length": 12}), 31)
    assert bs.mesh is not None and bs.mesh.shape["data"] == 4, bs.mesh
    nb = bs.search(host["src_ids"][:5], host["src_mask"][:5])
    dec = [h[0]["tokens"] for h in nb]

    print("RESULT " + json.dumps({
        "pid": pid,
        "ce": float(metrics["ce_sum"]),
        "gnorm": float(metrics["gnorm"]),
        "decode": dec,
        "n_dev": len(jax.devices()),
        "n_proc": jax.process_count()}))
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dp_step(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    coord = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    procs = [subprocess.Popen(
        [sys.executable, str(worker), coord, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
        for i in range(2)]
    results = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("RESULT ")][0]
        results.append(json.loads(line[len("RESULT "):]))
    assert all(r["n_proc"] == 2 and r["n_dev"] == 8 for r in results)
    # the loss/gnorm are global psums — both hosts must agree exactly
    assert results[0]["ce"] == results[1]["ce"]
    assert results[0]["gnorm"] == results[1]["gnorm"]
    # per-host decode (local 4-device mesh each) agrees bitwise
    assert results[0]["decode"] == results[1]["decode"]
    assert len(results[0]["decode"]) == 5
    import numpy as np
    assert np.isfinite(results[0]["ce"])
