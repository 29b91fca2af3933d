"""Tensor-parallel + sequence-parallel correctness on the 8-device CPU mesh.

The strong invariant (SURVEY.md §4 "we can do better than the reference's
2-real-GPUs CI gap"): the SAME train step run (a) single-device, (b) pure-DP,
(c) dp×tp×sp sharded must produce the same loss/gradients up to fp tolerance,
because GSPMD partitioning and ring collectives are numerically equivalent
reorderings of the dense program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.optimizers.optimizers import OptimizerConfig, init_state
from marian_tpu.optimizers.schedule import LRSchedule
from marian_tpu.parallel import mesh as M
from marian_tpu.parallel import tensor as T
from marian_tpu.parallel.zero import build_train_step, place
from marian_tpu.parallel.sequence import ring_attention_sharded
from marian_tpu.ops.attention import dense_attention


VOCAB = 64


def _options(mesh=None, sp="none"):
    return Options({
        **({"mesh": mesh} if mesh else {}),
        "sequence-parallel": sp,
        "type": "transformer",
        "dim-emb": 32, "transformer-heads": 8, "transformer-dim-ffn": 64,
        "enc-depth": 2, "dec-depth": 2,
        "tied-embeddings-all": True,
        "precision": ["float32", "float32"],
        "label-smoothing": 0.0,
        "cost-type": "ce-mean-words",
        "learn-rate": 1e-3, "optimizer": "adam",
        "clip-norm": 0.0,
        "max-length": 32,
    })


def _batch(b=8, t=16, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "src_ids": jnp.asarray(rs.randint(2, VOCAB, (b, t)), jnp.int32),
        "src_mask": jnp.ones((b, t), jnp.float32),
        "trg_ids": jnp.asarray(rs.randint(2, VOCAB, (b, t)), jnp.int32),
        "trg_mask": jnp.ones((b, t), jnp.float32),
    }


def _run_step(mesh_spec, devices, optimizer="adam", sp="none"):
    opts = _options(mesh_spec, sp=sp)
    opts.set("optimizer", optimizer)
    opts.set("num-devices", len(devices))
    mesh = M.make_mesh(opts, devices)
    model = create_model(opts, VOCAB, VOCAB)
    params = model.init(jax.random.key(0))
    p0 = jax.device_get(params)
    opt_cfg = OptimizerConfig.from_options(opts)
    opt_state = init_state(opt_cfg, params)
    params, opt_state = place(params, opt_state, mesh)
    step = build_train_step(model, opt_cfg, LRSchedule.from_options(opts),
                            "ce-mean-words", mesh, params, opt_state,
                            donate=False)
    batch = M.shard_batch(_batch(), mesh)
    p2, _, metrics = step(params, opt_state, batch,
                          jnp.asarray(1.0, jnp.float32), jax.random.key(1))
    p2 = jax.device_get(p2)
    deltas = {k: p2[k] - p0[k] for k in p0}
    return float(metrics["ce_sum"]), deltas


class TestTensorParallel:
    def test_specs_cover_transformer_params(self):
        opts = _options(["data:2", "model:2", "seq:2"])
        mesh = M.make_mesh(opts, jax.devices()[:8])
        model = create_model(opts, VOCAB, VOCAB)
        params = model.init(jax.random.key(0))
        specs = T.tp_param_specs(params, mesh)
        # every attention/ffn matmul weight must actually be model-sharded
        sharded = [k for k, s in specs.items() if "model" in jax.tree_util.tree_leaves(tuple(s))]
        for pat in ("_Wq", "_Wk", "_Wv", "_Wo", "_ffn_W1", "_ffn_W2", "Wemb"):
            assert any(pat in k for k in sharded), f"no model-sharding for {pat}"

    def test_zero1_composes_with_tp(self):
        opts = _options(["data:2", "model:2", "seq:2"])
        mesh = M.make_mesh(opts, jax.devices()[:8])
        spec = T.zero1_combined_spec(
            jax.sharding.PartitionSpec(None, "model"), (32, 32), mesh)
        assert tuple(spec) == ("data", "model")

    def test_tp_sp_matches_single_device_loss(self):
        # SGD so the param delta is LINEAR in the gradient (Adam's t=1 update
        # is sign(g), unstable for near-zero grads across reduction orders)
        devices = jax.devices()
        assert len(devices) >= 8
        loss_1, d_1 = _run_step(["data:1", "model:1", "seq:1"], devices[:1],
                                optimizer="sgd")
        loss_dp, d_dp = _run_step(["data:8"], devices[:8], optimizer="sgd")
        loss_tp, d_tp = _run_step(["data:2", "model:2", "seq:2"], devices[:8],
                                  optimizer="sgd")
        assert abs(loss_dp - loss_1) / abs(loss_1) < 1e-4
        assert abs(loss_tp - loss_1) / abs(loss_1) < 1e-4
        # gradient (= param delta / lr) identical across sharding layouts.
        # _bk is skipped: the q·bk score term is constant over keys, softmax
        # cancels it, so its analytic grad is 0 — computed values are pure
        # cancellation noise that differs across reduction orders.
        for k in d_1:
            if k.endswith("_bk"):
                continue
            scale = max(np.abs(d_1[k]).max(), 1e-8)
            np.testing.assert_allclose(d_tp[k] / scale, d_1[k] / scale,
                                       atol=1e-3, err_msg=k)
            np.testing.assert_allclose(d_dp[k] / scale, d_1[k] / scale,
                                       atol=1e-3, err_msg=k)


class TestSequenceParallel:
    @pytest.mark.parametrize("sp", ["ring", "ulysses"])
    def test_sp_training_step_matches_dense(self, sp):
        """Full train step with ring/ulysses attention INSIDE the model
        (shard_map within the GSPMD-jitted step) matches the dense program."""
        devices = jax.devices()
        loss_1, d_1 = _run_step(["data:1", "model:1", "seq:1"], devices[:1],
                                optimizer="sgd")
        loss_sp, d_sp = _run_step(["data:2", "model:2", "seq:2"], devices[:8],
                                  optimizer="sgd", sp=sp)
        assert abs(loss_sp - loss_1) / abs(loss_1) < 1e-4
        for k in d_1:
            if k.endswith("_bk"):
                continue  # analytic grad 0 (softmax shift-invariance), noise
            scale = max(np.abs(d_1[k]).max(), 1e-8)
            np.testing.assert_allclose(d_sp[k] / scale, d_1[k] / scale,
                                       atol=1e-3, err_msg=k)

    @pytest.mark.parametrize("mode", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mode, causal):
        opts = _options(["data:1", "model:1", "seq:8"])
        mesh = M.make_mesh(opts, jax.devices()[:8])
        rs = np.random.RandomState(7)
        b, h, t, dh = 2, 8, 32, 8
        q = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        k = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        v = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        kv_mask = jnp.asarray(rs.rand(b, t) > 0.2, jnp.float32)
        # keep at least position 0 unmasked per row
        kv_mask = kv_mask.at[:, 0].set(1.0)

        out = ring_attention_sharded(mesh, q, k, v, kv_mask=kv_mask,
                                     causal=causal, mode=mode)
        mask = kv_mask[:, None, None, :]
        if causal:
            mask = mask * jnp.tril(jnp.ones((t, t)))[None, None]
        ref = dense_attention(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_grad_finite_with_empty_rows(self):
        """Batch-padding sentences have all-zero masks (bucket_batch_size
        pads B to a multiple of 8); the ring backward must stay finite
        (regression: o/l with l=0 produced inf*0=NaN in the VJP)."""
        opts = _options(["data:1", "model:1", "seq:2"])
        opts.set("num-devices", 2)
        mesh = M.make_mesh(opts, jax.devices()[:2])
        rs = np.random.RandomState(5)
        b, h, t, dh = 4, 2, 8, 4
        q = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        k = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        v = jnp.asarray(rs.randn(b, h, t, dh), jnp.float32)
        kv_mask = np.ones((b, t), np.float32)
        kv_mask[2:, :] = 0.0                     # empty padding rows
        kv_mask[0, 3:] = 0.0                     # plus a fully-masked chunk
        kv_mask = jnp.asarray(kv_mask)

        def f(q, k, v):
            out = ring_attention_sharded(mesh, q, k, v, kv_mask=kv_mask,
                                         causal=True)
            return jnp.sum(out ** 2)

        val = f(q, k, v)
        grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        assert np.isfinite(float(val))
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))

    def test_ring_is_differentiable(self):
        opts = _options(["data:1", "model:1", "seq:8"])
        mesh = M.make_mesh(opts, jax.devices()[:8])
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(1, 2, 16, 4), jnp.float32)
        k = jnp.asarray(rs.randn(1, 2, 16, 4), jnp.float32)
        v = jnp.asarray(rs.randn(1, 2, 16, 4), jnp.float32)

        def f_ring(q, k, v):
            return jnp.sum(ring_attention_sharded(mesh, q, k, v, causal=True))

        def f_dense(q, k, v):
            t = q.shape[2]
            m = jnp.tril(jnp.ones((t, t)))[None, None]
            return jnp.sum(dense_attention(q, k, v, m))

        g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-5, atol=2e-5)


class TestGraftEntry:
    def test_dryrun_multichip_8(self):
        import importlib.util
        import os
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "__graft_entry__.py")
        spec = importlib.util.spec_from_file_location("__graft_entry__", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.dryrun_multichip(8)


class TestFusedQKVGateOnTPMesh:
    """r4-advisor medium finding: a plain ``--mesh model:N`` run (no
    --sequence-parallel, so seq_mesh is None) must still see the 'model'
    axis and NOT fuse Q/K/V — the runtime concat crosses the Megatron
    column split and GSPMD would replicate the attention weights."""

    def test_plain_tp_mesh_sets_n_model_tp(self):
        from marian_tpu.models import transformer as TT
        cfg = TT.config_from_options(_options(["model:2"]), VOCAB, VOCAB)
        assert cfg.seq_mesh is None          # the advisor's exact case
        assert cfg.n_model_tp == 2

    def test_no_mesh_keeps_fusion_eligible(self):
        from marian_tpu.models import transformer as TT
        cfg = TT.config_from_options(_options(), VOCAB, VOCAB)
        assert cfg.n_model_tp == 1

    def test_data_only_mesh_keeps_fusion_eligible(self):
        from marian_tpu.models import transformer as TT
        cfg = TT.config_from_options(_options(["data:8"]), VOCAB, VOCAB)
        assert cfg.n_model_tp == 1
