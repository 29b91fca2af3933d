"""Distributed (ZeRO-1 data-parallel) tests on the 8-virtual-device CPU mesh —
the coverage upgrade over the reference's real-2-GPU-only CI (SURVEY.md §4).

Gate (SURVEY.md §7 stage 5): the 8-device sharded step must produce the SAME
loss trajectory and parameters as the 1-device step on identical total
batches — SyncGraphGroup's contract that device count is a throughput knob,
not a semantics knob."""

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


def opts():
    return Options({
        "type": "transformer",
        "dim-emb": 32, "transformer-heads": 4, "transformer-dim-ffn": 64,
        "enc-depth": 2, "dec-depth": 2, "tied-embeddings-all": True,
        "precision": ["float32", "float32"], "max-length": 64,
        "label-smoothing": 0.1, "cost-type": "ce-mean-words",
        "learn-rate": 0.001, "optimizer": "adam",
        "optimizer-params": [0.9, 0.98, 1e-9], "clip-norm": 1.0,
        "exponential-smoothing": 1e-4,
    })


def batch(vocab, b=16, ts=12, tt=14, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "src_ids": jnp.asarray(rs.randint(2, vocab, (b, ts)), jnp.int32),
        "src_mask": jnp.ones((b, ts), jnp.float32),
        "trg_ids": jnp.asarray(rs.randint(2, vocab, (b, tt)), jnp.int32),
        "trg_mask": jnp.ones((b, tt), jnp.float32),
    }


_run_steps_memo = {}


def run_steps(n_devices, n_steps=4, vocab=19, force_gspmd=False):
    # memoized on the full argument tuple: the 8-device manual run is the
    # baseline of BOTH trajectory tests, and on this 1-core box the jit
    # compile dominates — pay it once per session. Training never mutates
    # its inputs (donate=False) and results are device_get'd copies.
    key = (n_devices, n_steps, vocab, force_gspmd)
    if key in _run_steps_memo:
        return _run_steps_memo[key]
    o = opts()
    devices = jax.devices()[:n_devices]
    mesh = M.make_mesh(None, devices)
    model = create_model(o, vocab, vocab)
    params = model.init(jax.random.key(7))
    opt_cfg = OptimizerConfig.from_options(o)
    opt_state = init_state(opt_cfg, params)
    params, opt_state = place(params, opt_state, mesh)
    schedule = LRSchedule.from_options(o)
    step = build_train_step(model, opt_cfg, schedule, "ce-mean-words", mesh,
                            params, opt_state, donate=False,
                            force_gspmd=force_gspmd)
    losses = []
    for i in range(n_steps):
        b = M.shard_batch(batch(vocab, seed=i), mesh)
        params, opt_state, metrics = step(
            params, opt_state, b, jnp.asarray(i + 1, jnp.float32),
            jax.random.key(0))  # train rng fixed; dropout off anyway
        losses.append(float(metrics["ce_sum"]) / float(metrics["labels"]))
    out = losses, jax.device_get(params), jax.device_get(opt_state)
    _run_steps_memo[key] = out
    return out


@pytest.mark.slow
class TestZero1DataParallel:
    def test_8dev_matches_1dev_trajectory(self):
        assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
        l1, p1, s1 = run_steps(1)
        l8, p8, s8 = run_steps(8)
        np.testing.assert_allclose(l1, l8, rtol=2e-4)
        for k in p1:
            if k.endswith("_bk"):
                continue  # structurally zero grad → Adam amplifies float noise
            np.testing.assert_allclose(p1[k], p8[k], rtol=2e-3, atol=2e-5,
                                       err_msg=k)

    def test_manual_and_gspmd_paths_agree(self):
        """The explicit scatter-reduce shard_map path and the GSPMD
        annotation path are two renderings of the SAME SyncGraphGroup
        semantics — head-to-head on the same 8-device mesh and batches
        they must produce matching trajectories and parameters (isolates
        manual-path bugs from batch-scaling effects; dropout off, so the
        rng-stream difference between the paths is inert)."""
        assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
        lm, pm, _ = run_steps(8)
        lg, pg, _ = run_steps(8, force_gspmd=True)
        np.testing.assert_allclose(lm, lg, rtol=2e-4)
        for k in pm:
            if k.endswith("_bk"):
                continue
            np.testing.assert_allclose(pm[k], pg[k], rtol=2e-3,
                                       atol=2e-5, err_msg=k)

    def test_opt_state_is_sharded(self):
        o = opts()
        vocab = 19
        mesh = M.make_mesh(None, jax.devices()[:8])
        model = create_model(o, vocab, vocab)
        params = model.init(jax.random.key(0))
        opt_cfg = OptimizerConfig.from_options(o)
        opt_state = init_state(opt_cfg, params)
        params, opt_state = place(params, opt_state, mesh)
        # a [dim_ffn, dim] tensor (64, 32): dim0 divisible by 8 → sharded
        leaf = opt_state["m"]["encoder_l1_ffn_W1"]
        shard_shapes = {s.data.shape for s in leaf.addressable_shards}
        assert shard_shapes == {(4, 64)}  # 32/8 rows per device
        # params stay replicated
        pleaf = params["encoder_l1_ffn_W1"]
        assert {s.data.shape for s in pleaf.addressable_shards} == {(32, 64)}

    def test_ema_state_sharded_and_used(self):
        from marian_tpu.optimizers.optimizers import smoothed_params
        o = opts()
        vocab = 19
        mesh = M.make_mesh(None, jax.devices()[:8])
        model = create_model(o, vocab, vocab)
        params = model.init(jax.random.key(0))
        opt_cfg = OptimizerConfig.from_options(o)
        opt_state = init_state(opt_cfg, params)
        params, opt_state = place(params, opt_state, mesh)
        sm = smoothed_params(opt_cfg, opt_state, params)
        for k in params:
            np.testing.assert_allclose(np.asarray(sm[k]),
                                       np.asarray(params[k]), rtol=1e-6)


class TestMeshSpec:
    def test_default_mesh_all_data(self):
        m = M.make_mesh(None, jax.devices()[:8])
        assert m.shape == {"data": 8, "model": 1, "seq": 1,
                           "pipe": 1, "expert": 1}

    def test_mesh_option_spec(self):
        o = Options({"mesh": ["data:4", "model:2"]})
        m = M.make_mesh(o, jax.devices()[:8])
        assert m.shape == {"data": 4, "model": 2, "seq": 1,
                           "pipe": 1, "expert": 1}

    def test_mesh_mismatch_raises(self):
        o = Options({"mesh": ["data:3"]})
        with pytest.raises(ValueError):
            M.make_mesh(o, jax.devices()[:8])

    def test_zero1_leaf_spec(self):
        m = M.make_mesh(None, jax.devices()[:8])
        from jax.sharding import PartitionSpec as P
        assert M.zero1_leaf_spec((64, 32), m) == P("data")
        assert M.zero1_leaf_spec((30, 64), m) == P(None, "data")
        assert M.zero1_leaf_spec((7, 5), m) == P()
        assert M.zero1_leaf_spec((), m) == P()


class TestZero1CollectivePattern:
    """Pin the compiled communication pattern of the ZeRO-1 step (VERDICT
    r3 #2): gradients must reduce-scatter onto their shard axis and updated
    params must all-gather back — NCCLCommunicator::scatterReduceAndReset-
    Grads / allGatherParams — with NO param-sized all-reduce. A sharding
    regression that degrades to all-reduce + replicated Adam keeps numerics
    bit-identical (every other test stays green) while inflating collective
    bytes ~1.5× and optimizer FLOPs N×; only the HLO shows it."""

    def _compiled_text(self):
        o = Options({
            "type": "transformer", "dim-emb": 16, "transformer-heads": 2,
            "transformer-dim-ffn": 32, "enc-depth": 1, "dec-depth": 1,
            "tied-embeddings-all": True, "precision": ["float32", "float32"],
            "max-length": 16, "label-smoothing": 0.1,
            "cost-type": "ce-mean-words", "learn-rate": 0.001,
            "optimizer": "adam", "optimizer-params": [0.9, 0.98, 1e-9],
            "clip-norm": 1.0, "exponential-smoothing": 1e-4,
        })
        vocab = 32
        mesh = M.make_mesh(None, jax.devices()[:8])
        model = create_model(o, vocab, vocab)
        params = model.init(jax.random.key(7))
        opt_cfg = OptimizerConfig.from_options(o)
        opt_state = init_state(opt_cfg, params)
        params, opt_state = place(params, opt_state, mesh)
        step = build_train_step(model, opt_cfg, LRSchedule.from_options(o),
                                "ce-mean-words", mesh, params, opt_state,
                                donate=False)
        b = M.shard_batch(batch(vocab, b=16, ts=8, tt=8), mesh)
        txt = step.lower(params, opt_state, b,
                         jnp.asarray(1.0, jnp.float32),
                         jax.random.key(0)).compile().as_text()
        return txt, params

    @pytest.mark.slow
    def test_reduce_scatter_plus_all_gather_no_fat_all_reduce(self):
        from marian_tpu.parallel.collectives import collective_stats
        txt, params = self._compiled_text()
        stats = collective_stats(txt)
        n_leaves = len(params)
        param_bytes = sum(int(np.prod(v.shape)) * 4 for v in params.values())

        # every sharded gradient leaf reduce-scatters; every updated param
        # leaf all-gathers back to replicated
        rs = stats.get("reduce-scatter", {"count": 0, "bytes": 0})
        ag = stats.get("all-gather", {"count": 0, "bytes": 0})
        assert rs["count"] == n_leaves, (rs, n_leaves)
        assert ag["count"] == n_leaves, (ag, n_leaves)
        # reduce-scatter outputs are the 1/8 shards of what all-gather
        # reassembles — byte accounting ties the two ends of the cycle
        assert rs["bytes"] * 8 == ag["bytes"] == param_bytes

        # all-reduces may only carry scalar reductions (loss sums, global
        # grad norm) — never a parameter-sized gradient. The smallest param
        # leaf here is 16 elems; scalar tuples stay well under it.
        ar = stats.get("all-reduce", {"max_elems": 0, "bytes": 0})
        assert ar["max_elems"] < 16, f"param-sized all-reduce: {ar}"
        assert ar["bytes"] < 0.02 * param_bytes

    @pytest.mark.slow
    def test_collective_bytes_accounting(self):
        from marian_tpu.parallel.collectives import (collective_stats,
                                                     format_stats)
        hlo = """
          %rs = f32[4,16]{1,0} reduce-scatter(%a), channel_id=1
          %ag.1 = f32[32,16]{1,0} all-gather(%b), channel_id=2
          %ar = (f32[], f32[8]{0}) all-reduce(%c, %d), channel_id=3
          %ars = bf16[64]{0} all-reduce-start(%e), channel_id=4
          %ard = bf16[64]{0} all-reduce-done(%ars), channel_id=4
          %ags = (f32[4,16]{1,0}, f32[32,16]{1,0}) all-gather-start(%f), channel_id=5
          %agd = f32[32,16]{1,0} all-gather-done(%ags), channel_id=5
          %cps = (f32[8]{0}, f32[8]{0}, u32[], u32[]) collective-permute-start(%g), channel_id=6
        """
        s = collective_stats(hlo)
        assert s["reduce-scatter"] == {"count": 1, "bytes": 256,
                                       "max_elems": 64}
        # async -start tuples count only the transferred result buffer
        # (not the operand alias / u32 context members); -done skipped
        assert s["all-gather"] == {"count": 2, "bytes": 2048 * 2,
                                   "max_elems": 512}
        assert s["collective-permute"] == {"count": 1, "bytes": 32,
                                           "max_elems": 8}
        # sync tuple members (combiner-grouped results) DO sum
        assert s["all-reduce"]["count"] == 2
        assert s["all-reduce"]["bytes"] == (1 + 8) * 4 + 64 * 2
        assert "all-reduce" in format_stats(s)


class TestBufferDonation:
    def test_train_step_aliases_all_state_buffers(self):
        """Every param + optimizer-state leaf must be donated (aliased
        input→output) in the compiled train step — a lost alias doubles
        HBM for that buffer and adds a device copy per update (VERDICT r1
        asked for donation to be *verified*, not assumed)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from marian_tpu.common.options import Options
        from marian_tpu.models.encoder_decoder import create_model
        from marian_tpu.optimizers.optimizers import (OptimizerConfig,
                                                      init_state)
        from marian_tpu.optimizers.schedule import LRSchedule
        from marian_tpu.parallel import mesh as M
        from marian_tpu.parallel.zero import build_train_step, place

        opts = Options({
            "type": "transformer", "dim-emb": 16, "transformer-heads": 2,
            "transformer-dim-ffn": 32, "enc-depth": 1, "dec-depth": 1,
            "tied-embeddings-all": True, "precision": ["float32", "float32"],
            "learn-rate": 1e-3, "optimizer": "adam", "clip-norm": 0.0,
            "cost-type": "ce-mean-words", "max-length": 16,
        })
        mesh = M.make_mesh(None, jax.devices()[:1])
        model = create_model(opts, 31, 31)
        params = model.init(jax.random.key(0))
        cfg = OptimizerConfig.from_options(opts)
        st = init_state(cfg, params)
        params, st = place(params, st, mesh)
        step = build_train_step(model, cfg, LRSchedule.from_options(opts),
                                "ce-mean-words", mesh, params, st,
                                donate=True)
        r = np.random.RandomState(0)
        batch = M.shard_batch({
            "src_ids": jnp.asarray(r.randint(2, 31, (8, 8)), jnp.int32),
            "src_mask": jnp.ones((8, 8), jnp.float32),
            "trg_ids": jnp.asarray(r.randint(2, 31, (8, 8)), jnp.int32),
            "trg_mask": jnp.ones((8, 8), jnp.float32)}, mesh)
        txt = step.lower(params, st, batch, jnp.asarray(1.0, jnp.float32),
                         jax.random.key(1)).compile().as_text()
        head = txt.split("entry_computation_layout")[0]
        n_leaves = len(params) + sum(
            len(v) if isinstance(v, dict) else 1 for v in st.values())
        assert head.count("may-alias") >= n_leaves


@pytest.mark.slow
class TestGradientDtype:
    """--gradient-dtype bfloat16 (r5): gradients produced/reduce-scattered
    in bf16, optimizer math still f32. Marian's fp16 gradient-communication
    analogue — the trajectory must stay close to f32 grads, and the ZeRO-1
    reduce-scatter bytes must HALVE."""

    def _run(self, grad_dtype, n_steps=4, vocab=19):
        o = opts().with_(**{"precision": ["bfloat16", "float32"],
                            "gradient-dtype": grad_dtype})
        devices = jax.devices()[:8]
        mesh = M.make_mesh(None, devices)
        model = create_model(o, vocab, vocab)
        params = model.init(jax.random.key(7))
        opt_cfg = OptimizerConfig.from_options(o)
        opt_state = init_state(opt_cfg, params)
        params, opt_state = place(params, opt_state, mesh)
        step = build_train_step(model, opt_cfg, LRSchedule.from_options(o),
                                "ce-mean-words", mesh, params, opt_state,
                                donate=False,
                                grad_dtype=grad_dtype)
        losses = []
        for i in range(n_steps):
            b = M.shard_batch(batch(vocab, seed=i), mesh)
            params, opt_state, metrics = step(
                params, opt_state, b, jnp.asarray(i + 1, jnp.float32),
                jax.random.key(0))
            losses.append(float(metrics["ce_sum"]) / float(metrics["labels"]))
        lowered = step.lower(params, opt_state,
                             M.shard_batch(batch(vocab, seed=0), mesh),
                             jnp.asarray(1.0, jnp.float32), jax.random.key(0))
        return losses, lowered.as_text()

    def test_bf16_grads_close_trajectory_and_bf16_reduce_scatter(self):
        import re
        l32, txt32 = self._run("float32")
        l16, txt16 = self._run("bfloat16")
        # same data, same init: trajectories agree to bf16 rounding of the
        # gradient signal (the compute path is bf16 in BOTH runs)
        np.testing.assert_allclose(l32, l16, rtol=3e-2)
        # the program-level collective dtype IS the wire dtype on TPU
        # (bf16 collectives are native; the CPU test backend legalizes
        # them back to f32 post-partitioning, so the COMPILED text can't
        # be pinned here — program-level stablehlo can)
        def rs_dtypes(txt):
            return set(re.findall(
                r"reduce_scatter.*?\(tensor<[^>]*?x(bf16|f32)>\)", txt,
                re.S))
        assert rs_dtypes(txt32) == {"f32"}
        assert rs_dtypes(txt16) == {"bf16"}

    def test_f32_precision_refuses_bf16_grads(self):
        # f32 compute + bf16 grads would silently change the compute dtype
        # (the pre-cast makes model.loss's cast an identity) — the
        # machinery must warn and fall back to f32 grads
        from marian_tpu.parallel.zero import _GradMachinery
        o = opts()  # f32 precision
        vocab = 19
        model = create_model(o, vocab, vocab)
        params = model.init(jax.random.key(7))
        mesh = M.make_mesh(None, jax.devices()[:1])
        m = _GradMachinery(model, mesh, params, grad_dtype="bfloat16")
        assert m.grad_dtype is None


class TestGradientDtypeFailClosed:
    """The compute-dtype safety check fails CLOSED: a model whose compute
    dtype cannot be determined (no model.cfg) must not silently get bf16
    grads applied — it could be an f32-precision model (ISSUE 1
    satellite)."""

    def test_undeterminable_compute_dtype_forces_f32_grads(self):
        from marian_tpu.parallel.zero import _GradMachinery

        class NoCfgModel:          # e.g. a custom/legacy model family
            pass

        params = {"w": jnp.zeros((4, 4), jnp.float32)}
        mesh = M.make_mesh(None, jax.devices()[:1])
        m = _GradMachinery(NoCfgModel(), mesh, params,
                           grad_dtype="bfloat16")
        assert m.grad_dtype is None
