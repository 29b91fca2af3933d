"""Expert-parallel MoE FFN (--transformer-moe-experts) and pipeline
('pipe') depth-sharded parameter storage — the TPU extensions that complete
the dp/tp/sp/pp/ep sharding matrix (the reference scales only by data
parallelism; SURVEY §2.7)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.common import prng
from marian_tpu.models import transformer as T
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.training.graph_group import GraphGroup


def _opts(mesh=None, n=1, **kw):
    base = {"type": "transformer", "dim-emb": 32, "transformer-heads": 4,
            "transformer-dim-ffn": 64, "enc-depth": 2, "dec-depth": 2,
            "tied-embeddings-all": True,
            "precision": ["float32", "float32"],
            "label-smoothing": 0.1, "cost-type": "ce-mean-words",
            "learn-rate": 3e-4, "optimizer": "adam", "clip-norm": 1.0,
            "devices": [str(i) for i in range(n)], "seed": 7}
    base.update(kw)
    if mesh:
        base["mesh"] = mesh
    return Options(base)


def _batch(rng, v=64, b=8, ts=12, tt=12):
    return {
        "src_ids": jnp.asarray(rng.randint(2, v, (b, ts)), jnp.int32),
        "src_mask": jnp.ones((b, ts), jnp.float32),
        "trg_ids": jnp.asarray(rng.randint(2, v, (b, tt)), jnp.int32),
        "trg_mask": jnp.ones((b, tt), jnp.float32),
    }


class TestMoEMath:
    def test_forward_and_aux(self, rng):
        o = _opts(**{"transformer-moe-experts": 4})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        assert params["encoder_l1_moe_W1"].shape == (4, 32, 64)
        total, aux = model.loss(params, _batch(rng), None, train=False)
        assert np.isfinite(float(total))
        # balanced-ish router at init: aux near 1 (perfect balance = 1.0)
        assert 0.5 < float(aux["moe_aux"]) / 4 < 2.0   # 4 MoE layers

    def test_router_gradients_flow(self, rng):
        o = _opts(**{"transformer-moe-experts": 4})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        g = jax.grad(lambda p: model.loss(p, _batch(rng), None,
                                          train=False)[0])(params)
        assert float(jnp.sum(jnp.abs(g["encoder_l1_moe_gate"]))) > 0
        assert float(jnp.sum(jnp.abs(g["decoder_l2_moe_W2"]))) > 0

    def test_top1_switch_routing(self, rng):
        o = _opts(**{"transformer-moe-experts": 4,
                     "transformer-moe-top-k": 1})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        total, _ = model.loss(params, _batch(rng), None, train=False)
        assert np.isfinite(float(total))

    def test_capacity_overflow_falls_through_residual(self, rng):
        """With capacity factor ~0, every token overflows → the MoE update
        is (near-)zero and the layer reduces to the residual stream."""
        x = jnp.asarray(rng.randn(2, 8, 32), jnp.float32)
        o = _opts(**{"transformer-moe-experts": 4})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        cfg = model.cfg
        import dataclasses
        tiny = dataclasses.replace(cfg, moe_capacity_factor=1e-9)
        out, _ = T._moe_ffn(tiny, params, "encoder_l1_moe", x, train=True)
        # capacity clamps to 1 slot per expert: at most E tokens routed
        nonzero_tokens = int((jnp.abs(out).sum(-1) > 1e-6).sum())
        assert nonzero_tokens <= 4
        full = dataclasses.replace(cfg, moe_capacity_factor=8.0)
        out_full, _ = T._moe_ffn(full, params, "encoder_l1_moe", x,
                                 train=True)
        assert int((jnp.abs(out_full).sum(-1) > 1e-6).sum()) == 16

    def test_decode_matches_teacher_forcing(self, rng):
        o = _opts(**{"transformer-moe-experts": 4})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        v = 64
        src = jnp.asarray(rng.randint(2, v, (2, 5)), jnp.int32)
        mask = jnp.ones((2, 5), jnp.float32)
        trg = jnp.asarray(rng.randint(2, v, (2, 4)), jnp.int32)
        enc = model.encode_for_decode(params, src, mask)
        tf = T.decode_train(model.cfg, T.cast_params(
            params, model.cfg.compute_dtype), enc, mask, trg,
            jnp.ones((2, 4), jnp.float32), train=False)
        state = model.start_state(params, enc, mask, max_len=4)
        prev = jnp.zeros((2, 1), jnp.int32)
        for t in range(4):
            logits, state = model.step(params, state, prev, mask)
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(tf[:, t]),
                                       rtol=2e-3, atol=2e-3)
            prev = trg[:, t:t + 1]


class TestPadExclusion:
    def test_pads_claim_no_capacity_or_aux(self, rng):
        """Padding tokens must not displace real tokens from expert
        capacity nor skew the load-balance statistics."""
        import dataclasses
        o = _opts(**{"transformer-moe-experts": 4})
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        cfg = dataclasses.replace(model.cfg, moe_capacity_factor=1.0)
        x = jnp.asarray(rng.randn(1, 8, 32), jnp.float32)
        mask_full = jnp.ones((1, 8), jnp.float32)
        mask_half = mask_full.at[:, 4:].set(0.0)
        out_f, aux_f = T._moe_ffn(cfg, params, "encoder_l1_moe", x,
                                  train=True, mask=mask_full)
        out_h, aux_h = T._moe_ffn(cfg, params, "encoder_l1_moe", x,
                                  train=True, mask=mask_half)
        # masked positions produce exactly zero MoE output
        assert float(jnp.abs(out_h[:, 4:]).max()) == 0.0
        # real-token outputs are unaffected by pads' previous claims:
        # with only 4 real tokens and capacity for 8*1.0*2/4=4 per
        # expert, none of the real tokens can overflow
        assert float(jnp.abs(out_h[:, :4]).sum()) > 0
        assert np.isfinite(float(aux_h)) and float(aux_h) > 0


class TestStackRoundTrip:
    def test_stack_unstack_identity(self):
        o = _opts()
        model = create_model(o, 64, 64)
        params = model.init(jax.random.key(0))
        stacked = T.stack_layer_params(model.cfg, params)
        assert any("_stack_" in k for k in stacked)
        assert not any("_l1_" in k for k in stacked)
        back = T.unstack_layer_params(model.cfg, stacked)
        assert set(back) == set(params)
        for k in params:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(params[k]))


@pytest.mark.slow
class TestShardedEquivalence:
    """8-virtual-CPU-device mesh (conftest) equivalences."""

    def _loss_after(self, o, batch, steps=2, micro=False):
        model = create_model(o, 64, 64)
        gg = GraphGroup(model, o)
        gg.initialize(prng.root_key(7))
        out = None
        for s in range(steps):
            payload = [dict(b) for b in batch] if micro else dict(batch)
            out = gg.update(payload, s + 1, jax.random.key(3 + s))
        return float(out.loss_sum), gg

    def test_pipe_matches_single(self, rng):
        b = _batch(rng)
        single, _ = self._loss_after(_opts(n=1), b)
        piped, gg = self._loss_after(
            _opts(mesh=["data:2", "model:2", "pipe:2"], n=8), b)
        assert gg._stacked
        assert abs(single - piped) / abs(single) < 1e-5

    def test_expert_pipe_matches_single(self, rng):
        b = _batch(rng)
        kw = {"transformer-moe-experts": 4}
        single, _ = self._loss_after(_opts(n=1, **kw), b)
        sharded, _ = self._loss_after(
            _opts(mesh=["data:2", "pipe:2", "expert:2"], n=8, **kw), b)
        assert abs(single - sharded) / abs(single) < 1e-5

    def test_stacked_checkpoint_is_marian_flat(self, rng, tmp_path):
        from marian_tpu.common.io import load_model
        from marian_tpu.training.checkpoint import save_checkpoint
        o = _opts(mesh=["data:2", "model:2", "pipe:2"], n=8)
        model = create_model(o, 64, 64)
        gg = GraphGroup(model, o)
        gg.initialize(prng.root_key(7))
        gg.update(_batch(rng), 1, jax.random.key(1))
        path = str(tmp_path / "m.npz")
        from marian_tpu.training.training_state import TrainingState
        save_checkpoint(path, gg.export_params(), "{}", gg,
                        TrainingState())
        items, _cfg = load_model(path)
        assert any(k.startswith("encoder_l1_") for k in items)
        assert not any("_stack_" in k for k in items)
        opt = np.load(path + ".optimizer.npz")
        assert any(":encoder_l2_" in k or k.startswith("m:encoder_l2_")
                   for k in opt.files)

    def test_pipe_with_delay(self, rng):
        """Depth-stacked storage composes with --optimizer-delay: two
        micro-batches of different widths are accumulated over the stacked
        parameters (the sum keeps their stacked, pipe-sharded layout) and
        the second update's cost, which the first update's parameters
        make, is the single device's."""
        micro = [_batch(rng), _batch(rng, ts=9, tt=10)]
        single, _ = self._loss_after(
            _opts(n=1, **{"optimizer-delay": 2}), micro, micro=True)
        piped, gg = self._loss_after(
            _opts(mesh=["data:2", "model:2", "pipe:2"], n=8,
                  **{"optimizer-delay": 2}), micro, micro=True)
        assert gg._stacked and gg.delay == 2
        assert abs(single - piped) / abs(single) < 1e-5

    def test_pipe_refuses_tied_layers(self):
        o = _opts(mesh=["data:2", "model:2", "pipe:2"], n=8,
                  **{"transformer-tied-layers": [1, 1]})
        model = create_model(o, 64, 64)
        gg = GraphGroup(model, o)
        with pytest.raises(ValueError, match="tied"):
            gg.initialize(prng.root_key(0))
