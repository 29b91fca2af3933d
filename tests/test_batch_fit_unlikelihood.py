"""--mini-batch-fit empirical budget search + --unlikelihood-loss
(reference: GraphGroup::collectStats; layers/loss.h unlikelihood)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common import Options
from marian_tpu.layers.loss import cross_entropy_loss
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.training.graph_group import GraphGroup

from test_model import fake_batch


@pytest.fixture
def rng():
    return np.random.RandomState(17)


class TestMiniBatchFit:
    @pytest.mark.parametrize("message,fit_result", [
        # HBM: at compile time, and from the runtime allocator
        ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
         "memory in memory space hbm. Used 24.60G of 15.75G hbm.", True),
        ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
         "1073741824 bytes.", True),
        # a kernel's on-chip refusal: a compile failure, never "too big"
        ("RESOURCE_EXHAUSTED: Allocation (size=1572864) would exceed memory "
         "(size=1048576) :: #allocation5 [shape = 'u8[1572864]{0}', "
         "space=smem, size = 0x180000, tag = 'prefetched SMEM operand 0']",
         False),
        ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
         "allocating on stack: Scoped allocation with size 19.09M and limit "
         "16.00M exceeded scoped vmem limit by 3.09M.", False),
        ("INVALID_ARGUMENT: Unimplemented primitive in Pallas TPU lowering",
         False),
    ])
    def test_only_hbm_exhaustion_is_a_fit_result(self, message, fit_result):
        from marian_tpu.training.batch_fit import _oom
        assert _oom(RuntimeError(message)) is fit_result

    def test_kernel_refusal_propagates_instead_of_shrinking(self):
        from marian_tpu.training import batch_fit

        class Refused:
            delay = 1
            params = {}

            def update(self, *a):
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
                    "smem. Used 1.50M of 1.00M smem.")
        with pytest.raises(RuntimeError, match="memory space smem"):
            batch_fit._try_budget(Refused(), 2048, 50, 100)

    def test_search_converges_to_cap_when_memory_suffices(self):
        opts = Options({
            "type": "transformer", "dim-emb": 16, "transformer-heads": 2,
            "transformer-dim-ffn": 32, "enc-depth": 1, "dec-depth": 1,
            "tied-embeddings-all": True, "precision": ["float32", "float32"],
            "learn-rate": 0.01, "optimizer": "adam", "clip-norm": 0.0,
            "cost-type": "ce-mean-words", "max-length": 16,
        })
        model = create_model(opts, 31, 31)
        gg = GraphGroup(model, opts)
        gg.initialize(jax.random.key(0))
        from marian_tpu.training.batch_fit import fit_mini_batch_words
        fitted = fit_mini_batch_words(gg, opts, 31, cap=1024)
        # CPU never OOMs at these sizes → the search must hit the cap
        assert fitted == 1024


class TestUnlikelihood:
    def test_sign_selects_objective(self, rng):
        b, t, v = 2, 4, 12
        logits = jnp.asarray(rng.randn(b, t, v), jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, (b, t)), jnp.int32)
        mask = jnp.ones((b, t), jnp.float32)
        pos_w = jnp.ones((b, t), jnp.float32)
        neg_w = -jnp.ones((b, t), jnp.float32)
        rl_pos = cross_entropy_loss(logits, labels, mask, 0.0, pos_w,
                                    unlikelihood=True)
        rl_base = cross_entropy_loss(logits, labels, mask, 0.0)
        np.testing.assert_allclose(float(rl_pos.loss_sum),
                                   float(rl_base.loss_sum), rtol=1e-6)
        rl_neg = cross_entropy_loss(logits, labels, mask, 0.0, neg_w,
                                    unlikelihood=True)
        # unlikelihood of the same tokens is a different, finite number
        assert np.isfinite(float(rl_neg.loss_sum))
        assert float(rl_neg.loss_sum) != pytest.approx(
            float(rl_base.loss_sum))

    def test_unlikelihood_pushes_probability_down(self, rng):
        """Gradient descent on -log(1-p) must DECREASE p(label)."""
        v = 8
        logits = jnp.zeros((1, 1, v), jnp.float32)
        labels = jnp.asarray([[3]], jnp.int32)
        mask = jnp.ones((1, 1), jnp.float32)
        neg_w = -jnp.ones((1, 1), jnp.float32)

        def loss(lg):
            return cross_entropy_loss(lg, labels, mask, 0.0, neg_w,
                                      unlikelihood=True).loss_sum

        g = jax.grad(loss)(logits)
        lg2 = logits - 1.0 * g
        p0 = jax.nn.softmax(logits[0, 0])[3]
        p1 = jax.nn.softmax(lg2[0, 0])[3]
        assert float(p1) < float(p0)

    def test_model_level_flag(self, rng):
        opts = Options({
            "type": "transformer", "dim-emb": 16, "transformer-heads": 2,
            "transformer-dim-ffn": 32, "enc-depth": 1, "dec-depth": 1,
            "tied-embeddings-all": True, "precision": ["float32", "float32"],
            "max-length": 32, "unlikelihood-loss": True,
        })
        model = create_model(opts, 23, 23)
        params = model.init(jax.random.key(0))
        batch = dict(fake_batch(rng, b=2, ts=5, tt=6, vocab=23))
        batch["data_weights"] = jnp.asarray(
            rng.choice([-1.0, 1.0], (2, 6)), jnp.float32)
        total, aux = model.loss(params, batch, key=None, train=False)
        assert np.isfinite(float(total))
