"""--precompile-buckets: the train step compiled ahead, on host threads,
for every shape the loader's bucket table can give (training/
graph_group.py :: GraphGroup._compile_ahead, data/batch_generator.py ::
budget_shapes)."""

import jax
import jax.numpy as jnp
import numpy as np

from marian_tpu.common import prng
from marian_tpu.common.config_parser import parse_options
from marian_tpu.data.batch_generator import budget_shapes
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.training.graph_group import GraphGroup

VOCAB = 96


def _bucket_updates(threads, widths=(16, 32, 48, 32, 24)):
    """A few updates of a one-layer plan over a three-bucket table, as
    the trainer's loop makes them; 24 is no bucket of the table."""
    argv = ["--type", "transformer-lm", "--transformer-layer-plan",
            "mla:dense", "--dim-emb", "32", "--transformer-heads", "2",
            "--transformer-dim-ffn", "64", "--plan-mla-dim-nope", "8",
            "--plan-mla-dim-shared", "8", "--plan-mla-dim-v", "8",
            "--plan-mla-latent", "16", "--precision", "float32", "float32",
            "--train-sets", "x", "--vocabs", "v", "--length-buckets", "16",
            "32", "48", "--mini-batch-words", "64", "--batch-row-multiple",
            "1", "--learn-rate", "0.01", "--precompile-buckets",
            str(threads), "--devices", "0"]
    opts = parse_options(argv, mode="training")
    model = create_model(opts, VOCAB, VOCAB)
    gg = GraphGroup(model, opts)
    key = prng.root_key(5)
    gg.initialize(key, jax.jit(model.init)(key))
    costs = []
    for step, width in enumerate(widths, 1):
        rows = 64 // width
        ids = np.asarray(jax.random.randint(
            jax.random.PRNGKey(step), (rows, width), 2, VOCAB))
        lens = np.full((rows,), width - 3, np.int32)
        batch = {"src_tok": jnp.asarray(ids.astype(np.uint16)),
                 "src_len": jnp.asarray(lens),
                 "trg_tok": jnp.asarray(ids.astype(np.uint16)),
                 "trg_len": jnp.asarray(lens)}
        costs.append(float(gg.update(batch, step, key).loss_sum))
    return gg, costs


def test_budget_shapes_are_the_loaders_canonical_shapes():
    opts = {"length-buckets": [1024, 1536, 4608, 8192],
            "mini-batch-words": 16384, "batch-row-multiple": 1}
    assert budget_shapes(opts) == [(1024, 16), (1536, 10), (4608, 3),
                                   (8192, 2)]
    assert budget_shapes(dict(opts, **{"batch-row-multiple": 8})) == [
        (1024, 16), (1536, 8), (4608, 8), (8192, 8)]
    assert budget_shapes({"mini-batch-words": 16384}) == []
    assert budget_shapes({"length-buckets": [64]}) == []


def test_steps_compiled_ahead_are_the_jitted_step():
    """Every bucket's step comes from the threads (the jitted step's own
    cache holds the one shape nobody foresaw) and the updates are the
    same updates."""
    plain, want = _bucket_updates(0)
    ahead, got = _bucket_updates(3)
    assert plain._fused._cache_size() == 4 and plain._ahead is None
    assert ahead._fused._cache_size() == 1          # width 24 alone
    assert len(ahead._ahead) == 3 and all(
        f.done() for f in ahead._ahead.values())
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for k, v in plain.export_params().items():
        np.testing.assert_allclose(ahead.export_params()[k], v, rtol=1e-5,
                                   atol=1e-7, err_msg=k)


def test_a_shape_still_queued_compiles_where_it_is_needed():
    """One thread, the widest bucket asked for first: its compile is
    queued behind the others, so it is taken off the queue and the
    jitted step compiles it in the caller."""
    gg, _ = _bucket_updates(1, widths=(48, 16))
    assert gg._fused._cache_size() == 1 and len(gg._ahead) == 2
    # the one thread is still compiling width 32: a compile that outlives
    # its test lands in the next one's compile ledger (test_compile_cache)
    for future in gg._ahead.values():
        future.result()
