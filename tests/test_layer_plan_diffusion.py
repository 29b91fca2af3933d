"""The layer plan's third mixing, `gqa` (grouped-query heads, per-head
norms, a rotation of whole heads in half-split pairs), softmax routing, and
training by diffusion over blocks (models/layer_plan.py,
ops/experts.py::route), beside tests/test_layer_plan.py and on its helpers:

  the cut model under keyed noise   == the plain float32 reference beside
      the benchmark's configuration (benchmark/configs/sdar_reference.py):
      cost and every parameter group's gradient, float32 tight, bfloat16
      at a stated limit; the evaluation rule's per-token costs through
      `data_weights`, as the benchmark's check reads them
  no leak                           the noised half's output at block b
      does not move with a clean token of block b or later, nor the clean
      half's with any noised position: exactly 0, dense and through the
      kernels
  the half-split rotation           == complex multiplication; bfloat16
      angles are caught
  softmax routing                   the shares of all 8 chips add up to the
      uncut reference's layer; a bfloat16 router or softmax is caught
  the validator's refusals, the counters, what a checkpoint keeps, and the
      lowering digest of this plan
"""

import dataclasses
import hashlib
import importlib
import json
import os
import re
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common.config_parser import parse_options
from marian_tpu.models import layer_plan as P
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.ops import experts as X
from test_layer_plan import F32_LIMIT, ROOT, _batch
from time_limit import time_limit

REF = importlib.import_module("benchmark.configs.sdar_reference")


def _dims():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as fh:
        config = json.load(fh)
    return dict(config, **config["rehearse"]["dims"], num_hidden_layers=2,
                layer_plan=config["layer_plan"][:2]), config


def _model(precision="float32", extra=(), held=None):
    """The benchmark's configuration at its rehearsal's widths, two layers
    deep: its `task_flags` (checkpointed halves and all) under the
    rehearsal's."""
    dims, config = _dims()
    flags = list(config["task_flags"])
    i = flags.index("--transformer-layer-plan")
    del flags[i + 1:i + 3]                         # four layers -> two
    tiny = list(config["rehearse"]["flags"])
    j = tiny.index("--precision")
    tiny[j + 1] = precision
    if held:
        tiny += ["--plan-experts-held", *map(str, held)]
    argv = flags + tiny + ["--train-sets", "x", "--vocabs", "v", *extra]
    return create_model(parse_options(argv, mode="training"),
                        dims["vocab"], dims["vocab"]), dims


@pytest.fixture(scope="module")
def tiny():
    model, dims = _model()
    params = model.init(jax.random.PRNGKey(7))
    # width 77: no multiple of the block (4), rows of 77, 51 and 25 tokens
    return model, dims, params, _batch(dims["vocab"], rows=3, width=77)


def _noise(key, batch):
    """What model.loss draws under `key`: its decoder key is fold_in(key,
    2), as dropout's."""
    return P.diffusion_noise(jax.random.fold_in(key, 2),
                             batch["trg_mask"].astype(jnp.float32))


def _reference_cost(params, dims, batch, masked, level):
    with jax.default_matmul_precision("highest"):
        return REF.noised_costs(params, dims, batch["trg_ids"],
                                batch["trg_mask"], masked, level)


@time_limit(300)
def test_the_plan_is_the_configurations(tiny):
    model, dims, _, _ = tiny
    cfg = model.cfg
    assert cfg.plan == (("gqa", "experts"),) * 2 == tuple(
        tuple(e.split(":")) for e in dims["layer_plan"])
    assert (cfg.heads, cfg.gqa_kv_heads, cfg.gqa_dim_head) == (8, 2, 16)
    assert (cfg.diffusion_block, cfg.experts_score, cfg.experts_shared,
            cfg.gqa_rope_theta, cfg.norm_eps) == (4, "softmax", 0, 1e6, 1e-6)
    assert cfg.gradient_checkpointing
    assert model.step_counters == X.COUNTERS + P.DIFFUSION_COUNTERS
    # nothing under marian_tpu/ names the model the plan was sized for
    hits = subprocess.run(
        ["grep", "-rli", "sdar", os.path.join(ROOT, "marian_tpu")],
        capture_output=True, text=True).stdout
    assert hits == ""


@pytest.mark.parametrize("flash", ["off", "on"])
@time_limit(600)
def test_cost_and_gradients_under_keyed_noise_are_the_references(tiny,
                                                                 flash):
    """Training: the noise from the step's key, handed to the reference as
    data; the summed cost and every leaf's gradient, the halves
    checkpointed; dense and through the flash kernels (interpret mode)."""
    model, dims, params, batch = tiny
    if flash == "on":
        model, _ = _model(extra=["--transformer-flash-attention", "on"])
    key = jax.random.PRNGKey(11)
    masked, level = _noise(key, batch)
    assert 0 < float(masked.sum()) < float(batch["trg_mask"].sum())
    assert float((masked * (1 - batch["trg_mask"])).sum()) == 0

    def ref_loss(p):
        return jnp.sum(_reference_cost(p, dims, batch, masked, level))
    want, want_g = jax.value_and_grad(ref_loss)(params)
    (got, aux), got_g = jax.value_and_grad(
        lambda p: model.loss(p, batch, key, True), has_aux=True)(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the labels are the rows' tokens, whatever was masked
    assert float(aux["labels"]) == float(batch["trg_mask"].sum())
    assert set(got_g) == set(want_g) == set(params)
    for name in sorted(params):
        scale = float(jnp.abs(want_g[name]).max())
        if name.endswith("_experts_router"):       # a share trains none
            assert scale == 0 == float(jnp.abs(got_g[name]).max()), name
            continue
        assert scale > 0, f"{name}: the reference's gradient is zero"
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-4 * scale, err_msg=name)


def _evaluation_costs(model, params, batch):
    w = jnp.ones_like(batch["trg_mask"])
    return jax.grad(lambda w: model.loss(
        params, dict(batch, data_weights=w), None, False)[0])(w)


def _token_error(model, dims, params, batch):
    """RMS error of a token's cost under the evaluation rule over the
    spread of the reference's costs: what the benchmark's `token_rtol`
    bounds."""
    want = REF.token_costs(params, dims, None, None, batch["trg_ids"],
                           batch["trg_mask"])
    got = _evaluation_costs(model, params, batch)
    real = np.asarray(batch["trg_mask"]) > 0
    want, got = np.asarray(want)[real], np.asarray(got)[real]
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


# Readings at these widths on the CPU (PR 34): float32 5.1e-8; bfloat16
# compute 1.5e-3; a bfloat16 router alone 8.2e-4, a bfloat16 softmax over
# float32 logits alone 7.1e-4, bfloat16 rotation angles alone 1.3e-3. They
# are a thirtieth of the other plans' (test_layer_plan.py) because the
# spread they are taken over is wider: under the evaluation rule half the
# tokens cost 0 and the others twice their cross-entropy.
BF16_LIMIT = 5e-3


@time_limit(600)
def test_the_evaluation_rule_through_data_weights(tiny):
    """Without a key: t = 1/2 and one draw over the width, the same in
    every row; the batch's own weights act beside the objective's; a row
    computed alone costs what it costs in its batch (the benchmark's
    check runs the reference in chunks of rows)."""
    model, dims, params, batch = tiny
    want = REF.token_costs(params, dims, None, None, batch["trg_ids"],
                           batch["trg_mask"])
    got = _evaluation_costs(model, params, batch)
    real = batch["trg_mask"] > 0
    np.testing.assert_allclose(jnp.where(real, got, 0),
                               jnp.where(real, want, 0), atol=3e-5)
    masked, level = P.diffusion_noise(None, batch["trg_mask"])
    assert level.tolist() == [0.5, 0.5, 0.5]
    np.testing.assert_array_equal(
        masked[0] > 0, np.asarray(REF.evaluation_noise(77)[0]))
    np.testing.assert_array_equal(masked[1], masked[0] * batch["trg_mask"][1])
    # an unmasked token costs nothing, a masked one twice its cross-entropy
    assert float(jnp.abs(jnp.where(masked > 0, 0, got)).max()) == 0
    one = {k: v[1:2] for k, v in batch.items()}
    np.testing.assert_allclose(_evaluation_costs(model, params, one)[0],
                               got[1], atol=3e-5)
    assert _token_error(model, dims, params, batch) < F32_LIMIT
    # one seeded +-1 weighting, as the check's projections
    signs = jnp.asarray(np.random.RandomState(5).choice(
        (-1.0, 1.0), size=got.shape), jnp.float32)
    total = model.loss(params, dict(batch, data_weights=signs), None,
                       False)[0]
    np.testing.assert_allclose(total, jnp.sum(want * real * signs),
                               rtol=1e-4, atol=1e-3)


def _bf16_router(x, w_router, top_k, scale, score="sigmoid", bias=None):
    s = jax.nn.softmax(jnp.dot(x.astype(jnp.bfloat16),
                               w_router.astype(jnp.bfloat16)
                               ).astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(s, top_k)
    return idx, vals / jnp.sum(vals, -1, keepdims=True) * scale


def _bf16_softmax(x, w_router, top_k, scale, score="sigmoid", bias=None):
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits.astype(jnp.bfloat16), axis=-1)
    vals, idx = jax.lax.top_k(s, top_k)
    vals = vals.astype(jnp.float32)
    return idx, vals / jnp.sum(vals, -1, keepdims=True) * scale


@time_limit(600)
def test_one_precision_lower_is_caught(tiny, monkeypatch):
    """float32 is tight; bfloat16 compute (float32 router, softmax and
    angles) is within its stated limit; a bfloat16 router, a bfloat16
    routing softmax or bfloat16 rotation angles, each alone in a float32
    model, exceeds the float32 limit."""
    model, dims, params, batch = tiny
    assert _token_error(model, dims, params, batch) < F32_LIMIT
    low, _ = _model("bfloat16")
    assert F32_LIMIT < _token_error(low, dims, params, batch) < BF16_LIMIT
    for route in (_bf16_router, _bf16_softmax):
        monkeypatch.setattr(X, "route", route)
        assert _token_error(model, dims, params, batch) > F32_LIMIT
        monkeypatch.undo()
    exact = P.rope_angles

    def bf16_angles(*a):
        return exact(*a).astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(P, "rope_angles", bf16_angles)
    assert _token_error(model, dims, params, batch) > F32_LIMIT


def _stack(cfg, params, x, mask, width):
    """The plan's layers over a doubled row's embeddings [B, 2T, d], as
    decode_train runs them."""
    rule = P.BlockDiffusion(width, cfg.diffusion_block)
    for lp, kinds in P._blocks(cfg):
        x, _ = P._layer(cfg, kinds, lp, params, x, mask, False, rule)
    return x


@pytest.mark.parametrize("flash", ["off", "on"])
@time_limit(600)
def test_no_leak(tiny, flash):
    """Exactly 0: the gradient of the noised half's output in block b with
    respect to the clean copy of block b or later (and to the noised copy
    of any other block), and of the clean half's output with respect to
    any noised position, or to a clean position of a later block."""
    model, _, params, batch = tiny
    cfg = dataclasses.replace(model.cfg, flash_attention=flash)
    width, blk = 77, cfg.diffusion_block
    mask = jnp.concatenate([batch["trg_mask"]] * 2, axis=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 2 * width, cfg.dim_emb))
    out, back = jax.vjp(lambda x: _stack(cfg, params, x, mask, width), x)
    pick = jax.random.normal(jax.random.PRNGKey(3), out.shape)
    index = jnp.arange(2 * width)
    position, noised = index % width, index < width
    for b in (0, 3, 19):                      # 19: the last, partial block
        here = noised & (position // blk == b)
        (g,) = back(pick * here[None, :, None])
        g = np.abs(np.asarray(g)).sum(axis=(0, 2))
        may = np.asarray(here | (~noised & (position // blk < b)))
        assert g[~may].max() == 0.0, (b, np.nonzero(g * ~may))
        assert g[may].min() > 0.0
        there = ~noised & (position // blk == b)
        (g,) = back(pick * there[None, :, None])
        g = np.abs(np.asarray(g)).sum(axis=(0, 2))
        may = np.asarray(~noised & (position // blk <= b))
        assert g[~may].max() == 0.0, (b, np.nonzero(g * ~may))
        assert g[may].min() > 0.0


@time_limit(120)
def test_half_split_rotation_is_complex_multiplication(monkeypatch):
    """_rotate(x, angles, "half") against (a + ib) e^{i p rate}: channel i
    with channel i + dim/2, rate theta^(-2i/dim); relative (scores depend
    on the distance alone); bfloat16 angles are caught past position
    256; the interleaved pairing is untouched by the argument."""
    dim, theta, t = 16, 1e6, 600
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, t, dim))
    angles = P.rope_angles(t, dim, theta, "half")
    got = P._rotate(x, angles, "half")
    rate = theta ** (-np.arange(dim // 2) / (dim // 2))
    turn = np.exp(1j * np.arange(t)[:, None] * rate[None, :])
    z = (np.asarray(x[..., :dim // 2], np.float64)
         + 1j * np.asarray(x[..., dim // 2:], np.float64)) * turn
    want = np.concatenate([z.real, z.imag], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # relative: a shift of both positions changes no score
    q, k = (P._rotate(jnp.broadcast_to(x[n, 0, :1], (t, dim)), angles,
                      "half") for n in (0, 1))
    np.testing.assert_allclose(q[5] @ k[2], q[405] @ k[402], rtol=1e-3,
                               atol=1e-3)
    assert abs(float(q[5] @ k[2] - q[5] @ k[5])) > 1e-2
    low = angles.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.abs(P._rotate(x, low, "half") - got)[:, :, 300:].max()) \
        > 0.1
    # the interleaved pairing, as the latent-attention layers use it
    inter = P._rotate(x, P.rope_angles(t, dim, theta))
    z = (np.asarray(x[..., 0::2], np.float64)
         + 1j * np.asarray(x[..., 1::2], np.float64)) \
        * np.exp(1j * np.arange(t)[:, None]
                 * (theta ** (-np.arange(0, dim, 2) / dim))[None, :])
    np.testing.assert_allclose(inter[..., 0::2], z.real, atol=2e-4)
    np.testing.assert_allclose(inter[..., 1::2], z.imag, atol=2e-4)


@time_limit(300)
def test_the_shares_of_all_eight_chips_add_up_to_the_uncut_layer():
    """Softmax routing over 32 experts, top 4, held 4 to a chip on 8
    chips: the shares' outputs sum to what the REFERENCE gives for the
    whole layer (all 32 held); and so do their routing counters."""
    whole, dims = _model(held=(0, 32))
    full = P.init_params(whole.cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    mask = jnp.ones((2, 24))
    lp = "decoder_l1"
    with jax.default_matmul_precision("highest"):
        want = REF._experts({k: v for k, v in full.items()}, lp,
                            dict(dims, num_experts=32), x)
    outs, counts = [], []
    for first in range(0, 32, 4):
        share, _ = _model(held=(first, 4))
        p = {k: (v[first:first + 4] if "_experts_W" in k else v)
             for k, v in full.items()}
        assert "decoder_l1_shared_Wg" not in p
        y, c = P._experts(share.cfg, p, lp, x, mask)
        outs.append(y)
        counts.append(c)
    np.testing.assert_allclose(sum(outs), want, atol=2e-5 * 8)
    got, _ = P._experts(whole.cfg, full, lp, x, mask)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(sum(c[1] for c in counts)) == 2 * 24 * 4
    # sigmoid stays the default scoring, softmax sums to 1 before the top k
    idx, w = X.route(x.reshape(-1, 64), full[f"{lp}_experts_router"], 4, 1.0,
                     "softmax")
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    idx_s, _ = X.route(x.reshape(-1, 64), full[f"{lp}_experts_router"], 4,
                       1.0)
    np.testing.assert_array_equal(idx, idx_s)      # monotone: the same picks


@time_limit(120)
def test_the_validator_refuses_what_the_rule_is_not_written_for():
    def build(*flags, plan=("gqa:dense", "gqa:dense")):
        return parse_options(
            ["--type", "transformer-lm", "--transformer-layer-plan", *plan,
             "--train-sets", "x", "--vocabs", "v", *flags], mode="training")
    build("--plan-diffusion-block", "4")
    for plan in (("gqa:dense", "mla:dense"), ("kda:dense", "gqa:dense")):
        with pytest.raises(ValueError, match="gqa layers only"):
            build("--plan-diffusion-block", "4", plan=plan)
    with pytest.raises(ValueError, match="gqa layers only"):
        build("--plan-diffusion-block", "4", "--plan-mtp-modules", "1")
    build("--plan-mtp-modules", "1")               # next-token: as it was
    for flags in (("--plan-gqa-kv-heads", "3"), ("--plan-gqa-dim-head", "7"),
                  ("--plan-experts-score", "tanh")):
        with pytest.raises(ValueError):
            create_model(build("--transformer-heads", "8", *flags), 16, 16)
    with pytest.raises(ValueError):
        P.parse_plan(["gqa:none"])


@time_limit(300)
def test_next_token_training_through_gqa_is_causal(tiny):
    """--plan-diffusion-block 0: the same layers under the causal rule on
    the row itself; the output at position p does not move with a later
    input."""
    model, _, params, batch = tiny
    cfg = dataclasses.replace(model.cfg, diffusion_block=0)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 77, cfg.dim_emb))

    def layer(x):
        return P._layer(cfg, ("gqa", "experts"), "decoder_l1", params, x,
                        batch["trg_mask"], False)[0]
    out, back = jax.vjp(layer, x)
    (g,) = back(jnp.zeros_like(out).at[:, 20].set(1.0))
    g = np.abs(np.asarray(g)).sum(axis=(0, 2))
    assert g[21:].max() == 0.0 and g[:21].min() > 0.0
    logits, counters = P.decode_train(cfg, params, None, None,
                                      batch["trg_ids"], batch["trg_mask"],
                                      train=False)
    assert logits.shape == (3, 77, cfg.trg_vocab)
    assert counters.shape == (len(X.COUNTERS),)


@time_limit(300)
def test_the_noise_counters_reach_the_tracer(tiny):
    from marian_tpu.obs import TRACER
    model, _, params, batch = tiny
    key = jax.random.PRNGKey(11)
    _, aux = jax.jit(lambda p: model.loss(p, batch, key, True))(params)
    masked, _ = _noise(key, batch)
    TRACER.reset()
    TRACER.enable()
    try:
        TRACER.count_lazy(model.step_counters, aux["counters"])
        TRACER.fetch_counters()
        got = TRACER.counters()
    finally:
        TRACER.disable()
        TRACER.reset()
    assert got["diffusion.labels"] == float(batch["trg_mask"].sum()) == 153
    assert got["diffusion.masked"] == float(masked.sum())
    # every position of the doubled row is routed: twice the row's tokens
    assert got["moe.assignments"] == 2 * 153 * 4 * 2


def _kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


@time_limit(600)
def test_a_checkpointed_gqa_half_keeps_the_flash_output(tiny):
    """Under --gradient-checkpointing the `gqa` half keeps what the `mla`
    half keeps, and the outputs of its k and v projections (no gate in
    this plan; q's is run again):
    flash_attention_fwd runs once a layer, as often as _dq and _dkv;
    `plan.remat_keep` says the names and the bytes, once a layer (no
    output norms: the feed-forward half keeps no name and says nothing),
    and `flash_attention.plan` the tiles of the doubled row."""
    from marian_tpu.obs import TRACER
    _, _, params, batch = tiny
    model, _ = _model(extra=["--transformer-flash-attention", "on"])
    grad = jax.grad(lambda p: model.loss(p, batch, jax.random.PRNGKey(1),
                                         True)[0])
    TRACER.reset()
    TRACER.enable()
    try:
        calls = list(_kernel_calls(jax.make_jaxpr(grad)(params).jaxpr))
        _, events = TRACER.snapshot()
    finally:
        TRACER.disable()
        TRACER.reset()
    assert [calls.count(f"flash_attention_{k}")
            for k in ("fwd", "dq", "dkv")] == [2, 2, 2]
    kept = [e["attrs"] for e in events if e["name"] == "plan.remat_keep"]
    assert [e["layer"] for e in kept] == ["decoder_l1", "decoder_l2"]
    cfg = model.cfg
    assert not cfg.post_norms and not cfg.gqa_gate
    for e in kept:
        assert e["half"] == "mixing"
        assert e["names"] == P._keeps(cfg, "gqa") \
            == P._FLASH_KEEPS + P._PROJECTION_KEEPS
        # the kernel's own shapes: 154 indices padded to one tile of 256;
        # k and v of the doubled rows as the matmuls leave them
        assert e["bytes"] == 3 * cfg.heads * 256 * (cfg.gqa_dim_head * 4 + 4) \
            + 3 * 154 * 2 * cfg.gqa_kv_heads * cfg.gqa_dim_head * 4
    plans = [e["attrs"] for e in events
             if e["name"] == "flash_attention.plan"]
    assert plans and all(
        (e["rule"], e["tq"], e["tk"], e["kv_group"])
        == ("block_diffusion(77,4)", 154, 154, 4) for e in plans)


# sha256 of the StableHLO text of this plan's loss and gradient (`_model()`,
# the batch and the key arguments), taken on the commit that added the plan
# (PR 34). A PR that changes this plan's program ON PURPOSE takes the digest
# again from its own parent and says so; one that only adds to the plan
# must leave it, as the two digests of tests/test_layer_plan_modules.py.
# Taken again ON PURPOSE by PR 39 from its own tree (parent b3b4d83): the
# expert layer's pool is a loop of one or two batches and counts three more
# things a step. And ON PURPOSE by PR 42 from its own tree (parent a59d2bb):
# `gqa`'s rotation forms a pair's other channel by a matmul, with a backward
# of its own (the other two digests, whose plans do not rotate, held). And ON
# PURPOSE by PR 47 from its own tree (parent 1b2dfbf): a checkpointed `gqa`
# half keeps its k and v projections' outputs by name, so its backward holds
# two matmuls fewer a layer (the other two digests, whose plans hold no `gqa`
# layer, held). That is ALL that moved: with those names not kept the plan
# lowers to the PARENT's text but for the numbers at the end of private
# functions' symbols (`@_where_123`), so the second digest is the parent's,
# taken from the parent's tree over the text with those numbers cut off.
# BOTH taken again ON PURPOSE by PR 50 from its own tree (parent a4ce82e):
# the expert layer is one loop of equal batches, as many as its list is
# long. The second is since then this tree's text with the names not kept
# (and the numbers cut off), no longer PR 47's parent's: what it still holds
# is that the kept projections move nothing else.
_THIS_PLAN_SHA256 = \
    "55bee35bfefbfa4e13236c156ae923d2bab0dbebbb3a755c0fc165c33536ab51"
_PARENT_UNNUMBERED_SHA256 = \
    "f031268d30e09de582c7d71408317c95a337c3873eb5cb645beea2915cbf10d3"


def _lowered(model, batch):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(7))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.jit(jax.value_and_grad(
        lambda p, b, k: model.loss(p, b, k, True), has_aux=True)).lower(
            shapes, batch, key).as_text()


@time_limit(300)
def test_this_plan_lowers_to_the_program_it_was(tiny):
    model, _, _, batch = tiny
    text = _lowered(model, batch)
    assert hashlib.sha256(text.encode()).hexdigest() == _THIS_PLAN_SHA256


@time_limit(300)
def test_the_kept_projections_are_all_that_moved_the_program(tiny,
                                                             monkeypatch):
    model, _, _, batch = tiny
    monkeypatch.setattr(P, "_PROJECTION_KEEPS", ())
    text = re.sub(r"(@\w+?)_\d+\b", r"\1", _lowered(model, batch))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_UNNUMBERED_SHA256
