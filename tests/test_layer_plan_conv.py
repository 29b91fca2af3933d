"""The layer plan's fifth mixing, `conv` (a doubly gated short convolution),
beside causal grouped-query attention at small heads, a sigmoid router
without a shared expert and ONE table for input and output
(models/layer_plan.py), beside tests/test_layer_plan.py and on its helpers:

  the cut model   == the plain float32 reference beside the benchmark's
      configuration (benchmark/configs/lfm2_reference.py) at the rehearsal's
      widths, three layers deep (a dense conv layer, an attention layer and
      a conv layer with experts): the cost, every parameter's gradient, the
      TIED table's among them (dense and through the flash kernels,
      checkpointed halves keeping `conv_bcx`, keeping nothing, and not
      checkpointed), the per-token costs as the benchmark's check reads them
  one mechanism lower is caught   the convolution left out, the taps reversed
      in time, B and C exchanged, the output table untied, the rotation left
      out, a per-head norm missing
  one precision lower is caught   bfloat16 gates, bfloat16 taps, a bfloat16
      router, matmul weights through float8
  the share   the eight shares' routed parts of one expert layer add up to
      the uncut reference's layer
  a row's padded tail changes no real position; `short_conv` reads the same
      to the bit through `kda` after its move to ops/ops.py; what a
      checkpointed `conv` half keeps (no gradient moves, W_in runs once,
      the tracer hears the name and its bytes); the scopes; the validator
"""

import copy
import dataclasses
import importlib
import json
import os
import re
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.cli import profile_summary
from marian_tpu.common.config_parser import parse_options
from marian_tpu.layers import initializers as inits
from marian_tpu.models import layer_plan as P
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.ops import experts as X
from marian_tpu.ops import kda, ops
from test_layer_plan import (F32_LIMIT, ROOT, _batch, _tiny_model,
                             _token_costs)
from time_limit import time_limit

REF = importlib.import_module("benchmark.configs.lfm2_reference")
# the source's layers 1 (conv, dense), 2 (attention), 3 (conv, experts)
BUILT = [1, 2, 3]
PLAN = ["conv:dense", "gqa:experts", "conv:experts"]
WIDTH = 150            # rows of 150, 100 and 50 tokens


def _dims():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as fh:
        config = json.load(fh)
    return dict(config, **config["rehearse"]["dims"], num_hidden_layers=3,
                layers_built=BUILT, layer_plan=PLAN), config


def _model(precision="float32", extra=(), drop=()):
    """The benchmark's configuration at its rehearsal's widths, three
    layers deep: its `task_flags` (checkpointed halves, the tied table and
    all) under the rehearsal's."""
    dims, config = _dims()
    flags = [f for f in config["task_flags"] if f not in drop]
    i = flags.index("--transformer-layer-plan")
    flags[i + 1:i + 6] = PLAN
    tiny = list(config["rehearse"]["flags"])
    tiny[tiny.index("--precision") + 1] = precision
    argv = flags + tiny + ["--train-sets", "x", "--vocabs", "v", *extra]
    return create_model(parse_options(argv, mode="training"),
                        dims["vocab"], dims["vocab"]), dims


def _with(model, **changes):
    other = copy.copy(model)
    other.cfg = dataclasses.replace(model.cfg, **changes)
    return other


@pytest.fixture(scope="module")
def tiny():
    model, dims = _model()
    params = model.init(jax.random.PRNGKey(7))
    return model, dims, params, _batch(dims["vocab"], rows=3, width=WIDTH)


@pytest.fixture(scope="module")
def reference(tiny):
    """The reference's summed cost and its gradient of every parameter."""
    _, dims, params, batch = tiny
    return jax.value_and_grad(lambda p: jnp.sum(REF.token_costs(
        p, dims, None, None, batch["trg_ids"], batch["trg_mask"])
        * batch["trg_mask"]))(params)


@time_limit(120)
def test_the_plan_is_the_configurations(tiny):
    model, dims, params, _ = tiny
    cfg = model.cfg
    assert "conv" in P.MIXINGS
    assert cfg.plan == tuple(tuple(e.split(":")) for e in PLAN)
    assert REF.layer_kinds(dims) == [("conv", True), ("full_attention", False),
                                     ("conv", False)]
    assert (cfg.heads, cfg.gqa_kv_heads, cfg.gqa_dim_head,
            cfg.gqa_rope_theta, cfg.gqa_gate, cfg.post_norms) \
        == (8, 2, 16, 1e6, False, False)
    assert (cfg.conv_taps, cfg.norm_eps, cfg.dim_ffn) == (3, 1e-5, 128)
    assert (cfg.experts_score, cfg.experts_shared, cfg.experts_scale,
            cfg.experts_held, cfg.experts, cfg.experts_top_k) \
        == ("sigmoid", 0, 1.0, 8, 64, 4)
    assert cfg.gradient_checkpointing and cfg.tied_embeddings
    assert model.step_counters == X.COUNTERS
    # ONE table; three streams out of one matrix; float32 taps
    assert "decoder_ff_logit_out_W" not in params
    assert params["decoder_Wemb"].shape == (512, 64)
    assert params["decoder_l1_conv_Win"].shape == (64, 192)
    assert params["decoder_l1_conv_taps"].shape == (3, 64)
    assert params["decoder_l1_conv_Wout"].shape == (64, 64)
    assert not any("_shared_" in k for k in params)
    low = P.cast_params(params, jnp.bfloat16)
    assert low["decoder_l3_conv_taps"].dtype == jnp.float32 \
        == low["decoder_l3_experts_router"].dtype
    assert low["decoder_l3_conv_Win"].dtype == jnp.bfloat16
    # a plan that does not ask for it keeps two tables
    untied, _ = _model(drop=("--tied-embeddings",))
    assert not untied.cfg.tied_embeddings and "decoder_ff_logit_out_W" in \
        jax.eval_shape(untied.init, jax.random.PRNGKey(7))
    # nothing under marian_tpu/ names the model the plan was sized for
    hits = subprocess.run(
        ["grep", "-rliE", "lfm2|liquid", os.path.join(ROOT, "marian_tpu")],
        capture_output=True, text=True).stdout
    assert hits == ""
    with pytest.raises(ValueError, match="plan-conv-taps 0.*1 tap or more"):
        _model(extra=["--plan-conv-taps", "0"])


@pytest.mark.parametrize("flash,remat", [
    ("off", "conv_bcx kept"), ("on", "conv_bcx kept"),
    ("off", "keeps emptied"), ("off", "not checkpointed")])
@time_limit(600)
def test_cost_and_gradients_are_the_references(tiny, reference, monkeypatch,
                                               flash, remat):
    """The summed cost and every leaf's gradient, the tied table's (which
    arrives from both ends) among them, against jax.grad of the reference;
    dense and through the flash kernels (interpret mode, tiles of 128);
    the halves checkpointed with their keeps, with every keep emptied, and
    not checkpointed."""
    model, dims, params, batch = tiny
    if flash == "on":
        monkeypatch.setenv("MARIAN_FLASH_BLOCK_Q", "128")
        monkeypatch.setenv("MARIAN_FLASH_BLOCK_K", "128")
        model, _ = _model(extra=["--transformer-flash-attention", "on"])
    if remat == "not checkpointed":
        model, _ = _model(drop=("--gradient-checkpointing",))
    elif remat == "keeps emptied":
        monkeypatch.setattr(P, "_keeps", lambda cfg, kind: ())
    else:
        assert P._keeps(model.cfg, "conv") == ("conv_bcx",)
    assert model.cfg.gradient_checkpointing == (remat != "not checkpointed")
    want, want_g = reference
    (got, aux), got_g = jax.value_and_grad(
        lambda p: model.loss(p, batch, jax.random.PRNGKey(11), True),
        has_aux=True)(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert float(aux["labels"]) == float(batch["trg_mask"].sum())
    assert set(got_g) == set(want_g) == set(params)
    assert "decoder_Wemb" in got_g
    for name in sorted(params):
        scale = float(jnp.abs(want_g[name]).max())
        if name.endswith("_experts_router"):       # a share trains none
            assert scale == 0 == float(jnp.abs(got_g[name]).max()), name
            continue
        if name.endswith("_experts_bias"):
            # no gradient reaches it: in its place the backward leaves
            # every expert's load less the mean load, whole numbers of
            # the real tokens' top-k choices over the WHOLE router
            assert scale == 0, name
            mean = float(aux["labels"]) * model.cfg.experts_top_k \
                / model.cfg.experts
            load = np.asarray(got_g[name]).reshape(-1) + mean
            assert load.shape == (model.cfg.experts,)
            np.testing.assert_allclose(load, np.round(load), atol=1e-3)
            assert load.min() >= -1e-3 and load.max() > mean
            np.testing.assert_allclose(load.sum(), mean * model.cfg.experts)
            continue
        assert scale > 0, f"{name}: the reference's gradient is zero"
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-4 * scale, err_msg=name)


def _token_error(model, dims, params, batch, given=None):
    """RMS error of a token's cost over the spread of the reference's
    costs: what the benchmark's `token_rtol` bounds. `given`: the
    parameters the PROGRAM is handed, where they differ."""
    want = REF.token_costs(params, dims, None, None, batch["trg_ids"],
                           batch["trg_mask"])
    got = _token_costs(model, given or params, batch)
    real = np.asarray(batch["trg_mask"]) > 0
    want, got = np.asarray(want)[real], np.asarray(got)[real]
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


# bfloat16 compute at these widths reads as the other plans' do
# (test_layer_plan_window.py); the benchmark's own limit, at the published
# widths on the chip, is in benchmark/traffic/train-docs8k-lfm2.json.
BF16_LIMIT = 1.65e-1


@time_limit(300)
def test_the_per_token_costs_are_the_references(tiny):
    model, dims, params, batch = tiny
    assert _token_error(model, dims, params, batch) < F32_LIMIT
    low, _ = _model("bfloat16")
    assert F32_LIMIT < _token_error(low, dims, params, batch) < BF16_LIMIT
    # a row alone costs what it costs in its batch (the benchmark's check
    # runs the reference in chunks of rows), the attention a head at a time
    # what it costs at once
    one = {k: v[1:2] for k, v in batch.items()}
    np.testing.assert_allclose(
        _token_costs(model, params, one)[0] * one["trg_mask"][0],
        _token_costs(model, params, batch)[1] * batch["trg_mask"][1],
        atol=3e-5)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    mask = batch["trg_mask"][:2, :40]
    np.testing.assert_allclose(
        REF._attention_by_head(params, "decoder_l2", dims, x, mask),
        REF._attention(params, "decoder_l2", dims, x, mask), atol=2e-5)


def _exchanged(params):
    """W_in's first two column blocks exchanged: the program reads C where
    the equations say B."""
    out = dict(params)
    for name, w in params.items():
        if name.endswith("_conv_Win"):
            d = w.shape[0]
            out[name] = jnp.concatenate(
                [w[:, d:2 * d], w[:, :d], w[:, 2 * d:]], axis=1)
    return out


@pytest.mark.parametrize("lower", [
    "the convolution left out", "the taps reversed in time",
    "B and C exchanged", "the output table untied", "the rotation left out",
    "a per-head norm missing"])
@time_limit(300)
def test_one_mechanism_lower_is_caught(tiny, monkeypatch, lower):
    """Each alone, in a float32 model on the reference's parameters,
    moves a token's cost past the float32 limit."""
    model, dims, params, batch = tiny
    given = None
    conv = P.short_conv
    if lower == "the convolution left out":
        monkeypatch.setattr(P, "short_conv", lambda x, w: x)
    elif lower == "the taps reversed in time":
        monkeypatch.setattr(P, "short_conv", lambda x, w: conv(x, w[::-1]))
    elif lower == "B and C exchanged":
        given = _exchanged(params)
    elif lower == "the output table untied":
        model = _with(model, tied_embeddings=False)
        given = dict(params, decoder_ff_logit_out_W=inits.glorot_uniform(
            jax.random.PRNGKey(3), (64, dims["vocab"])))
    elif lower == "the rotation left out":
        model = _with(model, gqa_rope_theta=0.0)
    else:
        norm = P.rms_norm
        monkeypatch.setattr(P, "rms_norm", lambda x, scale, **kw:
                            x if x.ndim == 4 else norm(x, scale, **kw))
    assert _token_error(model, dims, params, batch, given) > 10 * F32_LIMIT


def _bf16_router(x, w_router, top_k, scale, score="sigmoid", bias=None):
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.bfloat16),
                               w_router.astype(jnp.bfloat16)
                               ).astype(jnp.float32))
    idx = jax.lax.top_k(s + bias.reshape(-1), top_k)[1]
    vals = jnp.take_along_axis(s, idx, axis=-1)
    return idx, vals / jnp.sum(vals, -1, keepdims=True) * scale


def _bf16_gates(cfg, p, lp, x):
    d, low = cfg.dim_emb, jnp.bfloat16
    bcx = jnp.dot(x, p[f"{lp}_conv_Win"]).astype(low)
    b, c, h = (bcx[..., i * d:(i + 1) * d] for i in range(3))
    y = c * ops.short_conv(b * h, p[f"{lp}_conv_taps"].astype(low))
    return jnp.dot(y.astype(x.dtype), p[f"{lp}_conv_Wout"])


def _through(params, dtype, only):
    return {k: v.astype(dtype).astype(v.dtype) if only(k) else v
            for k, v in params.items()}


@pytest.mark.parametrize("lower", [
    "bfloat16 gates", "bfloat16 taps", "a bfloat16 router",
    "matmul weights through float8"])
@time_limit(300)
def test_one_precision_lower_is_caught(tiny, monkeypatch, lower):
    """Each alone in a float32 model exceeds the float32 limit."""
    model, dims, params, batch = tiny
    given = None
    if lower == "bfloat16 gates":
        monkeypatch.setattr(P, "_conv", _bf16_gates)
    elif lower == "bfloat16 taps":
        given = _through(params, jnp.bfloat16,
                         lambda name: name.endswith("_conv_taps"))
    elif lower == "a bfloat16 router":
        monkeypatch.setattr(X, "route", _bf16_router)
    else:
        given = _through(params, jnp.float8_e4m3fn,
                         lambda name: "_W" in name)
    assert _token_error(model, dims, params, batch, given) > F32_LIMIT


@time_limit(300)
def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """One expert layer at the published router geometry (top 4 of 64, 8
    held a chip): the routed parts that the PROGRAM's eight shares give,
    each told its `first, count` and handed its eight experts, add up to
    what the reference gives for the whole layer with all 64 held; and one
    share alone is the reference's same share."""
    model, dims, params, batch = tiny
    lp, d, f = "decoder_l2", 64, 32
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    whole = {f"{lp}_experts_router": params[f"{lp}_experts_router"],
             f"{lp}_experts_Wg": inits.glorot_uniform(
                 keys[0], (64, d, f), fan_in=d, fan_out=f),
             f"{lp}_experts_Wu": inits.glorot_uniform(
                 keys[1], (64, d, f), fan_in=d, fan_out=f),
             f"{lp}_experts_Wd": inits.glorot_uniform(
                 keys[2], (64, f, d), fan_in=f, fan_out=d)}
    x = jax.random.normal(keys[3], (3, WIDTH, d))
    mask = batch["trg_mask"]
    with jax.default_matmul_precision("highest"):
        uncut = REF._experts(whole, lp, dict(dims, num_experts=64), x)
    total, held = jnp.zeros_like(uncut), 0.0
    for share in range(8):
        first = 8 * share
        mine = {k: v if k.endswith("router") else v[first:first + 8]
                for k, v in whole.items()}
        cfg = dataclasses.replace(model.cfg, experts_first=first)
        y, counters = P._experts(cfg, mine, lp, x, mask)
        counted = dict(zip(X.COUNTERS, np.asarray(counters)))
        held += counted["moe.assignments_held"]
        assert counted["moe.dropped"] == 0
        total = total + y * mask[..., None]
        if share == 3:
            with jax.default_matmul_precision("highest"):
                same = REF._experts(mine, lp, dict(dims, experts_first=first),
                                    x)
            np.testing.assert_allclose(y * mask[..., None],
                                       same * mask[..., None], atol=2e-5)
    np.testing.assert_allclose(total, uncut * mask[..., None], atol=5e-5)
    # every real position's four picks were held by exactly one share
    assert held == 4 * float(mask.sum())


@time_limit(120)
def test_a_padded_tail_changes_no_real_position(tiny):
    """The taps read nothing after their own position: whatever stands in
    a row's padding, the positions before it read the same to the bit, in
    the half alone and in the model's per-token costs."""
    model, dims, params, batch = tiny
    x = jax.random.normal(jax.random.PRNGKey(4), (3, WIDTH, 64))
    other = x.at[:, 50:].set(jax.random.normal(jax.random.PRNGKey(6),
                                               (3, WIDTH - 50, 64)) * 9.0)
    got = P._conv(model.cfg, params, "decoder_l1", x)
    again = P._conv(model.cfg, params, "decoder_l1", other)
    assert bool((got[:, :50] == again[:, :50]).all())
    assert not bool((got[:, 50:] == again[:, 50:]).all())
    real = batch["trg_mask"] > 0
    filled = dict(batch, trg_ids=jnp.where(real, batch["trg_ids"], 7),
                  src_ids=jnp.where(real, batch["src_ids"], 7))
    np.testing.assert_array_equal(
        np.asarray(_token_costs(model, params, batch))[np.asarray(real)],
        np.asarray(_token_costs(model, params, filled))[np.asarray(real)])


def _short_conv_as_it_was(x, w):
    """ops/kda.py's function before it moved, word for word."""
    k = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t, :] * w[j] for j in range(k))


@time_limit(300)
def test_short_conv_reads_the_same_through_kda_after_the_move(monkeypatch):
    """ONE function, in ops/ops.py, read by both kinds; the `kda` half's
    output is to the bit what it was with the function it had."""
    assert P.short_conv is ops.short_conv and not hasattr(kda, "short_conv")
    model, dims = _tiny_model()
    assert model.cfg.plan[0][0] == "kda"
    params = model.init(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 80, model.cfg.dim_emb))
    got = P._kda(model.cfg, params, "decoder_l1", x)
    monkeypatch.setattr(P, "short_conv", _short_conv_as_it_was)
    was = P._kda(model.cfg, params, "decoder_l1", x)
    assert float(jnp.abs(got).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(was))


def _dots(cfg, params, n):
    """`dot`s of the COMPILED cost and gradient of layer n alone, its
    halves checkpointed, at [3, WIDTH]."""
    lp, kinds = f"decoder_l{n}", cfg.plan[n - 1]
    own = {k: v for k, v in params.items() if k.startswith(lp + "_")}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, WIDTH, cfg.dim_emb))
    mask = jnp.ones((3, WIDTH), jnp.float32)

    def cost(p, x, weights):
        return jnp.sum(P._layer(cfg, kinds, lp, p, x, mask, True)[0]
                       * weights)
    text = jax.jit(jax.value_and_grad(cost, argnums=(0, 1))).lower(
        own, x, x).compile().as_text()
    return len(re.findall(r" dot\(", text)), text


@time_limit(300)
def test_a_conv_half_keeps_w_in_and_says_so(tiny, monkeypatch):
    """STRUCTURE: with `conv_bcx` kept the compiled cost and gradient of
    the dense conv layer hold ONE matmul fewer than with nothing kept (W_in
    does not run again; W_out's forward is read by no backward either
    way); the ops carry the scopes `conv` and `conv.core`, which
    profile_summary's by-scope table knows; and the tracer hears the name
    and its bytes."""
    from marian_tpu.obs import TRACER
    model, _, params, batch = tiny
    kept, text = _dots(model.cfg, params, 1)
    assert "conv/conv.core" in text and "/conv/" in text
    assert {"conv", "conv.core"} <= set(profile_summary.LEVEL2)
    keeps = P._keeps
    monkeypatch.setattr(P, "_keeps", lambda cfg, kind: ())
    assert _dots(model.cfg, params, 1)[0] == kept + 1
    monkeypatch.setattr(P, "_keeps", keeps)
    TRACER.reset()
    TRACER.enable()
    try:
        jax.jit(lambda p: model.loss(p, batch, None, True))(params)
        events = TRACER.snapshot()[1]
    finally:
        TRACER.disable()
        TRACER.reset()
    said = {(e["attrs"]["layer"], e["attrs"]["half"]):
            (e["attrs"]["names"], e["attrs"]["bytes"])
            for e in events if e["name"] == "plan.remat_keep"}
    # float32 at the rehearsal's widths: W_in's output [3, 150, 3 x 64]
    for layer in ("decoder_l1", "decoder_l3"):
        assert said[(layer, "mixing")] == (("conv_bcx",),
                                           3 * WIDTH * 192 * 4)
    assert said[("decoder_l2", "mixing")][0] == P._FLASH_KEEPS \
        + P._PROJECTION_KEEPS
    # a feed-forward half without output norms keeps no name, says nothing
    assert not any(half == "feed-forward" for _, half in said)
