"""The layer plan's latent attention with its two optional sizes (a
low-rank query, a rotation by position) and the plan's prediction modules
(models/layer_plan.py), beside tests/test_layer_plan.py and on its helpers:

  rank 0 and theta 0              == the layer as it stood before it had
      either, bit for bit; the rotated low-rank layer == its equations
      written out; rotation is relative; the turn whose other channel
      is a signed-permutation matmul == the roll form it replaced, value
      and cotangent, in place from a start channel on
  the cut model with its module   == the plain float32 reference beside the
      benchmark's configuration (benchmark/configs/
      joyai_llm_flash_reference.py): per-token costs and every parameter
      group's gradient; the module sees nothing past its gold token; weight
      0 trains the stack as the plan without a module; the label count is
      the main head's; one precision lower is caught
  a plan that asks for none of it lowers to the program it was, and so
      does the encoder-decoder family, which hands the loss no extra head
"""

import dataclasses
import importlib
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu.common.config_parser import parse_options
from marian_tpu.models import layer_plan as P
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.ops import experts as X
from test_layer_plan import (F32_LIMIT, ROOT, _batch, _plan_model,  # noqa: F401
                             _token_costs, tiny)

def _mla_before(cfg, p, lp, x, mask):
    """models/layer_plan.py::_mla as it stood before the layer had a
    low-rank query or a rotation (PR 31), kept here word for word."""
    from marian_tpu.ops.attention import attention, causal_mask
    from marian_tpu.ops.ops import rms_norm
    h, dn, dv = cfg.heads, cfg.mla_dim_nope, cfg.mla_dim_v
    q = P._heads(jnp.dot(x, p[f"{lp}_mla_Wq"]), h)
    kva = jnp.dot(x, p[f"{lp}_mla_Wkva"])
    latent = rms_norm(kva[..., :cfg.mla_latent],
                      p[f"{lp}_mla_kv_norm_scale"], eps=cfg.norm_eps)
    shared = kva[..., cfg.mla_latent:]
    kv = P._heads(jnp.dot(latent, p[f"{lp}_mla_Wkvb"]), h)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(
            shared[:, None], (*kv.shape[:3], cfg.mla_dim_shared))],
        axis=-1)
    t = x.shape[1]
    o, _ = attention(q, k, kv[..., dn:],
                     mask=causal_mask(t) * mask[:, None, None, :],
                     kv_mask=mask, causal=True,
                     flash=cfg.flash_attention, packed="off")
    o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], t, h * dv)
    return jnp.dot(o, p[f"{lp}_mla_Wo"])


def _mla_case(**sizes):
    cfg = P.PlanConfig(src_vocab=8, trg_vocab=8, dim_emb=96, heads=2,
                       plan=(("mla", "dense"),), dec_depth=1,
                       flash_attention="off", compute_dtype=jnp.float32,
                       **sizes)
    p = P.init_params(cfg, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 96))
    mask = (jnp.arange(40)[None] < jnp.array([40, 25])[:, None]
            ).astype(jnp.float32)
    return cfg, p, x, mask


@pytest.mark.parametrize("flash", ["off", "on"])
def test_rank_0_and_theta_0_are_the_layer_as_it_was(flash):
    """A plan that asks for neither runs the program it ran: bit for bit,
    dense and through the flash kernel."""
    cfg, p, x, mask = _mla_case()
    cfg = dataclasses.replace(cfg, flash_attention=flash)
    assert cfg.mla_q_rank == 0 and cfg.mla_rope_theta == 0.0
    assert "decoder_l1_mla_Wq" in p and "decoder_l1_mla_Wqa" not in p
    np.testing.assert_array_equal(P._mla(cfg, p, "decoder_l1", x, mask),
                                  _mla_before(cfg, p, "decoder_l1", x, mask))


def test_the_rotated_low_rank_layer_is_its_equations_written_out():
    """c_q = RMSNorm(x W_qa), q = c_q W_qb; the 64 shared key channels
    and each head's last 64 query channels turned pair by pair by
    t theta^(-2i/64); scores, softmax and values written out."""
    theta = 32e6
    cfg, p, x, mask = _mla_case(mla_q_rank=48, mla_rope_theta=theta,
                                norm_eps=1e-6)
    lp = "decoder_l1_mla"
    assert p[f"{lp}_Wqa"].shape == (96, 48) and f"{lp}_Wq" not in p

    def rms(v):
        return v / jnp.sqrt(jnp.mean(v ** 2, -1, keepdims=True) + 1e-6)

    def turn(v):                       # [..., T(axis 1), ..., 64]
        pos = jnp.arange(40.0).reshape((1, 40) + (1,) * (v.ndim - 3))
        out = []
        for i in range(32):
            a, b, ang = v[..., 2 * i], v[..., 2 * i + 1], \
                pos * theta ** (-2 * i / 64)
            out += [a * jnp.cos(ang) - b * jnp.sin(ang),
                    a * jnp.sin(ang) + b * jnp.cos(ang)]
        return jnp.stack(out, -1)
    q = (rms(x @ p[f"{lp}_Wqa"]) @ p[f"{lp}_Wqb"]).reshape(2, 40, 2, 192)
    kva = x @ p[f"{lp}_Wkva"]
    kv = (rms(kva[..., :512]) @ p[f"{lp}_Wkvb"]).reshape(2, 40, 2, 256)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :128], kv[..., :128])
         + jnp.einsum("bqhd,bkd->bhqk", turn(q[..., 128:]),
                      turn(kva[..., 512:]))) / np.sqrt(192.0)
    see = (jnp.tril(jnp.ones((40, 40)))[None, None]
           * mask[:, None, None, :]) > 0
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(see, s, -1e30), -1),
                   kv[..., 128:])
    want = o.reshape(2, 40, 256) @ p[f"{lp}_Wo"]
    got = P._mla(cfg, p, "decoder_l1", x, mask)
    np.testing.assert_allclose(got, want, atol=3e-5)
    flash = P._mla(dataclasses.replace(cfg, flash_attention="on"), p,
                   "decoder_l1", x, mask)
    real = mask[..., None] > 0
    np.testing.assert_allclose(jnp.where(real, flash, 0),
                               jnp.where(real, want, 0), atol=5e-5)


def test_rotation_is_relative(monkeypatch):
    """Every position moved on by the same 37 places: the same layer
    output, because a score depends on the distance of its two positions
    alone. (theta small enough that float32 angles at 37 + 40 are exact
    to the comparison.)"""
    cfg, p, x, mask = _mla_case(mla_q_rank=48, mla_rope_theta=10000.0)
    here = P._mla(cfg, p, "decoder_l1", x, mask)
    angles = P.rope_angles
    monkeypatch.setattr(P, "rope_angles", lambda t, dim, theta: angles(
        t + 37, dim, theta)[37:])
    moved = P._mla(cfg, p, "decoder_l1", x, mask)
    assert float(jnp.abs(moved - here).max()) > 0     # another program
    np.testing.assert_allclose(moved, here, atol=2e-5)
    monkeypatch.setattr(P, "rope_angles", lambda t, dim, theta: 0.0 * angles(
        t, dim, theta))
    still = P._mla(cfg, p, "decoder_l1", x, mask)
    assert float(jnp.abs(still - here).max()) > 1e-3  # and it does turn


def _roll_form(x, angles, pairing):
    """`_rotate` as it stood before the pair's other channel came from a
    matmul (PR 42): a roll along the channels and a select, kept here word
    for word."""
    f = x.astype(jnp.float32)
    dim = x.shape[-1]
    if pairing == "half":
        first = jnp.arange(dim) < dim // 2
        other = jnp.where(first, -1.0, 1.0) * jnp.roll(f, dim // 2, axis=-1)
    else:
        even = jnp.arange(dim) % 2 == 0
        other = jnp.where(even, -jnp.roll(f, -1, axis=-1),
                          jnp.roll(f, 1, axis=-1))
    return (f * jnp.cos(angles) + other * jnp.sin(angles)).astype(x.dtype)


@pytest.mark.parametrize("start, dim", [(0, 64), (128, 192)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("pairing", ["interleaved", "half"])
def test_the_matmul_turn_equals_the_roll_form(pairing, dtype, start, dim):
    """Value and cotangent equal (==, every element) the roll form around
    a slice and a concatenate, op by op (under jit the CPU's compiler
    contracts the two forms' multiply-adds differently, a unit in the last
    place); the channels under `start` come back untouched, and so does
    their cotangent. Past position 256, where a bfloat16 angle is no
    longer its position's."""
    t = 300
    kx, kg = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (2, 3, t, dim)).astype(dtype)
    g = jax.random.normal(kg, (2, 3, t, dim)).astype(dtype)
    angles = P.rope_angles(t, dim - start, 1e4, pairing)

    def before(x):
        return jnp.concatenate(
            [x[..., :start], _roll_form(x[..., start:], angles, pairing)],
            axis=-1)
    want, back_before = jax.vjp(before, x)
    got, back = jax.vjp(lambda x: P._rotate(x, angles, pairing, start), x)
    assert got.dtype == x.dtype and back(g)[0].dtype == x.dtype
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(back(g)[0], back_before(g)[0])
    np.testing.assert_array_equal(got[..., :start], x[..., :start])
    np.testing.assert_array_equal(back(g)[0][..., :start], g[..., :start])
    assert float(jnp.abs(got.astype(jnp.float32)
                         - x.astype(jnp.float32))[..., start:].max()) > 0.5


def _joyai_reference():
    return importlib.import_module(
        "benchmark.configs.joyai_llm_flash_reference")


def _tiny_joyai(precision="float32", extra=(), **dims_over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as fh:
        config = json.load(fh)
    dims = {**config, **config["rehearse"]["dims"], **dims_over}
    flags = list(config["task_flags"])
    i = flags.index("--precision")
    del flags[i:i + 3]
    tiny = list(config["rehearse"]["flags"])
    j = tiny.index("--precision")
    del tiny[j:j + 3]
    argv = flags + tiny + ["--precision", precision, "float32",
                           "--train-sets", "x", "--vocabs", "v", *extra]
    model = create_model(parse_options(argv, mode="training"),
                         dims["vocab"], dims["vocab"])
    return model, dims


@pytest.fixture(scope="module")
def ahead():
    model, dims = _tiny_joyai()
    params = model.init(jax.random.PRNGKey(11))
    return model, dims, params, _batch(dims["vocab"])


def test_the_plan_with_a_module_costs_what_its_reference_costs(ahead):
    """Five layers with a low-rank rotated query in each, then one
    prediction module: per-token costs (the main head's plus 0.3 of the
    module's, booked on the token predicted) against
    benchmark/configs/joyai_llm_flash_reference.py."""
    model, dims, params, batch = ahead
    cfg = model.cfg
    assert (cfg.dec_depth, cfg.mtp_modules, len(cfg.plan)) == (5, 1, 6)
    assert cfg.mla_q_rank == dims["q_lora_rank"] \
        and cfg.mla_rope_theta == dims["rope_theta"] == 32e6
    want = _joyai_reference().token_costs(
        params, dims, None, None, batch["trg_ids"], batch["trg_mask"])
    got = _token_costs(model, params, batch)
    real = batch["trg_mask"] > 0
    np.testing.assert_allclose(jnp.where(real, got, 0),
                               jnp.where(real, want, 0), atol=3e-5)
    main, (module,) = _joyai_reference().head_costs(
        params, dims, batch["trg_ids"], batch["trg_mask"])
    _, aux = model.loss(params, batch, None, True)
    counts = dict(zip(model.step_counters, np.asarray(aux["counters"])))
    rows = batch["trg_mask"].shape[0]
    # the label count the trainer divides by and reports is the MAIN
    # head's; the module has one label a row fewer
    assert float(aux["labels"]) == float(batch["trg_mask"].sum())
    assert counts["mtp.labels"] == float(aux["labels"]) - rows
    has_next = jnp.pad(batch["trg_mask"][:, 1:], ((0, 0), (0, 1)))
    np.testing.assert_allclose(counts["mtp.ce_sum"],
                               float((module * has_next).sum()), rtol=1e-5)
    np.testing.assert_allclose(aux["ce_sum"], float((main * real).sum()),
                               rtol=1e-5)
    # the module's expert layer is summed into the routing counters
    assert counts["moe.assignments"] == 4 * (
        4 * float(aux["labels"]) + counts["mtp.labels"])
    assert counts["moe.dropped"] == 0.0


@pytest.mark.parametrize("held", ["share", "whole"])
def test_every_group_of_the_plan_with_a_module_gets_its_gradient(ahead,
                                                                 held):
    """Every leaf's gradient is the reference's: the query's two factors
    and its norm, W_eh, the module's three norms, and both tables, each
    used twice (the input table by the stack and by the module's join,
    the output table by both heads)."""
    model, dims, params, batch = ahead
    if held == "whole":
        n = dims["router_width"]
        model, dims = _tiny_joyai(extra=["--plan-experts-held", "0", str(n)],
                                  n_routed_experts=n)
        params = model.init(jax.random.PRNGKey(11))
    ref = _joyai_reference()

    def ref_loss(p):
        return jnp.sum(ref.token_costs(p, dims, None, None, batch["trg_ids"],
                                       batch["trg_mask"])
                       * batch["trg_mask"])
    want = jax.grad(ref_loss)(params)
    got = jax.grad(lambda p: model.loss(p, batch, None, True)[0])(params)
    assert set(got) == set(want) == set(params)
    for name in ("decoder_l3_mla_Wqa", "decoder_l3_mla_q_norm_scale",
                 "decoder_l3_mla_Wqb", "decoder_mtp1_Weh",
                 "decoder_mtp1_emb_norm_scale",
                 "decoder_mtp1_hidden_norm_scale",
                 "decoder_mtp1_top_norm_scale", "decoder_mtp1_mla_Wqa",
                 "decoder_mtp1_experts_Wg"):
        assert name in params
    for name in sorted(params):
        scale = float(jnp.abs(want[name]).max())
        if held == "share" and name.endswith("_experts_router"):
            assert scale == 0 == float(jnp.abs(got[name]).max()), name
            continue
        assert scale > 0, f"{name}: the reference's gradient is zero"
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale,
                                   err_msg=name)


def test_the_module_sees_nothing_past_its_gold_token(ahead):
    """The module at t is given y_t and predicts y_{t+1}: its cost there
    does not move when a later token changes, and does when y_t does."""
    model, dims, params, batch = ahead

    def module_costs(ids):
        """[B, T]: the module's cost booked on the token it predicts."""
        b = dict(batch, trg_ids=ids, src_ids=ids)
        whole = _token_costs(model, params, b)
        off = dataclasses.replace(model.cfg, mtp_weight=0.0)
        model.cfg, kept = off, model.cfg
        try:
            main = _token_costs(model, params, b)
        finally:
            model.cfg = kept
        return whole - main
    ids = batch["trg_ids"]
    before = module_costs(ids)
    j = 30
    after = module_costs(ids.at[:, j].set((ids[:, j] + 7) % dims["vocab"]))
    # y_j is the label of the prediction made at j - 1 and is given at j:
    # predictions of y_1 .. y_{j-1} (made at positions < j - 1) stand
    np.testing.assert_allclose(after[:, :j], before[:, :j], atol=1e-6)
    real = np.asarray(batch["trg_mask"][:, j + 1] > 0)
    moved = np.abs(np.asarray(after - before))
    assert (moved[real, j] > 1e-4).all() and (moved[real, j + 1] > 1e-4).all()
    assert float(before[:, 0].max()) == 0.0      # nothing predicts y_0


def test_weight_0_trains_the_stack_as_the_plan_without_a_module(ahead):
    """lambda 0: cost and every gradient of the stack and of both tables
    are bit-equal to the same plan without its last entry; the module's
    own parameters get zeros."""
    model, dims, params, batch = ahead
    off, _ = _tiny_joyai(extra=["--plan-mtp-weight", "0"])
    config_plan = [f"{m}:{f}" for m, f in model.cfg.plan[:-1]]
    bare, _ = _tiny_joyai(extra=["--transformer-layer-plan", *config_plan,
                                 "--plan-mtp-modules", "0"])
    assert bare.cfg.mtp_modules == 0 and bare.cfg.dec_depth == 5
    assert bare.step_counters == X.COUNTERS
    stack = {k: v for k, v in params.items() if "_mtp" not in k}
    assert set(stack) == set(bare.init(jax.random.PRNGKey(0)))
    (c_off, _), g_off = jax.value_and_grad(
        lambda p: off.loss(p, batch, None, True), has_aux=True)(params)
    (c_bare, _), g_bare = jax.value_and_grad(
        lambda p: bare.loss(p, batch, None, True), has_aux=True)(stack)
    assert float(c_off) == float(c_bare)
    for name in stack:
        np.testing.assert_array_equal(g_off[name], g_bare[name],
                                      err_msg=name)
    for name in set(params) - set(stack):
        assert float(jnp.abs(g_off[name]).max()) == 0.0, name


def _joyai_token_error(model, dims, params, batch):
    want = _joyai_reference().token_costs(
        params, dims, None, None, batch["trg_ids"], batch["trg_mask"])
    got = _token_costs(model, params, batch)
    real = np.asarray(batch["trg_mask"]) > 0
    want, got = np.asarray(want)[real], np.asarray(got)[real]
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


def test_one_precision_lower_is_caught_in_the_rotation_and_the_module(
        ahead, monkeypatch):
    """float32 is tight; the rotation's angles formed in bfloat16, or the
    module's projected state rounded to bfloat16, each alone in a float32
    model, exceed the float32 limit. (The benchmark's own limit, at the
    published widths on the chip, is in
    benchmark/traffic/train-docs8k-joyai.json.)"""
    model, dims, params, batch = ahead
    assert _joyai_token_error(model, dims, params, batch) < F32_LIMIT

    def bf16_angles(t, dim, theta):
        low = jnp.bfloat16
        rate = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        return jnp.repeat(
            (jnp.arange(t, dtype=low)[:, None] * rate.astype(low)[None, :]
             ).astype(jnp.float32), 2, axis=-1)
    monkeypatch.setattr(P, "rope_angles", bf16_angles)
    assert _joyai_token_error(model, dims, params, batch) > F32_LIMIT
    monkeypatch.undo()
    layer = P._layer

    def bf16_joined(cfg, kinds, lp, p, x, mask, remat):
        if lp.startswith("decoder_mtp"):
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        return layer(cfg, kinds, lp, p, x, mask, remat)
    monkeypatch.setattr(P, "_layer", bf16_joined)
    assert _joyai_token_error(model, dims, params, batch) > F32_LIMIT


def test_the_extra_heads_counters_reach_the_tracer(ahead):
    from marian_tpu.obs import TRACER
    model, _, params, batch = ahead
    assert model.step_counters == X.COUNTERS + ("mtp.ce_sum", "mtp.labels")
    _, aux = jax.jit(lambda p: model.loss(p, batch, None, True))(params)
    TRACER.reset()
    TRACER.enable()
    try:
        TRACER.count_lazy(model.step_counters, aux["counters"])
        TRACER.fetch_counters()
        got = TRACER.counters()
    finally:
        TRACER.reset()
    assert got["mtp.labels"] == float(batch["trg_mask"].sum()) - 2
    assert 0 < got["mtp.ce_sum"] / got["mtp.labels"] < 10


def test_a_plan_refuses_modules_it_cannot_hold():
    with pytest.raises(ValueError):
        _plan_model(extra=("--plan-mtp-modules", "3"))
    with pytest.raises(ValueError):
        _plan_model(extra=("--plan-mla-rope-theta", "1e4",
                           "--plan-mla-dim-shared", "7"))
    cfg = _plan_model(extra=("--plan-mtp-modules", "1")).cfg
    assert (cfg.dec_depth, cfg.mtp_modules, len(cfg.plan)) == (2, 1, 3)
    # nothing under marian_tpu/ names the model the plan was sized for
    hits = subprocess.run(
        ["grep", "-rli", "joyai", os.path.join(ROOT, "marian_tpu")],
        capture_output=True, text=True).stdout
    assert hits == ""


# sha256 of the StableHLO text of the tiny delta-rule plan's loss and
# gradient (`_tiny_model()`, the batch an argument), taken on the commit
# before the plan had a low-rank query, a rotation or prediction modules
# (PR 31, ca6b478). A PR that changes that plan's program ON PURPOSE takes
# the digest again from its own parent and says so; one that only adds to
# the plan must leave it.
# Taken again ON PURPOSE by PR 39 from its own tree (parent b3b4d83): the
# expert layer's pool is a loop of one or two batches and counts three more
# things a step. And ON PURPOSE by PR 50 from its own tree (parent a4ce82e):
# the expert layer is one loop of equal batches, as many as its list is
# long (the encoder-decoder family's digest below held).
_OTHER_PLAN_SHA256 = \
    "c5a89dfc0d21040462de40fb99b434b06cdf3a463ab9ac543c8c4dda2e529fb0"


def test_a_plan_that_asks_for_none_of_it_lowers_to_the_program_it_was(tiny):
    import hashlib
    model, dims, _, batch = tiny
    assert model.cfg.mla_q_rank == 0 and model.cfg.mla_rope_theta == 0.0 \
        and model.cfg.mtp_modules == 0 and model.step_counters == X.COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(7))
    text = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss(p, b, None, True), has_aux=True)).lower(
            shapes, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _OTHER_PLAN_SHA256


# The same for the encoder-decoder family, whose `EncoderDecoder.loss` this
# plan's extra heads rewrote: sha256 of the StableHLO text of loss and
# gradient of the benchmark's transformer-big (its file's `task_flags`, fused
# CE on, dropout on, a [128, 32] batch, shapes only), taken on the same
# commit (PR 31, ca6b478).
_BIG_SHA256 = \
    "8b72dc23b1b99c8929e82fa54f85a967d65c7ede99363d2f9c67daaf0f27e3d8"


def test_a_family_without_extra_heads_lowers_to_the_program_it_was():
    import hashlib
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "transformer-big.json")) as f:
        big = json.load(f)
    argv = list(big["task_flags"]) + [
        "--train-sets", "a", "b", "--vocabs", "v", "v", "--fused-ce", "on"]
    model = create_model(parse_options(argv, mode="training"),
                         big["vocab"], big["vocab"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(7))
    ids = jax.ShapeDtypeStruct((128, 32), jnp.int32)
    mask = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    batch = {"src_ids": ids, "src_mask": mask, "trg_ids": ids,
             "trg_mask": mask}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    text = jax.jit(jax.value_and_grad(
        lambda p, b, k: model.loss(p, b, k, True), has_aux=True)).lower(
            shapes, batch, key).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _BIG_SHA256
