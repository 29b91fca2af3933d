"""ZeRO-1 data-parallel training step — the heart of the rebuild
(reference: src/training/graph_group_sync.cpp :: SyncGraphGroup::update +
communicator_nccl.h :: NCCLCommunicator::scatterReduceAndResetGrads /
allGatherParams; SURVEY.md §2.7 "TPU-native equivalent").

One jitted function contains the full SyncGraphGroup cycle:

    per-shard fwd/bwd on the data-sharded batch
      → (GSPMD-inserted) reduce-scatter of gradients over 'data'
      → global-norm clip (psum'd norm), per-shard Adam update on the
        PartitionSpec('data') optimizer state
      → (GSPMD-inserted) all-gather of updated params back to replicated

The collectives are not written by hand: annotating the optimizer state
sharded and the params replicated makes XLA's SPMD partitioner emit exactly
the reduce-scatter + all-gather pattern (cf. "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training", arXiv:2004.13336 —
implemented in XLA; PAPERS.md). On a 1-device mesh the same program runs
collective-free — single-chip and pod training share one code path.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optimizers.optimizers import OptimizerConfig, apply_update
from ..ops.ops import clip_by_global_norm, global_norm
from . import mesh as M
from . import tensor as T

Params = Dict[str, jax.Array]


def finalize_update(opt_cfg: OptimizerConfig, opt_state, p, grads,
                    lr, labels, denom):
    """The shared tail of both update programs (the fused step and
    GraphGroup's update of an accumulated sum): cost normalization →
    --normalize-gradient → --dynamic-gradient-scaling (stats in
    opt_state['gstat']; outliers scaled down to factor x windowed
    average) → --clip-norm (sees the scaled norm, so the cap composes
    as min, never the product) → optimizer apply →
    --check-gradient-nan (non-finite norm reverts params + every
    optimizer-state part). Returns (new_p, new_opt, raw_gnorm,
    skipped)."""
    # jax.named_scope below is metadata only (HLO op_name): a profile
    # attributes device time by it, the compiled program is the same
    if opt_cfg.normalize_gradient:
        # reference: update normalizer x= updateTrgWords
        denom = denom * jnp.maximum(labels, 1.0)
    with jax.named_scope("clip"):
        grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
        gnorm = global_norm(grads)
    post_dyn_norm = gnorm
    opt_in = opt_state
    if opt_cfg.dyn_scale_factor > 0:
        # windowed running average of the (log-)norm; non-finite norms
        # leave the average untouched (one NaN must not poison it)
        gstat = opt_state["gstat"]
        finite = jnp.isfinite(gnorm)
        x = jnp.log(jnp.maximum(gnorm, 1e-30)) \
            if opt_cfg.dyn_scale_log else gnorm
        n = gstat["n"] + jnp.where(finite, 1.0, 0.0)
        w = jnp.minimum(jnp.maximum(n, 1.0), float(opt_cfg.norm_window))
        avg = jnp.where(finite, gstat["avg"] + (x - gstat["avg"]) / w,
                        gstat["avg"])
        thresh = (jnp.exp(avg) * opt_cfg.dyn_scale_factor
                  if opt_cfg.dyn_scale_log
                  else avg * opt_cfg.dyn_scale_factor)
        # statistics need a few steps before the threshold means much
        warm = n >= jnp.minimum(10.0, float(opt_cfg.norm_window))
        scale = jnp.where(warm & finite & (gnorm > thresh),
                          thresh / jnp.maximum(gnorm, 1e-30), 1.0)
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        post_dyn_norm = gnorm * scale
        opt_in = {**opt_state, "gstat": {"avg": avg, "n": n}}

    if opt_cfg.clip_norm > 0:
        with jax.named_scope("clip"):
            grads = clip_by_global_norm(grads, opt_cfg.clip_norm,
                                        post_dyn_norm)

    new_opt, new_p = apply_update(opt_cfg, opt_in, p, grads, lr, labels)
    skipped = jnp.zeros((), jnp.float32)
    if opt_cfg.check_gradient_nan:
        ok = jnp.isfinite(gnorm)
        new_p = jax.tree_util.tree_map(
            lambda n_, o: jnp.where(ok, n_, o), new_p, p)
        new_opt = jax.tree_util.tree_map(
            lambda n_, o: jnp.where(ok, n_, o), new_opt, opt_state)
        skipped = jnp.where(ok, 0.0, 1.0)
    return new_p, new_opt, gnorm, skipped


def expand_compact_batch(batch):
    """In-jit inverse of batch_to_arrays(compact=True): uint16 tokens →
    int32 ids, per-row lengths → 0/1 float prefix masks. Free on device
    (fuses into first use); the point is the 4× smaller host→device
    transfer each step."""
    if not any(k.endswith("_tok") for k in batch):
        return batch
    out = {}
    for k, v in batch.items():
        if k.endswith("_tok"):
            pfx = k[:-len("_tok")]
            ln = batch[f"{pfx}_len"]
            out[f"{pfx}_ids"] = v.astype(jnp.int32)
            out[f"{pfx}_mask"] = (
                jnp.arange(v.shape[-1], dtype=jnp.int32)
                < ln[..., None]).astype(jnp.float32)
        elif not k.endswith("_len"):
            out[k] = v
    return out


class _GradMachinery:
    """The gradient producer shared by the fused train step and the
    accumulating gradient program (build_grad_fn): per-device fwd/bwd +
    the explicit scatter-reduce cycle. ONE implementation, so a
    micro-batch's gradients are reduced and laid out as a whole
    update's."""

    def __init__(self, model, mesh: Mesh, params: Params,
                 frozen=(), dim_emb: int = 0, force_gspmd: bool = False,
                 grad_dtype=None):
        """``force_gspmd`` routes even pure-DP meshes through the GSPMD
        annotation path — test hook so the two gradient paths can be
        compared head-to-head on the same mesh
        (tests/test_distributed.py::test_manual_and_gspmd_paths_agree).

        ``grad_dtype`` (--gradient-dtype): dtype gradients are produced,
        reduce-scattered, and stored in until the optimizer's f32 upcast
        (apply_update reads g.astype(f32) in-register). bfloat16 halves
        the backward pass's gradient HBM writes and the ZeRO-1 collective
        bytes — the analogue of Marian's fp16 gradient communication
        (SURVEY: NCCLCommunicator fp16 path); the update math itself
        stays f32. None/float32 keeps gradients f32 end to end EXCEPT
        through the logits backward, which always rounds its cotangent to
        the compute dtype (ops/ops.py logits_matmul — the bf16 MXU-rate
        fix applies regardless of this setting; docs/PERFORMANCE.md)."""
        self.mesh = mesh
        self.n_data = mesh.shape["data"]
        # Explicit scatter-reduce runs on pure-DP meshes (the reference's
        # only parallelism and the north-star config); meshes with TP/SP/
        # pipe/expert axes compose through GSPMD annotations instead.
        self.manual_dp = not force_gspmd and self.n_data > 1 and all(
            mesh.shape[a] == 1 for a in mesh.shape if a != "data")
        if not dim_emb:
            dim_emb = int(getattr(getattr(model, "cfg", None),
                                  "dim_emb", 0) or 0)
        self.g_specs = T.tp_param_specs(params, mesh, dim_emb=dim_emb)
        self._shapes = {k: tuple(v.shape) for k, v in params.items()}
        self.data_axes = {
            k: T.zero1_data_axis(self.g_specs.get(k, P()), shape, mesh)
            for k, shape in self._shapes.items()}
        self.frozen_set = frozenset(frozen)
        self.model = model
        # counts a model family takes inside the step (routing counts of
        # an expert layer): one float32 vector beside the loss, summed
        # over devices; length 0 for most models
        self.n_counters = len(getattr(model, "step_counters", ()))
        gd = None if grad_dtype in (None, "float32") else jnp.dtype(grad_dtype)
        if gd is not None and gd == jnp.dtype(jnp.float32):
            gd = None
        cd = getattr(getattr(model, "cfg", None), "compute_dtype", None)
        if gd is not None and cd is None:
            # FAIL CLOSED: without a determinable compute dtype the safety
            # check below cannot run, and pre-casting params to grad_dtype
            # could silently change the COMPUTE dtype of an f32-precision
            # model (model.loss's cast becomes identity) — the one outcome
            # this check exists to prevent
            from ..common import logging as log
            log.warn("--gradient-dtype {} ignored: the model's compute "
                     "dtype could not be determined (no model.cfg."
                     "compute_dtype) — failing closed to float32 gradients",
                     gd)
            gd = None
        elif gd is not None and jnp.dtype(cd) != gd:
            # pre-casting params to grad_dtype would silently change the
            # COMPUTE dtype too (model.loss's cast becomes identity) —
            # refuse rather than corrupt f32-precision training
            from ..common import logging as log
            log.warn("--gradient-dtype {} ignored: compute precision is "
                     "{} (set --precision accordingly)", gd, jnp.dtype(cd))
            gd = None
        self.grad_dtype = gd

    def grads(self, p, batch, rng):
        """(grads, ce_sum, labels, counters) — grads globally reduced and
        ZeRO-1 sharded (manual path) or logically global (GSPMD path,
        pinned to the combined spec); counters [n_counters] float32."""
        if self.manual_dp:
            return self._sharded_grads(p, batch, rng)
        grads, ce_sum, labels, counters = self._local_grads(p, batch, rng)
        return self._constrain(grads), ce_sum, labels, counters

    def _counters(self, aux):
        return aux["counters"] if self.n_counters \
            else jnp.zeros((0,), jnp.float32)

    def grad_shardings(self):
        """NamedSharding per gradient leaf (combined TP + ZeRO-1 spec) —
        what self.grads() produces; also the right out_shardings for a
        grads-only jit."""
        return {
            k: NamedSharding(self.mesh, T.zero1_combined_spec(
                self.g_specs.get(k, P()), shape, self.mesh))
            for k, shape in self._shapes.items()}

    def _grads_of(self, p, b, rng):
        if self.grad_dtype is not None:
            # differentiate wrt the ALREADY-cast params: model.loss's
            # internal cast_params is then an identity, so the cotangents
            # come out in grad_dtype directly — the backward dots WRITE
            # bf16 (half the HBM bytes) instead of writing f32 through
            # the cast boundary's convert
            from ..ops.quantization import QTensor
            p = {k: (v.astype(self.grad_dtype)
                     if not isinstance(v, QTensor)
                     and jnp.issubdtype(v.dtype, jnp.floating) else v)
                 for k, v in p.items()}

        def loss_fn(pp, bb, r):
            return self.model.loss(pp, bb, r, train=True)
        (_, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b, rng)
        if self.frozen_set:
            # --embedding-fix-src/trg: fixed tables get no update and no
            # contribution to the global norm (reference: trainable=false)
            g = {k: (jnp.zeros_like(v) if k in self.frozen_set else v)
                 for k, v in g.items()}
        return g, aux

    def _local_grads(self, p, batch, rng):
        """GSPMD-path fwd/bwd: logically global gradients; the
        partitioner places the cross-device sums (graph_group_sync.cpp's
        per-device backward, expressed as annotations)."""
        grads, aux = self._grads_of(p, batch, rng)
        return grads, aux["ce_sum"], aux["labels"], self._counters(aux)

    def _constrain(self, grads):
        """GSPMD path: pin each gradient leaf to its combined TP+ZeRO-1
        layout (same spec as its Adam-moment leaf), so the partitioner
        reshards grads once, ahead of the sharded optimizer math."""
        return {
            k: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, T.zero1_combined_spec(
                    self.g_specs.get(k, P()), tuple(g.shape), self.mesh)))
            for k, g in grads.items()}

    def _scatter_reduce_body(self, p, batch, rng):
        """shard_map body, manual over 'data': per-device fwd/bwd on the
        local batch shard, then an EXPLICIT per-leaf reduce-scatter of the
        gradients onto each leaf's ZeRO-1 shard axis —
        NCCLCommunicator::scatterReduceAndResetGrads made visible in the
        program. Left to GSPMD alone, the partitioner materializes the
        gradient sum as a full-size all-reduce and slices afterwards
        (observed on the CPU partitioner): numerically identical but ~1.5×
        the collective bytes. psum_scatter pins the reduce-scatter on every
        backend; tests/test_distributed.py greps the compiled HLO for it.

        The shard_map runs with check_vma=False (classic manual-mode
        semantics): every value in the body is treated as device-varying,
        so autodiff keeps the cotangents of the replicated params as LOCAL
        partial sums (per-device backward, as in the reference). Under
        varying-manual-axes typing (check_vma=True) shard_map's autodiff
        would instead insert its own full-size psum for unvarying inputs —
        double-counting ahead of psum_scatter — and unvarying lax.scan
        carries inside the models (RNN hidden states) would need pcast
        plumbing throughout."""
        # independent per-device dropout streams (reference: per-device
        # cuRAND generators); with dropout off the key is never consumed
        key = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        g, aux = self._grads_of(p, batch, key)
        with jax.named_scope("collectives"):
            grads = self._scatter(g)
        counters = self._counters(aux)
        return (grads, jax.lax.psum(aux["ce_sum"], "data"),
                jax.lax.psum(aux["labels"], "data"),
                jax.lax.psum(counters, "data"))

    def _scatter(self, grads):
        """scatterReduceAndResetGrads on one gradient tree: per-leaf
        reduce-scatter onto its ZeRO-1 axis; whole-tensor psum for the
        few leaves no axis divides."""
        out = {}
        for k, g in grads.items():
            ax = self.data_axes[k]
            if ax is None:
                out[k] = jax.lax.psum(g, "data")
            else:
                out[k] = jax.lax.psum_scatter(
                    g, "data", scatter_dimension=ax, tiled=True)
        return out

    @staticmethod
    def _data_only(spec: P) -> P:
        return P(*tuple(s if s == "data" else None for s in spec))

    def _sharded_grads(self, p, batch, rng):
        b_specs = {k: self._data_only(M.batch_leaf_spec(
                       k, getattr(v, "ndim", 2)))
                   for k, v in batch.items()}
        g_out = {k: (P() if ax is None else P(*([None] * ax + ["data"])))
                 for k, ax in self.data_axes.items()}
        return jax.shard_map(
            self._scatter_reduce_body, mesh=self.mesh,
            in_specs=(P(), b_specs, P()),
            out_specs=(g_out, P(), P(), P()),
            check_vma=False)(p, batch, rng)


def _step_key(rng, step):
    """(the update's dropout key, the step as int32) from the RAW
    training stream key: fold_in(rng, step - 1), folded ON DEVICE by the
    absolute step number, so the host dispatches no tiny
    _threefry_fold_in program between steps. GraphGroup passes step as
    int32 so the fold index is EXACT at any step count; a float step
    (direct callers) is tolerated but its fold saturates f32's 2^24
    integer range."""
    step = jnp.asarray(step)
    step_i = (step if jnp.issubdtype(step.dtype, jnp.integer)
              else step.astype(jnp.int32))
    return jax.random.fold_in(rng, step_i - 1), step_i


def build_grad_fn(model, mesh: Mesh, params: Params, frozen=(),
                  dim_emb: int = 0, grad_dtype=None, donate: bool = True):
    """The two programs an --optimizer-delay update accumulates with
    (GraphGroup.update, a list of micro-batches), over the SAME gradient
    machinery as the fused step:

    - zero_sum() -> the running sum at its start: float32 zeros per
      gradient leaf, laid out ZeRO-1 sharded by grad_shardings(), and
      zero `ce_sum` / `labels`;
    - accumulate(params, sum, batch, step, i, rng) -> (sum, counters):
      micro-batch i's gradients under the key fold_in(fold_in(rng,
      step - 1), i), added INTO the donated sum (acc + g.astype(float32),
      float32 whatever --gradient-dtype says: bfloat16 adds would absorb
      a late micro-batch's small terms), with its `ce_sum` and `labels`;
      `counters` is the model family's lazy step-counter vector, None
      where it keeps none. One program per batch shape."""
    m = _GradMachinery(model, mesh, params, frozen=frozen,
                       dim_emb=dim_emb, grad_dtype=grad_dtype)
    rep = M.replicated(mesh)
    sum_shardings = {"grads": m.grad_shardings(), "ce_sum": rep,
                     "labels": rep}

    def zero_sum():
        zero = jnp.zeros((), jnp.float32)
        return {"grads": {k: jnp.zeros(shape, jnp.float32)
                          for k, shape in m._shapes.items()},
                "ce_sum": zero, "labels": zero}

    def accumulate(p, total, batch, step, i, rng):
        key, _ = _step_key(rng, step)
        rng = jax.random.fold_in(key, i)
        batch = expand_compact_batch(batch)
        with jax.named_scope("grads"):
            grads, ce_sum, labels, counters = m.grads(p, batch, rng)
        total = {"grads": {k: acc + grads[k].astype(jnp.float32)
                           for k, acc in total["grads"].items()},
                 "ce_sum": total["ce_sum"] + ce_sum,
                 "labels": total["labels"] + labels}
        return total, (counters if m.n_counters else None)

    return (jax.jit(zero_sum, out_shardings=sum_shardings),
            jax.jit(accumulate, out_shardings=(sum_shardings, None),
                    donate_argnums=(1,) if donate else ()))


def take_load_signals(model, grads):
    """(grads, signals): the leaves that `model.load_moved` names hold
    no gradient but their experts' excess load (ops/experts.py::
    load_signal), summed over micro-batches and chips as gradients are.
    They leave the gradients as zeros BEFORE anything norms, clips or
    applies them, so the optimizer does not move those leaves and the
    global norm does not hold them; `move_by_load` does the moving."""
    suffix, rate = model.load_moved
    if not rate:
        return grads, {}
    signals = {k: g for k, g in grads.items() if k.endswith(suffix)}
    return {k: (jnp.zeros_like(g) if k in signals else g)
            for k, g in grads.items()}, signals


def move_by_load(model, p, new_p, signals):
    """The updated parameters with each load-moved leaf stepped from its
    OLD value by the model's rate against the sign of its signal: an
    expert over the mean load loses `rate` of selection bias, one under
    it gains as much, one at it stays."""
    rate = model.load_moved[1]
    return {**new_p, **{
        k: p[k] - rate * jnp.sign(s).astype(p[k].dtype).reshape(p[k].shape)
        for k, s in signals.items()}}


def build_train_step(model, opt_cfg: OptimizerConfig, schedule, cost_type: str,
                     mesh: Mesh, params: Params, opt_state,
                     donate: bool = True, shardings=None,
                     frozen=(), force_gspmd: bool = False,
                     grad_dtype=None):
    """Returns a jitted fn(params, opt_state, batch, step, rng) →
    (params, opt_state, metrics) with SyncGraphGroup semantics: ONE batch,
    one update (--optimizer-delay accumulates outside it: build_grad_fn).

    Inputs must arrive committed: params/opt_state via place(), batches via
    mesh.shard_batch (per-leaf name-aware specs). Only the outputs
    are pinned here so donation layouts match. `shardings` optionally passes
    precomputed (param_shardings, opt_state_shardings) to avoid recomputing.
    """
    machinery = _GradMachinery(model, mesh, params,
                               frozen=frozen, force_gspmd=force_gspmd,
                               grad_dtype=grad_dtype)
    g_specs = machinery.g_specs

    def one_update(p, opt_state, batch, step, rng):
        # rng is the RAW training stream key
        rng, step_i = _step_key(rng, step)
        step = step_i.astype(jnp.float32)     # schedule/metrics math
        with jax.named_scope("expand_batch"):
            batch = expand_compact_batch(batch)
        # autodiff marks the forward ops jvp(..) and the backward ops
        # transpose(jvp(..)) inside this scope by itself
        with jax.named_scope("grads"):
            grads, ce_sum, labels, counters = machinery.grads(p, batch,
                                                              rng)

        # cost normalization → gradient scale (Marian's costScaleFactor)
        if cost_type in ("ce-mean-words", "perplexity"):
            denom = jnp.maximum(labels, 1.0)
        elif cost_type == "ce-mean":
            denom = jnp.asarray(batch["trg_ids"].shape[0], jnp.float32)
        else:
            denom = jnp.asarray(1.0, jnp.float32)
        grads, signals = take_load_signals(model, grads)
        with jax.named_scope("optimizer"):
            lr = schedule(step)
            new_p, new_opt, gnorm, skipped = finalize_update(
                opt_cfg, opt_state, p, grads, lr, labels, denom)
            new_p = move_by_load(model, p, new_p, signals)
        metrics = {"ce_sum": ce_sum, "labels": labels, "gnorm": gnorm,
                   "lr": lr}
        if machinery.n_counters:
            metrics["counters"] = counters
        if opt_cfg.check_gradient_nan:
            metrics["skipped"] = skipped
            # a skipped batch must not poison the display window's cost
            # (nan ce_sum would read as divergence the skip just averted)
            metrics["ce_sum"] = jnp.where(skipped > 0, 0.0, ce_sum)
            metrics["labels"] = jnp.where(skipped > 0, 0.0, labels)
        return new_p, new_opt, metrics

    rep = M.replicated(mesh)
    # TP (Megatron-style over 'model') via GSPMD param specs; replicated when
    # the model axis is 1. ZeRO-1 'data' sharding composes on the opt state.
    if shardings is None:
        p_shardings = T.param_shardings(params, mesh, g_specs)
        o_shardings = T.opt_state_shardings(opt_state, g_specs, mesh)
    else:
        p_shardings, o_shardings = shardings
    metrics_shardings = {"ce_sum": rep, "labels": rep, "gnorm": rep, "lr": rep}
    if opt_cfg.check_gradient_nan:
        metrics_shardings["skipped"] = rep
    if machinery.n_counters:
        metrics_shardings["counters"] = rep

    return jax.jit(
        one_update,
        out_shardings=(p_shardings, o_shardings, metrics_shardings),
        donate_argnums=(0, 1) if donate else ())


def optimizer_sweep_bytes(opt_state) -> "Dict[int, int]":
    """Per-device resident bytes of the optimizer SWEEP state — every
    tensor leaf of the m/v/gt/avg/... groups; scalars like 't' excluded.

    This is the ZeRO-1 claim from VERDICT #6 / ROADMAP item 3 made
    measurable: on an N-device 'data' axis each device must hold ~1/N of
    the logical bytes (the swept shard), so a regression that silently
    re-replicates optimizer state shows up as a per-device total ~equal to
    optimizer_logical_bytes() instead of ~1/N of it. Replicated leaves
    report their FULL size on every device (each device really does hold
    a copy), which is exactly what makes re-replication detectable."""
    out: Dict[int, int] = {}
    for group in opt_state.values():
        if not isinstance(group, dict):
            continue
        for arr in group.values():
            if not isinstance(arr, jax.Array):
                continue
            for shard in arr.addressable_shards:
                did = int(getattr(shard.device, "id", 0))
                out[did] = out.get(did, 0) + int(shard.data.nbytes)
    return out


def optimizer_logical_bytes(opt_state) -> int:
    """Total bytes of the logical (unsharded) optimizer sweep state —
    the denominator for the re-replication check above."""
    total = 0
    for group in opt_state.values():
        if not isinstance(group, dict):
            continue
        for arr in group.values():
            if isinstance(arr, jax.Array):
                total += int(arr.nbytes)
    return total


def place(params, opt_state, mesh: Mesh, dim_emb: int = 0):
    """Put params TP-sharded-over-'model' (replicated when model axis is 1)
    and optimizer state ZeRO-1-sharded on the mesh (reference:
    SyncGraphGroup::initialize laying out per-device shards)."""
    p_specs = T.tp_param_specs(params, mesh, dim_emb=dim_emb)
    params = jax.device_put(params, T.param_shardings(params, mesh, p_specs))
    opt_state = jax.device_put(
        opt_state, T.opt_state_shardings(opt_state, p_specs, mesh))
    return params, opt_state


# ---------------------------------------------------------------------------
# driver dry-run (called by __graft_entry__.dryrun_multichip)
# ---------------------------------------------------------------------------

def dryrun(n_devices: int, options, batch_maker, vocab: int = 256) -> None:
    import numpy as np
    from ..models.encoder_decoder import create_model
    from ..optimizers.optimizers import init_state
    from ..optimizers.schedule import LRSchedule

    devices = jax.devices()[:n_devices]
    if len(devices) != n_devices:
        raise RuntimeError(
            f"dryrun requested {n_devices} devices but the platform "
            f"provides only {len(devices)} — refusing to silently "
            f"under-provision")
    mesh = M.make_mesh(options, devices)
    model = create_model(options, vocab, vocab)
    params = model.init(jax.random.key(0))
    if mesh.shape.get("pipe", 1) > 1:
        # depth-stacked storage so the layer axis shards over 'pipe'
        from ..models import transformer as TT
        params = TT.stack_layer_params(model.cfg, params)
    opt_cfg = OptimizerConfig.from_options(options)
    opt_state = init_state(opt_cfg, params)
    params, opt_state = place(
        params, opt_state, mesh,
        dim_emb=int(getattr(model.cfg, "dim_emb", 0) or 0))
    schedule = LRSchedule.from_options(options)
    step = build_train_step(model, opt_cfg, schedule,
                            options.get("cost-type", "ce-sum"), mesh,
                            params, opt_state, donate=False)
    batch = batch_maker(8 * max(1, mesh.shape["data"]), 16, 16, vocab)
    batch = M.shard_batch(batch, mesh)
    p2, o2, metrics = step(params, opt_state,
                           batch, jnp.asarray(1.0, jnp.float32),
                           jax.random.key(1))
    jax.block_until_ready(p2)
    assert np.isfinite(float(metrics["ce_sum"]))
