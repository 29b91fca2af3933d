"""The training-step engine — equivalent of the reference's GraphGroup stack
(src/training/graph_group_sync.cpp :: SyncGraphGroup::update,
graph_group.cpp :: GraphGroup base).

Where the reference spawns one host thread per GPU, builds a tape per
replica, reduce-scatters gradients over NCCL, Adam-updates a 1/N parameter
shard per device and all-gathers params, here ONE jitted function contains
the whole cycle and GSPMD inserts the identical collectives over ICI
(parallel/zero.py). A single device is the same program on a 1-device mesh —
SingletonGraph (graph_group_singleton.cpp) is not a separate code path.

Semantics carried over exactly:
- --optimizer-delay N: `update` is handed N micro-batches of any shapes and
  dispatches one gradient program per micro-batch, each adding into a
  donated float32 sum, then one update program. Gradient normalization
  follows the cost-type over the whole sum (ce-mean-words divides by the
  accumulated label count, like Marian's costScaleFactor);
- clip-then-update order: global-norm clip on the FULL gradient before the
  sharded optimizer update;
- EMA (exponential smoothing) updated after each optimizer step, stored with
  the sharded optimizer state;
- async-SGD (--sync-sgd false) intentionally maps to sync with a warning —
  hogwild updates have no TPU/SPMD equivalent and sync is the reference's
  recommended path (AsyncGraphGroup is legacy).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import logging as log
from ..data.batch_generator import budget_shapes
from ..models.encoder_decoder import EncoderDecoder
from ..obs import trace as obs_trace
from ..optimizers.optimizers import (OptimizerConfig, init_state,
                                     smoothed_params)
from ..optimizers.schedule import LRSchedule
from ..parallel import mesh as M
from ..parallel.zero import (build_grad_fn, build_train_step,
                             finalize_update, move_by_load, place,
                             take_load_signals)
from . import hbm
from . import program_store
from .program_store import ProgramStore, Refused, describe

Params = Dict[str, jax.Array]

# a step executable's memory_analysis() under the ledger's names
_PROGRAM_COSTS = (("code", "generated_code_size_in_bytes"),
                  ("temp", "temp_size_in_bytes"),
                  ("args", "argument_size_in_bytes"),
                  ("out", "output_size_in_bytes"),
                  ("alias", "alias_size_in_bytes"))


def _costs_of(cost) -> Dict[str, int]:
    return {field: int(getattr(cost, attr, 0))
            for field, attr in _PROGRAM_COSTS}


def _mb(n: Optional[int]) -> str:
    return "?" if n is None else f"{n / 1e6:.1f} MB"


def _how_built(record: Dict[str, Any]) -> str:
    """One step program's start-up record (`_compile_ahead`) in words."""
    if record["source"] == "store":
        return (f"loaded from the program store in {record['load_s']:.1f} s "
                f"({_mb(record['bytes'])})")
    return (f"trace {record['trace_s']:.1f} s, lower "
            f"{record['lower_s']:.1f} s, compile or cache load "
            f"{record['compile_s']:.1f} s; {_mb(record.get('bytes', 0))} "
            f"kept in the program store")


@dataclasses.dataclass
class TrainOutput:
    """Per-update metrics. Fields hold LAZY device scalars (jax.Array):
    converting with float() blocks on the step — callers on the hot path
    (train loop, bench) must NOT convert per step; the Scheduler defers the
    sync to display boundaries so JAX's async dispatch can pipeline steps."""
    loss_sum: Any
    labels: Any
    grad_norm: Any
    # lazy 0/1 --check-gradient-nan flag: 1 when this update was skipped
    # (params + optimizer reverted in-jit). None when the guard is off.
    # Same laziness contract as the scalars above — the Scheduler drains
    # it with bounded lag, never per-step (ISSUE 19).
    skipped: Any = None


class GraphGroup:
    """Owns params + sharded optimizer state + the jitted step functions."""

    def __init__(self, model: EncoderDecoder, options,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 donate: bool = True):
        self.model = model
        self.options = options
        self.opt_cfg = OptimizerConfig.from_options(options)
        self.schedule = LRSchedule.from_options(options)
        self.delay = max(1, int(float(options.get("optimizer-delay", 1))))
        if options.has("sync-sgd") and options.get("sync-sgd") is False:
            log.warn("Asynchronous SGD has no SPMD equivalent; using sync-sgd")
        self.mesh = mesh if mesh is not None else M.make_mesh(options)
        self.cost_type = options.get("cost-type", "ce-sum")
        self.params: Optional[Params] = None
        self.opt_state: Optional[Dict[str, Any]] = None
        self._donate = donate
        self._fused = None
        # a list of micro-batches: the sum's zeros, one micro-batch's
        # gradients added into it, the update from the sum
        self._zero_sum = None
        self._grad_fn = None
        self._update_fn = None
        self._fix_src = bool(options.get("embedding-fix-src", False))
        self._fix_trg = bool(options.get("embedding-fix-trg", False))
        self._dump_hlo = options.get("dump-hlo", None)
        # --precompile-buckets: shape key -> future of the step compiled
        # ahead for it; None until the first update shows what a batch,
        # its step number and its key look like
        self._ahead_threads = int(options.get("precompile-buckets", 0) or 0)
        self._ahead: Optional[Dict[tuple, Any]] = None
        # the memory ledger, host integers: what `params` and `opt_state`
        # hold on one device, and by shape key what the compiler says of
        # each step executable compiled ahead (`name`, `code`, `temp`,
        # `args`, `out`, `alias`). The jitted step without
        # --precompile-buckets reaches no executable without a second
        # lowering: its ledger holds the state alone.
        self._state_bytes = 0
        self._programs: Dict[tuple, Dict[str, Any]] = {}
        self._ledger_gauged: Optional[Dict[str, int]] = None

    def _frozen_names(self) -> frozenset:
        """Params excluded from updates: --embedding-fix-src/trg tables
        (with tied embeddings the shared table freezes if either side is
        fixed — reference: Embedding with trainable=false), plus the fixed
        ULR query/key tables (and A unless --ulr-trainable-transformation)."""
        names = set()
        if self._fix_src or self._fix_trg:
            for k in self.params:
                is_src = ((k.endswith("_Wemb") or k.endswith("_Wemb_factors"))
                          and not k.startswith("decoder")) or (k == "Wemb")
                is_trg = k in ("decoder_Wemb", "decoder_Wemb_factors",
                               "Wemb_dec") or (
                    k == "Wemb" and not any(
                        o in self.params
                        for o in ("decoder_Wemb", "Wemb_dec")))
                if (self._fix_src and is_src) or (self._fix_trg and is_trg):
                    names.add(k)
        if "ulr_Q" in self.params:
            names.update(("ulr_Q", "ulr_K"))
            if not self.options.get("ulr-trainable-transformation", False):
                names.add("ulr_A")
        return frozenset(names)

    def rebuild(self) -> None:
        """Re-trace the jitted step functions. Needed whenever host-side
        schedule state that is baked into the trace changes (decay factor,
        warmup offset) — the compiled step otherwise keeps using the values
        from build time."""
        self._build()

    def reset_optimizer(self) -> None:
        """Re-initialize optimizer moments (--lr-decay-reset-optimizer),
        keeping params and step count."""
        self.opt_state = init_state(self.opt_cfg, self.params)
        _, self.opt_state = place(
            self.params, self.opt_state, self.mesh,
            dim_emb=int(getattr(self.model.cfg, "dim_emb", 0) or 0))
        self._build()

    # -- init / load --------------------------------------------------------
    def _maybe_stack(self) -> None:
        """Depth-stacked storage when the mesh has a 'pipe' axis, or on
        --stacked-params: layer leaves become '{prefix}_stack_{suffix}'
        [L, ...] sharded P('pipe', ...) (a no-op axis of size 1 without
        pipeline sharding) — each pipeline stage holds and updates only
        its layers (models/transformer.py stack_layer_params). Without
        'pipe', the point is eliminating --scan-layers' per-step restack:
        the scan consumes the stored stack directly, saving one full
        HBM read+write of every layer weight per micro-batch."""
        self._stacked = False
        if self.mesh.shape.get("pipe", 1) <= 1 \
                and not self.options.get("stacked-params", False):
            return
        what = ("pipeline ('pipe') sharding"
                if self.mesh.shape.get("pipe", 1) > 1 else "--stacked-params")
        from ..models import transformer as TT
        cfg = getattr(self.model, "cfg", None)
        if not isinstance(cfg, TT.TransformerConfig):
            raise ValueError(f"{what} is only supported "
                             f"for the transformer family")
        reason = TT.can_stack_layers(cfg)
        # the CLI default for --guided-alignment is the STRING "none";
        # comparison kept identical to encoder_decoder.use_guided /
        # config_validator / train.py so every site agrees on off
        ga = self.options.get("guided-alignment", None)
        if reason is None and ga and ga != "none":
            reason = "guided alignment extracts one layer's attention " \
                     "weights (unrolled stack)"
        if reason is not None:
            raise ValueError(f"{what} unavailable: {reason}")
        pipe = self.mesh.shape["pipe"]
        for prefix, depth in TT.layer_param_groups(cfg):
            if depth % pipe != 0:
                # GSPMD requires divisibility; the silent alternative would
                # replicate the whole stack (4x memory, no residency win)
                raise ValueError(
                    f"pipeline sharding: {prefix} depth {depth} is not "
                    f"divisible by the 'pipe' axis size {pipe}")
        self.params = TT.stack_layer_params(cfg, self.params)
        if self.opt_state is not None:
            for part, group in self.opt_state.items():
                if isinstance(group, dict):
                    self.opt_state[part] = TT.stack_layer_params(cfg, group)
        self._stacked = True

    def _unstack(self, tree: Params) -> Params:
        if not getattr(self, "_stacked", False):
            return tree
        from ..models import transformer as TT
        return TT.unstack_layer_params(self.model.cfg, tree)

    def initialize(self, key: jax.Array,
                   init_params: Optional[Params] = None) -> None:
        self.params = init_params if init_params is not None \
            else self.model.init(key)
        self._maybe_stack()
        if self.opt_state is None:  # keep state restored from checkpoint
            self.opt_state = init_state(self.opt_cfg, self.params)
        else:
            # a restored checkpoint may predate newly-enabled features
            # (EMA, --quantize-bits, --gradient-dropping-rate): backfill
            # any missing state groups with fresh zeros
            template = init_state(self.opt_cfg, self.params)
            for k, v in template.items():
                self.opt_state.setdefault(k, v)
        self.params, self.opt_state = place(
            self.params, self.opt_state, self.mesh,
            dim_emb=int(getattr(self.model.cfg, "dim_emb", 0) or 0))
        # as placed: a sharded leaf costs a device its shard
        self._state_bytes = sum(
            math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves((self.params,
                                                self.opt_state)))
        self._build()

    def _build(self) -> None:
        from ..parallel import tensor as T
        mesh = self.mesh
        rep = M.replicated(mesh)
        dim_emb = int(getattr(self.model.cfg, "dim_emb", 0) or 0)
        p_specs = T.tp_param_specs(self.params, mesh, dim_emb=dim_emb)
        p_sh = T.param_shardings(self.params, mesh, p_specs)
        o_sh = T.opt_state_shardings(self.opt_state, p_specs, mesh)
        model, opt_cfg, schedule = self.model, self.opt_cfg, self.schedule

        # fused single-batch step (the hot path)
        frozen = self._frozen_names()
        grad_dtype = self.options.get("gradient-dtype", "float32")
        self._fused = build_train_step(model, opt_cfg, schedule,
                                       self.cost_type, mesh, self.params,
                                       self.opt_state,
                                       donate=self._donate,
                                       shardings=(p_sh, o_sh), frozen=frozen,
                                       grad_dtype=grad_dtype)
        self._ahead = None              # executables of the step before
        self._programs = {}

        # --optimizer-delay: the accumulating gradient program over the
        # fused step's gradient machinery (per-device backward + explicit
        # scatter-reduce; the sum stays ZeRO-1 sharded for the sharded
        # update tail). Batches arrive committed via M.shard_batch
        # (per-leaf name-aware specs), so no in_shardings here.
        self._zero_sum, self._grad_fn = build_grad_fn(
            model, mesh, self.params, frozen=frozen, grad_dtype=grad_dtype,
            donate=self._donate)

        # hoisted: the branch below is resolved AT TRACE TIME, so the
        # traced fn must not read self.cost_type through its closure — a
        # later rebind would silently retrace (MT-JIT-CLOSURE-VARYING)
        cost_type = self.cost_type

        def update_step(p, opt_state, total, step, n_sents):
            labels = total["labels"]
            if cost_type in ("ce-mean-words", "perplexity"):
                denom = jnp.maximum(labels, 1.0)
            elif cost_type == "ce-mean":
                denom = jnp.maximum(n_sents, 1.0)
            else:
                denom = jnp.asarray(1.0, jnp.float32)
            # shared tail (zero.py finalize_update): normalize-gradient,
            # dynamic scaling, clip-as-min, nan-skip
            grads, signals = take_load_signals(model, total["grads"])
            new_p, new_opt, gnorm, skipped = finalize_update(
                opt_cfg, opt_state, p, grads, schedule(step),
                labels, denom)
            new_p = move_by_load(model, p, new_p, signals)
            ce_sum = total["ce_sum"]
            metrics = {"gnorm": gnorm}
            if opt_cfg.check_gradient_nan:
                # as the fused step: a skipped update must not poison the
                # display window's cost
                metrics["skipped"] = skipped
                ce_sum = jnp.where(skipped > 0, 0.0, ce_sum)
                labels = jnp.where(skipped > 0, 0.0, labels)
            return new_p, new_opt, dict(metrics, ce_sum=ce_sum,
                                        labels=labels)

        # the sum is not donated: no output has its shape to take it
        self._update_fn = jax.jit(
            update_step,
            out_shardings=(p_sh, o_sh, rep),
            donate_argnums=(0, 1) if self._donate else ())

    # -- one (macro-)update --------------------------------------------------
    def _output(self, metrics) -> TrainOutput:
        """A step's metrics as a TrainOutput. A model family's step
        counters go, still lazy, to the tracer, which keeps them while
        spans are live and fetches them where the Scheduler's display
        syncs anyway."""
        if "counters" in metrics:
            obs_trace.TRACER.count_lazy(self.model.step_counters,
                                        metrics["counters"])
        return TrainOutput(metrics["ce_sum"], metrics["labels"],
                           metrics["gnorm"], metrics.get("skipped"))

    def _dispatch(self, fn, step: int, *args, batch=None):
        """The jitted call alone, as the span ``train.dispatch``:
        ``step`` is the update number the spans of one update share,
        ``retraced`` whether the step function's cache grew in this call
        (a trace and a compile or cache load hid in the dispatch). Where
        the device's allocator keeps statistics the span also says which
        step ``program`` the call runs (`batch`'s shape) and what was
        ``free_before`` it went in, and the ``hbm.*`` gauges take the
        same sample beside the ledger's sums. A call the device's memory
        refuses is reported with the ledger, and raised as it was."""
        try:
            with obs_trace.span("train.dispatch", step=int(step)) as sp:
                if not sp:
                    return fn(*args)
                mem = hbm.device_memory()
                if mem is not None:
                    self._sample_memory(sp, mem, batch)
                # an executable compiled ahead has no cache to grow
                size = getattr(fn, "_cache_size", lambda: 0)
                before = size()
                out = fn(*args)
                sp.set_attrs(retraced=int(size() > before))
                return out
        except Exception as e:
            if "RESOURCE_EXHAUSTED" in str(e):
                self._report_exhausted(step, batch)
            raise

    # -- the memory ledger ----------------------------------------------------
    def _report_exhausted(self, step: int, batch) -> None:
        """ONE error line for a dispatch the device's memory refused. Best
        effort: it asks a client that has just failed an allocation for
        its statistics, and nothing raised here may take the place of the
        exception being reported."""
        try:
            held = list(self._programs.values())
            log.error(
                "Update {}: step program {} does not fit the device. {}; "
                "step programs held: {}", int(step),
                self._program_name(batch),
                self._memory_line(hbm.device_memory()),
                ", ".join(f"{p['name']} {_mb(p['code'])}"
                          for p in held) or "none compiled ahead")
        except Exception as e:  # noqa: BLE001 — the caller raises its own
            log.error("Update {}: a step program does not fit the device "
                      "(no memory report: {!r})", int(step), e)

    @staticmethod
    def _program_name(batch) -> str:
        """Which step program a batch runs: rows x width of its 2-D
        leaves ("10x1536"; both, where two streams differ)."""
        if batch is None:
            return "update"         # the update from an accumulated sum
        return "+".join(dict.fromkeys(
            "x".join(map(str, v.shape))
            for _, v in sorted(batch.items()) if v.ndim >= 2)) or "-"

    def _sample_memory(self, sp, mem: Dict[str, int], batch) -> None:
        """One live dispatch's sample, taken BEFORE the call."""
        held = list(self._programs.values())    # compile threads add to it
        sp.set_attrs(program=self._program_name(batch))
        # what does not change from one dispatch to the next
        ledger = {"hbm.state": self._state_bytes}
        if "limit" in mem:
            ledger["hbm.limit"] = mem["limit"]
        if held:
            ledger["hbm.programs_code"] = sum(p["code"] for p in held)
            ledger["hbm.step_temp_max"] = max(p["temp"] for p in held)
        samples = {}
        if "limit" in mem and "in_use" in mem:
            free = samples["hbm.free"] = mem["limit"] - mem["in_use"]
            sp.set_attrs(free_before=free)
            if held:
                # `in_use` holds the state and the programs loaded, never
                # a step's temporaries: what a program load can count on
                # is what is free LESS the widest step's, whichever step
                # is running when it comes
                samples["hbm.headroom"] = free - ledger["hbm.step_temp_max"]
        for key in ("largest_free", "reserved"):
            if key in mem:
                samples["hbm." + key] = mem[key]
        # by name in a loop: mtlint reads a literal `.gauge("...")` as a
        # Prometheus registration (MT-METRIC-UNUSED)
        gauge = obs_trace.TRACER.gauge
        counts = [gauge(name, value) for name, value in samples.items()]
        # the ledger once a stretch of recording (a gauge's first sample
        # opens one), and again when a compile thread has added to it
        if 1 in counts or ledger != self._ledger_gauged:
            self._ledger_gauged = ledger
            for name, value in ledger.items():
                gauge(name, value)

    def _memory_line(self, mem: Optional[Dict[str, int]]) -> str:
        """The ledger and the allocator's word in one line."""
        mem = mem or {}
        held = list(self._programs.values())
        line = f"HBM {_mb(mem.get('limit'))}: state {_mb(self._state_bytes)}, "
        if held:
            code = max(held, key=lambda p: p["code"])
            temp = max(held, key=lambda p: p["temp"])
            line += (f"{len(held)} step programs "
                     f"{_mb(sum(p['code'] for p in held))} (largest "
                     f"{_mb(code['code'])}, {code['name']}), widest step's "
                     f"temporaries {_mb(temp['temp'])} ({temp['name']}), ")
        else:
            line += "no step program compiled ahead, "
        line += (f"in use now {_mb(mem.get('in_use'))}, reserved "
                 f"{_mb(mem.get('reserved'))}, largest free block "
                 f"{_mb(mem.get('largest_free'))}")
        if held and "limit" in mem and "in_use" in mem:
            # the gauge `hbm.headroom` (_sample_memory), as of now
            line += (", headroom (free less those temporaries) "
                     + _mb(mem["limit"] - mem["in_use"] - temp["temp"]))
        return line

    def _program_store(self) -> Optional[ProgramStore]:
        """Where this trainer's step programs are kept between starts:
        beside the persistent cache, so only where the process has one
        (common/profiling.py::program_store_dir), on a platform whose
        serialized programs load whole (not the CPU's), and in one
        process: what a program serialized for devices of several hosts
        loads as is not tried. The schedule's and the optimizer's fields
        are traced into the step as they stand NOW (`rebuild`), the
        options aside."""
        from ..common.profiling import program_store_dir
        directory = program_store_dir()
        if directory is None or jax.process_count() > 1 or \
                self.mesh.devices.flat[0].platform in \
                program_store.OFF_PLATFORMS:
            return None
        return ProgramStore(directory, self.mesh, self.options, {
            "schedule": dataclasses.asdict(self.schedule),
            "optimizer": dataclasses.asdict(self.opt_cfg),
            "model": [type(self.model).__name__,
                      repr(getattr(self.model, "cfg", None))],
            "frozen": sorted(self._frozen_names())})

    @staticmethod
    def _shape_key(batch) -> tuple:
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in batch.items()))

    def _compile_ahead(self, batch, step, rng) -> Dict[tuple, Any]:
        """Start compiling the fused step for every shape the loader's
        bucket table can give (data/batch_generator.py::budget_shapes),
        narrowest first, on --precompile-buckets threads. A step program
        of a large model compiles for a minute on a few cores and every
        bucket is a program of its own: compiled one after another as
        their first batches arrive they are most of a start, side by
        side they overlap each other and the updates already running.
        `batch`, `step` and `rng` are the first update's own arguments:
        what is lowered here is what `update` will pass."""
        shapes = budget_shapes(self.options)
        widths = {v.shape for v in batch.values() if v.ndim == 2}
        if not shapes or len(widths) != 1:
            return {}

        def like(a, shape=None, sharding=None):
            return jax.ShapeDtypeStruct(a.shape if shape is None else shape,
                                        a.dtype, sharding=sharding)
        # the arrays themselves are donated to the next update; their
        # shapes and placements are what a later lowering needs
        p, o = jax.tree_util.tree_map(lambda a: like(a, sharding=a.sharding),
                                      (self.params, self.opt_state))
        step, rng = like(step), like(rng)
        fused = self._fused
        programs = self._programs = {}

        store = self._program_store()
        donate = (0, 1) if self._donate else ()
        if store is not None:
            state = {"params": describe(p), "opt_state": describe(o),
                     "step": describe(step), "rng": describe(rng)}
        # one record a program, appended by its compile thread: where the
        # executable came from, the seconds and the bytes
        records = []

        def build(name, b, mark, inputs):
            """The executable for batch `b`, from the store where it holds
            one under `mark`; what it says it costs the device; its
            record."""
            args, entry, refused = (p, o, b, step, rng), None, 0
            t0 = time.perf_counter()
            if store is not None:
                try:
                    entry = store.load(mark, inputs)
                except Refused as e:
                    log.warn("Step program {}: its entry in the program "
                             "store is refused and compiled over: {} ({})",
                             name, e, store.path(mark))
                    refused = 1
            if entry is not None:
                record = {"source": "store", "bytes": entry.bytes,
                          "load_s": time.perf_counter() - t0}
                cost = entry.exe.memory_analysis()
                return (entry.exe, entry.costs if cost is None
                        else _costs_of(cost), record)
            t0 = time.perf_counter()
            traced = fused.trace(*args)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            exe = lowered.compile()
            t3 = time.perf_counter()  # mtlint: ok -- host work: a trace, a lowering and a compile dispatch nothing
            record = {"source": "compiled", "trace_s": t1 - t0,
                      "lower_s": t2 - t1, "compile_s": t3 - t2,
                      "refused": refused}
            # what the compiler says the executable costs the device
            cost = exe.memory_analysis()
            costs = None if cost is None else _costs_of(cost)
            if store is not None:
                record["bytes"] = store.save(mark, inputs, name, exe,
                                             lowered.as_text(), costs or {})
            return exe, costs, record

        def compile_one(key, b, mark, inputs):
            name = self._program_name(b)
            thread = threading.current_thread().name
            with obs_trace.span("train.compile_ahead", program=name,
                                thread=thread) as sp:
                exe, costs, record = build(name, b, mark, inputs)
                sp.set_attrs(**record)
            if store is not None:
                count = obs_trace.TRACER.count
                count("startup.store_hits", record["source"] == "store")
                count("startup.store_misses", record["source"] != "store")
                count("startup.store_refused", record.get("refused", 0))
            records.append(record)
            log.info("Step program {} on {}: {}", name, thread,
                     _how_built(record))
            if costs:
                programs[key] = {"name": name, **costs}
            return exe

        pool = ThreadPoolExecutor(self._ahead_threads,
                                  thread_name_prefix="precompile")
        ahead, held = {}, 0
        for width, rows in shapes:
            b = {k: like(v, (rows, width) if v.ndim == 2
                         else (rows,) + v.shape[1:], v.sharding)
                 for k, v in batch.items()}
            # the fingerprint costs milliseconds and needs no trace
            mark, inputs = (None, None) if store is None else \
                store.fingerprint(dict(state, batch=describe(b)), donate)
            held += store is not None and store.holds(mark)
            # the futures outlive the pool's handle; its threads end with
            # the last of them (shutdown below)
            key = self._shape_key(b)
            ahead[key] = pool.submit(  # mtlint: transfers
                compile_one, key, b, mark, inputs)
        pool.shutdown(wait=False)       # the queued compiles still run
        # the ledger's line when the last of them is done, on its compile
        # thread. A future `_step_for` cancels is done on the update path:
        # it is counted, and should it be the last the line is left out
        done, last = itertools.count(1), len(ahead)  # next(): GIL-atomic

        def one_done(future):
            if next(done) == last and not future.cancelled():
                def total(field):
                    return sum(r.get(field, 0) for r in records)
                stored = [r for r in records if r["source"] == "store"]
                log.info("Step programs ahead: {} from the program store "
                         "(load {:.1f} s), {} compiled (trace {:.1f} s, "
                         "lower {:.1f} s, compile or cache load {:.1f} s), "
                         "{} entries refused; {} in the store's files",
                         len(stored), total("load_s"),
                         len(records) - len(stored), total("trace_s"),
                         total("lower_s"), total("compile_s"),
                         total("refused"), _mb(total("bytes")))
                log.info("{}", self._memory_line(hbm.device_memory()))
        for future in list(ahead.values()):
            future.add_done_callback(one_done)
        log.info("Compiling the train step ahead for {} shapes on {} "
                 "threads ({})", len(ahead), self._ahead_threads,
                 "no program store: no persistent cache" if store is None
                 else f"{held} of them are in the program store "
                      f"{store.directory}: loaded, not traced")
        return ahead

    def _step_for(self, batch, step, rng):
        """The step to dispatch for this batch: the jitted step, or with
        --precompile-buckets the executable compiled ahead for its
        shape. A shape nobody foresaw, or whose compile is still queued
        behind others, compiles here and now through the jitted step."""
        if not self._ahead_threads:
            return self._fused
        if self._ahead is None:
            self._ahead = self._compile_ahead(batch, step, rng)
        key = self._shape_key(batch)
        future = self._ahead.get(key)
        if future is None or future.cancel():
            self._ahead.pop(key, None)
            return self._fused
        try:
            return future.result()
        except Exception as e:  # noqa: BLE001 — the jitted step says it again
            log.warn("Compiling ahead failed for {}: {}", key, e)
            del self._ahead[key]
            return self._fused

    def _dump_hlo_once(self, fn, *args) -> None:
        """--dump-hlo: the first program `update` dispatches, lowered
        with its own arguments (a delayed update's gradient program, the
        compute-heavy one of its two)."""
        if self._dump_hlo:
            from ..common.profiling import dump_lowered
            dump_lowered(self._dump_hlo, fn.lower(*args))
            self._dump_hlo = None

    def update(self, batches, step: int, rng) -> TrainOutput:
        """One update from one batch dict (the fused step), or from a list
        of --optimizer-delay micro-batch dicts of any shapes: micro-batch
        i's gradients, under the key fold_in(fold_in(rng, step - 1), i),
        are added into one donated float32 sum by one dispatch each, and
        one more dispatch updates from the sum.

        `rng` is the RAW training stream key — the folds happen inside
        the jitted programs, saving 2-3 tiny host dispatches per step
        (the r4 TPU trace showed separate _threefry_fold_in +
        convert_element_type programs between steps). The plain np.int32
        step scalar avoids a compiled scalar-convert dispatch and keeps
        the fold index exact at any step count (a f32 step would
        saturate fold indices past 2^24)."""
        if isinstance(batches, dict):
            batches = [batches]
        step_i = np.int32(step)
        if len(batches) == 1:
            b = M.shard_batch(batches[0], self.mesh)
            self._dump_hlo_once(self._fused, self.params, self.opt_state, b,
                                step_i, rng)
            self.params, self.opt_state, metrics = self._dispatch(
                self._step_for(b, step_i, rng), step, self.params,
                self.opt_state, b, step_i, rng, batch=b)
            return self._output(metrics)
        total = self._zero_sum()
        n_sents = 0
        for i, b in enumerate(batches):
            b = M.shard_batch(b, self.mesh)
            args = (self.params, total, b, step_i, np.int32(i), rng)
            self._dump_hlo_once(self._grad_fn, *args)
            total, counters = self._dispatch(self._grad_fn, step, *args,
                                             batch=b)
            if counters is not None:
                obs_trace.TRACER.count_lazy(self.model.step_counters,
                                            counters)
            # rows from whichever target form shipped (compact batches
            # carry trg_tok/trg_len instead of trg_ids/trg_mask)
            trg = b["trg_ids"] if "trg_ids" in b else b["trg_tok"]
            n_sents += int(trg.shape[0])
        self.params, self.opt_state, metrics = self._dispatch(
            self._update_fn, step, self.params, self.opt_state, total,
            np.float32(step), np.float32(n_sents))
        return self._output(metrics)

    # -- EMA access for validation/saving -----------------------------------
    def smoothed(self) -> Params:
        return self._unstack(
            smoothed_params(self.opt_cfg, self.opt_state, self.params))

    def export_params(self) -> Params:
        """Params in flat Marian naming for checkpoint IO / validators /
        decoding (inverse of the depth-stacked training layout)."""
        return self._unstack(self.params)

    # -- checkpoint glue -----------------------------------------------------
    def mesh_geometry(self) -> Dict[str, Any]:
        """Save-time device geometry for the bundle manifest (elastic
        resume, ISSUE 19). Purely descriptive: the .optimizer.npz members
        are LOGICAL (gathered, unsharded) arrays, so restore re-shards for
        whatever mesh the resuming process builds — this record is what
        lets the restore log say so, and lets operators audit a resize."""
        return {"devices": int(jax.device_count()),
                "mesh": {str(name): int(size)
                         for name, size in self.mesh.shape.items()}}

    def optimizer_device_arrays(self) -> Dict[str, Any]:
        """Flat-named optimizer state, still as device arrays (unstacked
        from any pipeline layout) — the async saver snapshots these and
        fetches them off-thread."""
        flat: Dict[str, Any] = {"t": self.opt_state["t"]}
        for part in ("m", "v", "gt", "avg", "qerr", "gerr", "gstat"):
            if part in self.opt_state:
                for k, v in self._unstack(self.opt_state[part]).items():
                    # bf16 state (--optimizer-state-dtype) is stored as
                    # f32 in the npz: numpy has no native bfloat16, and
                    # f32 checkpoints stay loadable regardless of the
                    # flag the resuming run uses
                    flat[f"{part}:{k}"] = (
                        v.astype(jnp.float32)
                        if v.dtype == jnp.bfloat16 else v)
        return flat

    def optimizer_arrays(self) -> Dict[str, Any]:
        """Gather (device_get) sharded optimizer state for .optimizer.npz —
        the role of the reference's scatterState/gatherState shard IO."""
        import numpy as np
        return {k: np.asarray(v)
                for k, v in self.optimizer_device_arrays().items()}

    def load_optimizer_arrays(self, flat: Dict[str, Any]) -> None:
        m_dtype = jnp.dtype(getattr(self.opt_cfg, "state_dtype", "float32"))
        st: Dict[str, Any] = {"t": jnp.asarray(flat["t"])}
        for key, v in flat.items():
            if ":" in key:
                part, name = key.split(":", 1)
                arr = jnp.asarray(v)
                if part == "m":   # stored f32; live dtype follows the flag
                    arr = arr.astype(m_dtype)
                st.setdefault(part, {})[name] = arr
        self.opt_state = st
