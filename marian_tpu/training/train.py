"""The training driver — reference src/training/training.h :: Train<T>::run.

Builds vocabs/corpus/batch generator/model/graph-group/scheduler, restores
checkpoints (params + optimizer shards + training state + corpus position),
runs the epoch loop with validation/save triggers and SIGTERM-safe exit.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import faultpoints as fp
from ..common import logging as log
from ..common import prng, signal_handling
from ..data import BatchGenerator, Corpus, CorpusState, create_vocab
from ..models.encoder_decoder import batch_to_arrays, create_model
from . import bundle as bdl
from .checkpoint import load_checkpoint, save_checkpoint
from .graph_group import GraphGroup
from .scheduler import DivergenceError, Scheduler
from .training_state import TrainingState
from .validators import create_validators

# Training-step watchdog exit code (--train-stall-timeout): EX_TEMPFAIL —
# retriable, and distinct from faultpoints.FAULT_EXIT_CODE (117) and from
# ordinary failures, so a supervisor can tell "stalled, restart into the
# checkpoint-resume path" from "crashed, investigate".
STALL_EXIT_CODE = 75


class _StepWatchdog:
    """Monitor thread for a training step that never fences (wedged
    collective, hung data feed, device lockup) — the training twin of
    serving's dispatch watchdog. The update loop beats once per batch
    iteration; when no beat lands for --train-stall-timeout seconds the
    watchdog dumps a flight recording naming the stalled step, saves the
    host-side training state as a DIAGNOSTIC side file (device state is
    not safely checkpointable from here — the training thread may be
    wedged mid-dispatch, so resume comes from the last committed bundle),
    and hard-exits with the retriable STALL_EXIT_CODE."""

    def __init__(self, timeout: float, state: TrainingState,
                 model_path: str):
        self.timeout = float(timeout)
        self._state = state
        self._model_path = model_path
        self._last = time.monotonic()
        self._paused = False
        self._halt = threading.Event()
        from ..serving import metrics as msm
        self._m_trips = msm.counter(
            "marian_train_watchdog_trips_total",
            "Training-step watchdog trips (--train-stall-timeout)")
        self._thread = threading.Thread(target=self._run,
                                        name="train-watchdog", daemon=True)

    def start(self) -> None:
        self._thread.start()
        log.info("Training-step watchdog armed: stall timeout {}s "
                 "(exit code {} on trip)", self.timeout, STALL_EXIT_CODE)

    def beat(self) -> None:
        self._last = time.monotonic()

    def pause(self) -> None:
        """Suspend during legitimately slow non-step work (rollback
        reload + re-jit) so recovery is never mistaken for a stall."""
        self._paused = True

    def resume(self) -> None:
        self._last = time.monotonic()
        self._paused = False

    def stop(self) -> None:
        self._halt.set()

    def _run(self) -> None:
        poll = max(0.05, min(1.0, self.timeout / 4.0))
        while not self._halt.wait(poll):
            if self._paused:
                continue
            elapsed = time.monotonic() - self._last
            if elapsed >= self.timeout:
                self._trip(elapsed)
                return

    def _trip(self, elapsed: float) -> None:
        s = self._state
        stalled_step = s.batches + 1
        detail = (f"training step {stalled_step} never fenced: no loop "
                  f"progress for {elapsed:.1f}s "
                  f"(--train-stall-timeout {self.timeout}); last completed "
                  f"update {s.batches}, epoch {s.epochs + 1}")
        # raw stderr first: must be visible even under --quiet, and even
        # if the logging/obs stack is itself wedged
        sys.stderr.write(f"TRAIN WATCHDOG: {detail}; "
                         f"exiting {STALL_EXIT_CODE} (retriable)\n")
        sys.stderr.flush()
        log.error("TRAIN WATCHDOG: {}", detail)
        self._m_trips.inc()
        from .. import obs
        obs.event("train.watchdog_trip", step=stalled_step,
                  elapsed_s=round(elapsed, 3))
        obs.FLIGHT.trip("train-watchdog", detail=detail,
                        extra={"stalled_step": stalled_step,
                               "last_completed_update": s.batches,
                               "timeout_s": self.timeout})
        try:
            s.save(self._model_path + ".stalled.progress.yml")
        except Exception:  # noqa: BLE001 — diagnostics must not mask exit
            pass
        os._exit(STALL_EXIT_CODE)


class Train:
    def __init__(self, options):
        self.options = options
        log.create_loggers(options)
        signal_handling.set_signal_handlers()

    def run(self) -> None:
        opts = self.options
        seed = int(opts.get("seed", 0)) or 1234
        key = prng.root_key(seed)

        from ..common.profiling import enable_compilation_cache
        enable_compilation_cache()

        if opts.get("check-nan", False):
            # --check-nan: abort with a traceback on the first non-finite
            # value anywhere under jit (reference: graph NaN sanitizer;
            # SURVEY §5 "sanitizers/NaN-debug")
            jax.config.update("jax_debug_nans", True)
            log.info("NaN checking enabled (jax_debug_nans)")

        # -- data -----------------------------------------------------------
        train_sets = list(opts.get("train-sets"))
        vocab_paths = list(opts.get("vocabs", [])) or \
            [p + ".yml" for p in train_sets]
        # --tsv: ONE file holds every stream — each vocab trains against
        # the same file (an on-the-fly-trained vocab sees all columns,
        # like the reference's TSV mode with a joint vocab)
        train_per_vocab = (train_sets * len(vocab_paths)
                           if opts.get("tsv", False) and len(train_sets) == 1
                           else train_sets)
        dim_vocabs = list(opts.get("dim-vocabs", [0, 0]))
        vocabs = []
        for i, (vp, tp) in enumerate(zip(vocab_paths, train_per_vocab)):
            mx = dim_vocabs[i] if i < len(dim_vocabs) else 0
            vocabs.append(create_vocab(vp, opts, i, [tp], max_size=mx))
        log.info("Vocabulary sizes: {}", " ".join(str(len(v)) for v in vocabs))

        corpus = Corpus(train_sets, vocabs, opts)

        # -- model + graph group -------------------------------------------
        if opts.get("auto-tune", False):
            from ..ops.auto_tuner import calibrate_flash_attention
            thr = calibrate_flash_attention(
                heads=int(opts.get("transformer-heads", 8)),
                dim_head=max(int(opts.get("dim-emb", 512))
                             // max(int(opts.get("transformer-heads", 8)), 1), 1))
            log.info("Auto-tuned flash-attention crossover: {} tokens", thr)
        src_side = vocabs[:-1] if len(vocabs) > 2 else vocabs[0]
        model = create_model(opts, src_side, vocabs[-1])
        gg = GraphGroup(model, opts)

        model_path = opts.get("model", "model.npz")
        state = TrainingState(seed=seed)
        init_params = None
        # a checkpoint exists if the flat layout OR any committed bundle
        # does — a save killed between bundle commit and top-level publish
        # leaves only the bundle, and that moment must still resume
        has_checkpoint = (os.path.exists(model_path) or
                          bool(bdl.list_bundles(
                              bdl.bundle_root(model_path))))
        if has_checkpoint and not opts.get("no-reload", False):
            log.info("Loading model from {}", model_path)
            host_params, _, loaded_state = load_checkpoint(model_path, gg)
            init_params = {k: jnp.asarray(v) for k, v in host_params.items()}
            if loaded_state is not None:
                state = loaded_state
                if not opts.get("no-restore-corpus", False) and state.corpus:
                    saved_be = state.corpus.get("backend")
                    if saved_be not in (None, CorpusState.BACKEND):
                        # a checkpoint is input from outside: positions are
                        # not portable across loaders (this one counts raw
                        # lines, the C++ loader of earlier versions its
                        # filtered order) — restart the epoch rather than
                        # seek to the wrong sentence (ADVICE r1)
                        log.warn(
                            "Corpus state was saved by the '{}' data backend "
                            "but '{}' is active; restarting epoch {} from "
                            "the beginning", saved_be, CorpusState.BACKEND,
                            state.corpus.get("epoch"))
                        state.corpus = {**state.corpus, "position": 0}
                    corpus.restore(state.corpus)
                    log.info("Restored corpus position: epoch {}, sent {}",
                             state.corpus.get("epoch"), state.corpus.get("position"))
        elif opts.get("pretrained-model", None):
            host_params, _ = __import__("marian_tpu.common.io", fromlist=["io"]) \
                .load_model(opts.get("pretrained-model"))
            init_params = {k: jnp.asarray(v) for k, v in host_params.items()}

        emb_files = list(opts.get("embedding-vectors", []) or [])
        if emb_files and init_params is None:
            # --embedding-vectors src.vec [trg.vec]: word2vec-format init of
            # the embedding tables (reference: Embedding with embFile);
            # usually combined with --embedding-fix-src/trg
            from ..layers.embedding_io import load_word2vec, normalize_rows
            init_params = gg.model.init(prng.stream(key, prng.STREAM_INIT))
            dim = int(opts.get("dim-emb", 512))
            norm = bool(opts.get("embedding-normalization", False))

            def load_into(name, path, vocab):
                if name not in init_params:
                    return
                tab = load_word2vec(path, vocab, dim,
                                    init=np.asarray(init_params[name]))
                if norm:
                    tab = normalize_rows(tab)
                init_params[name] = jnp.asarray(tab)

            src_name = "Wemb" if "Wemb" in init_params else "encoder_Wemb"
            load_into(src_name, emb_files[0], vocabs[0])
            if len(emb_files) > 1:
                trg_name = ("decoder_Wemb" if "decoder_Wemb" in init_params
                            else "Wemb_dec" if "Wemb_dec" in init_params
                            else "Wemb")
                load_into(trg_name, emb_files[1], vocabs[-1])

        # schedule factors are baked into the compiled step at trace time —
        # restore them BEFORE initialize() builds the jitted functions
        gg.schedule.decay_factor = state.factor
        if state.batches > 0 and opts.get("lr-warmup-at-reload", False):
            gg.schedule.warmup_offset = state.batches
            log.info("Repeating learning-rate warmup from update {} "
                     "(--lr-warmup-at-reload)", state.batches)
        gg.initialize(prng.stream(key, prng.STREAM_INIT), init_params)
        n_params = sum(int(np.prod(v.shape)) for v in gg.params.values())
        log.info("Model created: {} parameters ({:.1f}M)", n_params,
                 n_params / 1e6)

        scheduler = Scheduler(opts, state)
        if state.batches > 0 and (opts.get("valid-reset-stalled", False)
                                  or opts.get("valid-reset-all", False)):
            scheduler.reset_stalled(
                reset_best=bool(opts.get("valid-reset-all", False)))
            log.info("Validation stall counters reset on resume")
        validators = create_validators(opts, vocabs, model)
        for v in validators:
            # the mutable TrainingState, attached once: validators read
            # the CURRENT moment for {U}/{E}/{B}/{T} output-path templates
            v.training_state = state

        config_yaml = opts.as_yaml()
        delay = gg.delay

        # --async-save: checkpoint writes overlap training (checkpoint.py
        # AsyncSaver — the training thread only snapshots device buffers)
        saver = None
        if opts.get("async-save", False):
            from .checkpoint import AsyncSaver
            saver = AsyncSaver()

        # resume snapshot of the last APPLIED batch (its post-maxi-window
        # corpus position), seeded with the PRE-iteration state (restored
        # position on resume, initial position on a fresh run) so a save
        # before the first applied update resumes from where this process
        # started. The live corpus.state is NOT a resume point at any
        # later moment: the prefetch thread consumes it arbitrarily far
        # ahead of what training has applied, so saving it used to skip
        # data (and drift whole epochs) on restart — exposed by the
        # ISSUE 4 chaos harness.
        last_corpus_state: List[dict] = [corpus.state.as_dict()]

        def do_save(suffix: str = "") -> None:
            state.corpus = last_corpus_state[0]
            smooth = gg.smoothed() if gg.opt_cfg.smoothing > 0 else None
            # without --overwrite, an iteration-numbered copy of every
            # periodic checkpoint is written in the SAME save unit
            # (reference: Train::save) — one snapshot, one worker job
            extra = (f".iter{state.batches}",) \
                if not suffix and not opts.get("overwrite", False) else ()
            save_checkpoint(model_path, gg.export_params(), config_yaml,
                            gg, state, smooth_params=smooth, suffix=suffix,
                            async_saver=saver,
                            extra_model_suffixes=extra,
                            keep_bundles=int(
                                opts.get("keep-checkpoint-bundles",
                                         bdl.DEFAULT_KEEP)
                                or bdl.DEFAULT_KEEP))

        def do_validate() -> None:
            if saver is not None:
                # file-reading validators (valid-script) must see the
                # checkpoint of THIS training moment, not a half-written
                # or previous-cycle one — flush the in-flight async save
                saver.wait()
            params = gg.smoothed() if gg.opt_cfg.smoothing > 0 \
                else gg.export_params()
            for v in validators:
                value = v.validate(params)
                improved = scheduler.register_validation(
                    v.name, value, v.lower_is_better)
                log.log_valid(
                    "info",
                    f"Ep. {state.epochs + 1} : Up. {state.batches} : "
                    f"{v.name} : {value:.6f} : "
                    + ("new best" if improved else
                       f"stalled {state.validators[v.name]['stalled']} times"))
                if improved and opts.get("keep-best", False):
                    do_save(suffix=".best-" + v.name)
            scheduler.maybe_decay_lr(gg.schedule, gg)

        if opts.get("mini-batch-fit", False):
            # empirical largest token budget on this device (batch_fit.py);
            # feeds BatchGenerator as the mini-batch-words budget
            from .batch_fit import fit_mini_batch_words
            fitted = fit_mini_batch_words(gg, opts, len(vocabs[-1]))
            opts.set("mini-batch-words", fitted)

        # --mini-batch-track-lr: scale LR with the actual batch size by
        # anchoring Marian's reference-batch mechanism at the (possibly
        # fitted) full token budget — the jitted step then multiplies lr
        # (and Adam eps) by actual_words/ref_words every update. opt_cfg is
        # baked into the compiled step, so rebuild after changing it.
        if opts.get("mini-batch-track-lr", False) \
                and not int(opts.get("mini-batch-words-ref", 0) or 0):
            ref = int(opts.get("mini-batch-words", 0) or 0)
            if ref > 0:
                opts.set("mini-batch-words-ref", ref)
                gg.opt_cfg.ref_mb_words = ref
                gg.rebuild()
                log.info("mini-batch-track-lr: LR tracks batch size "
                         "(reference {} words)", ref)

        # --mini-batch-warmup: ramp the effective batch (rows AND token
        # budget) linearly over the first N updates
        wu_n = _warmup_updates(opts)
        budget_scale = None
        if wu_n > 0:
            budget_scale = lambda: min(  # noqa: E731
                (state.batches + 1) / float(wu_n), 1.0)
            log.info("mini-batch-warmup: ramping batch size over the "
                     "first {} updates", wu_n)

        # -- epoch loop ------------------------------------------------------
        from ..common.profiling import maybe_start_profile_server
        from ..obs.profiling import TraceWindow
        maybe_start_profile_server(opts)
        # observability: the loop's phases are spans opened by the objects
        # it calls (data.wait, train.h2d, train.dispatch, train.bookkeep,
        # train.sync — docs/OBSERVABILITY.md), live whenever --trace is on
        # or a profiler session collects; --trace-dump arms the flight
        # recorder (a MARIAN_FAULTS kill dumps the ring)
        from .. import obs
        obs.configure(opts)
        if obs.PERF.enabled:
            # geometry for the live train-MFU gauge (obs/perf.py); the
            # per-window chip-seconds/token gauge needs no geometry
            obs.PERF.set_geometry(
                emb=int(opts.get("dim-emb", 512)),
                ffn=int(opts.get("transformer-dim-ffn", 2048)),
                enc_depth=int(opts.get("enc-depth", 6)),
                dec_depth=int(opts.get("dec-depth", 6)),
                vocab=len(vocabs[-1]))
        # --metrics-port: Prometheus scrape of the train-side series the
        # Scheduler publishes (serving/metrics.py — same registry
        # and types as marian-server, one metrics vocabulary end to end);
        # /tracez rides the same port, like marian-server
        from ..serving.metrics import maybe_start_metrics_server
        maybe_start_metrics_server(opts, routes=obs.trace_routes())
        trace = TraceWindow(opts)
        train_key = prng.stream(key, prng.STREAM_DROPOUT)
        # --compact-transfer: ship uint16 tokens + row lengths instead of
        # int32 ids + float masks (~4× less host→device traffic per step;
        # the jitted step rebuilds ids/masks on device). Static per-stream
        # vocab sizes keep the jit signature stable across batches.
        compact = bool(opts.get("compact-transfer", True))
        vocab_sizes = [len(v) for v in vocabs]
        log.info("Training started")
        stop = False

        # -- self-healing (ISSUE 19): divergence rollback ladder + step
        # watchdog. DivergenceError can surface from any scheduler
        # bookkeeping call (consecutive-NaN-skip detection or the display-
        # boundary cost sync); under --on-divergence rollback the retry
        # ladder below catches it, restores the last good bundle
        # in-process, and re-enters the epoch loop.
        from ..serving import metrics as msm
        div_mode = scheduler.divergence_mode
        div_retries = max(0, int(opts.get("divergence-retries", 3) or 0))
        div_backoff = float(opts.get("divergence-lr-backoff", 0.5) or 1.0)
        m_rollbacks = msm.counter(
            "marian_train_divergence_rollbacks_total",
            "In-process divergence rollbacks (--on-divergence rollback)")
        base_train_key = train_key
        watchdog = None
        stall_timeout = float(opts.get("train-stall-timeout", 0.0) or 0.0)
        if stall_timeout > 0:
            watchdog = _StepWatchdog(stall_timeout, state, model_path)
            watchdog.start()

        def _arrays(batch):
            """batch → device arrays, crossing the train.nan_grad drill
            point: an armed 'fail' rebuilds this one batch in the
            non-compact form and poisons its target mask with NaN — a REAL
            non-finite gradient through the full backward pass, which is
            what --check-gradient-nan's skip/revert and the rollback
            ladder must be proven against."""
            try:
                fp.fault_point("train.nan_grad")
            except fp.InjectedFault:
                a = batch_to_arrays(batch, compact=False)
                a["trg_mask"] = a["trg_mask"] * jnp.float32(float("nan"))
                log.warn("FAULT train.nan_grad: target mask poisoned with "
                         "NaN for update {}", state.batches + 1)
                return a
            return batch_to_arrays(batch, compact=compact,
                                   vocab_sizes=vocab_sizes)

        def _maybe_poison_cost(out):
            """train.diverge_cost drill: replace one APPLIED update's lazy
            loss sum with NaN before the scheduler accumulates it — the
            cost-blowup class that only surfaces at the display-boundary
            sync, without touching params (so post-rollback state really
            is clean)."""
            try:
                fp.fault_point("train.diverge_cost")
            except fp.InjectedFault:
                log.warn("FAULT train.diverge_cost: loss sum for update {} "
                         "replaced with NaN", state.batches + 1)
                return dataclasses.replace(out, loss_sum=float("nan"))
            return out

        def _check_stop():
            """Signal / stopping-condition tail of an update. Returns 'exit'
            (leave run() now), 'stop' (save done / limits hit), or None."""
            if signal_handling.signal_flag():
                if opts.get("sigterm", "save-and-exit") == \
                        "exit-immediately":
                    log.info("Caught termination signal; exiting "
                             "immediately (--sigterm exit-immediately)")
                    return "exit"
                log.info("Caught termination signal; saving and exiting")
                do_save()
                return "stop"
            if not scheduler.keep_going():
                return "stop"
            return None

        def _after_update(out, group):
            """Scheduler bookkeeping + triggers for ONE applied update.
            loss_sum stays a lazy device scalar (sync deferred to the
            display boundary); labels/lr come from host-side math so the
            hot loop never blocks on the device."""
            if group[-1].corpus_state is not None:
                last_corpus_state[0] = group[-1].corpus_state
            out = _maybe_poison_cost(out)
            scheduler.update(out.loss_sum, sum(b.words for b in group),
                             sum(b.size for b in group),
                             src_words=sum(b.src_words for b in group),
                             lr=gg.schedule.host_lr(state.batches + 1),
                             skipped=out.skipped)
            if scheduler.should_validate():
                do_validate()
            if scheduler.should_save():
                do_save()
            return _check_stop()

        def _epoch_loop() -> Optional[str]:
            nonlocal stop
            while scheduler.keep_going() and not stop:
                bg = BatchGenerator(corpus, opts, budget_scale=budget_scale)
                micro: List = []
                for batch in bg:
                    if watchdog is not None:
                        watchdog.beat()
                    # once per batch iteration: hang mode wedges the loop
                    # right here — a step that never fences, food for the
                    # --train-stall-timeout watchdog; kill mode is the
                    # mid-step preemption drill
                    fp.fault_point("train.hang")
                    micro.append(batch)
                    if len(micro) < delay:
                        continue
                    arrays = [_arrays(b) for b in micro]
                    trace.tick(state.batches + 1)
                    # dispatch may block on a LEGITIMATE jit compile (first
                    # step, new bucket shape) — not a stall. Execution hangs
                    # are still caught: dispatch itself is async, and a
                    # wedged device surfaces at the scheduler's sync points,
                    # outside this pause.
                    if watchdog is not None:
                        watchdog.pause()
                    try:
                        out = gg.update(arrays, state.batches + 1, train_key)
                    finally:
                        if watchdog is not None:
                            watchdog.resume()
                    rc = _after_update(out, micro)
                    micro = []
                    if rc == "exit":
                        return "exit"
                    if rc is not None:
                        stop = True
                        break
                if not stop:
                    scheduler.new_epoch()
            # skip flags from the last ~2 updates may still be lazily
            # pending — resolve them so a divergence at the very end of
            # the run raises here (inside the rollback ladder) instead of
            # being silently saved as the final checkpoint. SIGTERM exits
            # skip this: rolling back against an operator's stop is wrong.
            if not signal_handling.signal_flag():
                scheduler.drain_skips()
            return None

        def _rollback(n: int, reason: str) -> None:
            """--on-divergence rollback, attempt n of div_retries: restore
            the last good checkpoint bundle in-process (params + optimizer
            shards + training state), rewind the data pipeline to the
            bundle's corpus snapshot, back off the learning rate, and
            perturb the dropout stream so the replayed window is not
            forced down the bit-identical trajectory that just diverged."""
            nonlocal stop, corpus, train_key
            stop = False
            if watchdog is not None:
                watchdog.pause()     # reload + re-jit is not a stall
            log.warn("DIVERGENCE ROLLBACK {}/{}: {} — restoring the last "
                     "good checkpoint bundle", n, div_retries, reason)
            m_rollbacks.inc()
            obs.event("train.divergence_rollback", retry=n,
                      update=state.batches, reason=reason)
            # synchronous flight dump: one auditable artifact per rollback
            obs.FLIGHT.trip("divergence-rollback",
                            detail=f"rollback {n}/{div_retries} at update "
                                   f"{state.batches}: {reason}",
                            extra={"retry": n, "update": state.batches})
            if saver is not None:
                saver.wait()         # never reload under an in-flight save
            gg.opt_state = None      # drop poisoned moments before reload
            restored = TrainingState(seed=seed)
            reinit_params = None
            if (os.path.exists(model_path) or
                    bool(bdl.list_bundles(bdl.bundle_root(model_path)))):
                host_p, _, loaded = load_checkpoint(model_path, gg)
                reinit_params = {k: jnp.asarray(v)
                                 for k, v in host_p.items()}
                if loaded is not None:
                    restored = loaded
            else:
                # divergence before the first save: the only good state is
                # the initialization itself — still a counted, LR-backed-
                # off rollback, just to update 0
                log.warn("no checkpoint bundle exists yet — rolling back "
                         "to freshly initialized parameters")
            # in-place field copy: scheduler and validators hold this
            # TrainingState object by reference
            for field in dataclasses.fields(TrainingState):
                setattr(state, field.name, getattr(restored, field.name))
            if div_backoff > 0 and div_backoff != 1.0:
                prev = state.factor
                state.factor *= div_backoff ** n
                log.warn("learning-rate backoff: decay factor {} -> {} "
                         "(x{} per retry, retry {})", prev, state.factor,
                         div_backoff, n)
            gg.schedule.decay_factor = state.factor
            gg.initialize(prng.stream(key, prng.STREAM_INIT),
                          reinit_params)
            # data pipeline: a FRESH Corpus rewound to the bundle's
            # snapshot — past the poison window. The abandoned
            # BatchGenerator's prefetch thread still holds the old Corpus
            # (it parks on its bounded queue; daemon, leaked once per
            # rollback, bounded by --divergence-retries) — reusing that
            # object would race the restore.
            corpus = Corpus(train_sets, vocabs, opts)
            if state.corpus:
                corpus.restore(state.corpus)
            last_corpus_state[0] = corpus.state.as_dict()
            train_key = jax.random.fold_in(base_train_key, n)
            scheduler.reset_divergence_window()
            if watchdog is not None:
                watchdog.resume()
            log.info("rollback complete: resuming at update {} (epoch "
                     "{}), LR decay factor {}", state.batches,
                     state.epochs + 1, state.factor)

        rollbacks = 0
        try:
            while True:
                try:
                    if _epoch_loop() == "exit":
                        return
                    break
                except DivergenceError as err:
                    if div_mode != "rollback":
                        raise
                    if rollbacks >= div_retries:
                        detail = (f"divergence retries exhausted after "
                                  f"{rollbacks} rollback(s): {err}")
                        log.error("{}", detail)
                        obs.FLIGHT.trip("divergence-giveup", detail=detail)
                        raise DivergenceError(detail) from err
                    rollbacks += 1
                    _rollback(rollbacks, str(err))
        finally:
            if watchdog is not None:
                watchdog.stop()
        trace.close()
        scheduler.close()       # flush buffered TensorBoard scalars
        log.info("Training finished")
        do_save()
        if saver is not None:
            saver.wait()        # final checkpoint must be on disk at exit


def _warmup_updates(opts) -> int:
    """--mini-batch-warmup parsed to an update count; only the update unit
    is meaningful for a per-update ramp — other units refuse loudly rather
    than ramping over the wrong horizon."""
    raw = str(opts.get("mini-batch-warmup", "0") or "0")
    from ..common.scheduling_parameter import (SchedulingParameter,
                                               SchedulingUnit)
    wu = SchedulingParameter.parse(raw)
    if wu.n > 0 and wu.unit != SchedulingUnit.UPDATES:
        raise ValueError(
            f"--mini-batch-warmup {raw}: only update-counted warmup "
            f"(e.g. 4000 or 4000u) is supported")
    return wu.n


def train_main(options) -> None:
    Train(options).run()
