"""Scheduler: update/epoch/label counting, Marian-format progress logging,
validation/save triggers, LR decay strategies, early stopping.

Rebuild of reference src/training/scheduler.h :: Scheduler::update/validate.
The log line format is kept greppable-compatible with Marian:

Ep. 1 : Up. 1000 : Sen. 12,345 : Cost 4.52 : Time 12.3s : 45000.0 words/s : L.r. 3.0e-04
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional

from .. import obs
from ..common import logging as log
from ..common.scheduling_parameter import SchedulingParameter, SchedulingUnit
from . import hbm
from .training_state import TrainingState


class DivergenceError(RuntimeError):
    """--throw-on-divergence: training cost went non-finite (reference:
    divergence detection in training/scheduler.cpp — abort loudly so an
    orchestrator restarts from the last checkpoint instead of burning
    device hours on a dead run)."""


class Scheduler:
    def __init__(self, options, state: TrainingState):
        self.options = options
        self.state = state
        self.disp_freq = SchedulingParameter.parse(str(options.get("disp-freq", "1000u")))
        self.disp_first = int(options.get("disp-first", 0))
        self.save_freq = SchedulingParameter.parse(str(options.get("save-freq", "10000u")))
        self.valid_freq = SchedulingParameter.parse(str(options.get("valid-freq", "10000u")))
        self.after = SchedulingParameter.parse(str(options.get("after", "0e")))
        self.after_epochs = int(options.get("after-epochs", 0) or 0)
        self.after_batches = int(options.get("after-batches", 0) or 0)
        self.early_stopping = int(options.get("early-stopping", 10) or 0)
        # per-metric improvement margins (reference: --early-stopping-epsilon)
        eps = options.get("early-stopping-epsilon", [0.0]) or [0.0]
        self.early_stopping_eps = [float(e) for e in (
            eps if isinstance(eps, list) else [eps])]
        self.lr_report = bool(options.get("lr-report", False))
        self.disp_label_counts = bool(options.get("disp-label-counts", False))
        # --logical-epoch [size, decimals]: epoch redefined as a data amount
        # (e.g. 500Mt) for display/epoch-based scheduling consistency
        le = options.get("logical-epoch", []) or []
        if not isinstance(le, list):
            le = [le]
        self.logical_epoch = SchedulingParameter.parse(str(le[0])) \
            if le and str(le[0]) not in ("", "1e") else None
        self.logical_epoch_width = int(le[1]) if len(le) > 1 else 3
        # display accumulators
        self._cost_sum = 0.0
        self._label_sum = 0.0
        self._words_sum = 0.0
        self._sent_sum = 0
        self._timer = time.perf_counter()
        self._disp_count = 0
        # serving-grade observability (serving/metrics.py — ISSUE 1): the
        # trainer emits into the same process-wide registry the server
        # scrapes, so a training job started with --metrics-port exposes
        # live cost/throughput to Prometheus with zero extra deps. Get-or-
        # create semantics make repeated Scheduler construction safe.
        from ..serving import metrics as msm
        self._m_cost = msm.gauge(
            "marian_train_cost", "Displayed training cost (per cost-type)")
        self._m_wps = msm.gauge(
            "marian_train_words_per_second",
            "Training throughput over the last display window")
        self._m_lr = msm.gauge(
            "marian_train_learn_rate", "Current learning rate")
        self._m_updates = msm.counter(
            "marian_train_updates_total", "Optimizer updates applied")
        self._m_labels = msm.counter(
            "marian_train_labels_total", "Target labels consumed")
        self._m_skipped = msm.counter(
            "marian_train_updates_skipped_total",
            "Updates skipped by --check-gradient-nan (params and optimizer "
            "state reverted; non-finite gradient)")
        # -- divergence policy + live NaN-skip surfacing (ISSUE 19) --------
        # the optimizer's per-update `skipped` flag used to vanish into the
        # window average; here it is drained with BOUNDED lag (not a display
        # window) so consecutive skips are detected within ~_skip_lag updates
        mode = str(options.get("on-divergence", "") or "")
        if mode and mode not in ("throw", "warn", "rollback"):
            raise ValueError(
                f"--on-divergence {mode!r}: expected throw, warn or rollback")
        self._divergence_mode = mode or (
            "throw" if options.get("throw-on-divergence", False) else "warn")
        self.skip_window = int(options.get("divergence-skip-window", 0) or 0)
        self._skip_lag = 2           # max updates a skip flag stays lazy
        self._pending_skips: List = []   # [(batch_idx, lazy scalar)]
        self._consec_skips = 0
        self._skip_warned = False
        # --tensorboard DIR (TPU extension; the reference logs text only):
        # train/valid scalars via torch's SummaryWriter (baked-in). Never
        # a hard dependency — unavailable writer degrades to a warning.
        self._tb = None
        tb_dir = options.get("tensorboard", None)
        if tb_dir is not None:
            if not tb_dir:
                # bare --tensorboard still means ON (same convention as
                # --profile): default next to the model
                tb_dir = str(options.get("model", "model.npz")) + ".tb"
            try:
                from torch.utils.tensorboard import SummaryWriter
                self._tb = SummaryWriter(log_dir=str(tb_dir))
            except Exception as e:  # noqa: BLE001 — optional extra
                log.warn("--tensorboard unavailable ({}); scalars "
                         "disabled", e)

    def _tb_scalar(self, tag: str, value: float, step: int) -> None:
        if self._tb is not None:
            try:
                self._tb.add_scalar(tag, value, step)
            except Exception:  # noqa: BLE001 — never kill training for TB
                pass

    def close(self) -> None:
        """Flush+close the TensorBoard writer (torch's event thread
        buffers up to 120s — without this the final display/validation
        scalars are lost at process exit)."""
        if self._tb is not None:
            try:
                self._tb.close()
            except Exception:  # noqa: BLE001
                pass
            self._tb = None

    # -- continuation conditions (reference: keepGoing) ----------------------
    def keep_going(self) -> bool:
        s = self.state
        if self.after_epochs and s.epochs >= self.after_epochs:
            return False
        if self.after_batches and s.batches >= self.after_batches:
            return False
        if self.after:
            if self.after.unit == SchedulingUnit.EPOCHS and s.epochs >= self.after.n:
                return False
            if self.after.unit == SchedulingUnit.UPDATES and s.batches >= self.after.n:
                return False
            if self.after.unit == SchedulingUnit.TRG_LABELS and s.labels_total >= self.after.n:
                return False
        if self.early_stopping and s.stalled >= self.early_stopping:
            log.info("Early stopping after {} stalled validations", s.stalled)
            return False
        return True

    # -- per-update bookkeeping (reference: Scheduler::update) ---------------
    def update(self, loss_sum, labels: float, sentences: int,
               src_words: float = 0.0, lr: Optional[float] = None,
               skipped=None) -> None:
        """loss_sum may be a LAZY device scalar (jax.Array) — it is only
        accumulated here; the host-device sync happens at the display
        boundary (_display), keeping the hot loop free of per-step blocking
        so dispatch can run ahead of the device.

        `skipped` is the optimizer's lazy 0/1 --check-gradient-nan flag for
        this update (None when the guard is off): queued and drained with
        bounded lag by _drain_skips, never a per-step sync."""
        with obs.span("train.bookkeep", step=self.state.batches + 1):
            s = self.state
            s.batches += 1
            s.batches_epoch += 1
            s.samples_epoch += sentences
            s.labels_total += int(labels)
            self._m_updates.inc()
            self._m_labels.inc(int(labels))
            if lr is not None:
                s.eta = float(lr)
            if skipped is not None:
                self._pending_skips.append((s.batches, skipped))
            self._drain_skips()
            self._cost_sum += loss_sum
            self._label_sum += labels
            self._words_sum += (src_words or labels)
            self._sent_sum += sentences
            self._disp_count += 1

            show = False
            if self.disp_first and s.batches <= self.disp_first:
                show = True
            elif self._hit(self.disp_freq):
                show = True
            if show and self._disp_count:
                self._display()

    def _hit(self, freq: SchedulingParameter) -> bool:
        if not freq:
            return False
        s = self.state
        if freq.unit == SchedulingUnit.UPDATES:
            return s.batches % freq.n == 0
        if freq.unit == SchedulingUnit.TRG_LABELS:
            # fire when the label counter crosses a multiple
            return (s.labels_total // freq.n) > ((s.labels_total - self._label_sum) // freq.n)
        return False  # epoch-based handled in new_epoch

    # -- divergence detection + policy (ISSUE 19) ----------------------------
    @property
    def divergence_mode(self) -> str:
        """Resolved --on-divergence policy: throw | warn | rollback."""
        return self._divergence_mode

    def _drain_skips(self, block: bool = False) -> None:
        """Resolve queued --check-gradient-nan flags. Entries younger than
        _skip_lag updates are only read when already fenced (is_ready —
        non-blocking); older ones are force-synced, which is nearly free
        under async dispatch because the device has long finished them.
        Detection is therefore deterministic within ~_skip_lag updates of
        the skip, instead of a display window later."""
        s = self.state
        while self._pending_skips:
            batch, flag = self._pending_skips[0]
            if not block and s.batches - batch < self._skip_lag:
                ready = getattr(flag, "is_ready", None)
                if ready is not None and not ready():
                    return
            self._pending_skips.pop(0)
            if float(flag) <= 0.5:
                self._consec_skips = 0
                continue
            self._m_skipped.inc()
            self._consec_skips += 1
            if not self._skip_warned:
                self._skip_warned = True
                log.warn(
                    "Update {} skipped: non-finite gradient "
                    "(--check-gradient-nan reverted params + optimizer "
                    "state; counted in marian_train_updates_skipped_total)",
                    batch)
            if self.skip_window and self._consec_skips >= self.skip_window:
                self._divergence(
                    f"{self._consec_skips} consecutive NaN-skipped updates "
                    f"through update {batch} "
                    f"(--divergence-skip-window {self.skip_window})")

    def _divergence(self, reason: str) -> None:
        """Apply the resolved --on-divergence policy. throw and rollback
        both raise DivergenceError — the train loop's retry ladder decides
        whether to roll back in-process or let the raise abort the run."""
        mode = self._divergence_mode
        self._consec_skips = 0
        if mode in ("throw", "rollback"):
            raise DivergenceError(
                f"training diverged: {reason} (--on-divergence {mode})")
        armed = [
            f"--check-gradient-nan "
            f"{'on' if self.options.get('check-gradient-nan', False) else 'OFF'}",
            f"--divergence-skip-window {self.skip_window or 'off'}",
        ]
        log.warn(
            "training diverged: {} — continuing (--on-divergence warn; "
            "armed guards: {}). --on-divergence rollback would restore the "
            "last good checkpoint bundle, rewind the data pipeline to its "
            "corpus snapshot, retry with learning-rate backoff x{}, and "
            "give up after {} attempts",
            reason, ", ".join(armed),
            self.options.get("divergence-lr-backoff", 0.5),
            self.options.get("divergence-retries", 3))

    def drain_skips(self) -> None:
        """Blocking end-of-run fence: resolve every still-lazy skip flag so
        a divergence inside the final ~_skip_lag updates raises (into the
        rollback ladder) instead of being silently saved as the final
        checkpoint."""
        self._drain_skips(block=True)

    def reset_divergence_window(self) -> None:
        """Post-rollback reset: drop every accumulator that straddles the
        rollback point so the first display window of the retried run is
        not polluted by pre-rollback (possibly non-finite) cost, and stale
        lazy skip flags from the abandoned trajectory are never drained."""
        self._pending_skips.clear()
        self._consec_skips = 0
        self._cost_sum = self._label_sum = self._words_sum = 0.0
        self._sent_sum = 0
        self._disp_count = 0
        self._timer = time.perf_counter()

    def _display(self) -> None:
        s = self.state
        cost_type = self.options.get("cost-type", "ce-sum")
        # the one deferred sync: the host blocked on the device, as the
        # span `train.sync` (a child of `train.bookkeep`)
        with obs.span("train.sync", step=s.batches) as sp:
            self._cost_sum = float(self._cost_sum)
            # the step counters of the same updates (routing counts of an
            # expert layer), lazy until here: ready with the cost
            obs.TRACER.fetch_counters()
            # the device has drained: what its allocator holds now is the
            # resident set, no step's temporaries (a host call, no sync)
            mem = (hbm.device_memory() if sp else None) or {}
            for name, key in (("hbm.in_use_drained", "in_use"),
                              ("hbm.peak", "peak"), ("hbm.limit", "limit")):
                if key in mem:
                    obs.TRACER.gauge(name, mem[key])
        # clock read AFTER the cost sync (mtlint MT-SYNC-TIMER): forcing
        # the accumulated device scalar completes every update in the
        # display window, so words/s divides by real execution time.
        # Pre-fix the delta was read before the sync — under async
        # dispatch that clocked ENQUEUE time and overstated throughput.
        dt = max(time.perf_counter() - self._timer, 1e-9)
        self._drain_skips(block=True)   # display IS a fence — resolve all
        if not math.isfinite(self._cost_sum):
            # cost divergence surfaces here, at the display boundary — the
            # hot loop never syncs per step. (Consecutive NaN-SKIPPED
            # updates are caught earlier by _drain_skips; a non-finite cost
            # that reaches this sum means params actually took a bad step.)
            self._divergence(
                f"non-finite cost at update {s.batches}")
        if cost_type == "ce-mean-words" or cost_type == "ce-sum":
            cost = self._cost_sum / max(self._label_sum, 1.0)
        elif cost_type == "perplexity":
            cost = math.exp(min(self._cost_sum / max(self._label_sum, 1.0), 700))
        else:
            cost = self._cost_sum / max(self._sent_sum, 1)
        wps = self._words_sum / dt
        ep = self._epoch_display()
        cost_part = f"Cost {cost:.8f}"
        if self.disp_label_counts:
            cost_part += (f" * {int(self._label_sum):,} labels"
                          f" after {s.labels_total:,}")
        line = (f"Ep. {ep} : Up. {s.batches} : Sen. {s.samples_epoch:,} "
                f": {cost_part} : Time {dt:.2f}s : {wps:.2f} words/s")
        if self.lr_report:
            line += f" : L.r. {s.eta:.4e}"
        log.info("{}", line)
        self._tb_scalar("train/cost", cost, s.batches)
        self._tb_scalar("train/words_per_sec", wps, s.batches)
        self._tb_scalar("train/learn_rate", s.eta, s.batches)
        self._m_cost.set(cost)
        self._m_wps.set(wps)
        self._m_lr.set(s.eta)
        # live capacity accounting (obs/perf.py — ISSUE 9): this window's
        # dt is already sync-honest (clocked after the one deferred cost
        # sync above), so chip-seconds/token here is a real number, not
        # an enqueue-time artifact
        obs.PERF.record_train_window(labels=self._label_sum,
                                     src_words=self._words_sum,
                                     sentences=self._sent_sum, dt=dt)
        try:
            # same number the text line shows (1-based; honors
            # --logical-epoch's fractional display)
            self._tb_scalar("train/epoch", float(ep), s.batches)
        except ValueError:
            self._tb_scalar("train/epoch", s.epochs + 1, s.batches)
        self._cost_sum = self._label_sum = self._words_sum = 0.0
        self._sent_sum = 0
        self._disp_count = 0
        self._timer = time.perf_counter()  # mtlint: ok -- float(cost_sum) above is this window's sync fence; a block_until_ready here would stall the hot loop

    def _epoch_display(self):
        s = self.state
        if self.logical_epoch is None:
            return s.epochs + 1
        le = self.logical_epoch
        if le.unit == SchedulingUnit.TRG_LABELS:
            val = s.labels_total / max(le.n, 1)
        elif le.unit == SchedulingUnit.UPDATES:
            val = s.batches / max(le.n, 1)
        else:  # e.g. '2e': one logical epoch = n data epochs
            val = (s.epochs + 1) / max(le.n, 1)
        return f"{val:.{self.logical_epoch_width}f}"

    # -- triggers ------------------------------------------------------------
    def should_save(self) -> bool:
        return bool(self.save_freq) and self._hit(self.save_freq)

    def should_validate(self) -> bool:
        return bool(self.valid_freq) and self._hit(self.valid_freq)

    def new_epoch(self) -> None:
        seen = self.state.samples_epoch
        self.state.new_epoch()
        log.info("Seen {} samples in epoch {}", seen, self.state.epochs)

    # -- validation bookkeeping (reference: Scheduler::validate) -------------
    def register_validation(self, metric: str, value: float,
                            lower_is_better: bool = True) -> bool:
        """Track best/stalled per metric; returns True if improved."""
        s = self.state
        rec = s.validators.setdefault(metric, {"last-best": None, "stalled": 0})
        best = rec["last-best"]
        metrics_order = (self.options.get("valid-metrics", ["cross-entropy"])
                         or ["cross-entropy"])
        idx = metrics_order.index(metric) if metric in metrics_order else 0
        eps = self.early_stopping_eps[min(idx,
                                          len(self.early_stopping_eps) - 1)]
        improved = (best is None or
                    (value < best - eps if lower_is_better
                     else value > best + eps))
        self._tb_scalar(f"valid/{metric}", float(value), s.batches)
        if improved:
            rec["last-best"] = float(value)
            rec["stalled"] = 0
        else:
            rec["stalled"] += 1
        # --early-stopping-on: which metrics drive the global stall count
        # (reference: Scheduler::validated): first (default) = first
        # valid-metric only; any = most-stalled metric (stop as soon as any
        # metric stalls long enough); all = least-stalled (stop only when
        # every metric stalled)
        mode = str(self.options.get("early-stopping-on", "first") or "first")
        stalls = [r["stalled"] for r in s.validators.values()] or [0]
        if mode == "any":
            s.stalled = max(stalls)
        elif mode == "all":
            s.stalled = min(stalls)
        else:
            first_metric = metrics_order[0]
            if metric == first_metric:
                s.stalled = rec["stalled"]
        s.max_stalled = max(s.max_stalled, s.stalled)
        return improved

    def reset_stalled(self, reset_best: bool = False) -> None:
        """--valid-reset-stalled / --valid-reset-all on resume: clear stall
        counters (and optionally the recorded bests) so continued training
        isn't immediately early-stopped by pre-restart validations."""
        s = self.state
        s.stalled = 0
        s.max_stalled = 0
        for rec in s.validators.values():
            rec["stalled"] = 0
            if reset_best:
                rec["last-best"] = None

    # -- LR decay (reference: Scheduler::updateLearningRate strategies) ------
    def maybe_decay_lr(self, schedule, graph_group=None) -> None:
        decay = float(self.options.get("lr-decay", 0.0) or 0.0)
        if decay <= 0:
            return
        strategy = self.options.get("lr-decay-strategy", "epoch+stalled")
        start = self.options.get("lr-decay-start", [10, 1])
        s = self.state
        fire = False
        if "epoch" in strategy and s.epochs + 1 >= int(start[0]):
            if "stalled" in strategy:
                fire = s.stalled >= int(start[1] if len(start) > 1 else 1)
            elif "batches" in strategy:
                freq = int(self.options.get("lr-decay-freq", 50000))
                fire = s.batches > 0 and s.batches % freq == 0
            else:
                fire = True
        elif strategy == "batches":
            freq = int(self.options.get("lr-decay-freq", 50000))
            fire = s.batches > 0 and s.batches % freq == 0
        elif strategy == "stalled":
            fire = s.stalled >= int(start[0])
        if fire:
            s.factor *= decay
            schedule.decay_factor = s.factor
            log.info("Decaying learning rate to factor {}", s.factor)
            if self.options.get("lr-decay-repeat-warmup", False):
                schedule.warmup_offset = s.batches
                log.info("Restarting learning-rate warmup at update {}",
                         s.batches)
            if graph_group is not None:
                if self.options.get("lr-decay-reset-optimizer", False):
                    # re-initializes moments AND rebuilds the jitted steps
                    graph_group.reset_optimizer()
                    log.info("Optimizer state reset after learning-rate decay")
                else:
                    # schedule factors are baked into the compiled train step
                    # at trace time — rebuild so the decayed LR takes effect
                    graph_group.rebuild()
