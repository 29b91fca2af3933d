"""Live performance & capacity accounting (ISSUE 9 tentpole — the
"is the hardware being used well" half of the observability plane).

DECODE_ROOFLINE.md and PERFORMANCE.md are *static* analyses: they say
where the roofline sits, not where the process is right now. This module
turns the same analytic cost model (common/flops.py) into **live
gauges**, fed by the layers that actually spend device time:

- the serving scheduler reports every device batch (rows, width bucket,
  real tokens, seconds measured to the host-side result fence:
  ``translate_lines`` returns host strings, so the return IS the drain;
  the timestamp is taken after it, never at enqueue);
- the training scheduler reports every display window (whose duration
  is already clocked after the window's one deferred device sync);
- the lifecycle warmup and the scheduler report jit-compile activity
  per shape bucket, so ROADMAP 5's future AOT cache can prove
  hits-vs-misses and a steady-state recompile surfaces as the latency
  incident it is.

Exported series (docs/OBSERVABILITY.md "The perf plane"):

- ``marian_perf_device_seconds_total`` / ``marian_perf_tokens_total`` /
  ``marian_perf_trg_tokens_total`` {model_version} — the raw capacity
  integrals (loadgen --sweep differences these);
- ``marian_perf_chip_seconds_per_token`` {model_version} — rolling
  chip-seconds per real source token, THE autoscaling signal ROADMAP 4
  asks for (chip = wall seconds on the device worker × device count);
- ``marian_perf_tokens_per_second`` {model_version},
  ``marian_perf_device_busy_ratio`` — rolling throughput / utilization;
- ``marian_perf_mfu`` {model_version} — rolling model-FLOPs utilization
  against the analytic roofline for the configured geometry
  (``set_geometry``); 0 on a device with no peak (CPU) — a TPU kind
  the peak table does not list is an error at ``set_geometry``;
- ``marian_capacity_headroom_ratio`` — one scrape-time gauge combining
  device utilization and admission-queue pressure (see ``headroom``);
- ``marian_compile_total`` / ``marian_compile_seconds_total``
  {trigger, bucket} — compile telemetry per width bucket, trigger in
  {boot-warmup, swap-warmup, steady-state};
- ``marian_compile_backend_seconds_total`` {trigger} — TRUE XLA backend
  compile seconds via jax.monitoring, when jax is live (the bucket
  telemetry above is inferred at the serving layer and works with stub
  executors; this series is ground truth on a real device).

What the "device seconds" are: HOST WALL seconds on the worker thread
around the translate call (or the trainer's display window), not seconds
the chip was busy — they hold dispatch, transfers and the host's share of
every round. The busy-ratio, chip-seconds and MFU series are therefore
upper bounds of utilisation from the host's side; seconds the device was
busy come only from a profiler trace (cli/profile_summary.py).

Granularity honesty: serving "shape bucket" means the WIDTH bucket of
the repo's length-bucket table (``data/batch_generator.py``). The row
axis snaps to ``batch_multiple``, so width is the jit-cache-relevant
axis modulo row multiples; the backend series above is exact.

Disabled by default with zero overhead on the scheduler's batch path:
``PERF.enabled`` is one attribute read, and nothing below it runs (the
tier-1 raising-lock guard covers ``PerfMeter._lock`` alongside
``Tracer._lock``). Enable with ``--perf-accounting`` (the CLI default
for servers and trainers) or ``PERF.enable()``.

Threading: ``record_batch`` runs on the event loop, ``warm_bucket`` on
the watcher thread, ``headroom`` on the metrics scrape thread, the
train-window path on the training thread — the small shared state
(rolling window, warmed-bucket sets) lives under the lockdep-named
``PerfMeter._lock``; metric emission always happens OUTSIDE it.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Deque, Dict, Optional, Tuple

from ..common import lockdep
from ..common import logging as log
from .trace import TRACER

# rolling-window horizon for the rate gauges (seconds): long enough to
# smooth batch-to-batch jitter, short enough that an autoscaler acting
# on the headroom gauge sees load changes within one scrape interval
DEFAULT_WINDOW_S = 60.0

TRIGGER_BOOT = "boot-warmup"
TRIGGER_SWAP = "swap-warmup"
TRIGGER_STEADY = "steady-state"


def width_bucket_key(width: int) -> str:
    """The compile-telemetry bucket label for a padded width."""
    return f"w{int(width)}"


def round_bucket_key(row_bucket: int, encode_width: int, steps: int) -> str:
    """The compile-telemetry bucket label for one iteration-mode engine
    round (ISSUE 17): the engine's compile key is the (row bucket,
    encode width, steps-per-round) triple — a round landing on a triple
    nobody warmed is a steady-state recompile incident exactly like an
    unwarmed width in request mode. The lifecycle warmup drives the
    engine's full grid (PagedDecodeEngine.warm_grid) and registers
    these keys via ``warm_bucket``. Since ISSUE 18 the steps field is
    live for beam too: the fused-merge beam engine scans
    --iteration-steps decode steps per round (row buckets are
    beam-block multiples there), so beam rounds land on s>1 keys just
    like greedy — only the host-merge beam baseline stays pinned to
    s1."""
    return f"r{int(row_bucket)}.w{int(encode_width)}.s{int(steps)}"


class _Geometry:
    """Model geometry for the analytic MFU estimate (common/flops.py)."""

    __slots__ = ("emb", "ffn", "enc_depth", "dec_depth", "vocab", "beam",
                 "n_devices", "peak_flops")

    def __init__(self, emb: int, ffn: int, enc_depth: int, dec_depth: int,
                 vocab: int, beam: int, n_devices: int,
                 peak_flops: Optional[float]):
        self.emb = emb
        self.ffn = ffn
        self.enc_depth = enc_depth
        self.dec_depth = dec_depth
        self.vocab = vocab
        self.beam = max(1, beam)
        self.n_devices = max(1, n_devices)
        self.peak_flops = peak_flops      # per device; None = unknown


class PerfMeter:
    def __init__(self, window_s: float = DEFAULT_WINDOW_S):
        self.enabled = False
        self.window_s = float(window_s)
        self._lock = lockdep.make_lock("PerfMeter._lock")
        # rolling (ts, version, device_s, src_tokens, trg_tokens, flops,
        # rows) samples, newest right; pruned to window_s on every
        # append/read, with RUNNING sums maintained alongside (global +
        # per version label; subtract on prune) so one batch or one
        # scrape is O(pruned), not O(window) — at high batch rates the
        # window holds thousands of samples. Per-version sums keep a
        # hot-swap's NEW version's cost gauge unpolluted by the old
        # version's samples still inside the window.
        self._window: Deque[Tuple[float, str, float, float, float,
                                  float, float]] = \
            collections.deque()                     # guarded-by: _lock
        # [device_s, src_tokens, trg_tokens, flops, rows]
        self._sums = [0.0] * 5                      # guarded-by: _lock
        self._vsums: Dict[str, list] = {}           # guarded-by: _lock
        # versions whose tokens/s gauge child already has its sampler
        self._tps_wired: set = set()                # guarded-by: _lock
        # (model_version, bucket) pairs warmed by an explicit warmup pass
        self._warm: set = set()                     # guarded-by: _lock
        # (model_version, bucket) pairs seen by steady-state dispatch
        self._seen: set = set()                     # guarded-by: _lock
        self._geo: Optional[_Geometry] = None       # guarded-by: _lock
        self._depth_fn: Optional[Callable[[], int]] = None
        self._max_queue = 0
        self._registry = None
        self._jax_hooked = False
        # compile-trigger context for the jax.monitoring listener: the
        # warmup passes run on their own threads, so a thread-local tag
        # attributes backend compile seconds to the right trigger
        self._trigger_ctx = threading.local()

    # -- lifecycle ----------------------------------------------------------
    def enable(self, registry=None, window_s: Optional[float] = None,
               hook_jax: bool = True) -> None:
        from ..serving import metrics as msm    # lazy: no import cycle
        if window_s:
            self.window_s = float(window_s)
        target = registry if registry is not None else msm.REGISTRY
        if self._registry is not None and target is not self._registry:
            # re-enabled onto a DIFFERENT scrape surface (a second
            # ServingApp in one process): the accumulated state belongs
            # to the previous app — stale _tps_wired would leave the new
            # registry's tokens/s series without its sampler, a stale
            # _seen/_warm set would hide the new app's genuinely cold
            # first compiles, and old window samples would pollute the
            # fresh cost gauges. Start clean.
            with self._lock:
                self._window.clear()
                self._sums = [0.0] * 5
                self._vsums.clear()
                self._tps_wired.clear()
                self._warm.clear()
                self._seen.clear()
        self._registry = target
        self._declare_metrics()
        self.enabled = True
        if hook_jax:
            self._hook_jax_compiles()

    def reset(self) -> None:
        self.enabled = False
        self.window_s = DEFAULT_WINDOW_S
        with self._lock:
            self._window.clear()
            self._sums = [0.0] * 5
            self._vsums.clear()
            self._tps_wired.clear()
            self._warm.clear()
            self._seen.clear()
            self._geo = None
        self._depth_fn = None
        self._max_queue = 0
        self._registry = None

    def _declare_metrics(self) -> None:
        r = self._registry
        self.m_device_s = r.counter(
            "marian_perf_device_seconds_total",
            "HOST WALL seconds the device worker spent in translate "
            "calls, measured to the host-side result fence (not seconds "
            "the chip was busy)",
            labels=("model_version",))
        self.m_tokens = r.counter(
            "marian_perf_tokens_total",
            "Real (unpadded) source tokens through the device",
            labels=("model_version",))
        self.m_trg_tokens = r.counter(
            "marian_perf_trg_tokens_total",
            "Real target tokens produced by the device",
            labels=("model_version",))
        self.m_cspt = r.gauge(
            "marian_perf_chip_seconds_per_token",
            "Rolling chip-seconds per real source token (worker host wall "
            "seconds x device count / tokens over the last window; not "
            "device-busy seconds) — the capacity / autoscaling signal "
            "(ROADMAP 4)",
            labels=("model_version",))
        self.m_tps = r.gauge(
            "marian_perf_tokens_per_second",
            "Rolling real source tokens per second through the device "
            "(scrape-time over the window — decays to 0 at idle)",
            labels=("model_version",))
        self.m_busy = r.gauge(
            "marian_perf_device_busy_ratio",
            "Rolling fraction of wall-clock the device worker THREAD "
            "spent inside translate calls: host wall seconds, not time "
            "the chip was busy (scrape-time over the window — "
            "decays to 0 at idle, so an autoscaler never sees phantom "
            "saturation on an idle replica)")
        self.m_busy.set_function(self._busy_now)
        self.m_devices = r.gauge(
            "marian_perf_devices",
            "JAX device count the chip-seconds gauges are scaled by "
            "(loadgen --sweep multiplies its wall-second deltas by "
            "this to match marian_perf_chip_seconds_per_token)")
        self.m_devices.set(1)
        self.m_mfu = r.gauge(
            "marian_perf_mfu",
            "Rolling model-FLOPs utilization vs the analytic roofline "
            "for the configured geometry (0 = unknown chip / no "
            "geometry; see docs/PERFORMANCE.md 'Live vs static')",
            labels=("model_version",))
        self.m_peak = r.gauge(
            "marian_perf_roofline_peak_flops",
            "Peak bf16 FLOPs/s assumed by the MFU gauge across all "
            "devices (0 = unknown chip generation)")
        self.m_headroom = r.gauge(
            "marian_capacity_headroom_ratio",
            "Scrape-time capacity headroom in [0,1]: (1 - rolling device "
            "busy fraction) x (1 - admission queue pressure). 1 = idle, "
            "0 = saturated or queue full — feed this to the autoscaler "
            "(docs/DEPLOYMENT.md)")
        self.m_headroom.set_function(self.headroom)
        self.m_compiles = r.counter(
            "marian_compile_total",
            "Inferred jit compilations by width bucket and trigger "
            "(boot-warmup | swap-warmup | steady-state; steady-state "
            "recompiles are latency incidents and also land on the "
            "event timeline)",
            labels=("trigger", "bucket"))
        self.m_compile_s = r.counter(
            "marian_compile_seconds_total",
            "Wall seconds attributed to the inferred compilations (for "
            "steady-state: the first batch's device seconds, an upper "
            "bound — compile and run are fused)",
            labels=("trigger", "bucket"))
        self.m_backend_s = r.counter(
            "marian_compile_backend_seconds_total",
            "TRUE XLA backend compile seconds (jax.monitoring), by "
            "trigger — ground truth next to the inferred bucket series",
            labels=("trigger",))
        self.m_train_cspt = r.gauge(
            "marian_train_chip_seconds_per_token",
            "Training: host wall seconds x device count per target label "
            "over the last display window (clocked after the window's "
            "deferred device sync; not device-busy seconds)")
        self.m_train_mfu = r.gauge(
            "marian_train_mfu",
            "Training: rolling model-FLOPs utilization of the last "
            "display window vs the analytic roofline (0 = no peak (CPU) "
            "/ no geometry)")

    # -- configuration ------------------------------------------------------
    def set_geometry(self, emb: int, ffn: int, enc_depth: int,
                     dec_depth: int, vocab: int, beam: int = 1,
                     n_devices: Optional[int] = None,
                     peak_flops: Optional[float] = None,
                     device_kind: Optional[str] = None) -> None:
        """Model geometry + device peak for the MFU gauges. When
        ``peak_flops`` (per device) is not given, it is resolved from
        ``device_kind`` — or from the live jax device when neither is
        given. CPU has no peak (the gauges read 0); a TPU kind missing
        from common/flops.py's table raises."""
        if peak_flops is None:
            if device_kind is None or n_devices is None:
                kind, n = self._probe_devices()
                device_kind = device_kind if device_kind is not None else kind
                n_devices = n_devices if n_devices is not None else n
            from ..common.flops import peak_bf16_flops
            peak_flops = peak_bf16_flops(device_kind or "")
        geo = _Geometry(int(emb), int(ffn), int(enc_depth), int(dec_depth),
                        int(vocab), int(beam), int(n_devices or 1),
                        peak_flops)
        with self._lock:
            self._geo = geo
        if self.enabled:
            self.m_peak.set((peak_flops or 0.0) * geo.n_devices)
            self.m_devices.set(geo.n_devices)

    @staticmethod
    def _probe_devices() -> Tuple[str, int]:
        import jax
        devs = jax.devices()
        return devs[0].device_kind, len(devs)

    def set_capacity_inputs(self, depth_fn: Optional[Callable[[], int]],
                            max_queue_units: int) -> None:
        """Wire the admission-pressure half of the headroom gauge: the
        scheduler's live queue depth and the admission bound
        (0 = unbounded — pressure is then queue debt in device-seconds
        relative to the rolling window). The UNITS follow the batching
        mode: sentences against --max-queue in request mode, KV-pool
        PAGES against --max-queue-pages in iteration mode (the ratio
        math is identical; dashboards read the mode off
        marian_serving_queue_depth_pages being live — see
        docs/DEPLOYMENT.md). Pass ``None`` to unwire (a closed
        ServingApp must not leave the process-global gauge sampling a
        dead scheduler — and keeping its whole object graph alive
        through the bound method)."""
        self._depth_fn = depth_fn
        self._max_queue = int(max_queue_units)

    # -- serving batch accounting (event-loop thread) -----------------------
    def record_batch(self, model_version: str, rows: int, width: int,
                     src_tokens: int, trg_tokens: int,
                     device_s: float,
                     bucket_key: Optional[str] = None) -> None:
        """One device batch: integrate counters, refresh the rolling
        gauges, and run the steady-state compile check for the batch's
        width bucket. ``device_s`` must be measured to the result fence
        (the caller's contract — see the module docstring).
        ``bucket_key`` overrides the default ``width_bucket_key(width)``
        compile-bucket label — iteration mode passes the engine round's
        :func:`round_bucket_key` triple so the steady-state recompile
        check tracks the engine's REAL compile key, not just the padded
        width.

        Attribution caveat: ``model_version`` is the label the CALLER
        stamps (the scheduler's version_fn — the live version at batch
        time), so during a canary phase canary batches are attributed
        to the live version; per-version canary HEALTH lives in the
        lifecycle's own ``marian_model_*`` series, which the routing
        decision stamps exactly. The per-version windows here keep a
        hot-swap's before/after cost separated — not canary vs live."""
        if not self.enabled:
            return
        now = time.perf_counter()
        version = str(model_version)
        flops = 0.0
        with self._lock:
            geo = self._geo
        if geo is not None:
            from ..common.flops import transformer_serve_flops
            # trg width = the AVERAGE generated length (trg_tokens over
            # real rows), not the source bucket: the decoder's
            # self-attention cache grows with what was actually
            # generated, and expansion-heavy pairs would otherwise read
            # systematically wrong MFU
            trg_w = max(1, int(round(trg_tokens / max(1, rows))))
            flops = transformer_serve_flops(
                geo.emb, geo.ffn, geo.enc_depth, geo.dec_depth, geo.vocab,
                src_tokens=float(src_tokens), trg_tokens=float(trg_tokens),
                src_width=int(width), trg_width=trg_w,
                beam=geo.beam)
        with self._lock:
            self._window.append((now, version, float(device_s),
                                 float(src_tokens), float(trg_tokens),
                                 flops, float(rows)))
            vs = self._vsums.setdefault(version, [0.0] * 5 + [0])
            for tgt in (self._sums, vs):
                tgt[0] += float(device_s)
                tgt[1] += float(src_tokens)
                tgt[2] += float(trg_tokens)
                tgt[3] += flops
                tgt[4] += float(rows)
            vs[5] += 1
            v_first = version not in self._tps_wired
            self._tps_wired.add(version)
            self._prune(now)
            v_dev, v_src, v_flops = vs[0], vs[1], vs[3]
            n_dev = geo.n_devices if geo is not None else 1
            peak = (geo.peak_flops or 0.0) * n_dev if geo is not None \
                else 0.0
        self.m_device_s.labels(version).inc(float(device_s))
        self.m_tokens.labels(version).inc(int(src_tokens))
        self.m_trg_tokens.labels(version).inc(int(trg_tokens))
        if v_src > 0:
            # the COST of this version's recent traffic: deliberately
            # holds its last value at idle (a $/token figure does not
            # decay; the rate/utilization gauges are the ones that must)
            self.m_cspt.labels(version).set(v_dev * n_dev / v_src)
        if v_first:
            # throughput is scrape-time: assign this version's
            # window-rate sampler on its FIRST batch (it reads the live
            # sums, so later batches need no re-assignment) — an idle
            # replica reads 0, not the last burst's rate
            self.m_tps.labels(version).set_function(
                lambda v=version: self._rate_now(v))
        mfu = 0.0
        if peak > 0 and v_dev > 0:
            mfu = v_flops / (v_dev * peak)
        self.m_mfu.labels(version).set(mfu)
        self._bucket_seen(version, bucket_key or width_bucket_key(width),
                          device_s)

    def _prune(self, now: float) -> None:
        """Evict samples older than the window, decrementing the global
        and per-version running sums; caller holds the lock. O(pruned),
        not O(window). A version whose last sample ages out drops its
        sums entry (bounded memory over weeks of hot-swaps)."""
        w, s = self._window, self._sums
        while w and now - w[0][0] > self.window_s:
            _ts, ver, dev, src, trg, fl, rows = w.popleft()
            for tgt in (s, self._vsums.get(ver)):
                if tgt is None:
                    continue
                tgt[0] -= dev
                tgt[1] -= src
                tgt[2] -= trg
                tgt[3] -= fl
                tgt[4] -= rows
            vs = self._vsums.get(ver)
            if vs is not None:
                vs[5] -= 1
                if vs[5] <= 0:
                    del self._vsums[ver]
        if not w:
            s[0] = s[1] = s[2] = s[3] = s[4] = 0.0   # absorb float drift

    def _window_sums(self, now: float) -> Tuple[float, float, float, float,
                                                float]:
        """Prune, then return the global running sums (device_s,
        src_tokens, trg_tokens, flops, span_s); caller holds the lock.
        Span is the elapsed wall clock the samples cover (capped at the
        window horizon)."""
        self._prune(now)
        s = self._sums
        if not self._window:
            return 0.0, 0.0, 0.0, 0.0, 0.0
        span = max(now - self._window[0][0], s[0], 1e-9)
        return s[0], s[1], s[2], s[3], min(span, self.window_s)

    def _busy_now(self) -> float:
        """Scrape-time device-busy fraction over the rolling window."""
        now = time.perf_counter()
        with self._lock:
            dev, _s, _t, _f, span = self._window_sums(now)
        return min(1.0, dev / span) if span > 0 else 0.0

    def _rate_now(self, version: Optional[str] = None) -> float:
        """Scrape-time source tokens/s over the rolling window (one
        version's share, or global when ``version`` is None)."""
        now = time.perf_counter()
        with self._lock:
            _d, src, _t, _f, span = self._window_sums(now)
            if version is not None:
                vs = self._vsums.get(version)
                src = vs[1] if vs is not None else 0.0
        return src / span if span > 0 else 0.0

    # -- capacity headroom (metrics scrape thread) --------------------------
    def headroom(self) -> float:
        """(1 - busy) x (1 - queue pressure), clamped to [0, 1]. Busy is
        the rolling device-seconds fraction of the window; pressure is
        queued sentences over the admission bound, or (unbounded queue)
        the queued work priced at the rolling device-seconds-PER-SENTENCE
        rate relative to the window horizon (the queue depth is counted
        in sentences, so the price must be too — a per-token price would
        understate the backlog by the average sentence length)."""
        now = time.perf_counter()
        with self._lock:
            dev_sum, _src, _t, _f, span = self._window_sums(now)
            rows_sum = self._sums[4]
        busy = min(1.0, dev_sum / span) if span > 0 else 0.0
        pressure = 0.0
        if self._depth_fn is not None:
            try:
                depth = max(0, int(self._depth_fn()))
            except Exception:  # noqa: BLE001 — a scrape must never raise
                depth = 0
            if self._max_queue > 0:
                pressure = min(1.0, depth / self._max_queue)
            elif depth and rows_sum > 0 and dev_sum > 0:
                # unbounded queue: queued sentences priced at the rolling
                # device cost, as a fraction of one window horizon
                per_sentence = dev_sum / rows_sum
                pressure = min(1.0, depth * per_sentence / self.window_s)
        return max(0.0, (1.0 - busy) * (1.0 - pressure))

    # -- compile telemetry --------------------------------------------------
    def warm_bucket(self, model_version: str, bucket: str,
                    seconds: float, trigger: str) -> None:
        """A warmup pass compiled (executor ran) this width bucket; the
        bucket is now warm for ``model_version`` — steady-state traffic
        landing on it is NOT a recompile."""
        if not self.enabled:
            return
        with self._lock:
            self._warm.add((model_version, bucket))
        self.m_compiles.labels(trigger, bucket).inc()
        self.m_compile_s.labels(trigger, bucket).inc(float(seconds))

    def _bucket_seen(self, model_version: str, bucket: str,
                     device_s: float) -> None:
        key = (model_version, bucket)
        with self._lock:
            if key in self._seen:
                return
            self._seen.add(key)
            warmed = key in self._warm
        if warmed:
            return
        # first dispatch of a bucket nobody warmed: at steady state this
        # batch just paid a jit compile inline — a latency incident
        self.m_compiles.labels(TRIGGER_STEADY, bucket).inc()
        self.m_compile_s.labels(TRIGGER_STEADY, bucket).inc(float(device_s))
        TRACER.event("perf.recompile", bucket=bucket,
                     model_version=model_version,
                     device_s=round(float(device_s), 6))
        log.warn("perf: steady-state recompile — bucket {} of version {} "
                 "was never warmed (first batch paid the jit inline; "
                 "{:.3f}s)", bucket, model_version, device_s)

    def steady_recompiles(self) -> int:
        """Total steady-state recompile count (tests + /sloz-side
        introspection; the counter children are per bucket)."""
        if not self.enabled:
            return 0
        total = 0.0
        for key, child in self.m_compiles.children().items():
            if key and key[0] == TRIGGER_STEADY:
                total += child.value
        return int(total)

    # -- true backend compile seconds (jax.monitoring) ----------------------
    def compile_context(self, trigger: str):
        """Context manager tagging backend compile events fired on THIS
        thread with ``trigger`` (the warmup passes use it)."""
        meter = self

        class _Ctx:
            def __enter__(self):
                meter._trigger_ctx.trigger = trigger
                return self

            def __exit__(self, *exc):
                meter._trigger_ctx.trigger = None

        return _Ctx()

    def _hook_jax_compiles(self) -> None:
        if self._jax_hooked:
            return
        try:
            import jax.monitoring as jmon
        except Exception:  # noqa: BLE001 — obs must import without jax
            return
        self._jax_hooked = True

        def _on_event(name: str, secs: float, **_kw) -> None:
            if not self.enabled \
                    or not name.endswith("backend_compile_duration"):
                return
            trig = getattr(self._trigger_ctx, "trigger", None) \
                or TRIGGER_STEADY
            try:
                self.m_backend_s.labels(trig).inc(float(secs))
            except Exception:  # noqa: BLE001 — telemetry must never
                pass           # break a compile

        try:
            jmon.register_event_duration_secs_listener(_on_event)
        except Exception:  # noqa: BLE001 — jax API drift degrades to off
            self._jax_hooked = False

    # -- training window (training thread) ----------------------------------
    def record_train_window(self, labels: float, src_words: float,
                            sentences: int, dt: float) -> None:
        """One training display window: ``dt`` is the window's wall
        seconds (clocked after the window's deferred device sync —
        training/scheduler.py), ``labels`` its real target labels.
        Chip-seconds/token here means wall x devices (the chips are
        reserved for the whole window), the number a capacity planner
        actually pays for."""
        if not self.enabled or labels <= 0 or dt <= 0:
            return
        with self._lock:
            geo = self._geo
        n_dev = geo.n_devices if geo is not None else 1
        self.m_train_cspt.set(dt * n_dev / labels)
        mfu = 0.0
        if geo is not None and geo.peak_flops:
            from ..common.flops import transformer_train_flops
            sents = max(1, int(sentences))
            src_w = max(1, int(round((src_words or labels) / sents)))
            trg_w = max(1, int(round(labels / sents)))
            # unpadded average widths: understates the attention terms a
            # padded batch really pays, so this MFU reads slightly HIGH —
            # the benchmark's padded-shape accounting (mfu.train) is the
            # precise one
            flops = transformer_train_flops(
                geo.emb, geo.ffn, geo.enc_depth, geo.dec_depth, geo.vocab,
                src_tokens=float(src_words or labels),
                trg_tokens=float(labels),
                src_width=src_w, trg_width=trg_w)
            mfu = flops / (dt * geo.peak_flops * n_dev)
        self.m_train_mfu.set(mfu)

    # -- introspection ------------------------------------------------------
    def state(self) -> Dict:
        """JSON-ready snapshot (rides /sloz and flight dumps)."""
        if not self.enabled:
            return {"enabled": False}
        now = time.perf_counter()
        with self._lock:
            dev, src, trg, fl, span = self._window_sums(now)
            geo = self._geo
            warm = sorted(f"{v}:{b}" for v, b in self._warm)
            n_dev = geo.n_devices if geo is not None else 1
            versions = {
                v: {"device_seconds": round(vs[0], 6),
                    "src_tokens": vs[1], "batches": vs[5],
                    "chip_seconds_per_token":
                        round(vs[0] * n_dev / vs[1], 9) if vs[1] else None}
                for v, vs in sorted(self._vsums.items())}
        out = {
            "enabled": True,
            "window_s": self.window_s,
            "window": {
                "device_seconds": round(dev, 6),
                "src_tokens": src, "trg_tokens": trg,
                "busy_ratio": round(min(1.0, dev / span), 4)
                if span > 0 else 0.0,
                "chip_seconds_per_token":
                    round(dev * (geo.n_devices if geo else 1) / src, 9)
                    if src > 0 else None,
            },
            "headroom": round(self.headroom(), 4),
            "versions": versions,
            "warmed_buckets": warm,
            "steady_state_recompiles": self.steady_recompiles(),
        }
        if geo is not None:
            out["geometry"] = {
                "emb": geo.emb, "ffn": geo.ffn,
                "enc_depth": geo.enc_depth, "dec_depth": geo.dec_depth,
                "vocab": geo.vocab, "beam": geo.beam,
                "n_devices": geo.n_devices,
                "peak_flops_per_device": geo.peak_flops,
            }
        return out


# The process-wide meter, like TRACER / FLIGHT / the metrics REGISTRY.
PERF = PerfMeter()
