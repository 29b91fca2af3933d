"""Continuous token-budget batching scheduler — the host-side layer that
keeps the one jitted static-shape beam program fed under real load
(ISSUE 1 tentpole; replaces server/server.py :: _batching_worker's fixed
5 ms window + unbounded per-request batches).

Design (the serving-time mirror of data/batch_generator's maxi-batching,
which the reference applies only at training time):

- Requests split into SENTENCE UNITS; the scheduler packs units from many
  concurrent requests into one device batch by PADDED-TOKEN BUDGET against
  the same bucketed length table training uses (data/batch_generator
  bucket_length / padded_batch_cost) — batches land on warm jit-cache
  shapes instead of minting new ones per traffic pattern.
- CONTINUOUS: the worker loops as long as units are queued; a new batch
  forms the moment the device frees up, seeded by the oldest unit (no
  starvation), topped up with whatever else fits the budget.
- Per-request deadlines (--request-timeout) resolve expired requests with
  an explicit error even while queued; cancellation (client disconnect)
  propagates — a cancelled request's units are dropped before they cost
  device time.
- Priority lanes: higher-priority units always pack first.
- Retry-with-bisection on batch failure: one poison request costs
  O(log batch) retries to isolate, not the whole batch (upgrade over the
  previous one-by-one retry, O(batch) device calls).
- Observability (ISSUE 8, docs/OBSERVABILITY.md): with the span tracer
  enabled, every request grows a serve.request → serve.queue /
  serve.dispatch tree and every device batch a serve.batch →
  serve.translate span; watchdog trips and poison isolation fire the
  flight recorder. Tracer off = zero overhead on this hot path (no
  ring, no lock — tier-1 guarded). The reply-metadata breakdown
  (``submit(meta=...)``) is tracing-independent: plain timestamps.

Transport-agnostic and model-agnostic: ``translate_lines`` is any callable
``List[str] -> List[str]``; tests drive it with stubs under
JAX_PLATFORMS=cpu, the server wires in TranslationService, and the same
scheduler could front a scorer or embedder.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence

from .. import obs
from ..common import faultpoints as fp
from ..common import lockdep
from ..common import logging as log
from ..data.batch_generator import (DEFAULT_LENGTH_BUCKETS, bucket_length,
                                    padded_batch_cost)
from . import metrics as msm


class RequestTimeout(RuntimeError):
    """--request-timeout deadline expired before the request completed."""


class DispatchStalled(RuntimeError):
    """The dispatch watchdog (--dispatch-stall-timeout) fired: one device
    batch ran past the stall timeout. The batch's requests fail with THIS
    retriable error (transports reply !!SERVER-RETRY) and the scheduler
    moves onto a fresh device worker instead of wedging behind the stuck
    call."""

    retriable = True


class RowEvicted(RuntimeError):
    """A decoding row was evicted with its pages freed — the quiesce
    deadline expired mid-swap, brownout pressure reclaimed its capacity
    for a higher-priority lane, or a recoverable engine failure dropped
    the round (ISSUE 11). Retriable by contract: the server replies
    ``!!SERVER-RETRY`` and the replica is (or is about to be) healthy —
    a rolled-back / rebuilt engine serves the resend."""

    retriable = True


class _QuiesceOp:
    """One pending quiesce: stop admitting joins, drain active rows
    under ``deadline_s`` (evict the overdue with RowEvicted), run the
    pool audit, then ``install()`` re-points the engine at a step
    boundary with an empty join set. ``event`` releases the waiting
    caller (watcher / admin thread)."""

    __slots__ = ("install", "deadline_s", "reason", "deadline", "event",
                 "ok", "install_ok", "cancelled", "evicted", "t0")

    def __init__(self, install: Callable[[], None], deadline_s: float,
                 reason: str):
        self.install = install
        self.deadline_s = max(0.0, float(deadline_s))
        self.reason = reason
        self.deadline: Optional[float] = None   # set on first round seen
        self.event = threading.Event()
        self.ok = False            # install ran AND both audits clean
        self.install_ok = False    # install() returned without raising
        # a waiter that timed out CANCELS the op (cancel_quiesce): its
        # install must never run late — the caller has already treated
        # the re-point as failed (e.g. the lifecycle released the
        # candidate), so a late install would serve a dead executor
        self.cancelled = False
        self.evicted = 0
        self.t0 = 0.0


def default_length_fn(line: str) -> int:
    """Whitespace token estimate (+1 for EOS) — the budget packer only
    needs bucket-resolution accuracy; the translator re-measures with real
    vocab encodings when it builds the device batch."""
    return len(line.split()) + 1


class _Request:
    __slots__ = ("lines", "future", "priority", "arrival", "deadline",
                 "results", "remaining", "queued", "queued_pages",
                 "first_dispatch", "timeout_handle", "dead_accounted",
                 "trace_id", "span", "own_root", "q_span", "d_span",
                 "meta", "rounds", "prefix_hits", "evictions_n",
                 "on_partial", "ttft", "tenant")

    def __init__(self, lines: List[str], future: "asyncio.Future",
                 priority: int, arrival: float, deadline: Optional[float]):
        self.lines = lines
        self.future = future
        self.priority = priority
        self.arrival = arrival
        self.deadline = deadline
        self.results: List[Optional[str]] = [None] * len(lines)
        self.remaining = len(lines)
        self.queued = len(lines)        # units currently sitting in lanes
        self.queued_pages = 0           # page debt of those units (iteration)
        self.first_dispatch: Optional[float] = None
        self.timeout_handle = None
        # True once _on_request_done added this request's leftover queued
        # units to the scheduler's dead count. future.done() flips at
        # set_exception time but done-CALLBACKS run via call_soon — the
        # forming pass can sweep units in that gap, and must only deduct
        # from the dead count what the callback actually added.
        self.dead_accounted = False
        # observability (ISSUE 8): the request's trace id (client-given
        # or generated), its span tree handles (root/queue/dispatch —
        # None with the tracer disabled), and the caller's reply-metadata
        # dict (queue-wait vs service breakdown, filled at resolution)
        self.trace_id = ""
        self.span = None
        self.own_root = False       # this scheduler opened the root span
        self.q_span = None
        self.d_span = None
        self.meta: Optional[dict] = None
        # iteration-mode row breakdown (ISSUE 14), aggregated across
        # this request's rows and reported in the #trace reply
        # metadata: decode rounds participated (max over rows),
        # prefix-cache hits (replays + live forks), rows evicted with
        # a retriable error. Tracing-independent, like queue_s.
        self.rounds = 0
        self.prefix_hits = 0
        self.evictions_n = 0
        # streaming (ISSUE 16): transport callback for partial-token
        # delivery (#stream: clients; None = no streaming), and the
        # request's time-to-first-token, stamped at its FIRST partial
        self.on_partial: Optional[Callable[[int, str, int], None]] = None
        self.ttft: Optional[float] = None
        # multi-tenant fleet serving (ISSUE 20): the #model: tag this
        # request belongs to ("" = the single-model default). Batches
        # are formed single-tenant and routed through tenant_router;
        # fleet/accounting.py attributes KV-page owners through this
        # field (owner.req.tenant).
        self.tenant = ""


class _Unit:
    """One sentence of one request — the scheduling granule."""

    __slots__ = ("req", "idx", "text", "tokens", "pages", "row_span",
                 "rounds", "evict_reason", "partials_sent")

    def __init__(self, req: _Request, idx: int, text: str, tokens: int,
                 pages: int = 0):
        self.req = req
        self.idx = idx
        self.text = text
        self.tokens = tokens
        # KV-pool pages this sentence will claim (iteration mode's
        # admission currency; 0 in request mode)
        self.pages = pages
        # per-row decode tracing (ISSUE 14, iteration mode): the
        # serve.row span opened at join (None with tracing off), the
        # decode rounds this row participated in, and — when evicted —
        # why (quiesce / brownout / pool_exhausted / cancelled)
        self.row_span = None
        self.rounds = 0
        self.evict_reason: Optional[str] = None
        # streamed partial frames delivered for this row (#stream:);
        # the first one stamps ttft on the serve.row span
        self.partials_sent = 0


class ContinuousScheduler:
    def __init__(self, translate_lines: Callable[[List[str]], List[str]],
                 token_budget: int = 4096,
                 length_buckets: Sequence[int] = DEFAULT_LENGTH_BUCKETS,
                 batch_multiple: int = 8,
                 window_s: float = 0.002,
                 scan_limit: int = 512,
                 length_fn: Callable[[str], int] = default_length_fn,
                 registry: Optional[msm.Registry] = None,
                 executor: Optional[concurrent.futures.Executor] = None,
                 stall_timeout: float = 0.0,
                 version_fn: Optional[Callable[[], str]] = None,
                 batching_mode: str = "request",
                 engine=None,
                 engine_factory: Optional[Callable[[], object]] = None):
        self.translate_lines = translate_lines
        # --batching-mode (ISSUE 10): 'request' packs whole requests
        # into device batches (the PR 6 scheduler); 'iteration' moves
        # scheduling INSIDE the decode loop — the forming pass runs
        # every decode step against the paged KV pool's free pages, so
        # sentences join a RUNNING decode and finished ones leave it
        # (engine = translator/iteration.py::PagedDecodeEngine).
        if batching_mode not in ("request", "iteration"):
            raise ValueError(f"--batching-mode must be request or "
                             f"iteration, got {batching_mode!r}")
        if batching_mode == "iteration" and engine is None:
            raise ValueError("--batching-mode iteration needs a "
                             "PagedDecodeEngine (translate_lines alone "
                             "cannot join rows mid-decode)")
        self.batching_mode = batching_mode
        self.engine = engine
        # rebuilds the engine after a liveness trip (the wedged worker
        # thread owns the old engine's device state)
        self.engine_factory = engine_factory
        # model-version label source for the outcome counter; the
        # lifecycle SwapController installs its live_version_name here
        # so dashboards can pin an outcome shift to the exact hot-swap
        # that caused it (ISSUE 5). Read on the event-loop thread only.
        self.version_fn = version_fn or (lambda: "unversioned")
        # --dispatch-stall-timeout: liveness watchdog over each device
        # call (0 = off). See _translate_units / _trip_watchdog.
        self.stall_timeout = max(0.0, float(stall_timeout))
        # multi-tenant fleet serving (ISSUE 20), set by the server in
        # --fleet mode: tenant_router(tag) resolves (warming on demand)
        # the tenant's route for one batch — called on the DEVICE WORKER
        # thread so a cold start blocks only the batch that needs it;
        # tenant_version_fn(tag) labels outcomes per tenant. Both None
        # in single-model serving (tenant "" uses translate_lines).
        self.tenant_router: Optional[
            Callable[[str], Callable[[List[str]], List[str]]]] = None
        self.tenant_version_fn: Optional[Callable[[str], str]] = None
        self.token_budget = max(1, int(token_budget))
        self.length_buckets = length_buckets
        self.batch_multiple = batch_multiple
        # short coalescing pause before the FIRST batch of an idle period:
        # lets a burst of concurrent clients land in one device batch
        # (successor of the old fixed 5 ms window; once the queue is
        # non-empty the loop never sleeps — the device sets the cadence)
        self.window_s = window_s
        # bound on units examined per batch-forming pass, so one pass is
        # O(scan_limit) regardless of backlog depth
        self.scan_limit = scan_limit
        self.length_fn = length_fn
        # ONE device worker thread: the Translate driver's jit caches and
        # prefix state are not re-entrant, and the TPU program is serial
        # anyway — concurrency comes from batching, not threads.
        self._executor = executor or concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="serve-device")
        self._own_executor = executor is None
        # priority lanes: lane per priority value, highest served first.
        # Lanes are event-loop-thread-only; the COUNTERS below cross
        # threads (the metrics HTTP scrape thread samples queued_units via
        # the depth gauge's set_function) and carry a lock discipline that
        # mtlint's guarded-by checker enforces (docs/STATIC_ANALYSIS.md).
        self._lanes: Dict[int, Deque[_Unit]] = collections.defaultdict(
            collections.deque)
        self._state_lock = lockdep.make_lock(
            "ContinuousScheduler._state_lock")
        self._queued = 0                  # guarded-by: _state_lock
        # queue debt in KV-pool PAGES (iteration mode's admission
        # currency — a 500-token sentence owes more pool than a
        # 5-token one, which sentence counts cannot express)
        self._queued_pages = 0            # guarded-by: _state_lock
        self._dead_pages = 0              # guarded-by: _state_lock
        # units in lanes whose request already resolved (timed out /
        # cancelled / failed): still physically queued until the next
        # forming pass sweeps them, but DEAD — admission must not shed
        # live traffic against them (a timeout storm would otherwise
        # convert directly into a shed storm while a long device batch
        # keeps the worker busy)
        self._dead = 0                    # guarded-by: _state_lock
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._loop = None        # captured at start(); request_quiesce
        #                          wakes the worker cross-thread via it
        self._inflight = 0
        # pending quiesce operations (ISSUE 11), processed one at a
        # time by the iteration worker at round boundaries; appended
        # from any thread (the lifecycle watcher, admin verbs), hence
        # the lock
        self._quiesce_q: Deque[_QuiesceOp] = collections.deque()
        #                                   # guarded-by: _state_lock
        # brownout ladder effects (serving/brownout.py): the level is
        # written by the brownout evaluator thread and read per round;
        # a single int with no coupled invariant — no lock
        self._brownout_level = 0
        self._brownout_cap_factor = 0.5
        # lifecycle health hook (iteration mode): called after every
        # engine round with (error, device_s) so SwapController can
        # window per-version round health without owning the round loop
        self.round_observer: Optional[Callable[[bool, float], None]] = None
        # units currently on (or headed to) the device — loop-thread-only.
        # stop() fails their futures: a cancelled worker never returns
        # results for them, and their units left the lanes at forming
        # time, so the lane sweep alone would leave their clients hanging.
        self._inflight_units: List[_Unit] = []
        # iteration mode: units currently decoding in engine slots
        # (loop-thread-only; the engine holds the device-side rows)
        self._active_units: Dict[_Unit, None] = {}

        r = registry if registry is not None else msm.REGISTRY
        self._registry = r       # install_engine re-declares pool gauges
        self.m_requests = r.counter(
            "marian_serving_requests_total", "Requests submitted")
        self.m_queue_depth = r.gauge(
            "marian_serving_queue_depth_sentences",
            "Sentences currently queued (not yet in a device batch)")
        self.m_queue_depth.set_function(self.queued_units)
        self.m_batches = r.counter(
            "marian_serving_batches_total", "Device batches dispatched")
        self.m_batch_rows = r.histogram(
            "marian_serving_batch_rows", "Real sentences per device batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        self.m_fill = r.histogram(
            "marian_serving_batch_fill_ratio",
            "Real tokens / padded batch capacity per device batch",
            buckets=msm.RATIO_BUCKETS)
        self.m_waste = r.histogram(
            "marian_serving_padding_waste_ratio",
            "Padded tokens wasted per device batch (1 - fill ratio)",
            buckets=msm.RATIO_BUCKETS)
        self.m_ttfb = r.histogram(
            "marian_serving_time_to_first_batch_seconds",
            "Queue wait from request arrival to its first device batch")
        self.m_latency = r.histogram(
            "marian_serving_request_latency_seconds",
            "End-to-end request latency (submit to resolve)")
        self.m_timeouts = r.counter(
            "marian_serving_timeouts_total",
            "Requests failed by --request-timeout deadline expiry")
        self.m_cancelled = r.counter(
            "marian_serving_cancelled_total",
            "Requests cancelled by the client before completion")
        self.m_failures = r.counter(
            "marian_serving_failures_total",
            "Requests failed by translation errors")
        self.m_bisections = r.counter(
            "marian_serving_retry_bisections_total",
            "Failed-batch bisection retries (device calls re-issued)")
        self.m_watchdog = r.counter(
            "marian_serving_watchdog_trips_total",
            "Device batches failed by the dispatch stall watchdog "
            "(--dispatch-stall-timeout)")
        self.m_outcomes = r.counter(
            "marian_serving_request_outcomes_total",
            "Requests resolved, by outcome and the model version live at "
            "resolution time (ok|failure|timeout|cancelled|stalled|"
            "evicted — evicted is retriable row eviction: quiesce "
            "deadline, brownout, recoverable engine failure; excluded "
            "from the availability SLO like cancelled, because the "
            "client is told to retry and the retry's outcome counts)",
            labels=("outcome", "model_version"))
        # iteration-mode series (--batching-mode iteration): joins and
        # evictions happen PER DECODE STEP, not per batch — these are
        # the counters that prove mid-decode admission actually ran
        # (the loadgen A/B reads their deltas)
        self.m_joins = r.counter(
            "marian_serving_joins_total",
            "Sentences that joined a decode (iteration mode)")
        self.m_mid_joins = r.counter(
            "marian_serving_mid_decode_joins_total",
            "Sentences that joined a RUNNING decode step beside already-"
            "decoding rows (iteration mode)")
        self.m_evictions = r.counter(
            "marian_serving_evictions_total",
            "Mid-decode row evictions, all causes (request cancelled / "
            "timed out while decoding, quiesce deadline, brownout — the "
            "latter two also count in their dedicated series; iteration "
            "mode)")
        self.m_steps = r.counter(
            "marian_serving_decode_steps_total",
            "Decode steps run by the iteration-mode worker")
        self.m_step_rows = r.histogram(
            "marian_serving_step_active_rows",
            "Active decode rows per iteration-mode step (pre-bucket)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        self.m_queued_pages = r.gauge(
            "marian_serving_queue_depth_pages",
            "KV-pool pages owed by queued sentences (iteration mode's "
            "admission currency)")
        self.m_queued_pages.set_function(self.queued_pages)
        # quiesce + brownout series (ISSUE 11)
        self.m_quiesces = r.counter(
            "marian_serving_quiesces_total",
            "Quiesce operations completed (joins stopped, rows drained "
            "or evicted, engine re-pointed at a step boundary)")
        self.m_quiesce_evictions = r.counter(
            "marian_serving_quiesce_evictions_total",
            "Rows evicted with retriable !!SERVER-RETRY because the "
            "--quiesce-deadline expired before they drained")
        self.m_quiescing = r.gauge(
            "marian_serving_quiescing",
            "Quiesce operations pending/draining (joins are paused "
            "while this is > 0; back-to-back lifecycle verbs can queue "
            "more than one)")
        self.m_quiescing.set_function(self._quiesce_depth)
        self.m_brownout_evictions = r.counter(
            "marian_serving_brownout_evictions_total",
            "Rows evicted with retriable !!SERVER-RETRY by the brownout "
            "ladder (level >= 2) to free capacity for a higher-priority "
            "lane")
        # streaming series (ISSUE 16): #stream: clients get partial
        # target tokens as engine rounds complete (iteration mode)
        self.m_stream_partials = r.counter(
            "marian_stream_partials_total",
            "Partial-token frames delivered to streaming clients "
            "(#stream: protocol header, iteration mode)")
        self.m_stream_ttft = r.histogram(
            "marian_stream_ttft_seconds",
            "Time from request arrival to its first streamed partial "
            "token (#stream: clients; the streaming twin of "
            "time_to_first_batch, which measures join, not delivery)")

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        """Start the worker on the RUNNING loop (call from a coroutine)."""
        if self._task is None:
            self._loop = asyncio.get_event_loop()
            self._task = asyncio.ensure_future(self._run())

    async def stop(self) -> None:
        """Hard stop: cancel the worker; queued AND in-flight requests
        fail explicitly (never a silent hang)."""
        # capture before cancelling: _dispatch's finally clears the list
        # while the cancellation unwinds during `await self._task`
        pending = list(self._inflight_units) + list(self._active_units)
        self._active_units.clear()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._task = None
        for u in pending:
            if not u.req.future.done():
                u.req.future.set_exception(
                    RuntimeError("server shut down mid-batch"))
        for lane in self._lanes.values():
            for u in lane:
                # the unit leaves the lanes HERE: zero the request's
                # queued count so the set_exception done-callback (which
                # runs via call_soon AFTER stop returns and adds
                # req.queued to the dead count) cannot re-inflate the
                # counters we zero below — a reused scheduler would
                # otherwise under-report depth to admission forever
                u.req.queued = 0
                u.req.queued_pages = 0
                if not u.req.future.done():
                    u.req.future.set_exception(
                        RuntimeError("server shut down"))
            lane.clear()
        with self._state_lock:
            self._queued = 0
            self._dead = 0
            self._queued_pages = 0
            self._dead_pages = 0
            dangling = list(self._quiesce_q)
            self._quiesce_q.clear()
        for op in dangling:
            # release any thread blocked in request_quiesce(wait=True):
            # the loop is gone, the install will never run
            op.ok = False
            op.event.set()
        if self._own_executor:
            self._executor.shutdown(wait=False)

    async def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: finish everything queued/in flight, then
        stop. Pair with AdmissionController.begin_drain() so nothing new
        arrives. Returns True when fully drained, False on timeout."""
        loop = asyncio.get_event_loop()
        dl = loop.time() + timeout if timeout is not None else None

        def _done() -> bool:
            return (self._queue_size() == 0 and self._inflight == 0
                    and not self._active_units)

        while not _done():
            if dl is not None and loop.time() >= dl:
                await self.stop()
                return False
            self._wake.set()           # keep the worker moving
            await asyncio.sleep(0.005)
        await self.stop()
        return True

    # -- submission ---------------------------------------------------------
    def queued_units(self) -> int:
        """LIVE queued sentences — what admission and the depth gauge see
        (the gauge samples this from the metrics scrape THREAD, hence the
        lock). Dead units (resolved requests not yet swept from the lanes)
        are excluded, so expired backlog never sheds live traffic."""
        with self._state_lock:
            return max(0, self._queued - self._dead)

    def queued_pages(self) -> int:
        """LIVE queue debt in KV-pool pages (iteration mode; 0 in
        request mode) — what page-priced admission and the headroom
        gauge's queue-pressure input see. Sampled from the metrics
        scrape thread, hence the lock."""
        with self._state_lock:
            return max(0, self._queued_pages - self._dead_pages)

    def _queue_size(self) -> int:
        """Raw queued-unit count (live + dead) under the state lock."""
        with self._state_lock:
            return self._queued

    # -- quiesce protocol (ISSUE 11; iteration mode) ------------------------
    def _quiesce_depth(self) -> int:
        with self._state_lock:
            return len(self._quiesce_q)

    def _peek_quiesce(self) -> Optional[_QuiesceOp]:
        with self._state_lock:
            while self._quiesce_q and self._quiesce_q[0].cancelled:
                self._quiesce_q.popleft().event.set()
            return self._quiesce_q[0] if self._quiesce_q else None

    def cancel_quiesce(self, op: _QuiesceOp) -> None:
        """Withdraw a pending quiesce whose waiter gave up (wait budget
        exceeded): its install must not run late — the caller has
        already declared the re-point failed and may have released the
        target executor. A cancelled head is dropped at the next peek;
        an op already past its install cannot be recalled (the caller's
        event was set then)."""
        with self._state_lock:
            op.cancelled = True

    def request_quiesce(self, install: Callable[[], None],
                        deadline_s: float, reason: str,
                        wait: bool = True,
                        timeout: Optional[float] = None) -> _QuiesceOp:
        """Enqueue a quiesce: the iteration worker stops admitting joins,
        drains active rows until ``deadline_s`` (rows past it are evicted
        with retriable ``!!SERVER-RETRY`` and their pages freed), runs
        the pool audit, then calls ``install()`` at a step boundary with
        an empty join set (the only legal moment to re-point the engine)
        and resumes joins. Callable from ANY thread except — with
        ``wait=True`` — the event-loop thread itself (the loop is what
        executes the quiesce; waiting on it there would deadlock, which
        is why the lifecycle's rollback paths pass ``wait=False``).
        Returns the op; ``op.event``/``op.ok`` report completion."""
        op = _QuiesceOp(install, deadline_s, reason)
        with self._state_lock:
            self._quiesce_q.append(op)
        loop = self._loop
        if loop is not None:
            try:
                loop.call_soon_threadsafe(self._wake.set)
            except RuntimeError:   # loop already closed: stop() cleans up
                pass
        if wait:
            # bounded: drain deadline + generous slack for the install's
            # own work; a dead loop must not wedge the watcher forever
            op.event.wait(timeout if timeout is not None
                          else op.deadline_s + 30.0)
            if not op.event.is_set():
                # withdraw it: the caller will treat the re-point as
                # failed, so a LATE install (serving loop catching up
                # after the caller released the target) must not run
                self.cancel_quiesce(op)
                log.error("quiesce ({}) did not complete within its "
                          "wait budget — withdrawn; the serving loop "
                          "may be down", reason)
        return op

    def install_engine(self, engine) -> None:
        """Re-point the paged engine (the quiesce install callback is
        the only legitimate caller — loop thread, empty join set, zero
        active rows). Re-declares the pool gauges so the scrape tracks
        the NEW engine's pool, and re-applies the current brownout cap
        scale (a swap must not silently reset an active brownout)."""
        self.engine = engine
        decl = getattr(engine, "_declare_metrics", None)
        if decl is not None:
            decl(self._registry)
        scale_fn = getattr(engine, "set_cap_scale", None)
        if scale_fn is not None:
            scale_fn(self._brownout_cap_factor
                     if self._brownout_level >= 1 else 1.0)

    # -- brownout ladder effects (ISSUE 11; serving/brownout.py) ------------
    def set_brownout_level(self, level: int,
                           cap_factor: Optional[float] = None) -> None:
        """Apply one brownout level (called by the BrownoutController's
        evaluator thread): >= 1 tightens the decode cap of future joins,
        >= 2 arms the per-round priority eviction pass, >= 3 is enforced
        at admission (AdmissionController.set_brownout)."""
        if cap_factor is not None:
            self._brownout_cap_factor = float(cap_factor)
        self._brownout_level = max(0, int(level))
        engine = self.engine
        scale_fn = getattr(engine, "set_cap_scale", None) \
            if engine is not None else None
        if scale_fn is not None:
            scale_fn(self._brownout_cap_factor
                     if self._brownout_level >= 1 else 1.0)

    def submit(self, lines: List[str], priority: int = 0,
               timeout: Optional[float] = None,
               meta: Optional[dict] = None,
               trace_id: Optional[str] = None,
               on_partial: Optional[Callable[[int, str, int], None]]
               = None, tenant: str = "") -> "asyncio.Future":
        """Enqueue one request (a list of sentences); returns a future
        resolving to the list of translations in input order. Must be
        called from the event-loop thread (transports live there).
        Cancel the future to cancel the request.

        ``meta`` (optional dict) is filled at resolution time with the
        request's queue-wait vs service-time breakdown, outcome, model
        version and trace id — the transport prepends it to the reply
        for clients that asked (#trace protocol header; loadgen's
        client-side swap-blip attribution). ``trace_id`` labels the
        request's span tree; with the tracer enabled and no id given,
        one is generated (or inherited from the context's span).

        ``on_partial`` (iteration mode, #stream: clients) is called on
        the event-loop thread as ``on_partial(sentence_idx, text_so_far,
        n_tokens)`` every engine round a row of this request is still
        decoding; the future's resolution remains the FINAL reply. Never
        called after the future is done."""
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        now = loop.time()
        if not lines:
            # an empty request has nothing to queue: no unit would ever
            # complete it, so resolve NOW (PR 8 review: the future
            # previously hung forever without a timeout)
            self.m_requests.inc()
            fut.set_result([])
            self._outcome("ok")
            return fut
        deadline = now + timeout if timeout and timeout > 0 else None
        req = _Request(lines, fut, priority, now, deadline)
        req.meta = meta
        req.trace_id = trace_id or ""
        req.on_partial = on_partial
        req.tenant = tenant or ""
        if obs.enabled():
            # span tree: reuse the context's request-root span when the
            # transport opened one (server.handle_frame); open our own
            # root for direct scheduler callers (tests, embedders)
            parent = obs.current()
            if parent is None:
                req.span = obs.start_span(
                    "serve.request", trace_id=trace_id or None,
                    n_sentences=len(lines), priority=priority)
                req.own_root = True
            else:
                req.span = parent
            req.trace_id = req.span.trace_id
            req.q_span = obs.start_span("serve.queue", parent=req.span,
                                        n_sentences=len(lines))
        self.m_requests.inc()
        iteration = self.batching_mode == "iteration"
        with self._state_lock:
            for i, text in enumerate(lines):
                pages = (self.engine.pages_for_text(text) if iteration
                         else 0)
                u = _Unit(req, i, text, max(1, int(self.length_fn(text))),
                          pages=pages)
                self._lanes[priority].append(u)
                self._queued += 1
                self._queued_pages += pages
                req.queued_pages += pages
        if deadline is not None:
            # the deadline fires even if the unit is buried deep in the
            # backlog — a timed-out client gets its error ON TIME, and the
            # worker drops the dead units before they cost device work
            req.timeout_handle = loop.call_at(
                deadline, self._expire_request, req, loop)
        fut.add_done_callback(
            lambda f, _req=req: self._on_request_done(f, _req))
        self._wake.set()
        return fut

    def _version_label(self, req: Optional[_Request] = None) -> str:
        try:
            # fleet mode: a tenanted request labels with ITS tenant's
            # live version ("<tag>:<bundle>"), not the global one
            if req is not None and req.tenant \
                    and self.tenant_version_fn is not None:
                return str(self.tenant_version_fn(req.tenant))
            return str(self.version_fn())
        except Exception:  # noqa: BLE001 — labeling must never fail a reply
            return "unknown"

    def _outcome(self, outcome: str, req: Optional[_Request] = None,
                 now: Optional[float] = None) -> None:
        """One request resolved; label with the live model version so a
        swap-correlated outcome shift is visible per version. With
        ``req``, also finish its span tree and fill its reply-metadata
        dict (queue-wait vs service breakdown)."""
        version = self._version_label(req)
        self.m_outcomes.labels(outcome, version).inc()
        if req is None:
            return
        if now is None:
            try:
                now = asyncio.get_event_loop().time()
            except RuntimeError:  # pragma: no cover — loop gone at teardown
                now = req.arrival
        fd = req.first_dispatch
        queue_s = max(0.0, (fd if fd is not None else now) - req.arrival)
        service_s = max(0.0, now - fd) if fd is not None else 0.0
        if req.meta is not None:
            req.meta.update(trace_id=req.trace_id, outcome=outcome,
                            model_version=version,
                            queue_s=round(queue_s, 6),
                            service_s=round(service_s, 6))
            if self.batching_mode == "iteration":
                # the row breakdown (ISSUE 14): rounds participated
                # (max over this request's rows), time-to-first-join
                # (-1 = never joined a decode), prefix-cache hit flag,
                # retriable row evictions suffered
                req.meta.update(
                    rounds=req.rounds,
                    ttfj_ms=round(queue_s * 1e3, 1) if fd is not None
                    else -1.0,
                    prefix_hit=int(req.prefix_hits > 0),
                    evictions=req.evictions_n)
        if req.d_span is not None:
            obs.end(req.d_span, outcome=outcome, model_version=version)
            req.d_span = None
        if req.q_span is not None:       # resolved while still queued
            obs.end(req.q_span, outcome=outcome)
            req.q_span = None
        if req.own_root and req.span is not None:
            obs.end(req.span, outcome=outcome, model_version=version)
            req.span = None

    def _expire_request(self, req: _Request, loop) -> None:
        if not req.future.done():
            self.m_timeouts.inc()
            self._outcome("timeout", req, loop.time())
            req.future.set_exception(RequestTimeout(
                f"request deadline expired after "
                f"{(loop.time() - req.arrival):.3f}s "
                f"({req.remaining}/{len(req.lines)} sentences unfinished)"))

    def _on_request_done(self, fut: "asyncio.Future", req: _Request) -> None:
        if fut.cancelled():
            self.m_cancelled.inc()
            self._outcome("cancelled", req)
        # any units of this request still sitting in lanes are dead until
        # the next forming pass physically sweeps them — discount them
        # from the admission-visible depth IMMEDIATELY (a normal
        # completion has req.queued == 0, so this is a no-op there).
        # req.queued is read inside the lock: a forming pass that swept
        # units between set_exception and this callback already lowered
        # it, so the count added here is exactly the units still in lanes.
        with self._state_lock:
            req.dead_accounted = True
            self._dead += req.queued
            self._dead_pages += req.queued_pages

    # -- worker -------------------------------------------------------------
    async def _run(self) -> None:
        if self.batching_mode == "iteration":
            await self._run_iteration()
            return
        loop = asyncio.get_event_loop()
        while True:
            try:
                was_idle = False
                while self._queue_size() == 0:
                    self._wake.clear()
                    was_idle = True
                    await self._wake.wait()
                if was_idle and self.window_s > 0:
                    # idle-edge coalescing pause only; under sustained load
                    # the previous batch's device time IS the window
                    await asyncio.sleep(self.window_s)
                t_form = time.perf_counter() if obs.enabled() else 0.0
                batch = self._form_batch(loop.time())
                if not batch:
                    continue
                # batch-formation cost rides the batch span as an attr
                # (the forming pass runs under the state lock — no spans
                # from inside it; timed from out here instead)
                form_s = (time.perf_counter() - t_form) if t_form else 0.0
                await self._dispatch(batch, loop, form_s)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — supervision: never die
                log.error("serving scheduler error (recovered): {}", e)

    def _form_batch(self, now: float) -> List[_Unit]:
        """Pack one device batch: seed with the oldest live unit of the
        highest non-empty priority lane, then top up with queued units
        (same lane order) that fit the padded-token budget. Units of
        already-resolved requests (cancelled / timed out / failed) are
        discarded here, before they cost device time.

        Runs entirely under the state lock: one forming pass is bounded
        CPU-only work (O(scan_limit), no awaits), and the counters it
        rebalances must never be observed mid-pass by the metrics scrape
        thread or admission."""
        batch: List[_Unit] = []
        width = 0
        scanned = 0
        tenant: Optional[str] = None
        skipped: List[_Unit] = []
        with self._state_lock:
            for prio in sorted(self._lanes.keys(), reverse=True):
                lane = self._lanes[prio]
                while lane and scanned < self.scan_limit:
                    u = lane.popleft()
                    # dead sweeps count toward the scan bound too: a
                    # timeout storm in unbounded-queue mode must not turn
                    # one forming pass into an O(backlog) stall under the
                    # state lock
                    scanned += 1
                    self._queued -= 1
                    self._queued_pages -= u.pages
                    u.req.queued -= 1
                    u.req.queued_pages -= u.pages
                    if u.req.future.done():
                        if u.req.dead_accounted:
                            # drop a dead unit the done-callback counted;
                            # if the callback hasn't run yet it will see
                            # the already-lowered req.queued instead
                            self._dead -= 1
                            self._dead_pages -= u.pages
                        continue
                    # fleet mode (ISSUE 20): batches are SINGLE-tenant —
                    # one device call serves one model. The first live
                    # unit seeds the batch's tenant; other tenants' units
                    # keep FIFO order for the next pass via skipped
                    if tenant is None:
                        tenant = u.req.tenant
                    elif u.req.tenant != tenant:
                        skipped.append(u)
                        continue
                    new_width = max(width, bucket_length(u.tokens,
                                                         self.length_buckets))
                    # fit check on UNPADDED rows x bucketed width — the
                    # exact budget semantics of training's _split_maxi, so
                    # serving batches land on the shape grid the jit cache
                    # was warmed on. Row snap-up to batch_multiple can pad
                    # the realized device batch past the budget by
                    # < batch_multiple rows (same as training;
                    # --mini-batch-words has always meant real rows, not
                    # padded rows).
                    if batch and (len(batch) + 1) * new_width \
                            > self.token_budget:
                        # does not fit — keep scanning: a shorter unit
                        # further back may still fit this batch's width
                        skipped.append(u)
                        continue
                    batch.append(u)
                    width = new_width
                if scanned >= self.scan_limit:
                    break
            # skipped units go back to the FRONT of their lanes in order,
            # so FIFO is preserved for the next batch
            for u in reversed(skipped):
                self._lanes[u.req.priority].appendleft(u)
                self._queued += 1
                self._queued_pages += u.pages
                u.req.queued += 1
                u.req.queued_pages += u.pages
        return batch

    # -- iteration mode (ISSUE 10) ------------------------------------------
    async def _run_iteration(self) -> None:
        """Scheduling INSIDE the decode loop: every round is one decode
        step of the paged engine, preceded by a join pass that admits
        queued sentences against the pool's free pages. Finished rows
        resolve per step; the device never idles behind a draining
        batch, and a sentence never waits for one."""
        loop = asyncio.get_event_loop()
        while True:
            try:
                was_idle = False
                while self._queue_size() == 0 and not self._active_units \
                        and self._quiesce_depth() == 0:
                    self._wake.clear()
                    was_idle = True
                    await self._wake.wait()
                if was_idle and self.window_s > 0:
                    await asyncio.sleep(self.window_s)
                await self._iteration_round(loop)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — supervision: never die
                log.error("serving scheduler error (recovered): {}", e)

    def _form_join_set(self) -> List[_Unit]:
        """The iteration-mode forming pass: it runs EVERY decode step
        and packs against the pool's free pages + slots, not a token
        budget — a sentence joins the moment capacity exists. Same lane
        order, dead-unit sweep and scan bound as _form_batch."""
        joins: List[_Unit] = []
        budget_pages = self.engine.free_pages()
        budget_slots = self.engine.free_slots()
        scanned = 0
        skipped: List[_Unit] = []
        with self._state_lock:
            for prio in sorted(self._lanes.keys(), reverse=True):
                lane = self._lanes[prio]
                while lane and scanned < self.scan_limit:
                    u = lane.popleft()
                    scanned += 1
                    self._queued -= 1
                    self._queued_pages -= u.pages
                    u.req.queued -= 1
                    u.req.queued_pages -= u.pages
                    if u.req.future.done():
                        if u.req.dead_accounted:
                            self._dead -= 1
                            self._dead_pages -= u.pages
                        continue
                    if u.pages > self.engine.pool.usable_pages:
                        # estimate says this sentence can NEVER fit the
                        # pool: hand it to the engine anyway (outside
                        # the budget) — it re-measures with the real
                        # vocab encoding and either admits or FATALLY
                        # rejects. Skipping it here would park it at
                        # the queue head forever (livelock).
                        joins.append(u)
                        continue
                    if len(joins) >= budget_slots \
                            or u.pages > budget_pages:
                        skipped.append(u)
                        continue
                    budget_pages -= u.pages
                    joins.append(u)
                if scanned >= self.scan_limit:
                    break
            for u in reversed(skipped):
                self._lanes[u.req.priority].appendleft(u)
                self._queued += 1
                self._queued_pages += u.pages
                u.req.queued += 1
                u.req.queued_pages += u.pages
        return joins

    def _requeue_front(self, u: _Unit) -> None:
        """Return a join-rejected unit to the FRONT of its lane (the
        engine's claim re-check lost a capacity race — FIFO preserved)."""
        with self._state_lock:
            self._lanes[u.req.priority].appendleft(u)
            self._queued += 1
            self._queued_pages += u.pages
            u.req.queued += 1
            u.req.queued_pages += u.pages
            if u.req.future.done() and u.req.dead_accounted:
                # died between pop and requeue: restore the dead count
                # the done-callback could no longer see
                self._dead += 1
                self._dead_pages += u.pages

    def _fail_unit(self, u: _Unit, loop, message: str) -> None:
        if u.req.future.done():
            return
        self.m_failures.inc()
        self._outcome("failure", u.req, loop.time())
        log.error("iteration admission: {}", message)
        u.req.future.set_exception(RuntimeError(message))

    def _mark_joined(self, u: _Unit, now: float, rows_before: int,
                     bucket: int = 0) -> None:
        """A sentence entered the decode. queue_ms STOPS HERE — at join
        time, not at some enclosing batch's dispatch time: a sentence
        joining a running decode must not inherit the running rows'
        deadline/queue accounting (ISSUE 10 small fix; the #trace
        breakdown regression test pins it)."""
        self._active_units[u] = None
        self.m_joins.inc()
        if rows_before > 0:
            self.m_mid_joins.inc()
        req = u.req
        if req.first_dispatch is None:
            req.first_dispatch = now
            self.m_ttfb.observe(now - req.arrival,
                                trace_id=req.trace_id or None)
            if req.q_span is not None:
                obs.end(req.q_span)
                req.q_span = None
                req.d_span = obs.start_span(
                    "serve.dispatch", parent=req.span,
                    joined_mid_decode=rows_before > 0)
        if obs.enabled():
            # per-row lifecycle span (ISSUE 14): one serve.row per
            # sentence under the request root, opened at join with the
            # time-to-first-join and the compiled bucket the joining
            # round ran at; closed at EOS / evict / cancel with the
            # rounds count (serve.round spans cross-link back via
            # their `traces` attr)
            u.row_span = obs.start_span(
                "serve.row", parent=req.span,
                trace_id=req.trace_id or None,
                sentence=u.idx, bucket=bucket,
                mid_decode=rows_before > 0,
                ttfj_ms=round((now - req.arrival) * 1e3, 2))

    def _end_row_span(self, u: _Unit, outcome: str, **attrs) -> None:
        """Close one row's lifecycle: fold its rounds count into the
        request aggregate (the #trace row breakdown) and end its
        serve.row span if one was opened."""
        req = u.req
        if u.rounds > req.rounds:
            req.rounds = u.rounds
        sp = u.row_span
        if sp is not None:
            u.row_span = None
            obs.end(sp, outcome=outcome, rounds=u.rounds, **attrs)

    async def _iteration_round(self, loop) -> None:
        """One join-pass + decode-step round on the device worker. With
        a quiesce pending (ISSUE 11) the join set is EMPTY: active rows
        drain until the deadline, overdue rows are evicted with
        retriable errors, and once the engine is empty the op's install
        re-points it before joins resume."""
        engine = self.engine
        q = self._peek_quiesce()
        if q is not None and q.deadline is None:
            q.t0 = loop.time()
            q.deadline = q.t0 + q.deadline_s
            obs.event("quiesce.begin", reason=q.reason,
                      rows=len(self._active_units),
                      deadline_s=q.deadline_s)
            log.info("quiesce ({}): joins paused, draining {} active "
                     "row(s) under a {}s deadline", q.reason,
                     len(self._active_units), q.deadline_s)
        joins = [] if q is not None else self._form_join_set()
        evicts = [u for u in list(self._active_units)
                  if u.req.future.done()]
        if q is None and self._brownout_level >= 2:
            evicts.extend(self._brownout_victims(loop, evicts))
        if q is not None and loop.time() >= q.deadline:
            # quiesce deadline expired: the rows still decoding leave
            # NOW with a retriable error (their pages are freed by the
            # eviction below) — a swap is never held hostage by one
            # long sentence
            for u in list(self._active_units):
                if u in evicts:
                    continue
                u.evict_reason = "quiesce"
                self._evict_with_retry(
                    u, loop,
                    f"row evicted at the quiesce deadline "
                    f"({q.reason})")
                self.m_quiesce_evictions.inc()
                q.evicted += 1
                evicts.append(u)
        rows_before = engine.active_rows()
        if q is not None and not joins and not evicts \
                and not self._active_units:
            # drained (or never had rows): complete the quiesce without
            # burning a device round
            self._finish_quiesce(q, loop)
            return
        # queue_ms stops at JOIN time: stamp accepted units with the
        # round's start, not with a post-step timestamp that would bill
        # the step (and any jit warmup) as queueing
        t_round = loop.time()
        # serve.round span (ISSUE 14): one per engine round, its OWN
        # trace (a round serves many rows); participating rows cross-
        # link via the `traces` attr set at end, like serve.batch
        rspan = None
        if obs.enabled():
            rspan = obs.start_span(
                "serve.round", rows_before=rows_before,
                joins=len(joins), evicts=len(evicts),
                quiescing=q is not None)
        self._inflight += 1
        try:
            fp.fault_point("serving.dispatch")
            # per-row join metadata rides into the engine's claim: the
            # request-local sentence id (n-best numbering) and whether
            # the client asked for streamed partials (#stream:)
            payload = [(u, u.text,
                        {"sid": u.idx,
                         "stream": u.req.on_partial is not None})
                       for u in joins]

            def _round():
                fp.fault_point("serving.translate")
                return engine.admit_and_step(payload, evicts)

            call = loop.run_in_executor(self._executor, _round)
            if self.stall_timeout > 0:
                try:
                    res = await asyncio.wait_for(asyncio.shield(call),
                                                 self.stall_timeout)
                except asyncio.TimeoutError:
                    self._iteration_stalled(call, joins, loop)
                    obs.end(rspan, outcome="stalled")
                    return
            else:
                res = await call
        except asyncio.CancelledError:
            obs.end(rspan, outcome="cancelled")
            raise
        except Exception as e:  # noqa: BLE001
            # an engine-round failure has no per-sentence bisection (the
            # step computes all rows jointly): fail the round's requests
            # explicitly and rebuild the engine if a factory was given
            self._iteration_failed(joins, loop, e)
            obs.end(rspan, outcome="failed", error=str(e)[:200])
            return
        finally:
            self._inflight -= 1
        for u in evicts:
            if u in self._active_units:
                del self._active_units[u]
                self.m_evictions.inc()
                self._end_row_span(u, u.evict_reason or "cancelled",
                                   retriable=u.evict_reason is not None)
        for u in res.accepted:
            self._mark_joined(u, t_round, rows_before, res.bucket)
        if res.rows:
            # count the round for every row that rode this device step
            # (rows finishing this round are still active here). The
            # request aggregate updates HERE, not only at row end: an
            # eviction fills the reply metadata via _outcome before the
            # row's span closes, and must see the rounds already run
            for u in self._active_units:
                u.rounds += 1
                if u.rounds > u.req.rounds:
                    u.req.rounds = u.rounds
        # per-row lifecycle instants the engine reported (prefix hits /
        # COW forks — ISSUE 14): fold into the request's reply-metadata
        # counters always, and onto the timeline/row spans when tracing
        for key, name, attrs in res.row_events:
            u = key if isinstance(key, _Unit) else None
            if u is not None:
                if name.startswith("prefix."):
                    u.req.prefix_hits += 1
                if name == "prefix.fork" and u.row_span is not None:
                    u.row_span.set_attrs(prefix_fork=True, **attrs)
                if obs.enabled():
                    obs.event(name, trace=u.req.trace_id, **attrs)
            elif obs.enabled():
                obs.event(name, **attrs)
        from ..translator.iteration import FATAL_REASONS
        requeue: List[_Unit] = []
        for u, why in res.rejected:
            if why in FATAL_REASONS:
                # operator-actionable rejection: the engine computed the
                # page requirement — the error must say it, not leave
                # the operator guessing which knob to turn (ISSUE 11)
                detail = res.reject_detail.get(
                    u, "exceeds the engine's source cap or the whole "
                       "KV pool")
                self._fail_unit(
                    u, loop,
                    f"sentence cannot be admitted ({why}): {detail}")
            else:
                requeue.append(u)
        # appendleft in REVERSE so the lane keeps FIFO order across
        # rejection rounds (same discipline as _form_batch's skipped
        # path) — forward order would swap same-priority units every
        # round and starve the earliest request under pool pressure
        for u in reversed(requeue):
            self._requeue_front(u)
        # lazy COW claims (beam>1 divergence) that found the pool dry
        # evicted their sentence mid-decode: retriable by contract —
        # the pool is healthy, the resend lands once pressure passes
        for u in getattr(res, "pool_evicted", ()) or ():
            if u in self._active_units:
                del self._active_units[u]
                self.m_evictions.inc()
                u.evict_reason = "pool_exhausted"
                self._end_row_span(u, "pool_exhausted", retriable=True)
                self._evict_with_retry(
                    u, loop, "row evicted: KV pool exhausted mid-decode "
                             "(copy-on-write beam divergence)")
        # streaming fan-out (ISSUE 16): every still-decoding row of a
        # #stream: request delivers its text-so-far as one partial
        # frame per round; the FIRST partial stamps ttft. Rows that
        # finished this round are not in res.partials — the final
        # reply below is always the last frame a client sees.
        for u, text, ntok in getattr(res, "partials", ()) or ():
            req = getattr(u, "req", None)
            if req is None or req.future.done() \
                    or req.on_partial is None:
                continue
            now_p = loop.time()
            if u.partials_sent == 0 and u.row_span is not None:
                u.row_span.set_attrs(
                    ttft_ms=round((now_p - req.arrival) * 1e3, 2))
            if req.ttft is None:
                req.ttft = now_p - req.arrival
                self.m_stream_ttft.observe(
                    req.ttft, trace_id=req.trace_id or None)
            u.partials_sent += 1
            self.m_stream_partials.inc()
            try:
                req.on_partial(u.idx, text, ntok)
            except Exception as e:  # noqa: BLE001 — a broken client
                log.warn("stream partial delivery failed: {}", e)
                req.on_partial = None     # stream must never kill rounds
        src_done = 0
        for u, text in res.finished:
            self._active_units.pop(u, None)
            src_done += u.tokens
            self._end_row_span(u, "eos")
            self._complete_unit(u, text, loop)
        if res.rows:
            self.m_steps.inc(max(1, res.steps))
            self.m_step_rows.observe(res.rows)
            self.m_batches.inc()     # a step IS the device-batch unit here
            self.m_batch_rows.observe(res.rows)
            if obs.PERF.enabled:
                # PER-STEP device-seconds attribution: rows of different
                # ages share a step, so chip-seconds/token integrates
                # step cost over the tokens THIS step emitted (src
                # tokens credit at sentence completion, like request
                # mode credits on delivery)
                # the round's compile key is the (row bucket, encode
                # width, steps) TRIPLE, not the padded width — pass the
                # round key so an unwarmed engine shape fires the
                # steady-state recompile incident (ISSUE 17). res.steps
                # is live for fused-merge beam rounds too (ISSUE 18):
                # the beam scan covers --iteration-steps steps per
                # dispatch, so beam keys read r{block·k}.w{w}.s{steps}
                obs.PERF.record_batch(
                    self._version_label(), rows=res.rows,
                    width=res.bucket, src_tokens=src_done,
                    trg_tokens=res.tokens, device_s=res.device_s,
                    bucket_key=obs.perf.round_bucket_key(
                        res.bucket, res.enc_bucket, res.steps))
        if rspan is not None:
            # rows that finished this round already left _active_units;
            # their trace ids still belong on the round's cross-links
            traces = {u.req.trace_id for u in self._active_units
                      if u.req.trace_id}
            traces.update(u.req.trace_id for u, _ in res.finished
                          if u.req.trace_id)
            obs.end(
                rspan, outcome="ok", rows=res.rows, bucket=res.bucket,
                steps=res.steps, tokens=res.tokens,
                joined=len(res.accepted), left=len(res.finished),
                pool_evicted=len(res.pool_evicted),
                pages_claimed=res.pages_claimed,
                pages_freed=res.pages_freed,
                pages_aliased=res.pages_aliased,
                pages_copied=res.pages_copied,
                device_s=round(res.device_s, 6),
                traces=sorted(traces))
        self._notify_round(False, res.device_s)
        if q is not None and not self._active_units:
            self._finish_quiesce(q, loop)

    def _finish_quiesce(self, q: _QuiesceOp, loop) -> None:
        """The engine reached an empty join set with zero active rows:
        audit the outgoing engine (zero leaked pages is the contract),
        run the install (which may re-point self.engine), audit the
        incoming engine, resume joins. The serving.quiesce fault point
        sits BEFORE the install — kill mode is the kill-mid-quiesce
        chaos schedule (scripts/chaos.py --iteration)."""
        fp.fault_point("serving.quiesce")
        if q.cancelled:
            # the waiter gave up and withdrew the op mid-drain: do NOT
            # install (the target may already be released); just resume
            with self._state_lock:
                if self._quiesce_q and self._quiesce_q[0] is q:
                    self._quiesce_q.popleft()
            obs.event("quiesce.cancelled", reason=q.reason,
                      evicted=q.evicted)
            q.event.set()
            self._wake.set()
            return
        old = self.engine
        pre = self._audit_engine(old, "quiesce-drain")
        install_ok = True
        try:
            q.install()
        except Exception as e:  # noqa: BLE001 — a failed install keeps
            # the drained (but healthy) old engine serving; the caller
            # learns via op.ok and decides (the lifecycle fails the
            # candidate)
            install_ok = False
            log.error("quiesce ({}): install failed ({}); the previous "
                      "engine keeps serving", q.reason, e)
        post = [] if self.engine is old \
            else self._audit_engine(self.engine, "quiesce-install")
        q.install_ok = install_ok
        q.ok = install_ok and not pre and not post
        with self._state_lock:
            if self._quiesce_q and self._quiesce_q[0] is q:
                self._quiesce_q.popleft()
        self.m_quiesces.inc()
        obs.event("quiesce.complete", reason=q.reason, ok=q.ok,
                  evicted=q.evicted, install_ok=install_ok,
                  audit_violations=len(pre) + len(post),
                  duration_ms=round((loop.time() - q.t0) * 1e3, 1))
        if not q.ok:
            # an unhealthy quiesce (failed install or audit violations)
            # is a pool incident: dump — the flight recorder's `pool`
            # provider embeds the page map at this exact moment
            # (ISSUE 14)
            obs.FLIGHT.trip_async(
                "quiesce",
                detail=f"quiesce ({q.reason}) completed unhealthily: "
                       f"install_ok={install_ok}, "
                       f"{len(pre) + len(post)} audit violation(s)")
        log.info("quiesce ({}): complete in {:.0f}ms — {} row(s) "
                 "evicted with retry, audit {} ({} violation(s))",
                 q.reason, (loop.time() - q.t0) * 1e3, q.evicted,
                 "clean" if not (pre or post) else "FAILED",
                 len(pre) + len(post))
        q.event.set()
        self._wake.set()           # joins resume immediately

    @staticmethod
    def _audit_engine(engine, context: str) -> List[str]:
        """Run the engine's pool auditor if it has one (stub engines in
        tests may not); violations are already reported by the engine."""
        audit = getattr(engine, "audit", None)
        if audit is None:
            return []
        try:
            return list(audit(context=context))
        except TypeError:
            return list(audit())

    def _evict_with_retry(self, u: _Unit, loop, msg: str) -> None:
        """Fail one decoding row's request with the retriable RowEvicted
        (transports reply !!SERVER-RETRY); the row itself leaves the
        engine via the caller's evict list, freeing its pages."""
        if u.req.future.done():
            return
        # count the eviction BEFORE _outcome fills the reply metadata,
        # so the client's row breakdown includes this one (ISSUE 14)
        u.req.evictions_n += 1
        self._outcome("evicted", u.req, loop.time())
        u.req.future.set_exception(RowEvicted(msg + " — retry"))

    def _notify_round(self, error: bool, device_s: float) -> None:
        """Report one engine round's health to the lifecycle observer
        (SwapController windows these per version for canary promotion
        and live auto-rollback in iteration mode)."""
        fn = self.round_observer
        if fn is None:
            return
        try:
            fn(error, device_s)
        except Exception as e:  # noqa: BLE001 — health accounting must
            log.warn("round observer failed: {}", e)   # never kill rounds

    def _brownout_victims(self, loop, exclude: List[_Unit]) -> List[_Unit]:
        """Brownout level >= 2: when queued work outranks a decoding
        row and could not join this round, evict the lowest-priority
        active row (tie-break: longest remaining decode) with a
        retriable error — one per round, so the ladder degrades
        gradually and predictably rather than mass-evicting."""
        if self.queued_units() <= 0:
            return []
        with self._state_lock:
            top = max((p for p, lane in self._lanes.items() if lane),
                      default=None)
        if top is None:
            return []
        victims = [u for u in self._active_units
                   if u not in exclude and not u.req.future.done()
                   and u.req.priority < top]
        if not victims:
            return []

        def score(u: _Unit):
            prog = None
            fn = getattr(self.engine, "row_progress", None)
            if fn is not None:
                prog = fn(u)
            remaining = (prog[1] - prog[0]) if prog else 0
            return (u.req.priority, -remaining)

        worst = min(victims, key=score)
        worst.evict_reason = "brownout"
        self._evict_with_retry(
            worst, loop,
            f"row evicted under brownout (level "
            f"{self._brownout_level}) to free capacity for priority "
            f"{top} traffic")
        self.m_brownout_evictions.inc()
        obs.event("brownout.evict", victim_priority=worst.req.priority,
                  queued_priority=top)
        return [worst]

    def _iteration_stalled(self, call, joins: List[_Unit], loop) -> None:
        """The engine round exceeded --dispatch-stall-timeout. Fail every
        involved request retriably, abandon the wedged worker (with the
        old engine's device state) and rebuild via engine_factory.
        (The caller's finally still runs — inflight bookkeeping stays
        with the caller.)"""
        victims = list(self._active_units) + joins
        self._active_units.clear()
        self._trip_watchdog(call, len(victims))
        now = loop.time()
        for u in victims:
            self._end_row_span(u, "stalled", retriable=True)
            if not u.req.future.done():
                self._outcome("stalled", u.req, now)
                u.req.future.set_exception(DispatchStalled(
                    f"decode step stalled past {self.stall_timeout}s — "
                    f"retry"))
        obs.event("serve.watchdog_trip", rows=len(victims),
                  stall_timeout=self.stall_timeout, mode="iteration")
        obs.FLIGHT.trip_async(
            "watchdog",
            detail=f"iteration decode step ({len(victims)} sentences) "
                   f"stalled past {self.stall_timeout}s")
        self._notify_round(True, self.stall_timeout)
        if self.engine_factory is not None:
            try:
                # install_engine, not a bare assignment: the rebuilt
                # engine must inherit the brownout cap scale and take
                # over the pool gauges (the wedged engine's pool would
                # otherwise keep feeding the scrape)
                self.install_engine(self.engine_factory())
            except Exception as e:  # noqa: BLE001
                log.error("engine rebuild after stall failed: {}", e)

    def _iteration_failed(self, joins: List[_Unit], loop, exc) -> None:
        victims = list(self._active_units) + joins
        self._active_units.clear()
        log.error("iteration decode round failed ({} sentences): {}",
                  len(victims), exc)
        now = loop.time()
        # with a recovery path armed (engine_factory rebuild, or the
        # lifecycle observer that can roll back to a warm engine) the
        # victims' requests are retriable by construction — a resend
        # lands on a healthy engine. Without one, fail loud (the
        # documented no-bisection iteration contract).
        retriable = bool(getattr(exc, "retriable", False)) \
            or self.engine_factory is not None \
            or self.round_observer is not None
        for u in victims:
            self._end_row_span(u, "round_failed", retriable=retriable)
            if not u.req.future.done():
                if retriable:
                    self._evict_with_retry(
                        u, loop,
                        f"row evicted: decode round failed ({exc})")
                else:
                    self.m_failures.inc()
                    self._outcome("failure", u.req, now)
                    u.req.future.set_exception(RuntimeError(str(exc)))
        self._notify_round(True, 0.0)
        if self.engine_factory is not None and self._quiesce_depth() == 0:
            # the observer may have just initiated recovery itself (a
            # lifecycle rollback enqueues a quiesce re-point to the warm
            # previous engine) — rebuilding on top of that would load a
            # whole model on the event loop only to be replaced one
            # round later
            try:
                self.install_engine(self.engine_factory())
            except Exception as e:  # noqa: BLE001
                log.error("engine rebuild after failure failed: {}", e)

    async def _dispatch(self, units: List[_Unit], loop,
                        form_s: float = 0.0) -> None:
        self._inflight += 1
        self._inflight_units = list(units)
        bspan = None
        # [device seconds, real target tokens, src tokens delivered] for
        # this batch, summed across bisection retries on the device
        # worker thread (ISSUE 9: obs/perf.py — the happens-before is
        # the executor future)
        dev_acc = [0.0, 0.0, 0.0] if obs.PERF.enabled else None
        try:
            now = loop.time()
            rows = len(units)
            real_tokens = sum(u.tokens for u in units)
            width = max(bucket_length(u.tokens, self.length_buckets)
                        for u in units)
            capacity = padded_batch_cost(rows, width, self.length_buckets,
                                         self.batch_multiple)
            fill = min(1.0, real_tokens / max(capacity, 1))
            self.m_batches.inc()
            self.m_batch_rows.observe(rows)
            self.m_fill.observe(fill)
            self.m_waste.observe(1.0 - fill)
            if obs.enabled():
                # batch-level span: its OWN trace (a batch serves many
                # requests); member request trace ids ride as attrs and
                # each member's serve.dispatch span back-references the
                # batch span id, so the tree is walkable both ways
                bspan = obs.start_span(
                    "serve.batch", rows=rows, width=width,
                    fill=round(fill, 4),
                    form_ms=round(form_s * 1e3, 3),
                    traces=sorted({u.req.trace_id for u in units
                                   if u.req.trace_id}))
            seen: set = set()
            for u in units:
                if id(u.req) in seen:     # one request, many sentences
                    continue
                seen.add(id(u.req))
                if u.req.first_dispatch is None:
                    u.req.first_dispatch = now
                    self.m_ttfb.observe(now - u.req.arrival,
                                        trace_id=u.req.trace_id or None)
                    if u.req.q_span is not None:
                        obs.end(u.req.q_span)
                        u.req.q_span = None
                        u.req.d_span = obs.start_span(
                            "serve.dispatch", parent=u.req.span,
                            batch_span=bspan.span_id if bspan else "",
                            rows=rows)
                elif bspan is not None and u.req.d_span is not None:
                    # a LATER batch of a request split across batches
                    u.req.d_span.attrs["batches"] = \
                        u.req.d_span.attrs.get("batches", 1) + 1
            await self._translate_units(units, loop, bspan, dev_acc)
            if dev_acc is not None:
                # live perf/capacity accounting (obs/perf.py): device
                # seconds are measured to the host-side result fence on
                # the worker thread — translate_lines returns host
                # strings, so the return IS the drain — and include bisection
                # retries: poison isolation costs real device time
                obs.PERF.record_batch(
                    self._version_label(), rows=rows, width=width,
                    src_tokens=int(dev_acc[2]), trg_tokens=int(dev_acc[1]),
                    device_s=dev_acc[0])
        finally:
            if bspan is not None:
                if dev_acc is not None:
                    bspan.attrs["device_s"] = round(dev_acc[0], 6)
                obs.end(bspan)
            self._inflight -= 1
            self._inflight_units = []

    def _trip_watchdog(self, pending: "asyncio.Future", n_rows: int) -> None:
        """The in-flight device call exceeded --dispatch-stall-timeout.
        The stuck call cannot be killed (a thread wedged inside a device
        runtime has no cancellation point) — what CAN be saved is the
        scheduler: abandon the wedged worker thread to finish (or not) on
        its own, log if it ever does, and point the executor handle at a
        fresh single worker so subsequent batches keep serving."""
        self.m_watchdog.inc()
        log.error(
            "DISPATCH WATCHDOG: device batch ({} sentences) still running "
            "after {}s — failing its requests with a retriable error and "
            "replacing the device worker (the stuck thread is abandoned; "
            "see docs/ROBUSTNESS.md)", n_rows, self.stall_timeout)

        def _late(f) -> None:
            if f.cancelled():
                return
            exc = f.exception()
            log.warn("watchdog-abandoned device batch eventually {} — "
                     "its results were discarded",
                     f"failed: {exc}" if exc else "completed")
        pending.add_done_callback(_late)
        old, was_own = self._executor, self._own_executor
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="serve-device")
        self._own_executor = True
        if was_own and old is not None:
            # injected executors stay the caller's to shut down
            old.shutdown(wait=False)
            # detach the wedged worker from concurrent.futures' atexit
            # join: its threads are non-daemon, so without this a
            # PERMANENTLY stuck device call would hang interpreter
            # shutdown after an otherwise graceful drain (private API —
            # degrade to the documented orchestrator-kill backstop if it
            # moves)
            try:
                from concurrent.futures import thread as _cf_thread
                for t in list(getattr(old, "_threads", ())):
                    _cf_thread._threads_queues.pop(t, None)
            except Exception:  # noqa: BLE001
                pass

    async def _translate_units(self, units: List[_Unit], loop,
                               bspan=None, dev_acc=None) -> None:
        """One device call for the batch; on failure, bisect: split in two
        and retry each half, recursively, until single-unit batches isolate
        the poison request(s). Cost per poison unit: O(log batch) extra
        device calls against the old worker's O(batch) one-by-one retry.
        A call that exceeds --dispatch-stall-timeout instead fails the
        WHOLE batch with a retriable DispatchStalled (no bisection — the
        stall is a liveness event, not a poison sentence) and the
        scheduler moves on. ``bspan`` is the enclosing serve.batch span
        (None when tracing is off); device calls and bisection retries
        hang their spans under it."""
        # requests can die (deadline / cancel / a sibling batch's failure)
        # while this batch waited its turn — especially inside bisection
        # retries. Re-filter here so dead sentences never cost a device
        # call whose result would only be discarded.
        units = [u for u in units if not u.req.future.done()]
        if not units:
            return
        # the worker thread writes into its OWN accumulator, merged into
        # dev_acc only once the call has provably completed (a finished
        # await) — a watchdog-abandoned worker otherwise races its late
        # finally against record_batch and double-bills device seconds /
        # counts discarded outputs. Defined OUTSIDE the try: the generic
        # except below calls _merge_acc, and an injected serving.dispatch
        # fault raises before the try body gets this far.
        # dev_acc slots: [device_s, trg_tokens, src_tokens_done] — src
        # tokens are credited only for units whose results were
        # DELIVERED (below), so a stalled or failed call never counts
        # as throughput (cspt/tokens-per-second must spike, not read
        # "healthy", during an incident)
        local_acc = [0.0, 0.0] if dev_acc is not None else None

        def _merge_acc():
            if dev_acc is not None and local_acc is not None:
                dev_acc[0] += local_acc[0]
                dev_acc[1] += local_acc[1]
                local_acc[0] = local_acc[1] = 0.0

        try:
            # inside the try so an injected dispatch failure routes
            # through the normal failure path (futures fail explicitly —
            # never a dropped batch with hanging clients)
            fp.fault_point("serving.dispatch")
            lines = [u.text for u in units]
            translate = self.translate_lines
            # fleet mode (ISSUE 20): a tenanted batch (single-tenant by
            # _form_batch) resolves its route through the tenant router
            # ON THE WORKER THREAD — a warm-on-demand cold start blocks
            # only this batch, never the event loop
            tenant = units[0].req.tenant
            router = self.tenant_router

            def _call_translate():
                run = translate
                if router is not None and tenant:
                    # resolved BEFORE the device-time fence: a cold
                    # start is warmup, not this batch's service time
                    run = router(tenant)
                # device-time fence: translate_lines returns host-side
                # strings, so the perf_counter read AFTER it is an
                # honest device-seconds boundary (obs/perf.py)
                t0 = time.perf_counter()
                try:
                    out_ = run(lines)
                finally:
                    if local_acc is not None:
                        local_acc[0] += time.perf_counter() - t0
                if local_acc is not None:
                    local_acc[1] += sum(len(l.split()) for l in out_)
                return out_

            def _device_call():
                fp.fault_point("serving.translate")
                if bspan is None:
                    return _call_translate()
                # explicit parent handoff: this runs on the device
                # worker thread, outside the event loop's context; the
                # lifecycle SwapController stamps model_version onto
                # this span from inside route() (TRACER.set_attrs)
                sp = obs.start_span("serve.translate", parent=bspan,
                                    rows=len(lines))
                with obs.TRACER.use(sp):
                    try:
                        return _call_translate()
                    except BaseException as e:
                        sp.attrs.setdefault("error", repr(e))
                        raise
                    finally:
                        obs.end(sp)

            call = loop.run_in_executor(self._executor, _device_call)
            if self.stall_timeout > 0:
                try:
                    out = await asyncio.wait_for(asyncio.shield(call),
                                                 self.stall_timeout)
                except asyncio.TimeoutError:
                    if dev_acc is not None:
                        # the wedged call's own timing lands in
                        # local_acc, which is deliberately NOT merged on
                        # this path (the abandoned worker may still be
                        # running), but the device WAS busy for at least
                        # the stall window — bill that, or repeated
                        # stalls read as busy≈0/headroom≈1 and the
                        # autoscaler sees "idle" mid-incident
                        dev_acc[0] += self.stall_timeout
                    self._trip_watchdog(call, len(units))
                    victims = sorted({u.req.trace_id for u in units
                                      if u.req.trace_id})
                    now = loop.time()
                    for u in units:
                        if not u.req.future.done():
                            self._outcome("stalled", u.req, now)
                            u.req.future.set_exception(DispatchStalled(
                                f"device batch stalled past "
                                f"{self.stall_timeout}s — retry"))
                    # spans are ended ABOVE so the dump holds each
                    # victim's complete ingest→dispatch→failure tree
                    obs.event("serve.watchdog_trip", rows=len(units),
                              stall_timeout=self.stall_timeout,
                              traces=victims)
                    # async: this coroutine runs ON the event loop, and
                    # a dump (ring JSON + metrics render + file write)
                    # must not freeze every connection mid-incident
                    obs.FLIGHT.trip_async(
                        "watchdog",
                        trace_id=victims[0] if victims else None,
                        detail=f"device batch ({len(units)} sentences) "
                               f"stalled past {self.stall_timeout}s",
                        extra={"traces": victims})
                    return
            else:
                out = await call
            _merge_acc()        # the await finished: the worker's write
            if len(out) != len(lines):
                raise RuntimeError(
                    f"translator returned {len(out)} lines for "
                    f"{len(lines)} inputs — reply routing would misalign")
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001
            # a raising await still completed the worker future, so its
            # device seconds are safe to merge (zeroed after, so the
            # arity-check path above cannot double-merge)
            _merge_acc()
            if len(units) == 1:
                u = units[0]
                if not u.req.future.done():
                    self.m_failures.inc()
                    now = loop.time()
                    self._outcome("failure", u.req, now)
                    log.error("translation error: {}", e)
                    u.req.future.set_exception(RuntimeError(str(e)))
                    # the poison request is isolated (bisection endpoint
                    # or a single-sentence batch): record the victim and
                    # snapshot — the span ring still holds its tree
                    obs.event("serve.poison_isolated",
                              trace_id=u.req.trace_id, error=str(e)[:200])
                    obs.FLIGHT.trip_async(   # off the event loop thread
                        "poison", trace_id=u.req.trace_id or None,
                        detail=f"request failed in isolation: {e}")
                return
            self.m_bisections.inc()
            log.error("batch translation error ({} sentences — bisecting "
                      "to isolate): {}", len(units), e)
            mid = len(units) // 2
            await self._translate_units(units[:mid], loop, bspan, dev_acc)
            await self._translate_units(units[mid:], loop, bspan, dev_acc)
            return
        if dev_acc is not None:
            # results delivered: these units' tokens were really
            # processed (stall/failure paths never reach here)
            dev_acc[2] += sum(u.tokens for u in units)
        for u, line in zip(units, out):
            self._complete_unit(u, line, loop)

    def _complete_unit(self, u: _Unit, line: str, loop) -> None:
        req = u.req
        if req.future.done():
            return                    # cancelled/timed out while in flight
        req.results[u.idx] = line
        req.remaining -= 1
        if req.remaining == 0:
            if req.timeout_handle is not None:
                req.timeout_handle.cancel()
            req.future.set_result([r if r is not None else ""
                                   for r in req.results])
            now = loop.time()
            # trace-id exemplar: a p99 outlier on /metrics?exemplars=1
            # links straight to this request's span tree (ISSUE 8)
            self.m_latency.observe(now - req.arrival,
                                   trace_id=req.trace_id or None)
            self._outcome("ok", req, now)
