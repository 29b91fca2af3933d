"""Request-scoped span tracer (ISSUE 8 tentpole — the observability
layer's core).

The serving control plane (continuous batching, hot-swap, canary,
watchdog) and the training loop expose only AGGREGATE Prometheus series;
when a p99 blip, a rollback, or a watchdog trip happens there is no way
to reconstruct *which request went where and why*. This module is the
missing per-request record: named SPANS (start/end + attributes) and
instant EVENTS, linked by trace id into trees, recorded into a bounded
in-memory ring and exported as Chrome trace-event JSON (``/tracez`` on
the metrics port, loadable in Perfetto / chrome://tracing) and via the
flight recorder (obs/flight.py).

Design constraints (docs/OBSERVABILITY.md):

- **Stdlib-only**, importable from any layer (the scheduler, the
  trainer, the data loader, the analysis tooling): this module never
  imports jax. Where the process already has (``jax.profiler`` in
  ``sys.modules``) it binds ``jax.profiler.TraceAnnotation`` lazily.
- **"On" follows the profiler**: a span is LIVE when the tracer is
  enabled (``--trace``) OR a ``jax.profiler`` session is collecting
  (``marian-train --profile``, a capture attached through
  ``--profile-server``, the benchmark's ``--trace 1``). A live span is
  also a TraceMe on the calling thread, so it lands in the session's
  ``/host:CPU`` plane on the SAME CLOCK as the device ops; and it feeds
  the per-name totals (calls, seconds, self seconds) of
  :meth:`Tracer.totals`. The ring (``/tracez``, flight dumps) fills only
  while the tracer is enabled.
- **Zero overhead when off** (the default): ``start_span`` returns the
  NOOP_SPAN singleton after two flag reads — no ring is ever allocated,
  no lock is ever acquired, no dict is built. The tier-1 overhead-guard
  tests assert exactly this on the scheduler's per-batch hot path and
  on the trainer's loop objects.
- **Gauges** beside the totals and the step counters (which only sum):
  :meth:`Tracer.gauge` keeps a sampled level's last, least and largest
  value. The trainer samples the device allocator's free bytes with
  them (training/hbm.py).
- **Lock-free-ish when enabled**: spans are recorded once, at END time,
  with a single bounded-deque append under a lockdep-named lock
  (``Tracer._lock``) held for nanoseconds; exports snapshot under the
  same lock. Tracer calls are not made while other subsystem locks are
  held, with ONE modeled exception — the lifecycle registry's
  transition event under ``SwapController._lock`` (an edge the static
  lock graph carries; the lockdep witness flags any unmodeled edge).
- **Context propagation** via ``contextvars`` (follows asyncio tasks on
  the event loop) plus explicit ``parent=`` handoff where the request
  path crosses threads (scheduler -> device executor).

Span identity: ``trace_id`` (one per request, client-providable through
the ``#trace:<id>`` protocol header — server/server.py), ``span_id``
(process-unique), ``parent_id`` (tree edge). The scheduler's latency
histograms attach the trace id as an exemplar (serving/metrics.py), so a
p99 outlier on /metrics links back to its span tree here.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import random
import sys
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from ..common import lockdep

# wall-clock anchor: spans timestamp with the monotonic perf_counter;
# exports shift onto the epoch so dumps from different processes align
_EPOCH = time.time() - time.perf_counter()

# the current span for THIS task/thread (contextvars: each asyncio task
# and each thread sees its own value; worker threads get the parent
# passed explicitly instead)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "marian_current_span", default=None)

DEFAULT_RING = 4096
DEFAULT_EVENT_RING = 2048

# jax.profiler.TraceAnnotation once the process has imported jax.profiler
# (never imported from here: loadgen and the data layer stay off JAX)
_ANNOTATION = None


def profiler_collecting() -> bool:
    """Whether a jax.profiler session is collecting right now (a static
    flag read, ~30 ns); False in a process that never imported jax."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        mod = sys.modules.get("jax.profiler")
        ann = getattr(mod, "TraceAnnotation", None)
        if ann is None:
            return False
        _ANNOTATION = ann
    return ann.is_enabled()


def new_trace_id() -> str:
    """64-bit random hex trace id (the format loadgen generates too)."""
    return f"{random.getrandbits(64):016x}"


class _NoopSpan:
    """The disabled-mode span: every operation is a no-op. A singleton,
    so the disabled hot path allocates nothing."""

    __slots__ = ()
    name = ""
    trace_id = ""
    span_id = ""
    parent_id = ""

    def set_attrs(self, **kw) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self         # `with tracer.span(..)` while off

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False        # `if span:` guards read naturally

    def __repr__(self) -> str:
        return "<noop span>"


NOOP_SPAN = _NoopSpan()


class Span:
    """One named interval. Mutable until :meth:`Tracer.end` records it
    into the ring; setting attributes after end is a bug the MT-SPAN-LATE
    lint flags (the ring holds a reference, so a late write would
    silently rewrite history)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end_t", "attrs", "thread", "_up", "_child_s", "_ann")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: str, start: float,
                 attrs: Optional[Dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end_t: Optional[float] = None
        self.attrs: Dict = attrs if attrs is not None else {}
        self.thread = threading.current_thread().name
        self._up: Optional["Span"] = None   # live parent, until end
        self._child_s = 0.0     # seconds same-thread children covered
        self._ann = None        # the open TraceMe, while a session runs

    def set_attrs(self, **kw) -> "Span":
        self.attrs.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def duration(self) -> float:
        return (self.end_t - self.start) if self.end_t is not None else 0.0

    def __bool__(self) -> bool:
        return True

    def __repr__(self) -> str:
        return (f"<span {self.name} trace={self.trace_id} "
                f"id={self.span_id} parent={self.parent_id or '-'}>")


class _SpanScope:
    """``with`` form of one live span: makes it the context's current
    span, records an escaping exception, always ends it."""

    __slots__ = ("_tracer", "_span", "_token")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self._span)
        return self._span

    def __exit__(self, _etype, exc, _tb) -> bool:
        if exc is not None:
            self._span.attrs.setdefault("error", repr(exc))
        _CURRENT.reset(self._token)
        self._tracer.end(self._span)
        return False


class Tracer:
    """Bounded-ring span/event recorder. Disabled by default; see the
    module docstring for the overhead contract."""

    def __init__(self, capacity: int = DEFAULT_RING,
                 event_capacity: int = DEFAULT_EVENT_RING):
        self.capacity = int(capacity)
        self.event_capacity = int(event_capacity)
        self._enabled = False
        # rings are allocated on enable() ONLY — "tracer off" must mean
        # no ring allocation, not an empty ring (tier-1 overhead guard)
        self._ring: Optional[collections.deque] = None   # guarded-by: _lock
        self._events: Optional[collections.deque] = None  # guarded-by: _lock
        # name -> [calls, seconds, self seconds, thread]; allocated by
        # the first live span that ends, like the rings by enable()
        self._totals: Optional[Dict[str, List]] = None   # guarded-by: _lock
        # step counters (count_lazy): the names with the device vectors
        # not fetched yet, and the fetched sums by name
        self._lazy: Optional[Tuple] = None               # guarded-by: _lock
        self._counters: Optional[Dict[str, float]] = None  # guarded-by: _lock
        # gauges: name -> [last, min, max, n]
        self._gauges: Optional[Dict[str, List]] = None   # guarded-by: _lock
        self._lock = lockdep.make_lock("Tracer._lock")
        self._seq = itertools.count(1)   # span ids; count() is GIL-atomic

    # -- lifecycle ----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, capacity: Optional[int] = None,
               event_capacity: Optional[int] = None) -> None:
        if capacity:
            self.capacity = int(capacity)
        if event_capacity:
            self.event_capacity = int(event_capacity)
        with self._lock:
            if self._ring is None or self._ring.maxlen != self.capacity:
                self._ring = collections.deque(
                    self._ring or (), maxlen=self.capacity)
            if self._events is None \
                    or self._events.maxlen != self.event_capacity:
                self._events = collections.deque(
                    self._events or (), maxlen=self.event_capacity)
        self._enabled = True

    def disable(self) -> None:
        """Stop recording; the rings keep their contents (a flight dump
        after disable still has the history). reset() frees them. The
        gauges go: a minimum is over one stretch of recording."""
        self._enabled = False
        with self._lock:
            self._gauges = None

    def reset(self) -> None:
        self._enabled = False
        with self._lock:
            self._ring = None
            self._events = None
            self._totals = None
            self._lazy = None
            self._counters = None
            self._gauges = None

    def totals(self) -> Dict[str, Dict]:
        """Per span name, over every live span ended since reset():
        ``calls``, ``seconds``, ``self_seconds`` (the duration less what
        child spans on the same thread covered: same-thread self times
        add up to the outermost spans' durations) and the ``thread`` of
        the first call. Empty while nothing was live."""
        with self._lock:
            return {name: {"calls": t[0], "seconds": t[1],
                           "self_seconds": t[2], "thread": t[3]}
                    for name, t in (self._totals or {}).items()}

    # -- step counters -------------------------------------------------------
    def count_lazy(self, names, values) -> None:
        """Keep one step's counters: `values` is a LAZY device vector,
        one entry per name, and stays on the device, untouched (no device
        op, so nothing compiles; no sync). Live exactly when spans are;
        otherwise two flag reads."""
        if not self._enabled and not profiler_collecting():
            return
        with self._lock:
            if self._lazy is None or self._lazy[0] != tuple(names):
                self._lazy = (tuple(names), [])
            self._lazy[1].append(values)

    def fetch_counters(self) -> None:
        """Bring what count_lazy kept to the host and add it to the sums.
        Called where the caller syncs with the device anyway (the
        Scheduler's display, after it fetched the cost: the counters of
        the same steps are ready, so no wait of their own). With nothing
        kept it is one attribute read: no lock, as while off."""
        if self._lazy is None:        # mtlint: ok -- racy read by design; a value that lands now is fetched at the next display
            return
        with self._lock:
            held, self._lazy = self._lazy, None
        if held is None:
            return
        rows = [vec.tolist() for vec in held[1]]     # the fetch, unlocked
        with self._lock:
            if self._counters is None:
                self._counters = {}
            for name, column in zip(held[0], zip(*rows)):
                self._counters[name] = self._counters.get(name, 0.0) \
                    + float(sum(column))

    def count(self, name: str, n: float = 1) -> None:
        """Add `n` to a counter the HOST keeps (a start-up's programs
        taken from the store): read beside the step counters. Live
        exactly when spans are; otherwise two flag reads."""
        if not self._enabled and not profiler_collecting():
            return
        with self._lock:
            if self._counters is None:
                self._counters = {}
            self._counters[name] = self._counters.get(name, 0.0) + float(n)

    def counters(self) -> Dict[str, float]:
        """Counters by name, summed since reset(): the fetched step
        counters and the host's own."""
        with self._lock:
            return dict(self._counters or {})

    # -- gauges --------------------------------------------------------------
    def gauge(self, name: str, value) -> Optional[int]:
        """Keep a sampled level (free device memory before a dispatch):
        per name the last value, the least, the largest and how many.
        totals() and counters() only sum; a minimum cannot be rebuilt
        from a sum. Live exactly when spans are; otherwise two flag
        reads. Returns how many samples the name holds now (1: the first
        since reset() or disable(), where a caller writes what does not
        change once), None when off."""
        if not self._enabled and not profiler_collecting():
            return None
        with self._lock:
            if self._gauges is None:
                self._gauges = {}
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = [value, value, value, 1]
            else:
                g[0] = value
                g[1] = min(g[1], value)
                g[2] = max(g[2], value)
                g[3] += 1
            return g[3]

    def gauges(self) -> Dict[str, Dict]:
        """``last``, ``min``, ``max`` and ``n`` by gauge name, over the
        samples since reset() or disable()."""
        with self._lock:
            return {name: {"last": g[0], "min": g[1], "max": g[2], "n": g[3]}
                    for name, g in (self._gauges or {}).items()}

    # -- recording ----------------------------------------------------------
    def start_span(self, name: str, parent: Optional[Span] = None,
                   trace_id: Optional[str] = None, **attrs):
        """Open a span. ``parent=None`` inherits the context's current
        span (same task/thread); pass the parent explicitly when
        crossing threads. Not recorded until :meth:`end`."""
        if not self._enabled and not profiler_collecting():
            return NOOP_SPAN
        return self._open(name, parent, trace_id, attrs)

    def _open(self, name: str, parent, trace_id, attrs: Dict,
              past_start: Optional[float] = None) -> Span:
        if parent is None:
            parent = _CURRENT.get(None)
        if parent is NOOP_SPAN:
            parent = None
        if trace_id is None:
            trace_id = parent.trace_id if parent is not None \
                else new_trace_id()
        sp = Span(name, trace_id, f"{next(self._seq):x}",
                  parent.span_id if parent is not None else "",
                  time.perf_counter() if past_start is None else past_start,
                  dict(attrs) if attrs else None)
        sp._up = parent
        if past_start is None and profiler_collecting():
            # the same interval as a TraceMe on this thread: the
            # session's /host:CPU plane, on the device ops' clock
            sp._ann = _ANNOTATION(name, **attrs)
            sp._ann.__enter__()
        return sp

    def end(self, span, **attrs) -> None:
        """Close ``span``: its TraceMe, the totals, and (tracer enabled)
        the ring. Idempotent; a NOOP_SPAN or None is ignored."""
        if span is None or span is NOOP_SPAN or not isinstance(span, Span):
            return
        if span.end_t is not None:
            return
        if attrs:
            span.set_attrs(**attrs)
        span.end_t = time.perf_counter()
        ann, span._ann = span._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        self._account(span)

    def _account(self, span: Span) -> None:
        """Totals (always, while live) and the ring (tracer enabled)."""
        dur = span.end_t - span.start
        up, span._up = span._up, None
        if up is not None and up.thread == span.thread:
            up._child_s += dur
        with self._lock:
            if self._totals is None:
                self._totals = {}
            t = self._totals.get(span.name)
            if t is None:
                t = self._totals[span.name] = [0, 0.0, 0.0, span.thread]
            t[0] += 1
            t[1] += dur
            t[2] += max(0.0, dur - span._child_s)
            if self._enabled and self._ring is not None:
                self._ring.append(span)

    def record(self, name: str, start: float, end: float,
               parent: Optional[Span] = None, trace_id: Optional[str] = None,
               **attrs) -> None:
        """Record a retroactive complete span from two perf_counter
        timestamps (reply writes measured after the fact). Totals and
        ring only: a TraceMe cannot be opened in the past."""
        if not self._enabled and not profiler_collecting():
            return
        sp = self._open(name, parent, trace_id, attrs, past_start=start)
        sp.end_t = end
        self._account(sp)

    def event(self, name: str, **attrs) -> None:
        """Record an instant event onto the timeline (lifecycle
        transitions, admission sheds, watchdog trips, fault firings),
        tagged with the current context's trace id when one is set."""
        if not self._enabled:
            return
        cur = _CURRENT.get(None)
        ev = {
            "name": name,
            "ts": time.perf_counter(),
            "trace_id": cur.trace_id if cur is not None
            and cur is not NOOP_SPAN else "",
            "thread": threading.current_thread().name,
            "attrs": dict(attrs) if attrs else {},
        }
        with self._lock:
            if self._events is not None:
                self._events.append(ev)

    # -- context helpers ----------------------------------------------------
    def current(self) -> Optional[Span]:
        cur = _CURRENT.get(None)
        return None if cur is NOOP_SPAN else cur

    def set_attrs(self, **kw) -> None:
        """Attach attributes to the current context span (e.g. the
        lifecycle controller stamping model_version onto the device
        translate span it runs inside)."""
        cur = _CURRENT.get(None)
        if cur is not None and cur is not NOOP_SPAN:
            cur.attrs.update(kw)

    @contextlib.contextmanager
    def use(self, span) -> Iterator:
        """Make ``span`` the context's current span WITHOUT owning its
        lifetime (the caller ends it) — the cross-thread handoff tool."""
        if span is None or span is NOOP_SPAN:
            yield span
            return
        token = _CURRENT.set(span)
        try:
            yield span
        finally:
            _CURRENT.reset(token)

    def span(self, name: str, parent: Optional[Span] = None,
             trace_id: Optional[str] = None, **attrs):
        """``with tracer.span("name"):`` — start, set context, always
        end. The safe default; manual start_span/end pairs are for spans
        whose lifetime crosses callbacks (MT-SPAN-UNCLOSED lints those).
        While off this returns the NOOP_SPAN singleton, itself a context
        manager: a span site costs the two flag reads and no object."""
        if not self._enabled and not profiler_collecting():
            return NOOP_SPAN
        return _SpanScope(self, self._open(name, parent, trace_id, attrs))

    # -- export -------------------------------------------------------------
    def snapshot(self, last: Optional[int] = None
                 ) -> Tuple[List[Span], List[Dict]]:
        """(spans, events) copies; ``last`` bounds the span count to the
        most recent N."""
        with self._lock:
            spans = list(self._ring) if self._ring is not None else []
            events = list(self._events) if self._events is not None else []
        if last is not None and last >= 0:
            spans = spans[-last:]
        return spans, events

    def spans_for_trace(self, trace_id: str) -> List[Span]:
        spans, _ = self.snapshot()
        return [s for s in spans if s.trace_id == trace_id]

    def chrome_trace(self, last: Optional[int] = None) -> Dict:
        """Chrome trace-event JSON (the ``/tracez`` document): complete
        ("X") events for spans, instant ("i") events for the timeline.
        Loadable in Perfetto (ui.perfetto.dev) or chrome://tracing."""
        spans, events = self.snapshot(last)
        pid = os.getpid()
        out: List[Dict] = []
        for s in spans:
            args = {"trace_id": s.trace_id, "span_id": s.span_id}
            if s.parent_id:
                args["parent_id"] = s.parent_id
            args.update(s.attrs)
            out.append({
                "name": s.name, "cat": s.name.split(".")[0], "ph": "X",
                "ts": (s.start + _EPOCH) * 1e6,
                "dur": max(0.0, s.duration()) * 1e6,
                "pid": pid, "tid": s.thread, "args": args,
            })
        for e in events:
            args = {"trace_id": e["trace_id"]} if e["trace_id"] else {}
            args.update(e["attrs"])
            out.append({
                "name": e["name"], "cat": "event", "ph": "i", "s": "t",
                "ts": (e["ts"] + _EPOCH) * 1e6,
                "pid": pid, "tid": e["thread"], "args": args,
            })
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {"tracer_enabled": self._enabled,
                          "ring_capacity": self.capacity},
        }


# The process-wide tracer: serving, training, and the CLI layers all
# record here, like metrics' REGISTRY — one /tracez for the process.
TRACER = Tracer()


def enabled() -> bool:
    return TRACER._enabled


def current() -> Optional[Span]:
    return TRACER.current()


def start_span(name: str, parent: Optional[Span] = None,
               trace_id: Optional[str] = None, **attrs):
    return TRACER.start_span(name, parent=parent, trace_id=trace_id, **attrs)


def end(span, **attrs) -> None:
    TRACER.end(span, **attrs)


def event(name: str, **attrs) -> None:
    TRACER.event(name, **attrs)


def span(name: str, **attrs):
    return TRACER.span(name, **attrs)


def set_attrs(**kw) -> None:
    TRACER.set_attrs(**kw)


def trace_routes() -> Dict:
    """Extra handlers for serving/metrics.py's MetricsServer ``routes``:
    ``GET /tracez?last=N`` returns the Chrome trace JSON of the last N
    spans (all, when unset) plus the event timeline — curl it to a file
    and open in Perfetto."""

    def _tracez(method: str, query: str):
        last: Optional[int] = None
        from urllib.parse import parse_qs
        try:
            vals = parse_qs(query or "").get("last")
            if vals:
                last = max(0, int(vals[0]))
        except (ValueError, TypeError):
            last = None
        body = json.dumps(TRACER.chrome_trace(last), indent=1).encode() \
            + b"\n"
        return 200, body, "application/json"

    return {"/tracez": _tracez}
