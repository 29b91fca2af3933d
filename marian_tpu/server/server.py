"""marian-server: translation service (reference: src/command/marian_server.cpp
+ vendored simple-websocket-server), fronted by the production serving
subsystem (marian_tpu/serving/ — ISSUE 1).

Protocol kept Marian-compatible: client sends newline-joined source
sentences as a text frame, server replies with newline-joined translations.
Transports:

- WebSocket (the Marian protocol) via the ``websockets`` package, gated —
  when unavailable the server falls back to
- a dependency-free length-prefixed TCP framing (``MTPU <nbytes>\\n`` +
  UTF-8 payload, replies framed the same way) that ``scripts/loadgen.py``
  speaks. Both transports share one ServingApp, so admission, scheduling,
  and metrics behave identically.

Beyond the reference (which serves each connection on its own thread
against per-thread graphs): ALL requests flow through ONE continuous
token-budget batching scheduler (serving/scheduler.py) that packs
sentences from concurrent clients into bucketed static-shape device
batches, behind bounded-queue admission control (serving/admission.py),
with Prometheus metrics + health endpoints (serving/metrics.py,
``--metrics-port``). Error replies are explicit: a shed request gets
``!!SERVER-OVERLOADED ...``, an expired one ``!!SERVER-TIMEOUT ...`` —
never a silent hang.
"""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from .. import obs
from ..common import logging as log
from ..data.batch_generator import bucket_length
from ..obs import slo as mslo
from ..serving import metrics as msm
from ..serving.admission import AdmissionController, Overloaded
from ..serving.scheduler import (ContinuousScheduler, DispatchStalled,
                                 RequestTimeout, RowEvicted)
from ..training import bundle as bdl

try:
    import websockets
    HAVE_WS = True
except ImportError:  # pragma: no cover
    HAVE_WS = False

# graceful-drain budget on shutdown: long enough for a queued maximal batch
# to finish decoding, far below any orchestrator's kill timeout
DRAIN_TIMEOUT_S = 30.0

# Request-tracing protocol extension (ISSUE 8, backwards-compatible): a
# client MAY make the first line of its frame `#trace:<id>` (id: up to 64
# alnum/-/_ chars — scripts/loadgen.py generates 16-hex ones). The server
# strips it, labels the request's span tree with the id, and prepends a
# `#trace:<id> outcome=.. queue_ms=.. service_ms=.. model_version=..`
# metadata line to the reply, so the client can attribute latency to
# queue wait vs device service (swap/canary blips become attributable
# client-side). Clients that send no header see the exact old protocol.
TRACE_PREFIX = "#trace:"
_MAX_TRACE_ID = 64


def split_trace_header(text: str) -> Tuple[Optional[str], str]:
    """(trace_id | None, body) — see TRACE_PREFIX above. A malformed id
    is treated as payload, never an error (the header is advisory)."""
    if not text.startswith(TRACE_PREFIX):
        return None, text
    first, sep, rest = text.partition("\n")
    tid = first[len(TRACE_PREFIX):].strip()
    if not tid or len(tid) > _MAX_TRACE_ID \
            or not all(c.isalnum() or c in "-_" for c in tid):
        return None, text
    return tid, rest if sep else ""


# Tenant-selection protocol extension (ISSUE 20, backwards-compatible
# like #trace): in --fleet mode a client picks its model family by
# making the next line `#model:<tag>`. Headers stack in order #trace,
# #model, #priority, #stream. A MALFORMED tag is payload, never an
# error (the usual header discipline) — but a WELL-FORMED tag naming no
# configured tenant is an explicit !!SERVER-ERROR reply: silently
# translating legal text with the wrong model is the one failure mode a
# fleet must never have. Tags share the trace-id alphabet plus '.', so
# the first '/' in a pool owner label is an unambiguous tenant prefix
# (serving/fleet/accounting.py).
MODEL_PREFIX = "#model:"
_MAX_MODEL_TAG = 64


def split_model_header(text: str) -> Tuple[Optional[str], str]:
    """(tenant tag | None, body) — see MODEL_PREFIX above."""
    if not text.startswith(MODEL_PREFIX):
        return None, text
    first, sep, rest = text.partition("\n")
    tag = first[len(MODEL_PREFIX):].strip()
    if not tag or len(tag) > _MAX_MODEL_TAG \
            or not all(c.isalnum() or c in "-_." for c in tag):
        return None, text
    return tag, rest if sep else ""


# Priority-lane protocol extension (ISSUE 11, backwards-compatible like
# #trace): a client MAY make the first body line `#priority:<int>`; the
# server strips it and admits/schedules the request in that lane. Under
# brownout level 3 the low lanes are shed explicitly while high lanes
# keep serving (serving/brownout.py). Headers stack: #trace first, then
# #priority. A malformed value is payload, never an error. The value is
# CLAMPED to [PRIORITY_MIN, PRIORITY_MAX]: the scheduler keeps one lane
# per distinct priority forever, so an unclamped client-controlled int
# would let any client grow the lane table (and its per-round sort)
# without bound.
PRIORITY_PREFIX = "#priority:"
PRIORITY_MIN, PRIORITY_MAX = -9, 9


def split_priority_header(text: str) -> Tuple[Optional[int], str]:
    """(clamped priority | None, body) — see PRIORITY_PREFIX above."""
    if not text.startswith(PRIORITY_PREFIX):
        return None, text
    first, sep, rest = text.partition("\n")
    raw = first[len(PRIORITY_PREFIX):].strip()
    try:
        prio = int(raw)
    except ValueError:
        return None, text
    return max(PRIORITY_MIN, min(PRIORITY_MAX, prio)), rest if sep else ""


# Streaming protocol extension (ISSUE 16, backwards-compatible like
# #trace / #priority; headers stack in that order, #stream last): a
# client MAY send `#stream:1` — the server then delivers partial target
# text as the decode progresses, one `#partial:<sentence_idx> <text>`
# frame per engine round per still-decoding sentence, followed by the
# normal final reply frame (which for tracing clients carries the
# #trace metadata line, and on retriable eviction is the usual
# !!SERVER-RETRY — i.e. the stream closes retriably). Greedy partials
# are append-only prefixes of the final text; beam partials are the
# CURRENT best hypothesis and may retract earlier text when the beam
# reranks. Only iteration mode produces partials; a request-mode server
# accepts the header and simply never emits any (clients NaN-suppress
# ttft, like loadgen). A malformed value is payload, never an error.
STREAM_PREFIX = "#stream:"
PARTIAL_PREFIX = "#partial:"


def split_stream_header(text: str) -> Tuple[Optional[bool], str]:
    """(stream | None, body) — see STREAM_PREFIX above."""
    if not text.startswith(STREAM_PREFIX):
        return None, text
    first, sep, rest = text.partition("\n")
    raw = first[len(STREAM_PREFIX):].strip()
    if raw not in ("0", "1"):
        return None, text
    return raw == "1", rest if sep else ""
# per-connection cap on bytes the EOF watch may read ahead of the framing
# parser while a reply is pending — bounds what a flooding pipelined
# client can make the server buffer
MAX_READAHEAD = 1 << 20


def _fleet_unrouted(lines: List[str]) -> List[str]:
    """The fleet-mode scheduler's translate_lines: every request must
    resolve through the tenant router, so reaching this is a routing
    bug (handle_frame rejects un-tagged requests without a default
    tenant BEFORE they queue), never a client error."""
    raise RuntimeError(
        "fleet-mode batch reached the un-routed translate path — a "
        "request was queued without a tenant tag")


class TranslationService:
    """Preloaded graphs + jitted search shared across requests (reference:
    TranslationService in marian_server.cpp)."""

    def __init__(self, options):
        from ..translator.translator import Translate
        self.translator = Translate(options)

    def translate_lines(self, lines: List[str]) -> List[str]:
        import io as _io
        buf = _io.StringIO()
        got = self.translator.run(lines=lines, stream=buf)
        if len(got) != len(lines):
            # one entry per input line is what the batched reply slicing
            # relies on — a silent mismatch would route one client's
            # translations to another, so fail loudly instead
            raise RuntimeError(
                f"translator returned {len(got)} lines for {len(lines)} "
                f"inputs — per-request reply slicing would misalign")
        return got

    def translate(self, text: str) -> str:
        return "\n".join(self.translate_lines(text.split("\n")))


def resolve_token_budget(options) -> int:
    """--batch-token-budget, or derived from --mini-batch x the bucketed
    --max-length when unset — the derived value reproduces the sentence-
    count batching the pre-serving server did, so the flagless command
    line keeps its old capacity."""
    budget = int(options.get("batch-token-budget", 0) or 0)
    if budget > 0:
        return budget
    mb = max(1, int(options.get("mini-batch", 1) or 1))
    ml = max(1, int(options.get("max-length", 50) or 50))
    return mb * bucket_length(ml + 1)


class ServingApp:
    """One serving stack: TranslationService (or an injected
    translate_lines — tests, load generators) + continuous scheduler +
    admission control + metrics endpoint + (with ``--model-watch``) the
    zero-downtime model lifecycle (serving/lifecycle/ — ISSUE 5: bundle
    watcher, warmed hot-swap, canary routing, auto-rollback). Shared by
    every transport."""

    def __init__(self, options, translate_lines=None,
                 registry: Optional[msm.Registry] = None,
                 executor_factory=None, engine=None):
        self.options = options
        self.registry = registry if registry is not None else msm.REGISTRY
        # observability (ISSUE 8): --trace enables the span tracer,
        # --trace-dump arms the flight recorder; /tracez rides the
        # metrics port (start() below)
        obs.configure(options)
        budget = resolve_token_budget(options)
        # --batching-mode iteration (ISSUE 10): scheduling moves INSIDE
        # the decode loop over a paged KV pool — sentences join a
        # running decode each step and leave the step they finish
        # (translator/iteration.py; docs/DEPLOYMENT.md "Iteration-level
        # batching"). `engine` injects a prebuilt engine (tests).
        self.batching_mode = str(
            options.get("batching-mode", "request") or "request")
        if self.batching_mode == "iteration":
            self._validate_iteration_options(options)
        # multi-tenant fleet serving (ISSUE 20): --fleet replaces the
        # single boot model with N tenants warmed on demand; requests
        # route by the #model: header. Request mode only — the paged
        # iteration engine drives ONE model's decode loop; iteration
        # tenants belong on dedicated replicas.
        self.fleet = None
        self._fleet_default = str(
            options.get("fleet-default-tenant", "") or "")
        fleet_spec = str(options.get("fleet", "") or "")
        if fleet_spec:
            if self.batching_mode == "iteration":
                raise ValueError(
                    "--fleet serves --batching-mode request only: the "
                    "paged iteration engine is single-model (route "
                    "iteration tenants to dedicated replicas)")
            if float(options.get("model-watch", 0) or 0) > 0:
                raise ValueError(
                    "--fleet and --model-watch are mutually exclusive: "
                    "the fleet already runs one bundle watcher per "
                    "tenant (--fleet-watch)")
            if translate_lines is None:
                # no single boot model to load — every request resolves
                # through the tenant router; align the Translate-internal
                # batcher exactly as the single-model path below does
                options.set("mini-batch-words", budget)
                options.set("mini-batch", budget)
                options.set("maxi-batch", 1)
                translate_lines = _fleet_unrouted
                self.service = None
        if translate_lines is None:
            # align the Translate-internal batcher with the scheduler's
            # groups: one scheduler batch == one device batch, hitting the
            # bucket table's warm jit shapes. All three knobs matter: the
            # token budget governs splitting, and maxi-batch x mini-batch
            # is the maxi-WINDOW cap in sentences (translate-mode
            # mini-batch defaults to 1 — left alone, the window cap of 1
            # would shred every scheduler batch back into single-sentence
            # device batches). Rows per batch can never exceed
            # budget / min-bucket-width, so the budget itself is a safe
            # window cap.
            options.set("mini-batch-words", budget)
            options.set("mini-batch", budget)
            options.set("maxi-batch", 1)
            service = TranslationService(options)
            translate_lines = service.translate_lines
            self.service: Optional[TranslationService] = service
        else:
            self.service = None
        engine_factory = None
        if self.batching_mode == "iteration":
            if engine is None:
                if self.service is None:
                    raise ValueError(
                        "--batching-mode iteration with an injected "
                        "translate_lines needs an injected engine too "
                        "(the paged engine drives the model directly)")
                # rebuild hook resolves THROUGH the lifecycle when one
                # is attached: after a watchdog trip the fresh engine
                # must serve the CURRENT live version, not the boot one
                engine_factory = self._rebuild_live_engine
                engine = self._build_engine()
            # admission prices queue debt in PAGES: default bound is
            # 4x the pool (a full pool of backlog ahead of you is
            # already seconds of queueing; --max-queue-pages overrides)
            self.max_queue_pages = int(
                options.get("max-queue-pages", 0) or 0) \
                or 4 * engine.pool.usable_pages
            # resolved THROUGH the scheduler at call time: a watchdog
            # trip rebuilds scheduler.engine, and a method bound to the
            # dead engine would both misprice admission and keep its
            # whole device-side pool alive (the retention class
            # PERF.set_capacity_inputs's docstring warns about)
            self._pages_for_text = \
                lambda text: self.scheduler.engine.pages_for_text(text)
            self._pool_provider = True
        else:
            self._pages_for_text = None
            self.max_queue_pages = 0
            self._pool_provider = False
        self.scheduler = ContinuousScheduler(
            translate_lines, token_budget=budget, registry=self.registry,
            stall_timeout=float(
                options.get("dispatch-stall-timeout", 0) or 0),
            batching_mode=self.batching_mode, engine=engine,
            engine_factory=engine_factory)
        if self._pool_provider:
            # every flight dump (pool.audit_failed, failed quiesce,
            # brownout escalation, watchdog, poison...) embeds the KV
            # page map at incident time (ISSUE 14). Resolved through
            # the scheduler so swaps/rebuilds dump the live engine.
            from ..obs import poolz as mpoolz
            obs.FLIGHT.add_snapshot_provider(
                "pool", lambda: mpoolz.snapshot(self.scheduler))
        self.admission = AdmissionController(
            int(options.get("max-queue", 512) or 0),
            self.scheduler.queued_units, registry=self.registry,
            max_queue_pages=self.max_queue_pages,
            pages_fn=self.scheduler.queued_pages)
        self.request_timeout = float(options.get("request-timeout", 0) or 0)
        self.metrics_server: Optional[msm.MetricsServer] = None
        self._started = False
        # whether the request port accepts connections. An embedded app
        # (tests, fleet drills) has no listener of its own; _serve owns
        # one and holds /readyz back until it is bound
        self.listening = True
        # perf/capacity plane (ISSUE 9, obs/perf.py): wire the headroom
        # gauge's admission-pressure inputs and the MFU geometry; both
        # no-ops when --perf-accounting is off
        self._perf_wired = obs.PERF.enabled
        if obs.PERF.enabled:
            if self.registry is not msm.REGISTRY:
                # configure() enabled the plane on the process-global
                # registry; this app scrapes ITS registry — re-declare
                # the perf series there so /metrics actually shows them
                # (the global copies stay registered but un-emitted)
                obs.PERF.enable(registry=self.registry)
            if self.batching_mode == "iteration":
                # the headroom gauge's queue-pressure units become
                # PAGES (docs/DEPLOYMENT.md): queued page debt against
                # the page bound is what predicts pool saturation
                obs.PERF.set_capacity_inputs(self.scheduler.queued_pages,
                                             self.max_queue_pages)
            else:
                obs.PERF.set_capacity_inputs(
                    self.scheduler.queued_units,
                    self.admission.max_queue_units)
            self._set_perf_geometry()
        # SLO burn-rate engine (obs/slo.py): constructed only when an
        # objective is declared (--slo-availability / --slo-p99-ms);
        # it reads the scheduler's existing counters on its own thread —
        # nothing on the batch path
        self.slo: Optional[mslo.SloEngine] = \
            mslo.maybe_build_engine(options, self.registry)
        if self.slo is not None:
            obs.FLIGHT.add_snapshot_provider("slo", self.slo.state)
        # brownout ladder (--brownout, ISSUE 11; serving/brownout.py):
        # signal-driven degradation levels over the SLO burn-rate and
        # capacity-headroom signals the obs plane already maintains
        self.brownout = None
        self._brownout_cap_factor = float(
            options.get("brownout-cap-factor", 0.5) or 0.5)
        self._brownout_min_priority = int(
            options.get("brownout-min-priority", 1) or 1)
        if options.get("brownout", False):
            from ..serving.brownout import BrownoutController
            burn_thr = float(options.get("brownout-burn", 0) or 0)
            if burn_thr <= 0:
                # default to the SLO engine's fast-burn factor; with no
                # SLO declared the burn signal is off and headroom
                # drives the ladder alone
                burn_thr = self.slo.fast_factor \
                    if self.slo is not None else 0.0
            self.brownout = BrownoutController(
                apply_fn=self._apply_brownout,
                headroom_fn=obs.PERF.headroom if obs.PERF.enabled
                else None,
                burn_fn=self.slo.fast_burn if self.slo is not None
                else None,
                registry=self.registry,
                headroom_floor=float(
                    options.get("brownout-headroom", 0.1) or 0.1),
                burn_threshold=burn_thr,
                hold_s=float(options.get("brownout-hold", 5.0) or 5.0),
                cool_s=float(options.get("brownout-cool", 15.0) or 15.0))
            obs.FLIGHT.add_snapshot_provider("brownout",
                                             self.brownout.state)
            if not obs.PERF.enabled and burn_thr <= 0:
                # both signals dead: headroom_fn is None (reads 1.0,
                # never at the floor) and the burn guard is off — the
                # ladder would tick forever without ever escalating
                # while the operator believes overload protection is on
                log.warn("--brownout is armed but BOTH of its signals "
                         "are disabled (--perf-accounting off and no "
                         "--slo-* objective declared): the ladder will "
                         "never escalate. Enable --perf-accounting or "
                         "declare an SLO (or set --brownout-burn > 0).")
        # zero-downtime lifecycle (--model-watch SECONDS): registry +
        # watcher + warmup + swap controller over <model>.bundles/
        self.lifecycle = None
        self.watcher = None
        watch_s = float(options.get("model-watch", 0) or 0)
        if watch_s > 0:
            self._init_lifecycle(watch_s, translate_lines,
                                 executor_factory)
        if fleet_spec:
            self._init_fleet(fleet_spec, executor_factory)

    # The decode-output-shaping flags iteration mode must take a
    # position on, and that position (ISSUE 16). True = lifted into the
    # paged engines (translator/decode_features.py); a string = why the
    # paged path still refuses it. EVERY flag in DECODE_SURFACE_FLAGS
    # must appear here: a set flag with no entry is refused as
    # UNCLASSIFIED rather than silently decoded without its feature —
    # no flag may fall through to wrong output (the regression test in
    # tests/test_decode_features.py pins exactly that).
    DECODE_SURFACE_FLAGS = ("n-best", "output-sampling", "force-decode",
                            "shortlist", "alignment", "word-scores",
                            "output-approx-knn")
    ITERATION_DECODE_SURFACE = {
        "n-best": True,
        "output-sampling": True,
        "force-decode": True,
        "shortlist": True,
        "alignment": "alignment output — the paged step keeps no "
                     "per-row attention tap",
        "word-scores": "per-word scores — the paged step keeps no "
                       "per-token logp trail",
        "output-approx-knn": "approximate-knn output layers — the LSH "
                             "projection is batch-shaped, not per-row",
    }

    @classmethod
    def _validate_iteration_options(cls, options) -> None:
        """--batching-mode iteration composes with a restricted option
        surface (docs/DEPLOYMENT.md "decode-surface matrix"): the paged
        engines decode a single model (greedily at --beam-size 1,
        copy-on-write beam search above) and — since ISSUE 16 — carry
        the per-row decode-feature plane (shortlist, sampling, n-best,
        force-decode). What remains unsupported fails LOUDLY at boot
        via ITERATION_DECODE_SURFACE above, rather than serving
        something subtly different from what was asked. --model-watch
        DOES compose since ISSUE 11: swaps/canaries/rollbacks re-point
        the engine through the quiesce protocol at a step boundary with
        an empty join set (--quiesce-deadline bounds the drain)."""
        problems = []
        beam = int(options.get("beam-size", 6) or 6)
        if beam < 1:
            problems.append("--beam-size must be >= 1")
        steps = int(options.get("iteration-steps", 1) or 1)
        if steps < 1:
            problems.append("--iteration-steps must be >= 1 (got "
                            f"{steps})")
        merge = str(options.get("iteration-beam-merge", "fused")
                    or "fused")
        if merge not in ("fused", "host"):
            problems.append(f"--iteration-beam-merge {merge!r} "
                            "(choose 'fused' or 'host')")
        elif merge == "host" and steps > 1 \
                and (beam > 1 or bool(options.get("n-best", False))):
            problems.append(
                "--iteration-beam-merge host with --iteration-steps "
                f"{steps}: the host merge needs the host between steps "
                "(rounds run single-step) — drop to --iteration-steps 1 "
                "or keep the default fused merge")
        if beam > int(options.get("iteration-rows", 32) or 32):
            problems.append(
                f"--beam-size {beam} exceeds --iteration-rows "
                f"{options.get('iteration-rows', 32)} (one sentence "
                f"needs beam-size decode slots)")
        models = list(options.get("models", []) or [])
        if len(models) > 1:
            problems.append("--models ensembles are not supported")
        set_flags = []
        for flag in cls.DECODE_SURFACE_FLAGS:
            v = options.get(flag, None)
            if v in (None, False, [], "", 0):
                continue
            set_flags.append(flag)
            verdict = cls.ITERATION_DECODE_SURFACE.get(flag)
            if verdict is True:
                continue
            if not verdict:
                verdict = ("UNCLASSIFIED decode flag — add it to "
                           "ITERATION_DECODE_SURFACE before serving it "
                           "in iteration mode")
            problems.append(f"--{flag} ({verdict})")
        if "shortlist" in set_flags and "force-decode" in set_flags:
            # same refusal the FeaturePlane constructor makes — caught
            # here so the operator sees it at boot, not at first claim
            problems.append(
                "--shortlist together with --force-decode (forced "
                "prefix ids are full-vocab, shortlisted logits are not)")
        if int(options.get("num-devices", 0) or 0) > 1:
            problems.append("--num-devices > 1 (the paged pallas call "
                            "is GSPMD-opaque, like the fused decode "
                            "kernel)")
        if problems:
            raise ValueError(
                "--batching-mode iteration does not support: "
                + "; ".join(problems))

    def _build_engine(self):
        """Fresh PagedDecodeEngine over the boot TranslationService's
        model."""
        return self._engine_for(self.service, self.registry)

    def _engine_for(self, service, registry):
        from ..translator.iteration import PagedDecodeEngine
        tr = service.translator
        opts = self.options
        ml = max(1, int(opts.get("max-length", 50) or 50))
        # per-row decode-feature plane (ISSUE 16): shortlist / sampling
        # / n-best / force-decode, parsed from the SAME flags the dense
        # request-mode path reads; None when no feature is on (engines
        # keep their exact pre-feature compiled step)
        from ..translator.decode_features import FeaturePlane
        plane = FeaturePlane.from_options(opts, tr.src_vocab,
                                          tr.trg_vocab)
        if plane is not None:
            log.info("iteration decode-feature plane: {}",
                     plane.describe())
        prefix = None
        if opts.get("prefix-cache", False):
            from ..translator.prefix_cache import PrefixCache
            # engine-scoped cache, version-stamped with the model path:
            # a hot swap builds a fresh engine + fresh cache, so a
            # stale version's pages/outputs are unreachable
            prefix = PrefixCache(
                max_entries=int(
                    opts.get("prefix-cache-entries", 64) or 64),
                version=str((opts.get("models", None) or ["model"])[0]))
            if plane is not None and plane.n_best:
                # a cached reply would bake in the ORIGINAL request's
                # sentence numbering (the n-best block carries sids) —
                # replaying it to another request mislabels every line
                log.info("--n-best disables the prefix cache: cached "
                         "n-best replies would carry another request's "
                         "sentence ids")
                prefix = None
        kw = dict(
            max_rows=int(opts.get("iteration-rows", 32) or 32),
            page_len=int(opts.get("kv-page-len", 16) or 16),
            pool_bytes=int(opts.get("kv-pool-bytes", 0) or 0),
            src_len_cap=bucket_length(ml + 1),
            max_length_cap=ml,
            max_length_factor=float(
                opts.get("max-length-factor", 3.0) or 3.0),
            registry=registry,
            prefix_cache=prefix,
            features=plane)
        beam = int(opts.get("beam-size", 6) or 6)
        use_beam = beam > 1 or (plane is not None and plane.n_best)
        if use_beam:
            # COW paged beam search (ISSUE 12): same slot engine, one
            # sentence = beam slots, full pages shared by refcount
            from ..translator.beam_iteration import PagedBeamEngine
            norm = opts.get("normalize", 0.0)
            if norm is True:
                norm = 1.0
            # beam rounds scan --iteration-steps like greedy since
            # ISSUE 18: the fused on-device merge keeps EOS freezing
            # and the COW reorder in-graph, one host sync per round.
            # merge='host' (the A/B baseline) clamps itself to
            # single-step inside the engine; the boot validator already
            # rejected the explicit host+steps combo loudly.
            return PagedBeamEngine(
                tr.model, tr.params_list[0], tr.src_vocab, tr.trg_vocab,
                beam_size=beam,
                normalize=float(norm or 0.0),
                word_penalty=float(opts.get("word-penalty", 0.0) or 0.0),
                allow_unk=bool(opts.get("allow-unk", False)),
                merge=str(opts.get("iteration-beam-merge", "fused")
                          or "fused"),
                steps_per_round=int(opts.get("iteration-steps", 1) or 1),
                **kw)
        return PagedDecodeEngine(
            tr.model, tr.params_list[0], tr.src_vocab, tr.trg_vocab,
            steps_per_round=int(opts.get("iteration-steps", 1) or 1),
            **kw)

    def _bundle_engine_factory(self, bundle_dir: str, manifest):
        """executor_factory for iteration mode (ISSUE 11): a warmed
        candidate is a whole PagedDecodeEngine (model + its own device
        page pool) over a fresh TranslationService built against the
        bundle's model member. The EngineExecutor wrapper is callable
        for the golden smoke (warm_executor drives the engine's real
        install/step jits off the serving path) and carries ``.engine``
        for the quiesce re-point. Candidate engines declare no gauges —
        the pool gauges re-point to whichever engine installs
        (scheduler.install_engine)."""
        from ..translator.iteration import EngineExecutor
        member = os.path.basename(self._model_path())
        bopts = self.options.with_(
            models=[os.path.join(bundle_dir, member)])
        return EngineExecutor(
            self._engine_for(TranslationService(bopts), registry=None))

    def _rebuild_live_engine(self):
        """The scheduler's engine_factory (watchdog-trip rebuild — the
        wedged worker thread owns the old engine's device state): a
        fresh engine for the CURRENT live version. With the lifecycle
        attached, rebuild from the live version's bundle and hand the
        controller the replacement executor so round attribution and
        rollbacks track the engine actually serving.

        The bundle case loads a whole model ON THE EVENT LOOP — a
        bounded (seconds) stall of every connection, paid only on a
        watchdog trip / unrecovered round failure. The alternative
        (deferring the build to a thread) would let queued sentences
        join the known-broken engine in the meantime, which is worse
        than a rare bounded stall."""
        from ..translator.iteration import EngineExecutor
        lc = self.lifecycle
        if lc is not None:
            v = lc.live_version()
            if v is not None and getattr(v, "bundle_dir", ""):
                ex = self._bundle_engine_factory(v.bundle_dir,
                                                 v.manifest or {})
                lc.adopt_live_executor(ex)
                return ex.engine
        engine = self._build_engine()
        if lc is not None:
            lc.adopt_live_executor(EngineExecutor(engine))
        return engine

    def _apply_brownout(self, level: int) -> None:
        """BrownoutController's effect hook: push the level into the
        scheduler (cap tightening + row eviction) and admission (lane
        shedding)."""
        self.scheduler.set_brownout_level(
            level, cap_factor=self._brownout_cap_factor)
        self.admission.set_brownout(level, self._brownout_min_priority)

    def _set_perf_geometry(self) -> None:
        """Feed the live-MFU gauges the real model geometry when a real
        TranslationService is behind the scheduler; injected stubs
        (tests, load generators) leave the geometry unset and MFU reads
        0 rather than a guess."""
        if self.service is None:
            return
        cfg = getattr(self.service.translator.model, "cfg", None)
        if cfg is None or not hasattr(cfg, "dim_ffn"):
            return            # RNN family: no priced decode path
        obs.PERF.set_geometry(
            emb=int(cfg.dim_emb), ffn=int(cfg.dim_ffn),
            enc_depth=int(getattr(cfg, "enc_depth", 6)),
            dec_depth=int(getattr(cfg, "dec_depth", 6)),
            vocab=len(self.service.translator.trg_vocab),
            beam=int(self.options.get("beam-size", 12) or 12))

    def _model_path(self) -> str:
        models = self.options.get("models", []) or []
        return str(models[0] if models
                   else self.options.get("model", "") or "")

    @staticmethod
    def _adopt_boot_bundle(model_path: str, valid):
        """Which committed bundle IS the flat (published) model file?
        Same inode in the normal hardlink-publish case; otherwise ONE
        content hash of the flat file compared against each manifest's
        recorded member sha256 (copy-fallback publish). None when it
        matches no bundle (stale publish, hand-copied model)."""
        base = os.path.basename(model_path)
        for b in reversed(valid):
            try:
                if os.path.samefile(model_path,
                                    os.path.join(b.bundle_dir, base)):
                    return b
            except OSError:
                continue
        try:
            flat_sha = bdl.file_sha256(model_path)
        except OSError:
            return None
        for b in reversed(valid):
            rec = (b.manifest or {}).get("members", {}).get(base) or {}
            if rec.get("sha256") == flat_sha:
                return b
        return None

    def _init_lifecycle(self, interval: float, boot_translate,
                        executor_factory) -> None:
        from ..serving.lifecycle import (BundleWatcher, SwapController,
                                         load_golden, scan_bundles)
        model_path = self._model_path()
        if not model_path:
            log.warn("--model-watch: no model path to watch; lifecycle "
                     "disabled")
            return
        iteration = self.batching_mode == "iteration"
        factory = executor_factory or (
            self._bundle_engine_factory if iteration
            else self._bundle_executor_factory)
        self.lifecycle = SwapController(
            executor_factory=factory,
            metrics_registry=self.registry,
            canary_fraction=float(
                self.options.get("canary-fraction", 0) or 0),
            rollback_error_rate=float(
                self.options.get("rollback-error-rate", 0.5) or 0.5),
            rollback_p99_factor=float(
                self.options.get("rollback-p99-factor", 0) or 0),
            canary_min_batches=int(
                self.options.get("canary-min-batches", 8) or 8),
            golden=load_golden(
                self.options.get("warmup-golden", "") or None))
        # seed the boot model as the live version. The flat model file is
        # NORMALLY the published view of the newest valid bundle — but
        # only when it verifiably IS that bundle's member (a crash
        # between bundle commit and flat publish, or a hand-copied
        # model, leaves the flat file older). Adopt the seq of the
        # bundle the flat file actually matches, so the watcher warms +
        # swaps to anything newer instead of silently serving stale
        # weights labeled with the newest bundle's name.
        boot_seq, boot_name, boot_compat = 0, "boot", None
        valid = [b for b in scan_bundles(model_path) if b.ok]
        adopted = self._adopt_boot_bundle(model_path, valid)
        if adopted is not None:
            boot_seq = adopted.seq
            boot_name = os.path.basename(adopted.bundle_dir)
            boot_compat = bdl.manifest_compat(adopted.manifest)
            if adopted is not valid[-1]:
                log.warn("--model-watch: boot model {} matches {} but "
                         "newer committed bundles exist (stale publish?); "
                         "the watcher will hot-swap to the newest",
                         model_path, boot_name)
        elif valid:
            # valid bundles exist but the flat file matches none of them:
            # seed one seq below the newest so the watcher ingests it
            boot_seq = valid[-1].seq - 1
            log.warn("--model-watch: boot model {} matches no committed "
                     "bundle; seeding as '{}' (seq {}) so the newest "
                     "bundle is warmed and swapped in", model_path,
                     boot_name, boot_seq)
        if boot_compat is None and self.service is not None:
            opts = self.service.translator.options
            boot_compat = bdl.compat_block(
                opts, list(opts.get("vocabs", None) or []))
        if iteration:
            # the boot "executor" in iteration mode wraps the engine the
            # scheduler is already running; the quiesce protocol re-
            # points at successors' engines (ISSUE 11)
            from ..translator.iteration import EngineExecutor
            self.lifecycle.seed_live(
                boot_seq, boot_name, EngineExecutor(self.scheduler.engine),
                compat=boot_compat)
            self.lifecycle.attach_iteration(
                self.scheduler,
                float(self.options.get("quiesce-deadline", 2.0) or 2.0))
        else:
            self.lifecycle.seed_live(boot_seq, boot_name, boot_translate,
                                     compat=boot_compat)
            self.scheduler.translate_lines = self.lifecycle.route
        self.scheduler.version_fn = self.lifecycle.live_version_name
        self.watcher = BundleWatcher(bdl.bundle_root(model_path),
                                     self.lifecycle.ingest,
                                     interval=interval,
                                     last_seq=boot_seq)
        # same-process trainer (online learning): commits push the
        # watcher instead of waiting out the poll interval
        bdl.add_commit_hook(self._on_bundle_commit)

    def _on_bundle_commit(self, model_path: str, bundle_dir: str,
                          manifest) -> None:
        if self.watcher is not None \
                and os.path.dirname(os.path.abspath(bundle_dir)) \
                == os.path.abspath(self.watcher.root):
            self.watcher.notify()

    def _bundle_executor_factory(self, bundle_dir: str, manifest):
        """Build a fresh TranslationService against a bundle's model
        member (jit caches and all — warmed off the serving path, then
        swapped in whole)."""
        member = os.path.basename(self._model_path())
        bopts = self.options.with_(
            models=[os.path.join(bundle_dir, member)])
        return TranslationService(bopts).translate_lines

    def _init_fleet(self, spec: str, executor_factory) -> None:
        """--fleet (ISSUE 20): build the FleetManager — per-tenant
        lifecycle stacks under a shared HBM budget — and wire it into
        the scheduler's tenant router + per-tenant version labels and
        the per-tenant SLO engines (docs/DEPLOYMENT.md "Fleet
        serving")."""
        from ..serving import fleet as mfleet
        from ..serving.lifecycle import load_golden
        specs = mfleet.parse_fleet_spec(spec)
        tags = {s.tag for s in specs}
        if self._fleet_default and self._fleet_default not in tags:
            raise ValueError(
                f"--fleet-default-tenant '{self._fleet_default}' is not "
                f"a configured tenant (have: {', '.join(sorted(tags))})")
        opts = self.options
        self.fleet = mfleet.FleetManager(
            specs,
            executor_factory or self._fleet_executor_factory,
            metrics_registry=self.registry,
            hbm_budget_bytes=int(
                float(opts.get("fleet-hbm-budget-mb", 0) or 0) * (1 << 20)),
            watch_interval=float(opts.get("fleet-watch", 0) or 0),
            golden=load_golden(opts.get("warmup-golden", "") or None),
            canary_fraction=float(opts.get("canary-fraction", 0) or 0),
            rollback_error_rate=float(
                opts.get("rollback-error-rate", 0.5) or 0.5),
            rollback_p99_factor=float(
                opts.get("rollback-p99-factor", 0) or 0),
            canary_min_batches=int(
                opts.get("canary-min-batches", 8) or 8),
            brownout_min_priority=self._brownout_min_priority)
        n = self.fleet.build_slos(
            availability=float(opts.get("slo-availability", 0) or 0),
            p99_ms=float(opts.get("slo-p99-ms", 0) or 0))
        if n:
            log.info("fleet: per-tenant SLO engines armed for {} "
                     "tenant(s)", n)
        self.scheduler.tenant_router = self.fleet.executor_for
        self.scheduler.tenant_version_fn = self.fleet.live_version_name
        # every flight dump carries the fleet table (residency, per-
        # tenant burn, page sums) — the CI smoke's failure artifact
        obs.FLIGHT.add_snapshot_provider("fleet", self.fleet.status)

    def _fleet_executor_factory(self, bundle_dir: str, manifest):
        """Default per-tenant executor factory: a fresh
        TranslationService against the bundle's model member — or
        against ``bundle_dir`` itself when a tenant warms from a flat
        model path (no bundles committed yet)."""
        if os.path.isfile(bundle_dir):
            model = bundle_dir
        else:
            members = (manifest or {}).get("members", {}) or {}
            model = next(
                (os.path.join(bundle_dir, rel) for rel in sorted(members)
                 if rel.endswith(".npz") and "optimizer" not in rel),
                None)
            if model is None:
                raise ValueError(
                    f"fleet: bundle {bundle_dir} carries no model "
                    f"member (members: {sorted(members) or 'none'})")
        bopts = self.options.with_(models=[model])
        return TranslationService(bopts).translate_lines

    def _admin_routes(self) -> Dict:
        """Lifecycle endpoints on the metrics port: GET /lifecyclez
        (version table + health), POST /admin/pin | /admin/unpin |
        /admin/rollback (operator verbs; docs/DEPLOYMENT.md)."""
        lc = self.lifecycle

        def _lifecyclez(method: str, query: str):
            body = json.dumps(lc.status(), indent=1).encode() + b"\n"
            return 200, body, "application/json"

        def _verb(fn, name):
            def handler(method: str, query: str):
                if method != "POST":
                    return (405, b"POST only\n", "text/plain")
                ok = fn()
                ok = True if ok is None else bool(ok)
                body = json.dumps({"ok": ok, "verb": name,
                                   "live": lc.live_version_name()}
                                  ).encode() + b"\n"
                return (200 if ok else 409, body, "application/json")
            return handler

        return {
            "/lifecyclez": _lifecyclez,
            "/admin/pin": _verb(lc.pin, "pin"),
            "/admin/unpin": _verb(lc.unpin, "unpin"),
            "/admin/rollback": _verb(lc.rollback, "rollback"),
        }

    def ready(self) -> bool:
        """/readyz: accepting traffic (started, not draining, and — with
        the lifecycle — a warmed live version is routing; a replica
        still warming its first model reads 503 so load balancers hold
        traffic)."""
        if not self._started or not self.listening \
                or self.admission.draining:
            return False
        return self.lifecycle is None or self.lifecycle.has_live()

    async def start(self) -> None:
        self.scheduler.start()
        # /tracez and /sloz are always routed (they report "disabled"
        # rather than 404 — operators should not have to guess); admin
        # verbs only exist with the lifecycle
        routes = obs.trace_routes()
        routes.update(mslo.slo_routes(lambda: self.slo,
                                      lambda: self.brownout))
        # /poolz rides the metrics port like /tracez and /sloz: always
        # routed, request-mode servers answer enabled:false (ISSUE 14)
        routes.update(obs.pool_routes(lambda: self.scheduler))
        if self.lifecycle is not None:
            routes.update(self._admin_routes())
        if self.fleet is not None:
            # /fleetz: the fleet table — per-tenant residency, live
            # version, in-flight batches, cold starts, SLO burn, page
            # sums — same JSON the flight dump embeds
            routes["/fleetz"] = lambda method, query: (
                200, json.dumps(self.fleet.status(), indent=1).encode()
                + b"\n", "application/json")
        self.metrics_server = msm.maybe_start_metrics_server(
            self.options, ready_fn=self.ready, routes=routes)
        if self.slo is not None:
            self.slo.start()
        if self.brownout is not None:
            self.brownout.start()
        if self.options.get("warmup-on-boot", False):
            # not gated on the perf plane: the user asked for warm
            # buckets either way — without --perf-accounting only the
            # compile TELEMETRY is skipped (warm_bucket no-ops)
            self._boot_warmup()
        if self.watcher is not None:
            self.watcher.start()
        if self.fleet is not None:
            # pre-warm every tenant the budget allows (spec order; the
            # earliest-warmed become the LRU victims under pressure) and
            # start the per-tenant SLO evaluator + bundle watchers
            self.fleet.start()
        self._started = True
        log.info("Serving: token budget {} padded tokens/batch, queue "
                 "limit {} sentences, request timeout {}",
                 self.scheduler.token_budget,
                 self.admission.max_queue_units or "unbounded",
                 f"{self.request_timeout}s" if self.request_timeout
                 else "none")

    def _boot_warmup(self) -> None:
        """--warmup-on-boot: per-bucket golden warmup of the boot
        executor BEFORE the first client lands, reported as
        trigger=boot-warmup compile telemetry (ISSUE 9) — without it the
        first request of every width bucket pays the jit inline and
        shows up as a steady-state recompile incident. A failure here
        (a compile the chip refuses, a model that cannot decode) stops
        the boot: the same failure would otherwise meet the first
        client."""
        from ..serving.lifecycle.warmup import (DEFAULT_GOLDEN,
                                                load_golden, smoke_buckets)
        golden = load_golden(
            self.options.get("warmup-golden", "") or None) \
            or list(DEFAULT_GOLDEN)
        # warm under the EXACT label the scheduler will stamp on batches
        # (its version_fn — "unversioned" without a lifecycle): a
        # mismatched label would leave every warmed bucket reading as a
        # steady-state recompile incident
        version = self.scheduler._version_label()
        smoke_buckets(self.scheduler.translate_lines, golden,
                      version, "boot-warmup", "boot model")

    async def handle_text(self, text: str, priority: int = 0) -> str:
        """One protocol frame in, one reply frame out — the transport-
        agnostic request path (admission -> scheduler -> reply).
        Convenience over :meth:`handle_frame` for callers that don't
        report the reply-write moment (or stream partials)."""
        reply, done = await self.handle_frame(text, priority)
        done(len(reply.encode("utf-8")))   # nbytes means BYTES everywhere
        return reply

    async def handle_frame(self, text: str, priority: int = 0,
                           send_partial: Optional[
                               Callable[[str], None]] = None
                           ) -> Tuple[str, Callable[[int], None]]:
        """(reply, done) — the transports call ``done(nbytes)`` after
        the reply bytes hit the socket, which closes the request's root
        span with a ``reply.write`` child covering the write (ISSUE 8:
        the span tree spans ingest → … → reply write). ``done`` is a
        no-op when tracing is off.

        ``send_partial`` is the transport's partial-frame writer for
        #stream: clients (called on the event-loop thread, in order,
        strictly before this coroutine returns the final reply); None
        means the transport cannot stream — the header is then ignored,
        which is also the request-mode behavior."""
        t0 = time.perf_counter()
        trace_id, body = split_trace_header(text)
        model_tag, body = split_model_header(body)
        hdr_priority, body = split_priority_header(body)
        if hdr_priority is not None:
            priority = hdr_priority
        stream, body = split_stream_header(body)
        on_partial = None
        if stream and send_partial is not None:
            def on_partial(idx: int, partial: str, _ntok: int) -> None:
                send_partial(f"{PARTIAL_PREFIX}{idx} {partial}")
        lines = body.split("\n")
        # fleet mode (ISSUE 20): the #model: tag picks the tenant (or
        # --fleet-default-tenant); without a fleet the header is payload
        tenant = ""
        if self.fleet is not None:
            tenant = model_tag or self._fleet_default

        def finish(outcome: str, reply: str):
            if self.fleet is not None and tenant:
                # the tenant-labeled series the per-tenant SLO engines
                # burn against (end-to-end latency, this coroutine)
                self.fleet.note_outcome(tenant, outcome,
                                        time.perf_counter() - t0)
            return self._finish_frame(trace_id, meta, span, outcome,
                                      reply)

        span = None
        if obs.enabled():
            span = obs.start_span("request", trace_id=trace_id or None,
                                  n_sentences=len(lines),
                                  priority=priority, tenant=tenant)
        # reply metadata (queue vs service breakdown) is collected iff
        # the client asked for it by sending a trace header
        meta: Optional[Dict] = {} if trace_id is not None else None
        if self.fleet is not None and not self.fleet.has_tenant(tenant):
            # a WELL-FORMED but unconfigured tag (or no tag and no
            # default) is an explicit error — translating legal text
            # with the wrong model is the one thing a fleet must never
            # do. Shed label "?" — tags are client-controlled, and an
            # unbounded label value would be a cardinality bomb.
            self.fleet.note_shed("?", "unknown_tenant")
            tenant = ""     # don't bill outcomes to the unknown tag
            return finish(
                "failure",
                f"!!SERVER-ERROR unknown model tag "
                f"'{model_tag or self._fleet_default or '(none)'}' — "
                f"send #model:<tag> "
                f"(configured: {', '.join(self.fleet.tags())})")
        n_pages = (sum(self._pages_for_text(l) for l in lines)
                   if self._pages_for_text is not None else 0)
        try:
            # admit inside the span context so a shed's timeline event
            # inherits the trace id (flight dumps tie it to the victim);
            # the per-tenant gate runs first — a tenant burning its own
            # error budget sheds before it costs global queue space
            with obs.TRACER.use(span):
                if self.fleet is not None:
                    self.fleet.gate(tenant, priority)
                self.admission.admit(len(lines), n_pages=n_pages,
                                     priority=priority)
        except Overloaded as e:
            return finish("shed", f"!!SERVER-OVERLOADED {e}")
        with obs.TRACER.use(span):
            fut = self.scheduler.submit(
                lines, priority=priority,
                timeout=self.request_timeout or None,
                meta=meta, trace_id=trace_id, on_partial=on_partial,
                tenant=tenant)
        try:
            out = await fut
        except RequestTimeout as e:
            return finish("timeout", f"!!SERVER-TIMEOUT {e}")
        except DispatchStalled as e:
            # watchdog liveness trip: explicitly retriable — the replica
            # is healthy again (fresh device worker), resend the request
            return finish("stalled", f"!!SERVER-RETRY {e}")
        except RowEvicted as e:
            # quiesce-deadline / brownout / recoverable-engine-failure
            # eviction (ISSUE 11): pages freed, replica healthy or about
            # to be — explicitly retriable, counted, never silent
            return finish("evicted", f"!!SERVER-RETRY {e}")
        except asyncio.CancelledError:
            # client abort: record the root span before unwinding — an
            # aborted request is exactly what an operator inspects later,
            # and an un-ended span never reaches the ring
            if self.fleet is not None and tenant:
                self.fleet.note_outcome(tenant, "cancelled",
                                        time.perf_counter() - t0)
            obs.end(span, outcome="cancelled")
            raise
        except Exception:  # error already logged by the scheduler
            return finish("failure", "")
        return finish("ok", "\n".join(out))

    @staticmethod
    def _finish_frame(trace_id: Optional[str], meta: Optional[Dict],
                      span, outcome: str, reply: str
                      ) -> Tuple[str, Callable[[int], None]]:
        """Prepend the reply-metadata header for tracing clients and
        build the ``done`` callback that records the write + ends the
        root span."""
        if trace_id is not None:
            m = meta or {}
            line = (f"{TRACE_PREFIX}{trace_id} "
                    f"outcome={m.get('outcome', outcome)} "
                    f"queue_ms={m.get('queue_s', 0.0) * 1e3:.1f} "
                    f"service_ms={m.get('service_s', 0.0) * 1e3:.1f} "
                    f"model_version={m.get('model_version', '-')}")
            if "rounds" in m:
                # iteration-mode row breakdown (ISSUE 14): decode
                # rounds participated, time-to-first-join (-1 = never
                # joined), prefix-cache hit flag, retriable evictions
                line += (f" rounds={m['rounds']} "
                         f"ttfj_ms={m.get('ttfj_ms', -1.0):.1f} "
                         f"prefix_hit={m.get('prefix_hit', 0)} "
                         f"evictions={m.get('evictions', 0)}")
            reply = line + "\n" + reply
        if span is None:
            return reply, lambda nbytes=0: None
        t_reply = time.perf_counter()

        def done(nbytes: int = 0) -> None:
            obs.TRACER.record("reply.write", t_reply, time.perf_counter(),
                              parent=span, nbytes=nbytes)
            obs.end(span, outcome=outcome)
        return reply, done

    async def shutdown(self, drain_timeout: float = DRAIN_TIMEOUT_S) -> bool:
        """Drain-on-shutdown: stop admitting (readyz flips to 503 so load
        balancers stop routing here), finish queued work, then stop."""
        self.admission.begin_drain()
        queued = self.scheduler.queued_units()
        if queued:
            log.info("Draining {} queued sentences (up to {}s)", queued,
                     drain_timeout)
        ok = await self.scheduler.drain(drain_timeout)
        if not ok:
            log.warn("Drain timed out after {}s — queued requests failed",
                     drain_timeout)
        # the scheduler resolving the last futures and the per-connection
        # handler tasks WRITING those replies are separate loop steps — a
        # short grace lets the handlers flush before the transport (and
        # then the loop) tears down, else drained work still resets
        # client connections
        await asyncio.sleep(0.2)
        self.close_nowait()
        return ok

    def close_nowait(self) -> None:
        """Synchronous hard cleanup (cancelled contexts, test teardown)."""
        self._started = False
        if self._perf_wired:
            # unwire the process-global headroom gauge from this app's
            # scheduler: a scrape after close must not sample a dead
            # scheduler (or keep its model graph alive via the bound
            # method)
            obs.PERF.set_capacity_inputs(None, 0)
            self._perf_wired = False
        if self._pool_provider:
            obs.FLIGHT.remove_snapshot_provider("pool")
            self._pool_provider = False
        if self.slo is not None:
            self.slo.stop()
            obs.FLIGHT.remove_snapshot_provider("slo")
        if self.brownout is not None:
            self.brownout.stop()
            obs.FLIGHT.remove_snapshot_provider("brownout")
            self.brownout = None
        if self.watcher is not None:
            bdl.remove_commit_hook(self._on_bundle_commit)
            self.watcher.stop()
            self.watcher = None
        if self.fleet is not None:
            obs.FLIGHT.remove_snapshot_provider("fleet")
            self.fleet.stop()
            self.fleet = None
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None


def _make_ws_handler(app: ServingApp):
    """The per-connection WebSocket protocol, shared by _serve and the
    tests (so the real wiring is what gets exercised). A dropped
    connection cancels the handler task mid-await, which cancels the
    request future — the scheduler then discards its queued sentences
    before they cost device time (cancellation propagation).

    Streaming (#stream:, ISSUE 16): partial frames are enqueued by the
    scheduler's round loop while ``handle_frame`` is awaited; a per-
    connection drainer task sends them in order, and the final reply
    rides the SAME queue, so a client can never see it before (or
    interleaved with) its partials."""
    async def handler(ws):
        q: "asyncio.Queue[Optional[str]]" = asyncio.Queue()

        async def _drain():
            while True:
                frame = await q.get()
                try:
                    await ws.send(frame)
                finally:
                    q.task_done()

        drainer = asyncio.ensure_future(_drain())
        try:
            async for message in ws:
                reply, done = await app.handle_frame(
                    message, send_partial=q.put_nowait)
                nbytes = 0
                try:
                    q.put_nowait(reply)
                    flushed = asyncio.ensure_future(q.join())
                    # a dead drainer (send failed: client gone) leaves
                    # queue items un-acked forever — never await join
                    # unguarded
                    await asyncio.wait({flushed, drainer},
                                       return_when=asyncio.FIRST_COMPLETED)
                    if not flushed.done():
                        flushed.cancel()
                        drainer.result()     # surface the send error
                    # UTF-8 byte count, matching the TCP path — the trace
                    # attribute must mean the same thing on both
                    # transports
                    nbytes = len(reply.encode("utf-8"))
                finally:
                    # root span must close even when the send fails
                    # (client abort is exactly the case an operator
                    # inspects later)
                    done(nbytes)
        finally:
            drainer.cancel()
    return handler


def _make_tcp_handler(app: ServingApp):
    """Length-prefixed TCP framing: ``MTPU <nbytes>\\n`` + payload, both
    directions. Dependency-free stand-in for the ws transport (same
    ServingApp path) — used by scripts/loadgen.py and the serving tests.

    Cancellation parity with the ws transport: while a reply is pending,
    the connection is watched for EOF — a client that disconnects cancels
    its request, so the scheduler drops the queued sentences before they
    cost device time (same guarantee the ws path gets from the handler
    task being cancelled on close). The watch is RE-ARMED after every
    pipelined chunk (PR 8 review fix: it previously stopped at the first
    byte, so a pipelining client's disconnect was only noticed at
    reply-write time — its queued sentences still cost device work);
    read-ahead lands in a buffer the framing reads drain first."""
    async def on_connection(reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter):
        # bytes read ahead by the EOF watch of a pipelining client —
        # drained by _readline/_readexactly before touching the socket
        buf = b""

        async def _readline() -> bytes:
            nonlocal buf
            if b"\n" in buf:
                line, _, rest = buf.partition(b"\n")
                buf = rest
                return line + b"\n"
            line, buf = buf, b""
            return line + await reader.readline()

        async def _readexactly(n: int) -> bytes:
            nonlocal buf
            take, buf = buf[:n], buf[n:]
            if len(take) < n:
                take += await reader.readexactly(n - len(take))
            return take

        try:
            while True:
                header = await _readline()
                if not header:
                    break
                parts = header.split()
                # the length must be a NON-NEGATIVE integer before it
                # reaches _readexactly: python slicing with a negative
                # count would silently mis-slice buffered read-ahead
                # bytes (the raw StreamReader used to raise for us), and
                # a non-numeric length deserves the explicit bad-frame
                # reply, not a silent close
                nbytes = (int(parts[1])
                          if len(parts) == 2 and parts[0] == b"MTPU"
                          and parts[1].isdigit() else -1)
                if nbytes < 0:
                    writer.write(b"MTPU 24\n!!SERVER-ERROR bad frame")
                    await writer.drain()
                    break
                payload = await _readexactly(nbytes)

                def _send_partial(frame: str) -> None:
                    # one MTPU frame per partial (#stream:, ISSUE 16),
                    # written on the event-loop thread in delivery
                    # order, always before the final reply frame below;
                    # TCP backpressure is absorbed by the writer buffer
                    # and drained with the final reply
                    b = frame.encode("utf-8")
                    writer.write(b"MTPU %d\n" % len(b) + b)

                reply_t = asyncio.ensure_future(
                    app.handle_frame(payload.decode("utf-8"),
                                     send_partial=_send_partial))
                eof = False
                while not reply_t.done():
                    if len(buf) >= MAX_READAHEAD:
                        # bounded read-ahead: past the cap, stop reading
                        # and let TCP backpressure throttle the client
                        # (a flooding pipeliner must not grow server
                        # memory while a reply is in flight; EOF in this
                        # state is noticed at reply-write time, like the
                        # pre-watch behavior)
                        await asyncio.wait({reply_t})
                        break
                    watch = asyncio.ensure_future(reader.read(65536))
                    await asyncio.wait({reply_t, watch},
                                       return_when=asyncio.FIRST_COMPLETED)
                    if watch.done():
                        data = watch.result()
                        if not data:    # EOF: client gone mid-request
                            eof = True
                            break
                        buf += data     # pipelined bytes: keep, re-watch
                    else:
                        # cancelling an un-fired read() consumes nothing
                        watch.cancel()
                        try:
                            await watch
                        except asyncio.CancelledError:
                            pass
                if eof and not reply_t.done():
                    reply_t.cancel()
                    try:
                        await reply_t
                    except (asyncio.CancelledError, Exception):  # noqa: BLE001
                        pass
                    break
                reply, reply_done = await reply_t
                out = reply.encode("utf-8")
                nbytes = 0
                try:
                    writer.write(b"MTPU %d\n" % len(out) + out)
                    await writer.drain()
                    nbytes = len(out)
                finally:
                    # close the root span even when the write fails —
                    # a mid-write disconnect must not drop the request's
                    # span tree from /tracez and flight dumps
                    reply_done(nbytes)
        except (asyncio.IncompleteReadError, ConnectionError, ValueError):
            pass                     # client went away / malformed frame
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass
    return on_connection


async def _serve(options, ready: Optional[asyncio.Future] = None) -> None:
    """Serve forever. `ready` (tests): resolved with the bound port once
    listening — pass --port 0 to bind an ephemeral port."""
    app = ServingApp(options)
    # /readyz must not say ready between start() and the bind below: a
    # client that trusts it would be refused (seen on the chip, where the
    # gap is wide enough to hit)
    app.listening = False
    await app.start()
    port = int(options.get("port", 8080))

    def _announce(bound: int, transport: str) -> None:
        app.listening = True
        log.info("Server is listening on port {} ({})", bound, transport)
        if ready is not None and not ready.cancelled():
            ready.set_result(bound)

    async def _serve_until_cancelled() -> None:
        """Runs INSIDE the transport's serve context so the graceful
        drain completes while client connections are still open — in-
        flight clients get their replies before the listener (and with
        it every connection) is torn down on context exit."""
        try:
            await asyncio.Future()
        except asyncio.CancelledError:
            # shielded from the cancellation already delivered to this
            # task: finish queued work before going down
            await asyncio.shield(app.shutdown())
            raise

    try:
        if HAVE_WS:
            async with websockets.serve(_make_ws_handler(app), "0.0.0.0",
                                        port) as server:
                _announce(next(iter(server.sockets)).getsockname()[1],
                          "websocket")
                await _serve_until_cancelled()
        else:
            log.warn("the 'websockets' package is unavailable — serving "
                     "the length-prefixed TCP framing instead (Marian ws "
                     "clients cannot connect; scripts/loadgen.py "
                     "--transport tcp speaks it)")
            server = await asyncio.start_server(
                _make_tcp_handler(app), "0.0.0.0", port)
            async with server:
                _announce(server.sockets[0].getsockname()[1], "tcp")
                await _serve_until_cancelled()
    finally:
        app.close_nowait()


def serve_main(options) -> None:
    from ..common.profiling import enable_compilation_cache
    enable_compilation_cache()

    async def _main():
        import signal
        loop = asyncio.get_event_loop()
        task = asyncio.ensure_future(_serve(options))
        # SIGTERM (orchestrator shutdown) and SIGINT both route through
        # _serve's cancellation path: drain, then exit
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, task.cancel)
            except (NotImplementedError, RuntimeError):  # pragma: no cover
                pass                         # non-Unix / nested loop
        try:
            await task
        except asyncio.CancelledError:
            pass

    try:
        asyncio.run(_main())
    except KeyboardInterrupt:  # signal handler could not be installed
        pass
