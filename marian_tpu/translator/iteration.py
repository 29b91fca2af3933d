"""Iteration-level (continuous) decoding over a paged KV pool.

Request-mode serving (serving/scheduler.py default) packs whole requests
into device batches: a sentence admitted mid-decode waits for the
current batch to drain, and the dense per-batch cache makes every row
pay the longest member's decode length. This module turns decode rows
into SLOTS over one shared paged KV pool (ops/pallas/kv_pool.py):

- a sentence JOINS a running decode at any step boundary, claiming a
  slot and enough pages for its own decode cap, and starts at its own
  position 0 while its neighbors are at position 40;
- a finished sentence LEAVES at the step it emits EOS, releasing its
  pages immediately — capacity returns to the admission plane per
  sentence, not per batch;
- each step runs one jitted decode over the occupied slot prefix,
  rounded UP to a ROW BUCKET (ops/pallas/kv_pool.ROW_BUCKETS) so every
  step lands on one of a small closed set of compiled shapes — the TPU
  static-shape compilation model is preserved by bucketing, never by
  dynamic shapes.

This engine is GREEDY (beam 1) — the production high-throughput serving
config (cf. bench_decode's MARIAN_DECBENCH_BEAM=1 "student serving"
note). Beam>1 iteration decoding rides the SAME slot machinery via
copy-on-write page sharing across hypotheses — refcounted full pages,
per-beam partial pages (translator/beam_iteration.py; the server picks
the engine by --beam-size). Cross-request prefix sharing (ISSUE 12,
--prefix-cache) composes with both: an exact source repeat forks
copy-on-write from a live row or replays a completed decode
(translator/prefix_cache.py).

Threading contract: every device-touching method (``admit_and_step``)
runs on the serving scheduler's single device worker thread. The
metrics scrape thread reads only the counters guarded by
``PagedDecodeEngine._lock`` and the pool's own lock.

Determinism: joins are applied in caller order onto the LOWEST free
slot, page claims pop a deterministic free list, idle slots write only
zeros into the reserved trash page — replaying an identical join/evict
schedule yields bitwise-identical outputs (tests/test_iteration.py).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..common import faultpoints as fp
from ..common import jitwit
from ..common import lockdep
from ..common import logging as log
from ..data.vocab import EOS_ID
from ..ops.pallas.kv_pool import (DEFAULT_PAGE_LEN, KVPool, PoolCorruption,
                                  PoolExhausted, ROW_BUCKETS, bucket_rows,
                                  check_kernel_rows, pages_for_tokens)
from .decode_features import RowFeatures
from .prefix_cache import PrefixCache

# continuous pool auditing: with MARIAN_POOL_AUDIT=1 every admit+step
# round ends with a full invariant audit (tests/conftest.py arms it for
# the whole tier-1 run); without it the audit runs only at quiesce
# boundaries and the cheap row-exit leak check stays always-on
ENV_POOL_AUDIT = "MARIAN_POOL_AUDIT"

# fatal join-rejection reasons: the sentence can NEVER be admitted (the
# scheduler fails its request explicitly instead of re-queueing — this
# is what keeps a drained pool from deadlocking the step loop behind an
# unadmittable head-of-line sentence)
FATAL_REASONS = ("src_too_long", "too_large")


@dataclass
class StepResult:
    """One admit+step round on the device worker thread."""
    accepted: List[object] = field(default_factory=list)
    # key -> reason; reasons in FATAL_REASONS are permanent
    rejected: List[Tuple[object, str]] = field(default_factory=list)
    # key -> operator-actionable detail for FATAL rejections (the
    # computed page requirement vs the pool's capacity — ISSUE 11: the
    # error a client sees must tell the operator which knob to turn)
    reject_detail: Dict[object, str] = field(default_factory=dict)
    finished: List[Tuple[object, str]] = field(default_factory=list)
    # per-key decode detail for finished sentences (beam engine: raw /
    # length-normalized scores, hypothesis length — the parity tests
    # and n-best-curious callers read it; greedy leaves it empty)
    finished_info: Dict[object, dict] = field(default_factory=dict)
    # rows evicted MID-DECODE because a lazy COW page claim found the
    # pool dry (beam divergence): retriable by contract — the serving
    # scheduler fails them with RowEvicted (!!SERVER-RETRY)
    pool_evicted: List[object] = field(default_factory=list)
    rows: int = 0                 # active rows this round (before finishes)
    bucket: int = 0               # compiled row bucket the round ran at
    tokens: int = 0               # target tokens consumed this round
    steps: int = 0                # decode steps the round advanced
    # the engine's last install width (halving encode bucket; 0 before
    # any install) — with `bucket` and `steps` it forms the round's
    # steady-state compile key the scheduler reports to obs.PERF
    enc_bucket: int = 0
    device_s: float = 0.0         # admit+step wall on the worker thread
    mid_decode_joins: int = 0     # joins that landed beside running rows
    # per-row lifecycle instants this round (ISSUE 14): (key, name,
    # attrs) tuples — prefix-cache hits/forks, COW events — that the
    # serving scheduler turns into timeline events tagged with the
    # row's trace id (the engine never learns trace ids) and into the
    # #trace reply-metadata row breakdown. Always populated (the reply
    # metadata is tracing-independent); tiny and rare, never per-token.
    row_events: List[Tuple[object, str, dict]] = field(default_factory=list)
    # streaming (ISSUE 16): per-round partial target text for rows whose
    # join meta asked for it — (key, text_so_far, tokens_so_far) for
    # STILL-DECODING rows; a finishing row's last text arrives via
    # ``finished`` as always. The serving scheduler fans these out to
    # #stream: clients between rounds.
    partials: List[Tuple[object, str, int]] = field(default_factory=list)
    # pool page traffic THIS round (deltas of KVPool.stats + the
    # engine's fork-copy count) — the serve.round span attrs and the
    # marian_serving_kv_pool_pages_*_total series read these
    pages_claimed: int = 0
    pages_freed: int = 0
    pages_aliased: int = 0
    pages_copied: int = 0


class _Slot:
    __slots__ = ("key", "tokens", "pos", "cap", "prev", "src_tokens",
                 "expected_refs", "src_key", "feat")

    def __init__(self, key, cap: int, src_tokens: int,
                 expected_refs: int = 0, src_key=None, feat=None):
        self.key = key
        self.tokens: List[int] = []
        self.pos = 0                # next write position
        self.cap = cap              # decode cap (max positions)
        self.prev = 0               # previous token id (0 at pos 0)
        self.src_tokens = src_tokens
        # page REFERENCES this row's exit must give back (cap pages for
        # a cold join; aliased fulls + owned tail for a prefix fork) —
        # the row-exit leak check compares against it
        self.expected_refs = expected_refs
        self.src_key = src_key      # source id tuple (prefix-cache key)
        self.feat = feat            # RowFeatures (decode_features.py)


class PagedDecodeEngine:
    """Slot-based continuous greedy decoder over a paged KV pool."""

    # encode-at-join batch buckets (one compiled encoder shape per entry)
    JOIN_BUCKETS = (1, 2, 4, 8)
    # n-best needs per-hypothesis score bookkeeping (PagedBeamEngine)
    _SUPPORTS_NBEST = False

    def __init__(self, model, params, src_vocab, trg_vocab,
                 max_rows: int = 32,
                 page_len: int = DEFAULT_PAGE_LEN,
                 pool_bytes: int = 0,
                 src_len_cap: int = 64,
                 max_length_cap: int = 256,
                 max_length_factor: float = 3.0,
                 row_buckets: Sequence[int] = ROW_BUCKETS,
                 steps_per_round: int = 1,
                 registry=None,
                 prefix_cache: Optional[PrefixCache] = None,
                 features=None):
        # the annotation is load-bearing beyond documentation: the
        # static callgraph types self.prefix from it, which is what
        # links the engine's claim sites to the cache's adopt/release
        # sites in the ownership graph (ISSUE 15)
        cfg = getattr(model, "cfg", None)
        if cfg is None or getattr(cfg, "decoder_autoreg", "") \
                != "self-attention":
            raise ValueError("iteration-level decoding requires a "
                             "transformer with the self-attention "
                             "autoreg decoder")
        if getattr(cfg, "n_encoders", 1) != 1:
            raise ValueError("iteration-level decoding supports a single "
                             "source stream")
        self.model = model
        self.params = params
        self.src_vocab = src_vocab
        self.trg_vocab = trg_vocab
        self.max_rows = int(max_rows)
        self.page_len = int(page_len)
        self.src_cap = int(src_len_cap)
        self.max_length_cap = int(max_length_cap)
        self.max_length_factor = float(max_length_factor)
        self.row_buckets = tuple(sorted(set(
            min(b, self.max_rows) for b in row_buckets)))
        if self.max_rows > max(row_buckets):
            # slots past the largest compiled bucket would never step
            # (and the beam merge would index past the device output)
            raise ValueError(
                f"max_rows {self.max_rows} exceeds the largest row "
                f"bucket {max(row_buckets)} (extend row_buckets or "
                f"lower --iteration-rows)")
        self.max_pages = pages_for_tokens(self.max_length_cap,
                                          self.page_len)
        # the beam engine's buckets (blocks x k) stay under max_rows too
        check_kernel_rows(self.max_rows, self.max_pages)
        # decode steps per round, run as ONE jitted lax.scan: joins are
        # still admitted every round, so admission granularity is
        # steps_per_round steps (default 1 = pure iteration-level).
        # >1 amortizes per-call dispatch/transfer on host-bound
        # backends; a row finishing mid-scan self-feeds until the host
        # cuts at its EOS — those few wasted row-steps are the price of
        # the amortization (docs/DEPLOYMENT.md)
        self.steps_per_round = max(1, int(steps_per_round))

        h, dh, depth = cfg.heads, cfg.dim_head, cfg.dec_depth
        self._dtype = cfg.compute_dtype
        dtype_bytes = jnp.dtype(self._dtype).itemsize
        # bytes one PAGE costs across the whole decoder: K+V, all layers
        self.page_bytes = 2 * depth * h * self.page_len * dh * dtype_bytes
        if pool_bytes and pool_bytes > 0:
            n_pages = 1 + max(1, int(pool_bytes) // self.page_bytes)
        else:
            n_pages = 1 + self._default_pool_pages()
        self.pool = KVPool(n_pages, self.page_len,
                           max_pages_per_row=self.max_pages)

        # device state: model paged state (pools + cross caches) plus
        # the per-slot source mask; owned by the worker thread
        d = cfg.dim_emb
        enc0 = jnp.zeros((self.max_rows, self.src_cap, d), self._dtype)
        mask0 = np.zeros((self.max_rows, self.src_cap), np.float32)
        mask0[:, 0] = 1.0       # idle rows keep one live source position
        self._src_mask = jnp.asarray(mask0)
        self._state = model.start_paged_state(
            params, enc0, self._src_mask, n_pages, self.page_len,
            self.max_pages)

        # host slot bookkeeping (worker thread); the COUNTERS cross to
        # the metrics scrape thread and ride the lock
        self._slots: List[Optional[_Slot]] = [None] * self.max_rows
        self._by_key: Dict[object, int] = {}
        self._lock = lockdep.make_lock("PagedDecodeEngine._lock")
        self._n_active = 0              # guarded-by: _lock
        self._used_tokens = 0           # guarded-by: _lock
        self._ever_stepped = False
        # brownout level 1 (serving/brownout.py): NEW joins claim a
        # scaled-down decode cap so each row costs fewer pages/steps
        # under sustained overload. Written by the brownout thread,
        # read on the worker thread — a single float, no invariant
        # couples it to other state, so it rides no lock.
        self._cap_scale = 1.0
        self._audit_always = os.environ.get(ENV_POOL_AUDIT, "") == "1"
        # engine round counters + last-audit verdict for the /poolz
        # inspector (ISSUE 14): plain ints written on the worker thread,
        # read by the metrics/poolz HTTP threads — hence the lock
        self._counters: Dict[str, int] = {
            "rounds": 0, "joins": 0, "mid_decode_joins": 0,
            "prefix_hits": 0, "forks": 0, "pool_evictions": 0,
            "pages_copied": 0, "audits": 0,
            "audit_failures": 0}            # guarded-by: _lock
        self._last_audit: Optional[dict] = None   # guarded-by: _lock
        # fork-copied pages in the CURRENT round (worker thread only;
        # reset at the top of admit_and_step, folded into res at its end)
        self._round_copied = 0
        self._metrics_declared = False
        # per-row decode-feature plane (ISSUE 16, decode_features.py):
        # None keeps the exact pre-feature compiled step signature
        self.features = features
        if features is not None and features.n_best \
                and not self._SUPPORTS_NBEST:
            raise ValueError("n-best needs beam bookkeeping — the server "
                             "routes it to PagedBeamEngine (any beam "
                             "size)")
        # sampling RNG lane allocator: each admitted row gets the next
        # ordinal, so a replayed join schedule replays its dice
        self._lane_ctr = 0
        # cross-request prefix sharing (--prefix-cache; ISSUE 12):
        # engine-scoped — a hot swap builds a fresh engine with a fresh
        # cache, so stale-version pages are unreachable by construction
        if features is not None and not features.cacheable \
                and prefix_cache is not None:
            log.info("iteration engine: --output-sampling disables the "
                     "prefix cache (sampled decodes must not be "
                     "replayed or forked)")
            prefix_cache = None
        self.prefix = prefix_cache

        self._step_jit: Dict[int, object] = {}
        self._install_jit: Dict[int, object] = {}
        self._fork_jit = None
        # retrace witness (common/jitwit.py, ISSUE 17): every jit
        # object this engine creates is noted under this token, so a
        # REBUILD of an already-noted compile key is caught as a
        # retrace at suite teardown. (jb, w) install shapes are noted
        # on first admission — the install jit's own cache compiles
        # one kernel per shape pair.
        self._jitwit_token = jitwit.new_token()
        self._install_shapes: set = set()    # (jb, w) pairs compiled
        self._enc_w = 0     # last install width: the round's encode
        #                     bucket for steady-state recompile keys
        self._jit_drill_nonce = 0   # jit.closure_vary drill counter

        if registry is not None:
            self._declare_metrics(registry)

    def _default_pool_pages(self) -> int:
        """Unsized-pool page budget (no --kv-pool-bytes): every slot can
        hold a full-cap row, so the pool is never the constraint —
        shrink --kv-pool-bytes to make admission page-bound. Subclasses
        add round-transient headroom on top (the fused beam merge
        preclaims a round's worst-case fresh pages before each scan)."""
        return self.max_rows * self.max_pages

    # -- metrics ------------------------------------------------------------
    def _declare_metrics(self, r) -> None:
        self.m_pool_pages = r.gauge(
            "marian_serving_kv_pool_pages",
            "Paged KV pool size in allocatable pages (page 0 reserved)")
        self.m_pool_pages.set(self.pool.usable_pages)
        self.m_pool_free = r.gauge(
            "marian_serving_kv_pool_pages_free",
            "Paged KV pool pages currently free")
        self.m_pool_free.set_function(self.pool.free_pages)
        self.m_pool_frag = r.gauge(
            "marian_serving_kv_pool_fragmentation_ratio",
            "Internal fragmentation of claimed pages: 1 - written "
            "tokens / (claimed pages x page_len)")
        self.m_pool_frag.set_function(self.fragmentation)
        self.m_active_rows = r.gauge(
            "marian_serving_active_rows",
            "Decode slots occupied by live sentences (iteration mode)")
        self.m_active_rows.set_function(self.active_rows)
        self.m_audits = r.counter(
            "marian_serving_pool_audits_total",
            "Pool invariant audits run (quiesce boundaries; every round "
            "under MARIAN_POOL_AUDIT=1)")
        self.m_audit_failures = r.counter(
            "marian_serving_pool_audit_failures_total",
            "Pool invariant audits that found violations (double-free, "
            "table/claim mismatch, refcount drift, leaked pages, "
            "row-exit leak)")
        # pool occupancy / COW telemetry (ISSUE 14): live gauges the
        # scrape thread samples, plus cumulative page-traffic counters
        # fed per round by admit_and_step. The gauges re-point to the
        # engine actually serving on every install_engine re-declare.
        self.m_pool_occupancy = r.gauge(
            "marian_serving_kv_pool_occupancy_ratio",
            "Claimed pages / allocatable pages of the paged KV pool")
        self.m_pool_occupancy.set_function(self.occupancy)
        self.m_pool_shared = r.gauge(
            "marian_serving_kv_pool_pages_shared",
            "Pages currently COW-aliased (refcount >= 2): held by more "
            "than one hypothesis/row/cache entry")
        self.m_pool_shared.set_function(
            lambda: self.pool.alias_stats()["shared"])
        self.m_pool_refmax = r.gauge(
            "marian_serving_kv_pool_refcount_max",
            "Highest live page refcount (refcount-distribution summary; "
            "1 = no sharing at all right now)")
        self.m_pool_refmax.set_function(
            lambda: self.pool.alias_stats()["max"])
        self.m_pool_alias_ratio = r.gauge(
            "marian_serving_kv_pool_cow_alias_ratio",
            "Fraction of live page-table references that are COW "
            "aliases rather than sole ownership: (refs - live pages) / "
            "refs. 0 = no sharing; rises with beam forks and prefix "
            "hits")
        self.m_pool_alias_ratio.set_function(self.cow_alias_ratio)
        self.m_rounds = r.counter(
            "marian_serving_engine_rounds_total",
            "Admit+step rounds the paged engine ran — each round is "
            "one device dispatch covering --iteration-steps decode "
            "steps (greedy AND fused-merge beam scan; only the "
            "host-merge beam baseline pins rounds to one step)")
        self.m_pages_claimed = r.counter(
            "marian_serving_kv_pool_pages_claimed_total",
            "Fresh pages claimed off the pool free list (cold joins, "
            "lazy COW growth, fork partials)")
        self.m_pages_freed = r.counter(
            "marian_serving_kv_pool_pages_freed_total",
            "Pages returned to the pool free list (row exits, beam "
            "reorders dropping dead lineages, cache evictions)")
        self.m_pages_aliased = r.counter(
            "marian_serving_kv_pool_pages_aliased_total",
            "Copy-on-write references added to already-live pages "
            "(beam forks, prefix hits, reorder shares) — pages served "
            "by aliasing instead of recompute or copy")
        self.m_pages_copied = r.counter(
            "marian_serving_kv_pool_pages_copied_total",
            "Partial pages content-copied by pool_fork_partial (the "
            "one copy a COW fork pays; cow=False replication copies "
            "full histories here too)")
        self.m_bytes_copied = r.counter(
            "marian_serving_kv_pool_bytes_copied_total",
            "Bytes moved by pool_fork_partial copies "
            "(pages_copied x the whole-decoder page cost)")
        self.m_bytes_aliased = r.counter(
            "marian_serving_kv_pool_bytes_aliased_total",
            "Bytes served by COW page aliasing instead of being copied "
            "(pages_aliased x the whole-decoder page cost) — the "
            "data-movement win the reorder/prefix sharing buys")
        self.m_forks = r.counter(
            "marian_serving_cow_forks_total",
            "Copy-on-write forks performed (prefix-cache live forks + "
            "beam-reorder child hypotheses that left their parent's "
            "row)")
        if self.prefix is not None:
            self.prefix._declare_metrics(r)
            m_held = r.gauge(
                "marian_prefix_held_pages",
                "KV pages currently held by prefix-cache entries "
                "(retained decodes an exact repeat replays for free)")
            m_held.set_function(self.prefix.held_pages)
            m_recl = r.gauge(
                "marian_prefix_reclaimable_pages",
                "Pages evicting the whole prefix cache would free "
                "RIGHT NOW (held references with page refcount 1) — "
                "the pressure-relief headroom admission already counts")
            m_recl.set_function(
                lambda: self.prefix.reclaimable_pages(self.pool))
        if self.features is not None \
                and self.features.shortlist_gen is not None:
            # lexical-shortlist series (ISSUE 16): how many rows decode
            # through a sliced output GEMM and how wide their slices are
            # — the operator's check that --shortlist actually shrinks
            # the [rows, vocab] projection (PAPER.md's serving trick)
            self.m_shortlist_rows = r.counter(
                "marian_shortlist_rows_total",
                "Decode rows admitted with a per-row lexical shortlist "
                "(iteration mode)")
            self.m_shortlist_width = r.histogram(
                "marian_shortlist_width_tokens",
                "Per-row shortlist width (the row's true padded index "
                "count — the output GEMM runs at the engine's static K)",
                buckets=(128, 256, 384, 512, 768, 1024, 2048, 4096))
        self._metrics_declared = True

    # -- capacity (any thread) ----------------------------------------------
    def active_rows(self) -> int:
        with self._lock:
            return self._n_active

    def occupancy(self) -> float:
        """Claimed / allocatable pages (any thread)."""
        return self.pool.used_pages() / float(self.pool.usable_pages)

    def cow_alias_ratio(self) -> float:
        """(references - live pages) / references — see the gauge help
        and KVPool.alias_stats (any thread)."""
        st = self.pool.alias_stats()
        return (st["refs"] - st["live"]) / st["refs"] if st["refs"] \
            else 0.0

    def _count(self, name: str, n: int = 1) -> None:
        """Bump one /poolz round counter (worker thread writes, the
        HTTP threads read the dict under the same lock)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def fragmentation(self) -> float:
        used_pages = self.pool.used_pages()
        if used_pages == 0:
            return 0.0
        with self._lock:
            used_tokens = self._used_tokens
        if self.prefix is not None:
            # cache-held pages hold real (reusable) tokens — retention
            # must not read as waste
            used_tokens += self.prefix.held_tokens()
        return max(0.0, 1.0 - used_tokens
                   / float(used_pages * self.page_len))

    def free_pages(self) -> int:
        """Free pages PLUS what evicting the prefix cache would free
        right now — page-priced admission sees relievable pressure, and
        the claim path relieves it before failing (_claim_pages)."""
        free = self.pool.free_pages()
        if self.prefix is not None:
            free += self.prefix.reclaimable_pages(self.pool)
        return free

    def free_slots(self) -> int:
        with self._lock:
            return self.max_rows - self._n_active

    def idle(self) -> bool:
        return self.active_rows() == 0

    def decode_cap(self, n_src_tokens: int) -> int:
        """Static decode cap for a sentence (mirrors BeamSearch's
        max-length-factor rule so both modes price work the same).
        Brownout level >= 1 scales it down for NEW joins — shorter rows
        claim fewer pages and leave sooner (serving/brownout.py)."""
        base = min(self.max_length_cap,
                   max(8, round(self.max_length_factor
                                * max(1, n_src_tokens))))
        return int(max(8, round(base * self._cap_scale)))

    def set_cap_scale(self, scale: float) -> None:
        """Brownout level 1: scale the decode cap of FUTURE joins (rows
        already decoding keep the cap they claimed pages for). Clamped
        so the cap never collapses below the 8-token floor's reach."""
        self._cap_scale = min(1.0, max(0.05, float(scale)))

    def row_progress(self, key) -> Optional[Tuple[int, int]]:
        """(pos, cap) of an active row, or None — the brownout eviction
        policy's 'longest remaining' tiebreak reads this (any thread)."""
        with self._lock:
            slot = self._by_key.get(key)
            if slot is None:
                return None
            s = self._slots[slot]
            return (s.pos, s.cap) if s is not None else None

    def pages_for_text(self, text: str) -> int:
        """Pages one sentence will claim (admission pricing: queue debt
        in PAGES, serving/admission.py). Token estimate only — the join
        re-measures with the real vocab encoding."""
        n_src = len(text.split()) + 1
        return pages_for_tokens(self.decode_cap(n_src), self.page_len)

    # -- the admit + step round (device worker thread only) -----------------
    def admit_and_step(self, joins: Sequence[Tuple[object, str]],
                       evicts: Sequence[object] = ()) -> StepResult:
        """Apply evictions (dead requests), admit what fits, run ONE
        decode step over the occupied slots. Never blocks on pool
        space: a join that does not fit is rejected back to the caller
        (reason ``no_slot``/``no_pages`` = retry later; FATAL_REASONS =
        fail the request)."""
        t0 = time.perf_counter()
        res = StepResult()
        # page-traffic accounting (ISSUE 14): diff the pool's cumulative
        # counters across the round — two dict copies under the pool
        # lock, nothing on the tracer (the zero-overhead guard covers
        # this path with tracing disabled)
        stats0 = self.pool.stats()
        self._round_copied = 0
        # corruption-detection drills (no-ops unless the pool.* catalog
        # points are armed): they corrupt real state so the audit below
        # is proven against the bug classes it claims to catch
        self.pool.chaos_double_free()
        self.pool.chaos_refcount_corrupt()
        self._chaos_table_corrupt()
        for key in evicts:
            self._evict(key)
        rows_before = self.active_rows()
        joiners: List[Tuple[object, List[int], int]] = []
        # joins arrive as (key, text) or (key, text, meta) — the meta
        # dict carries serving-side per-row flags (stream, sid) the
        # engine keys its feature plane off (ISSUE 16); 2-tuples keep
        # every pre-feature caller working unchanged
        for j in joins:
            key, text = j[0], j[1]
            meta = j[2] if len(j) > 2 else None
            why = self._try_claim(key, text, joiners, res.reject_detail,
                                  res=res, meta=meta)
            if why is None:
                res.accepted.append(key)
            else:
                res.rejected.append((key, why))
        if joiners:
            self._install(joiners)
            if rows_before > 0:
                # distinct keys, not joiner rows: a beam-k sentence
                # installs k hypothesis rows but is ONE mid-decode join
                res.mid_decode_joins = len({k for k, _, _ in joiners})
        if self.active_rows() > 0:
            self._step(res)
        if self._audit_always:
            bad = self.audit(context="round")
            if bad:
                # fail the round loudly: the scheduler evicts the
                # round's rows with a retriable error and rebuilds the
                # engine — corrupted page state must never serve
                # another token (docs/ROBUSTNESS.md)
                raise PoolCorruption(
                    "pool audit failed: " + "; ".join(bad[:4]))
        stats1 = self.pool.stats()
        res.pages_claimed = stats1["claimed"] - stats0["claimed"]
        res.pages_freed = stats1["freed"] - stats0["freed"]
        res.pages_aliased = stats1["aliased"] - stats0["aliased"]
        res.pages_copied = self._round_copied
        with self._lock:
            self._counters["rounds"] += 1
            self._counters["joins"] += len(res.accepted)
            self._counters["mid_decode_joins"] += res.mid_decode_joins
            self._counters["pool_evictions"] += len(res.pool_evicted)
            self._counters["pages_copied"] += res.pages_copied
        if self._metrics_declared:
            self.m_rounds.inc()
            if res.pages_claimed:
                self.m_pages_claimed.inc(res.pages_claimed)
            if res.pages_freed:
                self.m_pages_freed.inc(res.pages_freed)
            if res.pages_aliased:
                self.m_pages_aliased.inc(res.pages_aliased)
                self.m_bytes_aliased.inc(res.pages_aliased
                                         * self.page_bytes)
            if res.pages_copied:
                self.m_pages_copied.inc(res.pages_copied)
                self.m_bytes_copied.inc(res.pages_copied
                                        * self.page_bytes)
        res.device_s = time.perf_counter() - t0  # mtlint: ok -- the step's per-token fetch (np.asarray in _step) IS the result fence; this window closes host-side after it
        return res

    def _try_claim(self, key, text: str, joiners: List,
                   detail: Optional[Dict[object, str]] = None,
                   res: Optional[StepResult] = None,
                   meta: Optional[dict] = None) -> Optional[str]:
        plane = self.features
        forced: List[int] = []
        if plane is not None and plane.force_decode:
            # iteration force-decode line convention: source<TAB>prefix
            text, forced = plane.split_forced(text, self.trg_vocab)
        ids = self.src_vocab.encode(text, add_eos=True, inference=True)
        if len(ids) > self.src_cap:
            if detail is not None:
                detail[key] = (f"source encodes to {len(ids)} tokens but "
                               f"the engine's source cap is "
                               f"{self.src_cap} (raise --max-length)")
            return "src_too_long"
        src_key = tuple(int(i) for i in ids)
        if plane is not None:
            # a forced trunk salts the cache/fork key: a constrained
            # prefix is a shareable trunk, but only among requests
            # constrained the SAME way (decode_features.cache_key)
            src_key = plane.cache_key(src_key, forced)
        # cross-request prefix sharing (ISSUE 12): an exact repeat of a
        # COMPLETED decode resolves instantly (greedy decode is
        # deterministic, so the cached tokens are bitwise what a cold
        # decode would emit); a repeat of a sentence decoding RIGHT NOW
        # forks from it copy-on-write below
        if self.prefix is not None and res is not None:
            ent = self.prefix.get(src_key, self.prefix.version)
            if ent is not None:
                res.finished.append((key, ent.text))
                res.row_events.append((key, "prefix.hit",
                                       {"kind": "replay",
                                        "tokens": len(ent.tokens)}))
                self._count("prefix_hits")
                return None
        cap = self.decode_cap(len(ids))
        if forced:
            # the cap must cover the forced trunk plus continuation
            # headroom (dense twin: beam_search pads L to plen + 8)
            if len(forced) + 8 > self.max_length_cap:
                if detail is not None:
                    detail[key] = (
                        f"forced target prefix is {len(forced)} tokens "
                        f"but the engine's decode cap is "
                        f"{self.max_length_cap} (raise --max-length)")
                return "too_large"
            cap = min(self.max_length_cap, max(cap, len(forced) + 8))
        stream = bool(meta.get("stream")) if meta else False
        sid = int(meta.get("sid", 0)) if meta else 0
        feat = None
        if plane is not None:
            feat = plane.row_features(ids, forced=forced,
                                      lane=self._lane_ctr,
                                      stream=stream, sid=sid)
        elif stream or sid:
            feat = RowFeatures(stream=stream, sid=sid)
        n_pages = pages_for_tokens(cap, self.page_len)
        if n_pages > self.pool.max_pages_per_row:
            if detail is not None:
                detail[key] = (
                    f"decode cap {cap} tokens needs {n_pages} KV pages "
                    f"of {self.page_len} tokens but the page table "
                    f"holds {self.pool.max_pages_per_row}/row (raise "
                    f"--kv-page-len or --kv-pool-bytes)")
            return "too_large"
        with self._lock:
            if self._n_active >= self.max_rows:
                return "no_slot"
        if self.prefix is not None:
            forked = self._try_fork(key, src_key, cap, n_pages, len(ids),
                                    res=res, feat=feat)
            if forked is not None:
                if forked:
                    self._row_admitted(feat)
                    return None
                return "no_pages"
            self.prefix.note_miss()
        try:
            pages = self._claim_pages(key, n_pages)
        except PoolExhausted:
            # retriable only if the pool could EVER satisfy it
            if n_pages > self.pool.usable_pages:
                if detail is not None:
                    detail[key] = (
                        f"decode cap {cap} tokens needs {n_pages} KV "
                        f"pages but the whole pool holds only "
                        f"{self.pool.usable_pages} allocatable pages "
                        f"of {self.page_len} tokens (raise "
                        f"--kv-pool-bytes or lower --max-length)")
                return "too_large"
            return "no_pages"
        # lowest free slot (deterministic; keeps the occupied prefix —
        # and with it the compiled row bucket — tight)
        with self._lock:
            slot = next(i for i, s in enumerate(self._slots) if s is None)
            self._slots[slot] = _Slot(key, cap, len(ids),
                                      expected_refs=n_pages,
                                      src_key=src_key, feat=feat)
            self._by_key[key] = slot
            self._n_active += 1
        if self.prefix is not None:
            self.prefix.register_live(src_key, key)
        # page table row on the host mirror; device copy goes with the
        # next step's table upload
        self._table[slot, :] = 0
        self._table[slot, :len(pages)] = pages
        joiners.append((key, ids, slot))
        self._row_admitted(feat)
        return None

    def _row_admitted(self, feat) -> None:
        """Post-admission feature bookkeeping: advance the sampling lane
        allocator (so a replayed join schedule replays its lanes) and
        feed the shortlist series."""
        if self.features is None:
            return
        self._lane_ctr += 1
        if feat is not None and feat.shortlist is not None:
            if hasattr(self, "m_shortlist_rows"):
                self.m_shortlist_rows.inc()
                self.m_shortlist_width.observe(feat.sl_len)

    def _claim_pages(self, key, n: int):  # owns: caller -- the claim joins the engine's slot machinery; _evict gives it back
        """Fresh-page claim with prefix-cache pressure relief: when the
        free list is short, LRU cache entries are evicted (their held
        references dropped) and the claim retried once."""
        try:
            return self.pool.claim(key, n)
        except PoolExhausted:
            if self.prefix is None \
                    or not self.prefix.evict_for_pages(self.pool, n):
                raise
            return self.pool.claim(key, n)

    def _try_fork(self, key, src_key, cap: int, n_pages: int,
                  n_src: int, res: Optional[StepResult] = None,
                  feat=None) -> Optional[bool]:
        """Copy-on-write fork from a LIVE row with the same source:
        alias its full (append-only) pages with refcount++, content-copy
        only its current partial page, copy its cross-attention rows
        slot-to-slot (no encoder forward), and resume at its position.
        Returns True (joined), False (fork viable but pool dry —
        caller defers), or None (no fork source; caller takes the cold
        path)."""
        leader_key = self.prefix.leader(src_key)
        if leader_key is None or leader_key == key:
            return None
        with self._lock:
            slot_l = self._by_key.get(leader_key)
            s_l = self._slots[slot_l] if slot_l is not None else None
            # the leader must have stepped at least once (its encoder
            # rows are installed) and price work identically (a brownout
            # cap change between the two joins vetoes the fork)
            if s_l is None or s_l.pos <= 0 or s_l.cap != cap:
                return None
            pos_l, prev_l, toks_l = s_l.pos, s_l.prev, list(s_l.tokens)
        n_full = pos_l // self.page_len
        has_partial = pos_l % self.page_len != 0
        leader_pages = self.pool.pages_of(leader_key)
        fulls = leader_pages[:n_full]
        own_needed = n_pages - n_full

        def build():  # owns: caller -- a successful fork's references live in the forked row; _evict gives them back
            self.pool.share(key, fulls)
            try:
                return self.pool.claim_extra(key, own_needed)
            except PoolExhausted:
                self.pool.release(key)
                raise
        try:
            own = build()
        except PoolExhausted:
            if not self.prefix.evict_for_pages(self.pool, own_needed):
                return False
            try:
                own = build()
            except PoolExhausted:
                return False
        with self._lock:
            slot = next(i for i, s in enumerate(self._slots) if s is None)
            s = _Slot(key, cap, n_src,
                      expected_refs=n_full + own_needed, src_key=src_key,
                      feat=feat)
            s.tokens = toks_l
            s.pos = pos_l
            s.prev = prev_l
            self._slots[slot] = s
            self._by_key[key] = slot
            self._n_active += 1
            # invariant: _used_tokens == sum of active row positions
            self._used_tokens += pos_l
        self.prefix.register_live(src_key, key)
        row = fulls + own
        self._table[slot, :] = 0
        self._table[slot, :len(row)] = row
        # device half: cross-attn rows + source mask slot copy, plus the
        # partial page's content (pairs of (0,0) are deterministic
        # no-ops, used when the leader sat exactly on a page boundary)
        src_page = leader_pages[n_full] if has_partial else 0
        dst_page = own[0] if has_partial else 0
        if self._fork_jit is None:
            self._fork_jit = self._make_fork()
        self._state, self._src_mask = self._fork_jit(
            self._state, self._src_mask,
            jnp.asarray([slot_l], jnp.int32),
            jnp.asarray([slot], jnp.int32),
            jnp.asarray([src_page], jnp.int32),
            jnp.asarray([dst_page], jnp.int32))
        self.prefix.note_fork(tokens_saved=pos_l, pages_reused=n_full)
        if has_partial:
            self._round_copied += 1
        self._count("forks")
        self._count("prefix_hits")
        if self._metrics_declared:
            self.m_forks.inc()
        if res is not None:
            res.row_events.append((key, "prefix.fork",
                                   {"kind": "live", "pos": pos_l,
                                    "aliased": n_full,
                                    "copied": int(has_partial)}))
        return True

    def _make_fork(self):
        model = self.model
        _, pool_keys, _ = self._state_key_groups()
        k_keys = tuple(sorted(k for k in pool_keys
                              if k.endswith("_pool_k")))

        def fork(state, src_mask, src_slot, dst_slot,
                 src_page, dst_page):
            from ..ops.pallas.kv_pool import pool_fork_partial
            new_state, new_mask = model.fork_paged_rows(
                state, src_mask, src_slot, dst_slot)
            for kk in k_keys:
                vk = kk[:-1] + "v"
                nk, nv = pool_fork_partial(new_state[kk], new_state[vk],
                                           src_page, dst_page)
                new_state[kk] = nk
                new_state[vk] = nv
            return new_state, new_mask

        jitwit.note_compile_key(self._jitwit_token, ("fork",))
        return jax.jit(fork, donate_argnums=(0, 1))

    def _evict(self, key, adopt_text: Optional[str] = None) -> bool:  # owns: callee -- the row exit: releases (or adopts into the prefix cache) what _try_claim acquired
        with self._lock:
            slot = self._by_key.pop(key, None)
            if slot is None:
                return False
            s = self._slots[slot]
            self._slots[slot] = None
            self._n_active -= 1
            self._used_tokens -= s.pos
        if self.prefix is not None and s.src_key is not None:
            self.prefix.unregister_live(s.src_key, key)
        # normal finish with the prefix cache armed: the row's page
        # references TRANSFER to the cache (refcounts unchanged) along
        # with its decode, instead of a release — an exact repeat then
        # replays the decode as a page-table hit (ISSUE 12)
        released = 0
        if adopt_text is not None and self.prefix is not None \
                and s.src_key is not None:
            released = self.prefix.adopt(self.pool, s.src_key, key,
                                         s.tokens, adopt_text)
        if released == 0:
            released = self.pool.release(key)
        # row-exit leak detector (always on — one comparison): the row
        # must give back exactly the page references it held (cap pages
        # cold, aliased fulls + owned tail after a fork); any drift
        # means the claim table and the slot state diverged
        expected = s.expected_refs or pages_for_tokens(s.cap,
                                                       self.page_len)
        if released != expected:
            self._report_audit(
                [f"row exit released {released} page reference(s) for "
                 f"key {key!r}, expected {expected} (cap {s.cap})"],
                context="row-exit")
        self._table[slot, :] = 0
        return True

    # -- pool invariant auditor (ISSUE 11) ----------------------------------
    def audit(self, context: str = "quiesce") -> List[str]:
        """Cross-check free-list / page-table / per-row position
        consistency plus leaked claims; returns violations (empty =
        clean) and reports them (log + timeline event + flight dump +
        counter). Run at every quiesce boundary, and after every round
        under ``MARIAN_POOL_AUDIT=1`` (tier-1 arms it process-wide).

        Called only from threads that own the engine state between
        rounds (the device worker, or the event loop at a quiesce
        boundary with no round in flight) — the snapshots below are
        taken under the engine lock only for the metrics-thread
        counters' sake."""
        with self._lock:
            slots = list(self._slots)
            by_key = dict(self._by_key)
            n_active = self._n_active
            used_tokens = self._used_tokens
        v = self.pool.audit()
        refs = self.pool.refcounts()
        active = [(i, s) for i, s in enumerate(slots) if s is not None]
        if n_active != len(active):
            v.append(f"active-row counter {n_active} != {len(active)} "
                     f"occupied slots")
        pos_sum = sum(s.pos for _, s in active)
        if used_tokens != pos_sum:
            v.append(f"used-token counter {used_tokens} != sum of row "
                     f"positions {pos_sum}")
        table = getattr(self, "_table_np", None)
        for i, s in active:
            if by_key.get(s.key) != i:
                v.append(f"slot {i} key {s.key!r} missing from the "
                         f"key index (maps to {by_key.get(s.key)})")
            if s.pos > s.cap:
                v.append(f"slot {i} position {s.pos} past its decode "
                         f"cap {s.cap}")
            pages = self.pool.pages_of(s.key)
            want = s.expected_refs or pages_for_tokens(s.cap,
                                                       self.page_len)
            if len(pages) != want:
                v.append(f"slot {i} holds {len(pages)} page "
                         f"reference(s), expected {want} (cap {s.cap})")
            if pages:
                # COW write safety (shared with the beam audit): the
                # page this row WRITES — the one holding position pos —
                # must be refcount-1; prefix forks alias only FULL
                # pages, so a shared write target means the fork
                # mis-split full/partial and every aliasing row's KV is
                # being corrupted
                wt = pages[min(s.pos // self.page_len, len(pages) - 1)]
                if refs.get(wt, 0) != 1:
                    v.append(f"slot {i} write-target page {wt} has "
                             f"refcount {refs.get(wt, 0)} (COW safety: "
                             f"partial pages must be exclusive)")
            if table is not None:
                row = table[i]
                if list(row[:len(pages)]) != pages \
                        or any(int(p) != 0 for p in row[len(pages):]):
                    v.append(f"slot {i} page-table row "
                             f"{[int(p) for p in row]} does not match "
                             f"its claim {pages} (table corruption)")
        cache_owners = (set(map(repr, self.prefix.owner_keys()))
                        if self.prefix is not None else set())
        for owner in self.pool.owners():
            if owner in by_key:
                continue
            if self.prefix is not None and self.prefix.owns(owner):
                if repr(owner) not in cache_owners:
                    v.append(f"pool claim for prefix-cache owner "
                             f"{owner!r} matches no cache entry "
                             f"(stale cache claim)")
                continue
            v.append(f"pool claim for {owner!r} has no active row "
                     f"(pages leaked at row exit)")
        self._note_audit(v, context)
        return v

    def _note_audit(self, violations: List[str], context: str) -> None:
        """Record the audit pass into the /poolz counters and the
        last-audit verdict (ISSUE 14), then report failures the usual
        loud way. Shared by both engines' auditors."""
        with self._lock:
            self._counters["audits"] += 1
            self._last_audit = {
                "context": context,
                "clean": not violations,
                "violations": list(violations[:8]),
                "ts": time.time(),
            }
        if hasattr(self, "m_audits"):    # registry-less engines: no series
            self.m_audits.inc()
        if violations:
            self._report_audit(violations, context)

    # -- /poolz live inspector (ISSUE 14) ------------------------------------
    def _slot_owner(self, slot: int, s: "_Slot"):
        """The pool-claim owner of an occupied slot (the beam engine's
        owners are (key, slot) pairs — it overrides this)."""
        return s.key

    @staticmethod
    def _owner_label(owner) -> str:
        """Human/JSON-safe label for a claim owner: serving units carry
        their request's trace id, prefix-cache owners their tag; bare
        keys (library/test callers) fall back to repr. Tenanted owners
        (ISSUE 20) get a ``<tag>/`` prefix — the label-level tenant
        convention fleet/accounting.py re-derives per-tenant page sums
        from, so a dead process's /poolz flight dump stays attributable
        (the shared prefix cache stays untenanted on purpose)."""
        probe = owner
        if isinstance(owner, tuple) and len(owner) == 2:
            probe = owner[0]              # beam (key, slot) pair
        req = getattr(probe, "req", None)
        tenant = getattr(req, "tenant", "") if req is not None \
            else getattr(probe, "tenant", "") or ""
        prefix = f"{tenant}/" if tenant else ""
        tid = getattr(req, "trace_id", "") if req is not None else ""
        if tid:
            base = f"{prefix}trace:{tid}"
            return base if probe is owner else f"{base}#{owner[1]}"
        if isinstance(owner, tuple) and len(owner) == 3 \
                and owner[0] == "prefix":
            return "prefix-cache"
        return (prefix + repr(owner))[:96]

    def pool_state(self) -> dict:
        """JSON-ready snapshot of the whole paged-serving data plane:
        the per-page map (refcount + owning rows/cache entries), the
        per-slot table (trace id, pos, cap, pages held), the engine
        round counters and the last audit verdict — the ``/poolz``
        document and the flight recorder's ``pool`` member. Snapshot
        semantics: each map is taken under its own lock (never nested);
        a round committing mid-snapshot can skew adjacent maps by one
        row, which the auditor (not this inspector) is the consistency
        oracle for."""
        refs = self.pool.refcounts()
        claims = self.pool.claims()
        alias = self.pool.alias_stats()
        stats = self.pool.stats()
        with self._lock:
            slots_snap = list(self._slots)
            counters = dict(self._counters)
            last_audit = dict(self._last_audit) if self._last_audit \
                else None
            n_active = self._n_active
            used_tokens = self._used_tokens
        owners_by_page: Dict[int, List[str]] = {}
        for owner, pages in claims.items():
            label = self._owner_label(owner)
            for p in pages:
                owners_by_page.setdefault(int(p), []).append(label)
        page_map = {
            str(p): {"refs": int(rc),
                     "owners": sorted(owners_by_page.get(p, []))}
            for p, rc in sorted(refs.items())}
        slot_rows = []
        for i, s in enumerate(slots_snap):
            if s is None:
                continue
            owner = self._slot_owner(i, s)
            slot_rows.append({
                "slot": i,
                "owner": self._owner_label(owner),
                "trace_id": getattr(getattr(s.key, "req", None),
                                    "trace_id", ""),
                "pos": int(s.pos),
                "cap": int(s.cap),
                "src_tokens": int(s.src_tokens),
                "pages": [int(p) for p in self.pool.pages_of(owner)],
            })
        state = {
            "enabled": True,
            "engine": type(self).__name__,
            "pool": {
                "n_pages": self.pool.n_pages,
                "usable_pages": self.pool.usable_pages,
                "free_pages": self.pool.free_pages(),
                "used_pages": self.pool.used_pages(),
                "occupancy": round(self.occupancy(), 4),
                "page_len": self.page_len,
                "page_bytes": self.page_bytes,
                "max_pages_per_row": self.pool.max_pages_per_row,
                "live_pages": alias["live"],
                "shared_pages": alias["shared"],
                "refs": alias["refs"],
                "refcount_max": alias["max"],
                "cow_alias_ratio": round(self.cow_alias_ratio(), 4),
                "traffic": stats,
            },
            "pages": page_map,
            "rows": {
                "active": n_active,
                "max_rows": self.max_rows,
                "used_tokens": used_tokens,
                "fragmentation": round(self.fragmentation(), 4),
                "slots": slot_rows,
            },
            "counters": counters,
            "last_audit": last_audit,
        }
        if self.prefix is not None:
            state["prefix_cache"] = {
                "entries": self.prefix.entries(),
                "held_tokens": self.prefix.held_tokens(),
                "held_pages": self.prefix.held_pages(),
                "reclaimable_pages":
                    self.prefix.reclaimable_pages(self.pool),
            }
        return state

    def _report_audit(self, violations: List[str], context: str) -> None:
        """One audit failure: loud log, timeline event, flight dump
        naming the fault, counter — the post-mortem must show WHAT was
        corrupted, not just that a round failed."""
        log.error("POOL AUDIT FAILED ({}): {} violation(s): {}", context,
                  len(violations), "; ".join(violations[:4]))
        self._count("audit_failures")
        if hasattr(self, "m_audit_failures"):
            self.m_audit_failures.inc()
        obs.event("pool.audit_failed", context=context,
                  violations=list(violations[:8]))
        obs.FLIGHT.trip_async(
            "pool-audit",
            detail=f"{context}: " + "; ".join(violations[:4]))

    def _chaos_table_corrupt(self) -> None:
        """``pool.table_corrupt`` detection drill (see
        KVPool.chaos_double_free): an armed 'fail' redirects one active
        row's first page-table entry to the trash page while its claim
        still names the real page — the audit's table/claim cross-check
        must catch exactly this."""
        try:
            fp.fault_point("pool.table_corrupt")
        except fp.InjectedFault:
            with self._lock:
                slot = next((i for i, s in enumerate(self._slots)
                             if s is not None), None)
            if slot is not None:
                self._table[slot, 0] = 0

    # host mirrors (worker thread only): allocated lazily so __init__
    # stays importable without numpy churn
    @property
    def _table(self) -> np.ndarray:
        t = getattr(self, "_table_np", None)
        if t is None:
            t = np.zeros((self.max_rows, self.max_pages), np.int32)
            self._table_np = t
        return t

    def _install(self, joiners: List[Tuple[object, List[int], int]]) -> None:
        """Encode the joiners (one bucketed device call) and scatter
        their cross-attention K/V + source masks into their slots. The
        encode runs at the chunk's own LENGTH BUCKET, not the engine's
        src_cap — a 5-token sentence must not pay a max-length-wide
        encoder forward at every join (the cross K/V rows are zero-
        padded to src_cap at scatter time; padded positions are masked,
        so the decode is unchanged)."""
        jb = next((b for b in self.JOIN_BUCKETS if b >= len(joiners)),
                  self.JOIN_BUCKETS[-1])
        for base in range(0, len(joiners), jb):
            chunk = joiners[base:base + jb]
            # halving widths only (src_cap, /2, /4, ...): a handful of
            # compiled encode shapes per join bucket, not one per
            # length bucket — the same closed-shape-set discipline as
            # ROW_BUCKETS (each extra shape is a multi-second inline
            # jit the first join of that shape pays)
            need = max(len(ids) for _, ids, _ in chunk)
            w = self.src_cap
            while w // 2 >= need and w // 2 >= 8:
                w //= 2
            ids_np = np.zeros((jb, w), np.int32)
            mask_np = np.zeros((jb, self.src_cap), np.float32)
            slot_np = np.zeros((jb,), np.int32)
            for i in range(jb):
                # padding rows duplicate joiner 0: their writes land on
                # the same slot with identical content (deterministic)
                key, ids, slot = chunk[min(i, len(chunk) - 1)]
                ids_np[i, :len(ids)] = ids
                mask_np[i, :len(ids)] = 1.0
                slot_np[i] = slot
            fn = self._install_jit.get(0)
            if fn is None:
                # one jit object; its own cache specializes per
                # (jb, w) shape pair
                fn = self._make_install()
                self._install_jit[0] = fn
            if (jb, w) not in self._install_shapes:
                self._install_shapes.add((jb, w))
                jitwit.note_compile_key(
                    self._jitwit_token, ("install", jb, w),
                    domains=(("JOIN_BUCKETS", jb), ("HALVING", w)))
            self._enc_w = w
            self._state, self._src_mask = fn(
                self._state, self._src_mask, self.params,
                jnp.asarray(ids_np), jnp.asarray(mask_np),
                jnp.asarray(slot_np))

    def _state_key_groups(self):
        """Static key classification, computed OUTSIDE the jitted
        closures (their bodies must stay free of Python conditionals);
        the contract lives in ops/pallas/kv_pool.state_key_groups,
        shared with greedy_decode_paged's comparator."""
        from ..ops.pallas.kv_pool import state_key_groups
        return state_key_groups(self._state)

    def _make_install(self):
        model = self.model
        row_keys, _, _ = self._state_key_groups()

        def install(state, src_mask, params, ids, mask, slot_idx):
            # ids arrive at the chunk's length bucket w <= src_cap;
            # mask at full src_cap width (zeros past w)
            w = ids.shape[1]
            enc = model.encode_for_decode(params, ids, mask[:, :w])
            # want_alignment=True forces the unrolled cross-K/V layout,
            # matching the paged state's keys; the tiny dense self
            # caches it allocates are simply not copied
            st = model.start_state(params, enc, mask[:, :w], 1,
                                   want_alignment=True)
            new_state = dict(state)
            for k in row_keys:
                v = st[k].astype(state[k].dtype)
                # zero-pad the source axis out to src_cap: the padded
                # positions are mask-dead, so attention never reads
                # them (deterministic zeros, like the trash page).
                # pad is SHAPE arithmetic (static at trace time); a
                # 0-width pad is a no-op
                pad = state[k].shape[-2] - v.shape[-2]
                v = jnp.pad(v, [(0, 0)] * (v.ndim - 2)
                            + [(0, pad), (0, 0)])
                new_state[k] = state[k].at[slot_idx].set(v)
            new_mask = src_mask.at[slot_idx].set(
                mask.astype(src_mask.dtype))
            return new_state, new_mask

        return jax.jit(install, donate_argnums=(0, 1))

    # buckets: ROW_BUCKETS
    def _make_step(self, rb: int):
        model = self.model
        k_steps = self.steps_per_round
        row_keys, pool_keys, whole_keys = self._state_key_groups()
        # feature plane (ISSUE 16): which per-row extras this engine's
        # compiled step takes is STATIC — a plane-less engine keeps the
        # exact pre-feature jit signature and computation
        plane = self.features
        has_sl = plane is not None and plane.shortlist_gen is not None
        sampling = tuple(plane.sampling) if plane is not None else ()
        has_force = plane is not None and plane.force_decode
        temp = max(float(sampling[-1]), 1e-6) if sampling else 1.0
        topn = int(sampling[1]) if sampling and sampling[0] == "topk" \
            else 0
        seed = int(plane.seed) if plane is not None else 0
        from .beam_search import NEG_INF
        # the jit.closure_vary drill's varying closure constant: 0 in
        # real runs (and folded away); under the armed faultpoint the
        # nonce changes per rebuild, making each rebuilt step a
        # genuinely different traced program — the retrace the witness
        # must catch
        drill_nonce = self._jit_drill_nonce
        jitwit.note_compile_key(self._jitwit_token,
                                ("step", rb, k_steps),
                                domains=(("ROW_BUCKETS", rb),))

        def step(state, src_mask, params, prev, pos, table, *extras):
            # row-indexed leaves run at the bucket prefix; pools and
            # beam-invariant leaves (lsh) stay whole
            sub = {k: state[k][:rb] for k in row_keys}
            for k in whole_keys:
                sub[k] = state[k]
            sm = src_mask[:rb]
            # positional extras, in feature order (host side: _step)
            it = iter(extras)
            sl = next(it) if has_sl else None          # [rb, K] full ids
            sl_len = next(it) if has_sl else None      # [rb] true width
            lane = next(it) if sampling else None      # [rb] RNG lane
            ctr = next(it) if sampling else None       # [rb] step counter
            forced = next(it) if has_force else None   # [rb, k_steps]

            def body(carry, j):
                pools, prev_t, pos_t = carry
                st = dict(sub)
                st.update(pools)
                st["pos"] = pos_t
                st["page_table"] = table
                logits, new_sub = model.step(params, st, prev_t, sm,
                                             shortlist=sl)
                if has_sl:
                    # coords past the row's true (dense-padded) width
                    # are engine padding, not the dense twin's — mask
                    # them out of the argmax/softmax
                    coords = jnp.arange(logits.shape[-1])[None, :]
                    logits = jnp.where(coords < sl_len[:, None],
                                       logits, NEG_INF)
                if sampling:
                    # gumbel-max over logp/temperature, one folded RNG
                    # lane per row (dense twin: beam_search's sampled
                    # top-k; lanes replace the per-batch call counter)
                    lp = jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1)
                    slp = lp / temp
                    if topn:
                        kth = jax.lax.top_k(slp, topn)[0][..., -1:]
                        slp = jnp.where(slp < kth, NEG_INF, slp)
                    keys = jax.vmap(lambda l, c: jax.random.fold_in(
                        jax.random.fold_in(jax.random.key(seed), l),
                        c))(lane, ctr + j)
                    g = jax.vmap(lambda kk: jax.random.gumbel(
                        kk, slp.shape[-1:], jnp.float32))(keys)
                    nxt = jnp.argmax(slp + g, axis=-1).astype(jnp.int32)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if has_sl:
                    # shortlist coords → full-vocab ids ON DEVICE: the
                    # next scan step embeds this token, so the map-back
                    # cannot wait for the host
                    nxt = jnp.take_along_axis(
                        sl, nxt[:, None], axis=1)[:, 0]
                if has_force:
                    f = forced[:, j]
                    nxt = jnp.where(f >= 0, f, nxt)
                new_pools = {k: new_sub[k] for k in pool_keys}
                return (new_pools, nxt[:, None], pos_t + 1), nxt

            init = ({k: state[k] for k in pool_keys}, prev,
                    pos + drill_nonce - drill_nonce)
            (pools, _, _), toks = jax.lax.scan(
                body, init, jnp.arange(k_steps))
            new_state = dict(state)
            new_state.update(pools)
            return toks, new_state          # toks [k_steps, rb]

        return jax.jit(step, donate_argnums=(0,))

    def _feature_args(self, rb: int) -> Tuple[object, ...]:
        """Per-row feature arrays for the compiled step, in the extras
        order _make_step unpacks. Idle rows get neutral values (full
        width, lane 0, unconstrained) — their outputs are discarded."""
        plane = self.features
        if plane is None:
            return ()
        extras: List[object] = []
        if plane.shortlist_gen is not None:
            k = plane.k_static
            sl_np = np.zeros((rb, k), np.int32)
            len_np = np.full((rb,), k, np.int32)
            for i in range(rb):
                s = self._slots[i]
                if s is not None and s.feat is not None \
                        and s.feat.shortlist is not None:
                    sl_np[i, :] = s.feat.shortlist
                    len_np[i] = s.feat.sl_len
            extras += [jnp.asarray(sl_np), jnp.asarray(len_np)]
        if plane.sampling:
            lane_np = np.zeros((rb,), np.int32)
            ctr_np = np.zeros((rb,), np.int32)
            for i in range(rb):
                s = self._slots[i]
                if s is not None and s.feat is not None:
                    lane_np[i] = s.feat.lane
                    ctr_np[i] = s.pos
            extras += [jnp.asarray(lane_np), jnp.asarray(ctr_np)]
        if plane.force_decode:
            forced_np = np.full((rb, self.steps_per_round), -1, np.int32)
            for i in range(rb):
                s = self._slots[i]
                if s is not None and s.feat is not None and s.feat.forced:
                    for j in range(self.steps_per_round):
                        forced_np[i, j] = s.feat.forced_at(s.pos + j)
            extras.append(jnp.asarray(forced_np))
        return tuple(extras)

    def _step(self, res: StepResult) -> None:
        # the occupied prefix, rounded up to a compiled row bucket
        top = max(i for i, s in enumerate(self._slots) if s is not None)
        rb = bucket_rows(top + 1, self.row_buckets)
        pos_np = np.full((rb,), -1, np.int32)
        prev_np = np.zeros((rb, 1), np.int32)
        for i in range(rb):
            s = self._slots[i]
            if s is not None:
                pos_np[i] = s.pos
                prev_np[i, 0] = s.prev
        # seeded retrace drill (jit.closure_vary): discard the cached
        # step jit and rebuild it around a varying closure constant —
        # a REAL retrace+recompile of an already-noted key, which the
        # jitwit must flag (tests/test_jitwit.py)
        try:
            fp.fault_point("jit.closure_vary")
        except fp.InjectedFault:
            self._jit_drill_nonce += 1
            self._step_jit.pop(rb, None)
        fn = self._step_jit.get(rb)
        if fn is None:
            fn = self._make_step(rb)
            self._step_jit[rb] = fn
        toks_dev, self._state = fn(
            self._state, self._src_mask, self.params,
            jnp.asarray(prev_np), jnp.asarray(pos_np),
            jnp.asarray(self._table[:rb]), *self._feature_args(rb))
        # the per-round host sync IS the design: the join/evict schedule
        # runs on the host between rounds (the serving scheduler's
        # iteration loop), so each round's tokens must land host-side
        toks = np.asarray(toks_dev)  # mtlint: ok -- iteration-level decode syncs once per round by design; admission runs host-side between rounds
        self._ever_stepped = True
        k_steps = toks.shape[0]
        emitted = 0
        consumed = 0
        finishes: List[_Slot] = []
        for i in range(rb):
            s = self._slots[i]
            if s is None:
                continue
            emitted += 1
            done = False
            for j in range(k_steps):
                tok = int(toks[j, i])
                s.pos += 1
                s.prev = tok
                consumed += 1
                done = tok == EOS_ID or s.pos >= s.cap
                if tok != EOS_ID:
                    s.tokens.append(tok)
                if done:
                    # a row finishing mid-scan self-fed to the end of
                    # the round on device; the host cuts HERE — the
                    # overshoot tokens are discarded and its cache
                    # positions past the cut are never read again
                    finishes.append(s)
                    break
        # ONE locked add per round (not per token — this loop runs on
        # the device-worker hot path against the metrics scrape
        # thread), and it must land BEFORE the evictions below subtract
        # each finished slot's full s.pos: the invariant is
        # _used_tokens == sum(s.pos) over active slots
        with self._lock:
            self._used_tokens += consumed
        for s in finishes:
            text = self.trg_vocab.decode(s.tokens, ignore_eos=True)
            res.finished.append((s.key, text))
            self._evict(s.key, adopt_text=text)
        # streaming rows still decoding emit their text-so-far each
        # round (#stream:, ISSUE 16); finishing rows already delivered
        # their final text above
        for i in range(rb):
            s = self._slots[i]
            if s is not None and s.feat is not None and s.feat.stream:
                res.partials.append(
                    (s.key,
                     self.trg_vocab.decode(s.tokens, ignore_eos=True),
                     s.pos))
        res.rows = emitted
        res.bucket = rb
        res.tokens = consumed
        res.steps += k_steps
        res.enc_bucket = self._enc_w

    # -- direct (non-serving) decoding: tests, benches, warmup smoke --------
    def decode_texts(self, texts: Sequence[str]) -> List[str]:
        """Decode a list of sentences to completion through the slot
        machinery (joins as capacity frees up) — the library-call
        equivalent of the serving loop, used by tests and bench A/Bs."""
        pending = list(enumerate(texts))
        out: Dict[int, str] = {}
        guard = 0
        while pending or not self.idle():
            joins = []
            while pending and len(joins) < self.max_rows:
                joins.append(pending[0])
                pending.pop(0)
            res = self.admit_and_step(joins)
            for key, why in res.rejected:
                if why in FATAL_REASONS:
                    raise ValueError(
                        f"sentence {key} rejected: {why}")
                pending.insert(0, (key, texts[key]))
            for key in res.pool_evicted:
                # serving retries these against the (healthy) engine
                # after the pressure passes; the library call does too
                pending.insert(0, (key, texts[key]))
            for key, text in res.finished:
                out[key] = text
            guard += 1
            if guard > 100000:
                raise RuntimeError("iteration decode failed to converge")
        return [out[i] for i in range(len(texts))]

    def encode_widths(self) -> Tuple[int, ...]:
        """The halving encode-width chain _install draws from:
        src_cap, /2, /4, ... down to 8 — the engine's full encode
        bucket table (descending)."""
        widths = []
        w = self.src_cap
        while True:
            widths.append(w)
            if w // 2 < 8:
                break
            w //= 2
        return tuple(widths)

    def warm_grid(self) -> List[Tuple[int, int, int, float]]:
        """Drive the engine's FULL compile-key grid off the serving
        path (lifecycle warmup, ISSUE 17 satellite): every row bucket
        at the narrowest width, then every encode width at one row —
        after this, steady-state traffic can reach no step or install
        shape that is not already compiled (the closed-shape-set
        claim, asserted by tests/test_iteration.py's jitwit strict
        window). Returns (row_bucket, encode_width, steps, seconds)
        rows for each driven decode; the lifecycle layer folds them
        into PERF's warm ledger under the round-key vocabulary."""
        rows: List[Tuple[int, int, int, float]] = []
        # joiner counts that reach every runtime-reachable bucket: each
        # row bucket as an active-row count (step grid) and each join
        # bucket clamped to capacity (install grid) — the two jit caches
        # key independently, so the count × width double loop closes
        # BOTH tables
        counts = sorted(set(self.row_buckets)
                        | {min(jb, self.max_rows)
                           for jb in self.JOIN_BUCKETS})
        for w in self.encode_widths():
            # enough source tokens that the halving loop stops at w
            # (> w/2), within the engine's source cap
            n_words = max(1, min(w // 2, self.src_cap - 2))
            text = " ".join(["a"] * n_words)
            for n in counts:
                t0 = time.perf_counter()
                self.decode_texts([text] * n)
                rows.append((bucket_rows(n, self.row_buckets),
                             self._enc_w, self.steps_per_round,
                             time.perf_counter() - t0))  # mtlint: ok -- decode_texts returns host strings: every round already synced, the window is wall-clock warmup cost by design
        return rows


class EngineExecutor:
    """The lifecycle plane's executor shape for iteration mode
    (ISSUE 11): a warmed candidate is a whole PagedDecodeEngine (model +
    params + its own device-side page pool), not a ``translate_lines``
    closure. Callable so ``warm_executor``'s golden smoke drives the
    engine's real install/step jits off the serving path; ``.engine`` is
    what the quiesce protocol re-points the scheduler at
    (SwapController._repoint)."""

    def __init__(self, engine: PagedDecodeEngine):
        self.engine = engine

    def __call__(self, lines: List[str]) -> List[str]:
        return self.engine.decode_texts(lines)
