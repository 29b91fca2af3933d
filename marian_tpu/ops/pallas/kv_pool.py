"""Paged KV-cache pool for iteration-level (continuous) batching.

The dense decode cache is a per-batch tensor ``[rows, H, L, dh]`` whose
row count and length are fixed for the LIFETIME of the batch: a sentence
admitted mid-decode waits for the whole batch to drain, and every row
pays L positions of HBM even when it finished at position 9. This module
replaces it with a POOL of fixed-size pages:

- ``pool_k`` / ``pool_v``: ``[n_pages, H, page_len, dh]`` — one shared
  allocation sized to a byte budget, not to any batch;
- a per-row PAGE TABLE ``[rows, max_pages]`` int32 mapping each row's
  logical positions ``[j*page_len, (j+1)*page_len)`` to a physical page;
- per-row positions ``row_pos`` int32 — rows decode at their OWN time
  index, so a sentence can join a running decode step at position 0
  while its neighbors are at position 40.

Page 0 is RESERVED as the trash page: it is never handed out by the
allocator, table entries of unclaimed slots point at it, and inactive
rows (``row_pos < 0``) write zeros into it — so scatter collisions
between idle rows write identical values and stay deterministic (the
join/evict replay test pins this).

``paged_decode_attention`` extends the fused decode kernel's
scalar-prefetch index map (ops/pallas/decode_attention.py) from beam
backpointers to page-table lookups: grid cell ``(row, head, page)``
pulls physical page ``page_table[row, page]`` through the block index
map, accumulates the row's K/V pages into VMEM scratch, and on the last
page runs EXACTLY the dense kernel's one-shot masked softmax over the
assembled ``[max_pages*page_len, dh]`` block — the op order is kept
identical to the dense kernel on purpose, so paged-vs-dense parity is
BITWISE in interpret mode (tests/test_kv_pool.py pins it), not just
allclose.

Update discipline: the dense fused kernel wrote the WHOLE reordered
cache back once per step because the beam reorder demanded it. Here the
reorder is a page-table remap (host-side int32 rows), so the per-step
pool update shrinks to ONE scatter of the new token's K/V into its page
(``pool_insert``) — the kernel reads the pool and writes nothing back.

Shapes stay static for the TPU compilation model: page counts come from
``auto_tuner.KERNEL_BLOCKS``-style capacity tables and active-row
counts round up to ``ROW_BUCKETS`` (the iteration engine slices a
bucket-sized prefix of its slot state per step).
"""

from __future__ import annotations

import bisect
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import MASK_VALUE, _interpret_default


# ---------------------------------------------------------------------------
# static-shape bucket tables (cf. auto_tuner.KERNEL_BLOCKS: shapes must
# come from a small closed set so serving stays on warm jit caches)
# ---------------------------------------------------------------------------

# active-row buckets for the iteration engine's per-step compiled shapes:
# n_active rounds UP to the next entry (one jit specialization per bucket).
# The largest must stay under paged_kernel_max_rows (checked per engine)
ROW_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)

# tokens per page. 16 × dh=64 × 4 B = 4 KiB per (page, head) K block —
# several HBM bursts per block read, small enough that a 10-token
# sentence wastes at most one mostly-empty page (docs/DECODE_ROOFLINE.md
# r7 discusses the trade)
DEFAULT_PAGE_LEN = 16


def pages_for_tokens(n_tokens: int, page_len: int) -> int:
    """Pages a row needs to hold ``n_tokens`` positions."""
    return max(1, -(-int(n_tokens) // max(1, int(page_len))))


def bucket_rows(n: int, buckets: Sequence[int] = ROW_BUCKETS) -> int:
    """Smallest row bucket >= n (the largest bucket caps it)."""
    buckets = sorted(buckets)
    i = bisect.bisect_left(buckets, max(1, int(n)))
    return buckets[min(i, len(buckets) - 1)]


# SMEM one paged_decode_attention call may fill with its scalar-prefetched
# page table and positions: the 1 MiB the v5e compiler grants a program
# (its refusal: "RESOURCE_EXHAUSTED ... space=smem ... prefetched SMEM
# operand 0"), less a reserve for the compiler's own scalars
SMEM_BUDGET_BYTES = (1 << 20) - (16 << 10)


def paged_kernel_max_rows(max_pages: int) -> int:
    """Most rows one paged_decode_attention call takes. A row's int32
    page-table entries pad to whole 128-word SMEM lines (512 B per 128
    pages), beside 4 B of position: 2000 rows at <= 128 pages/row."""
    row_bytes = 512 * -(-int(max_pages) // 128) + 4
    return SMEM_BUDGET_BYTES // row_bytes


def check_kernel_rows(rows: int, max_pages: int) -> None:
    """Refuse a row count the chip compiler would refuse in warm-up."""
    bound = paged_kernel_max_rows(max_pages)
    if rows > bound:
        raise ValueError(
            f"{rows} rows x {max_pages} pages/row exceeds the paged "
            f"decode kernel's SMEM bound of {bound} rows (the page table "
            f"is scalar-prefetched: {SMEM_BUDGET_BYTES} B at 512 B per "
            f"row per 128 pages) - lower --iteration-rows or raise "
            f"--kv-page-len")


def state_key_groups(state_keys) -> Tuple[Tuple[str, ...], Tuple[str, ...],
                                          Tuple[str, ...]]:
    """Classify a paged decode state's leaves for the per-step closures
    (ONE definition of the contract — translator/iteration.py's engine
    and translator/greedy.py's paged A/B comparator both consume it, so
    a state-layout change cannot silently diverge them):

    - row keys (cross-attention K/V): row-indexed, sliced to the step's
      bucket prefix;
    - pool keys (the paged K/V pools): rewritten by every step;
    - whole keys (beam-invariant extras like LSH tables): pass through.

    ``pos``/``page_table`` are the host-owned leaves and belong to
    neither group.
    """
    keys = tuple(state_keys)
    row_keys = tuple(k for k in keys if "_cross_" in k)
    pool_keys = tuple(k for k in keys if "_pool_" in k)
    whole_keys = tuple(k for k in keys
                       if k not in row_keys and k not in pool_keys
                       and k not in ("pos", "page_table"))
    return row_keys, pool_keys, whole_keys


# ---------------------------------------------------------------------------
# host-side page allocator
# ---------------------------------------------------------------------------

class PoolExhausted(RuntimeError):
    """A claim could not be satisfied — callers must treat this as an
    admission decision (defer/shed the sentence), never as a reason to
    stall a decode step that other rows are waiting on."""


class PoolCorruption(RuntimeError):
    """The pool auditor found an invariant violation (double-freed page,
    page-table/claim mismatch, leaked pages). ``retriable``: the engine
    holding the pool is rebuilt from scratch by the serving scheduler,
    so the evicted rows' requests can be retried against the fresh
    engine — clients see ``!!SERVER-RETRY``, never silent corruption."""

    retriable = True


class KVPool:
    """Refcounted free-list page allocator over the device pool's index
    space.

    Pure host bookkeeping (the device arrays live with the decode state).
    An owner's claim is the list of TABLE REFERENCES its page-table row
    holds; a page's refcount is the number of table references across all
    owners. Fresh claims are all-or-nothing per owner so a greedy
    sentence either holds every page its decode cap needs or none —
    mid-decode exhaustion is impossible by construction for that path,
    which is what keeps the decode step deadlock-free when the pool runs
    dry (admission defers instead).

    Copy-on-write sharing (beam>1 iteration decoding, cross-request
    prefix sharing) rides the refcounts: FULL pages are append-only and
    therefore shareable — :meth:`share` adds references to live pages,
    :meth:`retable` rewrites one owner's reference list as an
    incref/decref diff (the beam reorder), and a page returns to the
    free list only when its LAST reference drops. Only the current
    PARTIAL page of a row is ever written, so it must stay refcount-1
    per row (the engines fork it by content copy — ``pool_fork_partial``).

    Cross-thread: the device worker claims/releases while the metrics
    scrape thread samples the gauges — hence the lock discipline.
    """

    def __init__(self, n_pages: int, page_len: int = DEFAULT_PAGE_LEN,
                 max_pages_per_row: int = 0):
        if n_pages < 2:
            raise ValueError(f"KVPool needs >= 2 pages (page 0 is the "
                             f"reserved trash page); got {n_pages}")
        from ...common import lockdep
        from ...common import ownwit
        # runtime ownership witness (ISSUE 15): with MARIAN_OWNWIT=1
        # every acquire/release/transfer records its acting call site,
        # and tier-1 asserts observed pairings ⊆ the static ownership
        # graph. Read once at construction: one attribute check per
        # verb when disarmed.
        self._ownwit = ownwit.enabled()
        self._ownwit_tok = ownwit.new_token() if self._ownwit else 0
        self.n_pages = int(n_pages)
        self.page_len = int(page_len)
        self.max_pages_per_row = int(max_pages_per_row) or (n_pages - 1)
        self._lock = lockdep.make_lock("KVPool._lock")
        # LIFO free list, low pages first out — keeps early tests and
        # replays deterministic and dense near the pool's base
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))
        self._claims: Dict[object, List[int]] = {}  # guarded-by: _lock
        # page -> live reference count; a page is EITHER here (>= 1) or
        # on the free list, never both and never absent from both
        self._refs: Dict[int, int] = {}             # guarded-by: _lock
        # cumulative traffic counters (ISSUE 14 pool telemetry):
        #  claimed — fresh pages popped off the free list;
        #  freed   — pages returned to the free list (last ref dropped);
        #  aliased — references added to ALREADY-LIVE pages (the
        #            copy-on-write shares: beam forks, prefix hits,
        #            retable increfs of newly shared pages).
        # The engines read round deltas of these for the serve.round
        # span and the pages_*_total series.
        self._stats = {"claimed": 0, "freed": 0,
                       "aliased": 0}                # guarded-by: _lock

    @property
    def usable_pages(self) -> int:
        """Allocatable pages (total minus the reserved trash page)."""
        return self.n_pages - 1

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def used_pages(self) -> int:
        with self._lock:
            return self.n_pages - 1 - len(self._free)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refs.get(int(page), 0)

    def refcounts(self) -> Dict[int, int]:
        """Snapshot of the live refcount map (one lock acquisition —
        callers scanning many pages must use this, not per-page
        :meth:`refcount` calls against the device worker's lock)."""
        with self._lock:
            return dict(self._refs)

    def claim(self, owner, n: int, row_cap: bool = True) -> List[int]:
        """Claim ``n`` fresh pages (refcount 1 each) for ``owner``
        (all-or-nothing); raises :class:`PoolExhausted` when the free
        list is short. ``row_cap=False`` skips the per-row table bound —
        for TRANSIENT hold owners that never become a table row (the
        fused beam round's fresh-page pre-claim spans a whole sentence's
        worth of rows, not one)."""
        n = int(n)
        if row_cap and n > self.max_pages_per_row:
            raise PoolExhausted(
                f"row needs {n} pages but the page table holds "
                f"{self.max_pages_per_row} (raise --kv-page-len or the "
                f"pool budget)")
        with self._lock:
            if owner in self._claims:
                raise ValueError(f"owner {owner!r} already holds pages")
            if n > len(self._free):
                raise PoolExhausted(
                    f"pool exhausted: {n} pages requested, "
                    f"{len(self._free)} free of {self.n_pages - 1}")
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            self._claims[owner] = pages
            self._stats["claimed"] += n
        if self._ownwit:
            from ...common import ownwit
            ownwit.note_acquire("kv-pages", self._ownwit_tok, owner)
        return list(pages)

    def claim_extra(self, owner, n: int = 1,
                    row_cap: bool = True) -> List[int]:
        """Append ``n`` fresh pages to an EXISTING owner's reference
        list (lazy growth: a beam row crossing a page boundary, a COW
        fork's new partial page). All-or-nothing like :meth:`claim`.
        ``row_cap=False`` skips the per-row table bound — for TRANSIENT
        hold owners that never become a table row (the beam reorder's
        incref-before-decref window)."""
        n = int(n)
        with self._lock:
            held = self._claims.get(owner)
            if held is None:
                raise ValueError(f"owner {owner!r} holds no pages to "
                                 f"extend (use claim)")
            if row_cap and len(held) + n > self.max_pages_per_row:
                raise PoolExhausted(
                    f"row would hold {len(held) + n} pages but the page "
                    f"table holds {self.max_pages_per_row}")
            if n > len(self._free):
                raise PoolExhausted(
                    f"pool exhausted: {n} extra pages requested, "
                    f"{len(self._free)} free of {self.n_pages - 1}")
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            held.extend(pages)
            self._stats["claimed"] += n
        if self._ownwit:
            from ...common import ownwit
            ownwit.note_acquire("kv-pages", self._ownwit_tok, owner)
        return list(pages)

    def share(self, owner, pages: Sequence[int],
              row_cap: bool = True) -> None:
        """Add references to LIVE pages for ``owner`` (creating the
        owner if absent): the copy-on-write alias — a beam fork's or a
        prefix-cache hit's table row pointing at another lineage's full
        (append-only, immutable) pages. Refuses dead pages loudly: an
        alias to a freed page would serve recycled KV content.
        ``row_cap=False``: see :meth:`claim_extra`."""
        with self._lock:
            for p in pages:
                p = int(p)
                if self._refs.get(p, 0) < 1:
                    raise ValueError(
                        f"cannot share page {p}: not live (freed or "
                        f"never claimed)")
            held = self._claims.setdefault(owner, [])
            if row_cap and len(held) + len(pages) \
                    > self.max_pages_per_row:
                raise PoolExhausted(
                    f"row would hold {len(held) + len(pages)} pages but "
                    f"the page table holds {self.max_pages_per_row}")
            for p in pages:
                self._refs[int(p)] += 1
                held.append(int(p))
            self._stats["aliased"] += len(pages)
        if self._ownwit:
            from ...common import ownwit
            ownwit.note_acquire("kv-pages", self._ownwit_tok, owner)

    def retable(self, owner, new_pages: Sequence[int]) -> int:
        """Atomically rewrite ``owner``'s reference list to
        ``new_pages`` (the beam reorder's refcount fixup): increfs the
        additions, decrefs the removals, frees pages whose last
        reference dropped. Every page in ``new_pages`` must already be
        live (either kept from the old list or claimed/shared moments
        before). Returns the number of pages FREED. An empty
        ``new_pages`` drops the owner entirely."""
        new_list = [int(p) for p in new_pages]
        with self._lock:
            owner_existed = owner in self._claims
            old_list = self._claims.get(owner, [])
            if len(new_list) > self.max_pages_per_row:
                raise PoolExhausted(
                    f"row would hold {len(new_list)} pages but the page "
                    f"table holds {self.max_pages_per_row}")
            for p in new_list:
                if self._refs.get(p, 0) < 1:
                    raise ValueError(
                        f"cannot retable to page {p}: not live")
            old_set = set(old_list)
            for p in new_list:
                self._refs[p] += 1
                if p not in old_set:
                    # a reference this owner did not already hold: a
                    # genuinely new alias (kept pages incref+decref and
                    # must not read as COW traffic)
                    self._stats["aliased"] += 1
            freed = 0
            # decref the old list in reverse so a retable-to-empty frees
            # in release()'s deterministic order
            for p in reversed(old_list):
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._free.append(p)
                    freed += 1
            self._stats["freed"] += freed
            if new_list:
                self._claims[owner] = new_list
            else:
                self._claims.pop(owner, None)
        if self._ownwit:
            from ...common import ownwit
            if new_list:
                # kept or created: the retable site holds references now
                ownwit.note_acquire("kv-pages", self._ownwit_tok, owner)
            elif owner_existed:
                # retable-to-empty IS the beam engine's release verb
                ownwit.note_release("kv-pages", self._ownwit_tok, owner)
        return freed

    def transfer(self, src_owner, dst_owner) -> List[int]:
        """Move ``src_owner``'s whole reference list to ``dst_owner``
        (refcounts unchanged — the references change hands, they do not
        multiply): how a finished row's pages become a prefix-cache
        entry without a free/reclaim round trip. Returns the moved
        list; a missing source moves nothing."""
        with self._lock:
            if dst_owner in self._claims:
                raise ValueError(f"transfer target {dst_owner!r} "
                                 f"already holds pages")
            pages = self._claims.pop(src_owner, None)
            if not pages:
                return []
            self._claims[dst_owner] = pages
        if self._ownwit:
            from ...common import ownwit
            ownwit.note_transfer("kv-pages", self._ownwit_tok, src_owner, dst_owner)
        return list(pages)

    def release(self, owner) -> int:
        """Drop every reference ``owner`` holds (freeing pages whose
        last reference drops); returns how many REFERENCES were
        dropped (== pages freed when nothing was shared).

        An owner that holds NOTHING — released twice, or released after
        its references were transferred away (the prefix-cache adoption
        path) — is a loud ``ValueError``, never a silent no-op: a
        double release means some other owner's refcounts are about to
        be wrong, and the caller's bookkeeping has already diverged
        from the pool's (ISSUE 15; MT-OWN-DOUBLE is the static half).
        An owner holding an empty reference list (a zero-page share)
        releases normally."""
        from ...common import faultpoints as fp
        try:
            # the seeded-leak drill (ISSUE 15): an armed 'fail' makes
            # this release silently do NOTHING — the suppressed-release
            # bug class — so the ownership witness's and the auditors'
            # claims to catch a real leak are proven against one
            # (tests/test_ownwit.py; docs/ROBUSTNESS.md "Auditor
            # drills"). Unarmed: one dict lookup.
            fp.fault_point("pool.release_drop")
        except fp.InjectedFault:
            return 0
        with self._lock:
            pages = self._claims.pop(owner, None)
            if pages is None:
                raise ValueError(
                    f"release of owner {owner!r} which holds no pages — "
                    f"released twice, or released after its references "
                    f"were transferred away")
            # freed pages return in reverse so a release+reclaim of the
            # same count yields the same page ids (replay determinism)
            for p in reversed(pages):
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._free.append(p)
                    self._stats["freed"] += 1
        if self._ownwit:
            from ...common import ownwit
            ownwit.note_release("kv-pages", self._ownwit_tok, owner)
        return len(pages)

    def pages_of(self, owner) -> List[int]:
        with self._lock:
            return list(self._claims.get(owner, []))

    def owners(self) -> List[object]:
        with self._lock:
            return list(self._claims.keys())

    def claims(self) -> Dict[object, List[int]]:
        """Snapshot of the whole claims table (owner -> held page
        references) in one lock acquisition — the /poolz page map
        inverts this into per-page owner lists (ISSUE 14)."""
        with self._lock:
            return {k: list(v) for k, v in self._claims.items()}

    def stats(self) -> Dict[str, int]:
        """Cumulative claimed/freed/aliased counters (see __init__);
        the engines diff two snapshots for per-round accounting."""
        with self._lock:
            return dict(self._stats)

    def alias_stats(self) -> Dict[str, int]:
        """One-lock refcount-distribution summary for the pool gauges:
        ``live`` pages holding references, ``shared`` pages with
        refcount >= 2 (COW-aliased), total ``refs`` and the ``max``
        refcount. The COW alias ratio is (refs - live) / refs — the
        fraction of table references that are aliases rather than sole
        ownership."""
        with self._lock:
            refs = self._refs
            return {
                "live": len(refs),
                "shared": sum(1 for c in refs.values() if c > 1),
                "refs": sum(refs.values()),
                "max": max(refs.values(), default=0),
            }

    # -- invariant auditor (ISSUE 11, refcounts ISSUE 12) -------------------
    def audit(self) -> List[str]:
        """Cross-check the free list, the claims table and the refcount
        map; returns a list of human-readable violations (empty =
        clean). The checks are exactly the bug classes a refcounted
        paged allocator grows over time:

        - a page on the free list twice, or both free and refcounted
          (double-free / freed page with refcount > 0);
        - a claim naming a page out of the pool's index range, or the
          reserved trash page 0 handed out;
        - sum of table references per page != its refcount (a lost or
          phantom incref — the COW fork/reorder bug class);
        - a refcount <= 0 entry lingering in the map (a page with
          refcount 0 may exist ONLY on the free list);
        - pages accounted to neither side (leak).

        Runs on snapshots taken under the lock, so it never blocks the
        device worker for more than three dict copies; callers run it at
        every quiesce boundary and per round under MARIAN_POOL_AUDIT=1.
        """
        with self._lock:
            free = list(self._free)
            claims = {k: list(v) for k, v in self._claims.items()}
            refs = dict(self._refs)
        v: List[str] = []
        seen_free: Dict[int, bool] = {}
        for p in free:
            if p == 0:
                v.append("free list holds the reserved trash page 0")
                continue
            if not 1 <= p < self.n_pages:
                v.append(f"free list holds out-of-range page {p}")
                continue
            if p in seen_free:
                v.append(f"page {p} appears twice in the free list "
                         f"(double-free)")
            seen_free[p] = True
            if refs.get(p, 0) > 0:
                v.append(f"page {p} is free but still has refcount "
                         f"{refs[p]} (freed page with live references)")
        # rebuild the expected refcounts from the claims table
        expected: Dict[int, int] = {}
        for owner, pages in claims.items():
            for p in pages:
                if p == 0 or not 1 <= p < self.n_pages:
                    v.append(f"claim {owner!r} holds invalid page {p}")
                    continue
                expected[p] = expected.get(p, 0) + 1
        for p, want in sorted(expected.items()):
            have = refs.get(p, 0)
            if have != want:
                v.append(f"page {p} has refcount {have} but "
                         f"{want} table reference(s) (refcount drift)")
            if p in seen_free:
                v.append(f"page {p} is both free and referenced "
                         f"(double-free)")
        for p, rc in sorted(refs.items()):
            if rc <= 0:
                v.append(f"page {p} has non-positive refcount {rc} "
                         f"outside the free list")
            elif p not in expected:
                v.append(f"page {p} has refcount {rc} but no table "
                         f"reference names it (phantom refcount)")
        if not v:
            total = len(free) + len(refs)
            if total != self.usable_pages:
                v.append(f"{self.usable_pages - total} page(s) leaked: "
                         f"{len(free)} free + {len(refs)} live of "
                         f"{self.usable_pages} allocatable")
        return v

    def chaos_double_free(self) -> None:
        """Cross the ``pool.double_free`` detection drill. The catalog
        point's 'fail' mode does not model an exception here: it makes
        this helper re-free one still-claimed row's pages — the real
        double-free state — so the auditor's claim to catch that bug
        class is tested against actual corruption, never a mocked
        report (docs/ROBUSTNESS.md "Auditor drills"). Unarmed, this is
        one dict lookup under the faultpoint lock; kill/hang modes
        behave as at any other crossing."""
        from ...common import faultpoints as fp
        try:
            fp.fault_point("pool.double_free")
        except fp.InjectedFault:
            with self._lock:
                for pages in self._claims.values():
                    if pages:
                        self._free.extend(reversed(pages))
                        break

    def chaos_refcount_corrupt(self) -> None:
        """Cross the ``pool.refcount_corrupt`` detection drill: an armed
        'fail' bumps one live page's refcount by +1 WITHOUT adding a
        table reference — the lost-decref/phantom-incref bug class the
        COW fork/reorder paths could grow — so the auditor's
        references-vs-refcount cross-check is proven against real
        corrupted state (docs/ROBUSTNESS.md "Auditor drills")."""
        from ...common import faultpoints as fp
        try:
            fp.fault_point("pool.refcount_corrupt")
        except fp.InjectedFault:
            with self._lock:
                for p in sorted(self._refs):
                    self._refs[p] += 1
                    break

    def chaos_tenant_leak(self) -> None:
        """Cross the ``tenant.page_leak`` detection drill (ISSUE 20): an
        armed 'fail' moves ONE page reference from some tenant's claim
        list into a claim list owned by a DIFFERENT tenant — the
        mischarged-page bug class of multi-tenant accounting. The move
        changes no refcount, so :meth:`audit` stays green BY
        CONSTRUCTION; only the tenant-level auditor
        (serving/fleet/accounting.py::audit_tenants) can catch it, which
        is exactly what the drill proves. No-op (beyond the faultpoint
        crossing) when the pool holds claims from fewer than two
        distinct tenants."""
        from ...common import faultpoints as fp
        try:
            fp.fault_point("tenant.page_leak")
        except fp.InjectedFault:
            from ...serving.fleet import accounting as acc  # lazy: leaf
            with self._lock:
                by_tenant = {}
                for owner, pages in self._claims.items():
                    t = acc.tenant_of_owner(owner)
                    if t:
                        by_tenant.setdefault(t, []).append(owner)
                tenants = sorted(by_tenant)
                for src_t in tenants:
                    src = next((o for o in by_tenant[src_t]
                                if self._claims[o]), None)
                    dst_t = next((t for t in tenants if t != src_t), None)
                    if src is None or dst_t is None:
                        continue
                    dst = by_tenant[dst_t][0]
                    self._claims[dst].append(self._claims[src].pop())
                    return


# ---------------------------------------------------------------------------
# device-side pool ops
# ---------------------------------------------------------------------------

def pool_insert(pool_k: jax.Array, pool_v: jax.Array,
                k_new: jax.Array, v_new: jax.Array,
                page_table: jax.Array, row_pos: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Write each active row's new-token K/V into its page at
    ``row_pos`` — the paged pool's ONE write per step (the dense fused
    kernel's full write-back existed only to apply the beam reorder; the
    page table absorbs that, so only the new token moves).

    ``row_pos < 0`` marks an inactive row: its write is redirected to
    the trash page (0) offset 0 with a ZERO payload, so idle-row scatter
    collisions write identical values and the result is deterministic.
    """
    page_len = pool_k.shape[2]
    mp = page_table.shape[1]
    pos = jnp.asarray(row_pos, jnp.int32)
    active = pos >= 0
    # clamp into the table's span: a multi-step scan round can step a
    # row past its cap before the host sees the EOS and evicts it — the
    # overshoot lands on the row's own last slot (a position the host
    # has already cut at), never out of bounds
    posc = jnp.where(active, jnp.minimum(pos, mp * page_len - 1), 0)
    slot = posc // page_len                                   # [R]
    pidx = jnp.take_along_axis(jnp.asarray(page_table, jnp.int32),
                               slot[:, None], axis=1)[:, 0]   # [R]
    pidx = jnp.where(active, pidx, 0)
    off = jnp.where(active, posc % page_len, 0)
    kv = []
    for pool, new in ((pool_k, k_new), (pool_v, v_new)):
        payload = new[:, :, 0, :].astype(pool.dtype)          # [R,H,dh]
        payload = jnp.where(active[:, None, None], payload,
                            jnp.zeros_like(payload))
        kv.append(pool.at[pidx, :, off, :].set(payload))
    return kv[0], kv[1]


def pool_fork_partial(pool_k: jax.Array, pool_v: jax.Array,
                      src_pages: jax.Array, dst_pages: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Copy-on-write fork of PARTIAL pages: ``pool[dst] = pool[src]``
    for each (src, dst) pair — the one content copy a beam reorder (or
    a cross-request prefix fork) pays per diverging row, H·page_len·dh
    elements against the dense path's full H·L·dh reorder.

    Pairs with ``src == dst == 0`` are padding (they rewrite the trash
    page with its own content — deterministic no-ops), so callers can
    bucket the pair count to a static shape. Duplicate destinations are
    only ever the padded zeros, whose payloads are identical, so the
    scatter stays deterministic."""
    src = jnp.asarray(src_pages, jnp.int32)
    dst = jnp.asarray(dst_pages, jnp.int32)
    new_k = pool_k.at[dst].set(pool_k[src])
    new_v = pool_v.at[dst].set(pool_v[src])
    return new_k, new_v


def beam_table_reorder(page_table: jax.Array, parent: jax.Array,
                       write_slot: jax.Array, fresh_page: jax.Array,
                       needs_fresh: jax.Array, frozen: jax.Array
                       ) -> jax.Array:
    """The beam reorder's page-table half, as int32 table math: each
    surviving row inherits its ``parent`` row's table, and the rows
    that diverge (``needs_fresh`` — a page-boundary crossing or a
    non-keeper child that must fork the partial page) get their
    ``write_slot`` entry repointed at a host-claimed ``fresh_page``.
    ``frozen`` rows (EOS'd hypotheses carried for the merge) zero their
    table — they stop writing and hold no pages.

    Pure table→table function so the multi-step beam scan can carry it;
    refcounts stay a HOST concern: the engine applies the resulting
    table as a ``retable`` diff after the round syncs."""
    t = jnp.asarray(page_table, jnp.int32)
    new = t[jnp.asarray(parent, jnp.int32)]
    hot = (jnp.arange(t.shape[1], dtype=jnp.int32)[None, :]
           == jnp.asarray(write_slot, jnp.int32)[:, None])
    new = jnp.where(hot & jnp.asarray(needs_fresh)[:, None],
                    jnp.asarray(fresh_page, jnp.int32)[:, None], new)
    return jnp.where(jnp.asarray(frozen)[:, None], 0, new)


def _reference(q, pool_k, pool_v, page_table, row_pos, scale):
    """Pure-jnp paged attention read (interpret mode, or rows past the
    VMEM token cap). Gathers each row's pages and then runs the
    EXACT op sequence of the dense reference (decode_attention._reference)
    over the assembled [R, H, MP*PL, dh] view — elementwise-identical
    inputs at unmasked positions + identical ops = bitwise-identical
    outputs vs a dense cache of length MP*PL (tests pin this)."""
    r, mp = page_table.shape
    page_len = pool_k.shape[2]
    h, dh = pool_k.shape[1], pool_k.shape[3]

    def gather(pool):
        g = pool[page_table]                          # [R, MP, H, PL, dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(r, h, mp * page_len, dh)

    k_full, v_full = gather(pool_k), gather(pool_v)
    s = jnp.einsum("rhqd,rhkd->rhqk", q.astype(jnp.float32),
                   k_full.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    steps = jnp.arange(mp * page_len)[None, None, None, :]
    s = jnp.where(steps <= row_pos[:, None, None, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rhqk,rhkd->rhqd", p, v_full.astype(jnp.float32),
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _kernel(pt_ref, pos_ref, q_ref, pk_ref, pv_ref, o_ref, ks_ref, vs_ref,
            *, scale, page_len, n_pages_row):
    """Grid (R, H, MP): cells p = 0..MP-1 stage the row's pages into
    VMEM scratch (the physical page arrived via the scalar-prefetch
    block index map); the LAST cell runs the dense kernel's one-shot
    masked softmax over the assembled row — op order kept identical to
    decode_attention._kernel so parity is bitwise in interpret mode."""
    # program ids hoisted to the top level: the interpret-mode lowering
    # only rewrites program_id in the kernel's own trace, not inside a
    # pl.when branch (same hoist the flash kernels do)
    r = pl.program_id(0)
    p = pl.program_id(2)
    ks_ref[pl.ds(p * page_len, page_len), :] = pk_ref[0, 0]
    vs_ref[pl.ds(p * page_len, page_len), :] = pv_ref[0, 0]

    @pl.when(p == n_pages_row - 1)
    def _finish():
        pos = pos_ref[r]
        max_len = n_pages_row * page_len
        qv = q_ref[0, 0].astype(jnp.float32)              # [1, dh]
        s = jax.lax.dot_general(
            qv, ks_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [1, L]
        steps = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1)
        s = jnp.where(steps <= pos, s, MASK_VALUE)
        m = jnp.max(s, axis=1, keepdims=True)
        pr = jnp.exp(s - m)
        pr = pr / jnp.sum(pr, axis=1, keepdims=True)
        o = jax.lax.dot_general(
            pr, vs_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [1, dh]
        o_ref[0, 0] = o.astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           pool_k: jax.Array, pool_v: jax.Array,
                           page_table: jax.Array, row_pos: jax.Array,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One paged decode-attention step; see module docstring.

    q/k_new/v_new ``[R, H, 1, dh]``; pool_k/pool_v
    ``[n_pages, H, page_len, dh]``; page_table ``[R, max_pages]`` int32;
    row_pos ``[R]`` int32 per-row write positions (< 0 = inactive row —
    no pool write, deterministic-garbage output the caller masks).
    Returns ``(context [R,H,1,dh], new_pool_k, new_pool_v)`` — the new
    pools hold the inserted tokens (ONE scatter; no full write-back).
    """
    r, h, _, dh = q.shape
    mp = page_table.shape[1]
    page_len = pool_k.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)

    new_k, new_v = pool_insert(pool_k, pool_v, k_new, v_new,
                               page_table, row_pos)

    from ..auto_tuner import kv_pool_max_tokens
    if interpret is None:
        # default gate mirrors the fused decode kernel's 'auto': the
        # kernel only pays on the TPU backend — interpret mode
        # emulates every (row, head, page) grid cell sequentially
        # (seconds per step at serving widths), and the jnp gather
        # reference is BITWISE-identical anyway (tests pin it; tests
        # pass interpret=True explicitly to exercise the kernel)
        interpret = _interpret_default()
        if interpret:
            out = _reference(q, new_k, new_v, page_table, row_pos,
                             float(scale))
            return out, new_k, new_v
    if mp * page_len > kv_pool_max_tokens(dh):
        # degrade, don't OOM: the scratch row [MP*PL, dh] x2 must fit
        # the VMEM budget (auto_tuner scales the cap down for wide heads)
        out = _reference(q, new_k, new_v, page_table, row_pos,
                         float(scale))
        return out, new_k, new_v

    kernel = functools.partial(_kernel, scale=float(scale),
                               page_len=page_len, n_pages_row=mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, h, mp),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, p_, t, s: (r_, h_, 0, 0)),
            # the page-table gather: pool blocks come from the PHYSICAL
            # page the row's table names for logical page p
            pl.BlockSpec((1, 1, page_len, dh),
                         lambda r_, h_, p_, t, s: (t[r_, p_], h_, 0, 0)),
            pl.BlockSpec((1, 1, page_len, dh),
                         lambda r_, h_, p_, t, s: (t[r_, p_], h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda r_, h_, p_, t, s: (r_, h_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((mp * page_len, dh), pool_k.dtype),
            pltpu.VMEM((mp * page_len, dh), pool_v.dtype),
        ],
    )
    out, = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, h, 1, dh), q.dtype)],
        interpret=bool(interpret),
    )(page_table, row_pos, q, new_k, new_v)
    return out, new_k, new_v
