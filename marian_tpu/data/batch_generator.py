"""Token-budget batch generation with XLA-friendly static shapes.

Rebuild of reference src/data/batch_generator.h :: BatchGenerator<Corpus>::
fetchBatches and src/data/corpus_base.h :: CorpusBatch/SubBatch, redesigned
for the TPU compilation model:

- same maxi-batch logic: prefetch ``--maxi-batch`` × ``--mini-batch``
  sentences, sort by target (or source) length, fill minibatches by sentence
  count (``--mini-batch``) or token budget (``--mini-batch-words``), then
  shuffle the minibatch order;
- NEW (the one real design change vs. the GPU reference, SURVEY.md §7):
  every emitted batch is padded to a shape from a small static **bucket
  table** — sequence lengths snap up to a bucket boundary and the sentence
  dimension snaps up to a divisor-friendly size — so XLA compiles a handful
  of programs instead of one per shape (the reference's --mini-batch-fit
  binary search becomes this table);
- background prefetch on a host thread (the reference's fetchBatches thread).

Batch layout is batch-major ``[batch, time]`` (the reference is time-major
``[time * batch]``; batch-major is the natural XLA layout).
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .corpus import Corpus, SentenceTuple, CorpusState
from ..common import logging as log
from ..obs import trace as obs_trace

# Default sequence-length buckets: fine steps early (NMT sentences are short),
# geometric later. Snapping to these keeps compile count ~O(10).
DEFAULT_LENGTH_BUCKETS = (8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
                          768, 1024, 1536, 2048, 3072, 4096)


def bucket_length(n: int, buckets: Sequence[int] = DEFAULT_LENGTH_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 511) // 512) * 512


def bucket_batch_size(n: int, multiple: int = 8) -> int:
    """Snap sentence count up to a multiple (pad rows are fully masked)."""
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def padded_batch_cost(n_rows: int, max_len: int,
                      length_buckets: Sequence[int] = DEFAULT_LENGTH_BUCKETS,
                      batch_multiple: int = 8) -> int:
    """Device cost (padded tokens) of a batch of ``n_rows`` sentences whose
    longest member has ``max_len`` tokens, under the bucketed static-shape
    table. This is the ONE cost model shared by the training-side token
    budget (_split_maxi flushes on ``rows * bucket_length``) and the serving
    scheduler (serving/scheduler.py) — serve-time batches must land on the
    same (rows, width) grid the jit cache was warmed on, or every odd batch
    costs a fresh XLA compile."""
    return (bucket_batch_size(n_rows, batch_multiple)
            * bucket_length(max_len, length_buckets))


def budget_shapes(options) -> List[Tuple[int, int]]:
    """The (width, rows) a one-stream batch can have under a token budget
    (--mini-batch-words) and an explicit bucket table (--length-buckets):
    one canonical row count a width, as _split_maxi's flush derives it.
    Empty where either is missing (the default table is open-ended, and
    two streams make pairs of widths)."""
    buckets = [int(b) for b in options.get("length-buckets", None) or []]
    words = int(options.get("mini-batch-words", 0) or 0)
    multiple = int(options.get("batch-row-multiple", 8) or 8)
    if not buckets or words <= 0:
        return []
    return [(w, max(multiple, words // w // multiple * multiple))
            for w in buckets]


@dataclasses.dataclass
class SubBatch:
    """One stream of a batch (reference: SubBatch: indices + mask)."""
    ids: np.ndarray    # [batch, time] int32, EOS-terminated, 0-padded
    mask: np.ndarray   # [batch, time] float32; 1 on real tokens (incl. EOS)

    @property
    def batch_size(self) -> int:
        return self.ids.shape[0]

    @property
    def batch_width(self) -> int:
        return self.ids.shape[1]

    @property
    def batch_words(self) -> int:
        return int(self.mask.sum())


@dataclasses.dataclass
class CorpusBatch:
    """A training batch across streams (reference: CorpusBatch)."""
    sub: List[SubBatch]               # [src..., trg]; trg is last
    sentence_ids: np.ndarray          # [batch] corpus line numbers (-1 = pad row)
    guided_alignment: Optional[np.ndarray] = None  # [batch, trg_len, src_len]
    data_weights: Optional[np.ndarray] = None      # [batch, trg_len] or [batch, 1]
    corpus_state: Optional[dict] = None   # post-window resume snapshot:
    # where the corpus stands once this batch's whole maxi window has
    # been applied — what do_save records for crash-safe resume

    @property
    def src(self) -> SubBatch:
        return self.sub[0]

    @property
    def trg(self) -> SubBatch:
        return self.sub[-1]

    @property
    def size(self) -> int:
        return int((self.sentence_ids >= 0).sum())

    @property
    def batch_size(self) -> int:
        return self.sub[0].batch_size

    @property
    def words(self) -> int:
        """Real target labels (the scheduler's label count)."""
        return self.trg.batch_words

    @property
    def src_words(self) -> int:
        return self.src.batch_words

    def shape_key(self) -> Tuple[int, ...]:
        return tuple(s.ids.shape[1] for s in self.sub) + (self.batch_size,)


def make_batch(tuples: Sequence[SentenceTuple], n_streams: int,
               length_buckets=DEFAULT_LENGTH_BUCKETS,
               batch_multiple: int = 8,
               pad_batch: bool = True,
               corpus_state: Optional[dict] = None,
               weighting_type: Optional[str] = None,
               fixed_rows: int = 0) -> CorpusBatch:
    """Pad a list of SentenceTuples into one fixed-shape CorpusBatch.

    `fixed_rows` > 0 pins the row count (extra rows fully masked): with a
    token budget the generator derives ONE canonical row count per width
    combo, collapsing the compiled-shape space to ~#length-buckets. Every
    distinct (widths, rows) shape costs a full XLA compile of the train
    step — on TPU that is tens of seconds,
    so an unbounded shape space is the single worst data-layer decision a
    TPU port can make. Masked pad rows cost only the FLOPs of an
    already-budget-sized batch."""
    n = len(tuples)
    if fixed_rows > 0:
        # n can overshoot fixed_rows by < batch_multiple (the budget check
        # flushes on padded tokens, fixed_rows is the budget floored to the
        # multiple); snapping up bounds the shape by the pre-canonical
        # worst case, so at most 2 row counts exist per width combo
        bsz = max(fixed_rows, bucket_batch_size(n, batch_multiple))
    else:
        bsz = bucket_batch_size(n, batch_multiple) if pad_batch else n
    subs: List[SubBatch] = []
    for s in range(n_streams):
        maxlen = max(len(t.streams[s]) for t in tuples)
        width = bucket_length(maxlen, length_buckets) if pad_batch else maxlen
        ids = np.zeros((bsz, width), dtype=np.int32)
        mask = np.zeros((bsz, width), dtype=np.float32)
        for b, t in enumerate(tuples):
            seq = t.streams[s]
            ids[b, : len(seq)] = seq
            mask[b, : len(seq)] = 1.0
        subs.append(SubBatch(ids, mask))
    sent_ids = np.full((bsz,), -1, dtype=np.int64)
    for b, t in enumerate(tuples):
        sent_ids[b] = t.idx

    guided = None
    if any(t.alignment is not None for t in tuples):
        tw, sw = subs[-1].ids.shape[1], subs[0].ids.shape[1]
        guided = np.zeros((bsz, tw, sw), dtype=np.float32)
        for b, t in enumerate(tuples):
            if t.alignment is not None:
                t.alignment.fill_dense(guided[b])

    weights = None
    if any(t.weights is not None for t in tuples):
        tw = subs[-1].ids.shape[1]
        # --data-weighting-type declares the level explicitly; without it,
        # infer word-level from multi-valued weight lines
        if weighting_type in ("word", "sentence"):
            word_level = weighting_type == "word"
        else:
            word_level = any(t.weights is not None and len(t.weights) > 1
                             for t in tuples)
        if word_level:
            weights = np.ones((bsz, tw), dtype=np.float32)
            for b, t in enumerate(tuples):
                if t.weights is not None:
                    w = t.weights[:tw]
                    weights[b, : len(w)] = w
        else:
            weights = np.ones((bsz, 1), dtype=np.float32)
            for b, t in enumerate(tuples):
                if t.weights is not None:
                    weights[b, 0] = t.weights[0]

    return CorpusBatch(subs, sent_ids, guided, weights, corpus_state)


class BatchGenerator:
    """Iterator of CorpusBatches with maxi-batch sorting and prefetch."""

    def __init__(self, corpus: Corpus, options=None,
                 mini_batch: int = 64, mini_batch_words: int = 0,
                 maxi_batch: int = 100, maxi_batch_sort: str = "trg",
                 shuffle_batches: Optional[bool] = None,
                 batch_multiple: int = 8, pad_batch: bool = True,
                 length_buckets=DEFAULT_LENGTH_BUCKETS,
                 prefetch: bool = True, seed: int = 1,
                 budget_scale=None):
        self.corpus = corpus
        if options is not None:
            mini_batch = int(options.get("mini-batch", mini_batch) or mini_batch)
            mini_batch_words = int(options.get("mini-batch-words", mini_batch_words) or 0)
            maxi_batch = int(options.get("maxi-batch", maxi_batch) or 1)
            maxi_batch_sort = options.get("maxi-batch-sort", maxi_batch_sort)
            seed = int(options.get("seed", seed)) or seed
            if shuffle_batches is None:
                shuffle_batches = options.get("shuffle", "data") in ("data", "batches")
            # the padded shapes follow the configuration: long rows want a
            # row multiple of 1 and a bucket table that starts wide
            batch_multiple = int(options.get("batch-row-multiple",
                                             batch_multiple)
                                 or batch_multiple)
            length_buckets = tuple(int(b) for b in options.get(
                "length-buckets", None) or length_buckets)
        self.weighting_type = (str(options.get("data-weighting-type",
                                               "sentence"))
                               if options is not None
                               and options.get("data-weighting", None)
                               else None)
        self.mini_batch = max(1, mini_batch)
        self.mini_batch_words = mini_batch_words
        self.maxi_batch = max(1, maxi_batch)
        self.sort_key = maxi_batch_sort
        self.shuffle_batches = bool(shuffle_batches) and not corpus.inference
        self.batch_multiple = batch_multiple
        self.pad_batch = pad_batch
        self.length_buckets = length_buckets
        self.prefetch = prefetch
        # --mini-batch-warmup: a callable returning a scale in (0, 1] that
        # shrinks the effective batch early in training (checked per
        # maxi-window, so ramp-up is window-granular)
        self.budget_scale = budget_scale
        self._rs = np.random.RandomState(seed % (2**31))
        self.n_streams = len(corpus.vocabs)

    # -- batching core ------------------------------------------------------
    def _split_maxi(self, buf: List[SentenceTuple], state: dict) -> List[CorpusBatch]:
        if not buf:
            return []
        if self.sort_key == "trg":
            buf = sorted(buf, key=lambda t: (len(t.trg), len(t.src)))
        elif self.sort_key == "src":
            buf = sorted(buf, key=lambda t: (len(t.src), len(t.trg)))
        batches: List[CorpusBatch] = []
        cur: List[SentenceTuple] = []
        cur_maxlens = [0] * self.n_streams

        def flush():
            if not cur:
                return
            fixed = 0
            if self.pad_batch and words_budget > 0:
                # canonical row count per width combo: the shape a full
                # budget-sized batch of this width would have, so underfull
                # batches (maxi-window tails) reuse an existing compile
                # instead of minting a new (widths, rows) shape. Rounded
                # DOWN so the canonical shape never exceeds the worst case
                # --mini-batch-fit probed for this budget (batch_fit.py
                # rounds down too); the rows-counted path keeps its natural
                # sizes — inference entry points must not pay full-batch
                # compute for small inputs.
                w = bucket_length(max(len(t.trg) for t in cur),
                                  self.length_buckets)
                fixed = max(self.batch_multiple,
                            (words_budget // w) // self.batch_multiple
                            * self.batch_multiple)
            with obs_trace.span("data.make_batch") as sp:
                b = make_batch(cur, self.n_streams, self.length_buckets,
                               self.batch_multiple, self.pad_batch,
                               corpus_state=state,
                               weighting_type=self.weighting_type,
                               fixed_rows=fixed)
                if sp:
                    sp.set_attrs(rows=b.batch_size,
                                 width=b.trg.batch_width)
            batches.append(b)

        scale = 1.0
        if self.budget_scale is not None:
            scale = max(min(float(self.budget_scale()), 1.0), 1e-3)
        words_budget = max(int(self.mini_batch_words * scale), 1) \
            if self.mini_batch_words > 0 else 0
        rows_budget = max(int(self.mini_batch * scale), 1)
        for t in buf:
            lens = [len(s) for s in t.streams]
            new_maxlens = [max(a, b) for a, b in zip(cur_maxlens, lens)]
            n = len(cur) + 1
            if words_budget > 0:
                # token budget on padded target size (Marian counts labels);
                # use the bucketed width so the budget reflects real cost
                padded = bucket_length(new_maxlens[-1], self.length_buckets) \
                    if self.pad_batch else new_maxlens[-1]
                over = n * padded > words_budget and len(cur) > 0
            else:
                over = n > rows_budget
            if over:
                flush()
                cur = []
                new_maxlens = lens
            cur.append(t)
            cur_maxlens = new_maxlens
        flush()
        if self.shuffle_batches:
            self._rs.shuffle(batches)
        return batches

    def _generate(self) -> Iterator[CorpusBatch]:
        from ..common import faultpoints as fp
        cap = self.maxi_batch * self.mini_batch
        it = iter(self.corpus)
        first = True
        while True:
            # the epoch's FIRST window carries the corpus' shuffle (run by
            # Corpus.__iter__ on its first next()), the read and encode of
            # up to a whole maxi-batch, and its sort: the stall every
            # epoch opens with, as one span on the prefetch thread
            with (obs_trace.span("data.epoch_prepare") if first
                  else obs_trace.NOOP_SPAN) as sp:
                buf = list(itertools.islice(it, cap))
                if not buf:
                    break
                # POST-window snapshot: the corpus position once every
                # sentence of this maxi window has been consumed (after
                # the epoch rolled over, when the window ended it). A
                # save taken after applying this window's batches resumes
                # HERE — exact at window boundaries, window-granular in
                # between (docs/ROBUSTNESS.md). The LIVE corpus.state is
                # no resume point at all: the prefetch thread runs it
                # arbitrarily far ahead of what training has applied.
                state = self.corpus.state.as_dict()
                batches = self._split_maxi(buf, state)
                if sp:
                    sp.set_attrs(lines=len(buf), batches=len(batches))
            first = False
            for b in batches:
                # chaos harness hook: a corpus/pipeline failure (bad
                # shard, fs hiccup) surfaces HERE, mid-epoch — the
                # crash-resume protocol must cover it like any kill
                fp.fault_point("data.batch.next")
                yield b

    def __iter__(self) -> Iterator[CorpusBatch]:
        if not self.prefetch:
            yield from self._generate()
            return
        # background prefetch thread (reference: fetchBatches thread)
        q: "queue.Queue" = queue.Queue(maxsize=16)
        _END = object()
        err: List[BaseException] = []

        def worker():
            try:
                for b in self._generate():
                    q.put(b)
            except BaseException as e:  # propagate into consumer
                err.append(e)
            finally:
                q.put(_END)

        th = threading.Thread(target=worker, daemon=True, name="batchgen-prefetch")
        th.start()
        while True:
            with obs_trace.span("data.wait"):
                b = q.get()
            if b is _END:
                break
            yield b
        th.join()
        if err:
            raise err[0]

    # -- stats (reference: GraphGroup::collectStats analogue) ---------------
    def stats(self, n: int = 1000) -> dict:
        """Sample shape distribution for logging/tuning."""
        shapes = {}
        for i, b in enumerate(self):
            if i >= n:
                break
            shapes[b.shape_key()] = shapes.get(b.shape_key(), 0) + 1
        return shapes
