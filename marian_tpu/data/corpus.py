"""Line-parallel corpora with epoch shuffling and exact-resume positions.

Rebuild of reference src/data/corpus.cpp :: Corpus/CorpusBase and
src/data/corpus_sqlite.cpp (resumability). A SentenceTuple is one training
example across streams (source ∥ target ∥ optional alignment ∥ weights).

Resume design: instead of the reference's SQLite corpus (O(1) mid-epoch
restart) we checkpoint the iterator state — (epoch, position-in-epoch,
shuffle seed) — and fast-forward deterministically on restore; the shuffle
permutation is a function of (seed, epoch) so a restart reproduces the same
order without temp files.
"""

from __future__ import annotations

import dataclasses
import gzip
import os
from typing import Iterator, List, Optional, Sequence

import numpy as np

from .vocab import VocabBase
from ..common import logging as log


@dataclasses.dataclass
class SentenceTuple:
    """One example: token-id sequences per stream (reference:
    src/data/corpus_base.h :: SentenceTuple)."""
    idx: int                      # corpus line number (for alignments/weights)
    streams: List[List[int]]      # token ids per stream, EOS-terminated
    alignment: Optional[list] = None
    weights: Optional[List[float]] = None

    @property
    def src(self) -> List[int]:
        return self.streams[0]

    @property
    def trg(self) -> List[int]:
        return self.streams[-1]


def _open_maybe_gz(path: str):
    if path.endswith(".gz"):
        return gzip.open(path, "rt", encoding="utf-8")
    return open(path, "r", encoding="utf-8")


@dataclasses.dataclass
class CorpusState:
    """Serialized into training progress for exact resume."""
    epoch: int = 0
    position: int = 0   # sentences already yielded in this epoch
    seed: int = 1

    # Positions are the loader's own: this one counts raw corpus lines,
    # the C++ loader of earlier versions ("native") indexed its
    # length-filtered order. The tag lets resume tell a checkpoint of
    # another loader instead of silently seeking to the wrong sentence
    # (ADVICE r1). No annotation: a constant, not a field.
    BACKEND = "python"

    def as_dict(self):
        return {**dataclasses.asdict(self), "backend": self.BACKEND}

    @classmethod
    def from_dict(cls, d):
        if not d:
            return cls()
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


class Corpus:
    """Reads N parallel text files, encodes with vocabs, yields SentenceTuples.

    shuffle: 'data' (shuffle sentences each epoch), 'batches'/'none' handled
    by the BatchGenerator. Length filtering follows --max-length /
    --max-length-crop semantics.
    """

    def __init__(self, paths: Sequence[str], vocabs: Sequence[VocabBase],
                 options=None, inference: bool = False,
                 state: Optional[CorpusState] = None):
        # --tsv: ONE tab-separated file carries every stream (reference:
        # CorpusBase TSV mode); --tsv-fields pins the column count,
        # defaulting to the vocab count
        self.tsv = bool(options.get("tsv", False)) if options else False
        self.tsv_fields = (int(options.get("tsv-fields", 0) or 0)
                           if options else 0)
        # --input-reorder: permutation mapping stream i ← column perm[i]
        self.input_reorder = [int(i) for i in
                              (options.get("input-reorder", []) or [])] \
            if options else []
        if self.tsv:
            if len(paths) != 1:
                raise ValueError(
                    f"--tsv expects ONE tab-separated train file, got "
                    f"{len(paths)}")
            n_fields = self.tsv_fields or len(vocabs)
            if n_fields != len(vocabs):
                raise ValueError(
                    f"--tsv-fields {n_fields} must match the number of "
                    f"--vocabs ({len(vocabs)})")
            paths = list(paths) * len(vocabs)   # stream i = column i
        assert len(paths) == len(vocabs), (paths, len(vocabs))
        self.paths = list(paths)
        self.vocabs = list(vocabs)
        self.inference = inference
        self.max_length = int(options.get("max-length", 50)) if options else 10**9
        self.max_length_crop = bool(options.get("max-length-crop", False)) if options else False
        self.shuffle_mode = (options.get("shuffle", "data") if options else "none")
        self.right_left = bool(options.get("right-left", False)) if options else False
        # target-stream id reversal applies to teacher-forced streams
        # (training, scoring); decode-time TextInput leaves targets alone
        # (the printer un-reverses hypotheses instead). The n-best
        # rescorer overrides this to score hypotheses against an R2L
        # model (reverse_target=True despite inference encoding).
        self.reverse_target = self.right_left and not inference
        self.all_caps_every = int(options.get("all-caps-every", 0)) if options else 0
        self.title_case_every = int(options.get("english-title-case-every", 0)) if options else 0
        self.state = state or CorpusState(
            seed=int(options.get("seed", 1)) or 1 if options else 1)
        self._lines_cache: Optional[List[List[str]]] = None
        # guided alignment / data weighting side-streams
        self.align_path = None
        self.weight_path = None
        if options is not None:
            ga = options.get("guided-alignment", "none")
            if ga and ga != "none" and os.path.exists(str(ga)):
                self.align_path = str(ga)
            dw = options.get("data-weighting", None)
            if dw:
                self.weight_path = str(dw)

    # -- raw line access ----------------------------------------------------
    def _read_all(self) -> List[List[str]]:
        """Read the full corpus into RAM (the reference offers in-RAM shuffle
        via --shuffle-in-ram; NMT corpora of the baseline configs fit)."""
        if self._lines_cache is None:
            if self.tsv:
                with _open_maybe_gz(self.paths[0]) as fh:
                    rows = [l.rstrip("\n").split("\t") for l in fh]
                k = len(self.vocabs)
                for i, row in enumerate(rows):
                    if len(row) != k:
                        raise ValueError(
                            f"--tsv: line {i + 1} of {self.paths[0]} has "
                            f"{len(row)} fields, expected {k}")
                cols = list(range(k))
                if self.input_reorder:   # --input-reorder permutation
                    if sorted(self.input_reorder) != cols:
                        raise ValueError(
                            f"--input-reorder {self.input_reorder} is not "
                            f"a permutation of 0..{k - 1}")
                    cols = self.input_reorder
                streams = [[row[j] for row in rows] for j in cols]
            else:
                streams = []
                for p in self.paths:
                    with _open_maybe_gz(p) as fh:
                        streams.append([l.rstrip("\n") for l in fh])
            n = len(streams[0])
            for p, s in zip(self.paths[1:], streams[1:]):
                if len(s) != n:
                    raise ValueError(
                        f"Corpus streams differ in length: {self.paths[0]} has {n}, "
                        f"{p} has {len(s)}")
            if self.align_path:
                with _open_maybe_gz(self.align_path) as fh:
                    aligns = [l.rstrip("\n") for l in fh]
                if len(aligns) != n:
                    raise ValueError("Alignment file length mismatch")
                self._aligns = aligns
            else:
                self._aligns = None
            if self.weight_path:
                with _open_maybe_gz(self.weight_path) as fh:
                    weights = [l.rstrip("\n") for l in fh]
                if len(weights) != n:
                    raise ValueError("Weight file length mismatch")
                self._weights = weights
            else:
                self._weights = None
            self._lines_cache = streams
        return self._lines_cache

    def __len__(self) -> int:
        return len(self._read_all()[0])

    # -- epoch iteration ----------------------------------------------------
    def _permutation(self, epoch: int) -> np.ndarray:
        n = len(self)
        if self.shuffle_mode != "data" or self.inference:
            return np.arange(n)
        rs = np.random.RandomState((self.state.seed + 0x9E37 * (epoch + 1)) % (2**31))
        return rs.permutation(n)

    def _augment(self, line: str, sent_no: int) -> str:
        # --all-caps-every / --english-title-case-every (corpus.cpp augmentation)
        if self.all_caps_every and sent_no % self.all_caps_every == self.all_caps_every - 1:
            return line.upper()
        if self.title_case_every and sent_no % self.title_case_every == self.title_case_every - 1:
            return " ".join(w[:1].upper() + w[1:] if w else w for w in line.split(" "))
        return line

    def _make_tuple(self, idx: int, sent_no: int) -> Optional[SentenceTuple]:
        streams_txt = self._read_all()
        encoded: List[List[int]] = []
        for si, (lines, vocab) in enumerate(zip(streams_txt, self.vocabs)):
            text = self._augment(lines[idx], sent_no)
            ids = vocab.encode(text, add_eos=True, inference=self.inference)
            # length filter: count incl. EOS like Marian (maxLengthCrop keeps EOS)
            if len(ids) > self.max_length + 1:
                if self.max_length_crop or self.inference:
                    ids = ids[: self.max_length] + [vocab.eos_id]
                else:
                    return None
            # --right-left: train the target right-to-left (reference:
            # corpus rightLeft_ reversing the target stream, EOS stays last)
            if self.reverse_target and si == len(self.vocabs) - 1:
                ids = ids[-2::-1] + [ids[-1]]
            encoded.append(ids)
        align = None
        if getattr(self, "_aligns", None) is not None:
            from .alignment import WordAlignment
            align = WordAlignment.parse(self._aligns[idx])
        weights = None
        if getattr(self, "_weights", None) is not None:
            weights = [float(x) for x in self._weights[idx].split()]
        return SentenceTuple(idx, encoded, alignment=align, weights=weights)

    def __iter__(self) -> Iterator[SentenceTuple]:
        """Yield the remainder of the current epoch from self.state.position,
        then advance epochs indefinitely (the Train driver bounds epochs)."""
        while True:
            perm = self._permutation(self.state.epoch)
            n = len(perm)
            while self.state.position < n:
                pos = self.state.position
                self.state.position += 1
                st = self._make_tuple(int(perm[pos]), pos)
                if st is not None:
                    yield st
            self.state.epoch += 1
            self.state.position = 0
            return  # one epoch per iterator pass; Train driver loops epochs

    def iter_epoch(self) -> Iterator[SentenceTuple]:
        return iter(self)

    def restore(self, state_dict) -> None:
        self.state = CorpusState.from_dict(state_dict)


class TextInput(Corpus):
    """stdin/string input for the decoder/server (reference:
    src/data/text_input.cpp). No shuffling, no length filter by default."""

    def __init__(self, lines_per_stream: Sequence[Sequence[str]],
                 vocabs: Sequence[VocabBase], options=None,
                 reverse_target: bool = False):
        super().__init__(paths=["<text>"] * len(lines_per_stream), vocabs=vocabs,
                         options=None, inference=True)
        if options is not None:
            self.max_length = int(options.get("max-length", 1000))
            self.max_length_crop = True
        self.reverse_target = reverse_target
        self.shuffle_mode = "none"
        self._lines_cache = [list(s) for s in lines_per_stream]
        self._aligns = None
        self._weights = None
