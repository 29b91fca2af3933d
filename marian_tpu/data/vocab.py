"""Vocabularies: frequency-sorted word↔id maps with Marian's conventions.

Rebuild of reference src/data/vocab.cpp :: Vocab::create and
src/data/default_vocab.cpp :: DefaultVocab. Conventions kept:

- special tokens ``</s>`` = 0 (EOS) and ``<unk>`` = 1 (UNK);
- vocab files are YAML/JSON maps ``word: id`` (``.yml``/``.yaml``/``.json``)
  or plain text one-word-per-line (ids by line order after specials);
- ``Vocab.create`` dispatches on file extension: ``.spm`` → SentencePiece,
  ``.fsv`` → factored vocab, else default;
- creating a missing vocab from training data (marian-vocab equivalent).
"""

from __future__ import annotations

import collections
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence

import yaml

from ..common import logging as log

DEFAULT_EOS_STR = "</s>"
DEFAULT_UNK_STR = "<unk>"
EOS_ID = 0
UNK_ID = 1


class VocabBase:
    """Interface (reference: src/data/vocab_base.h :: IVocab)."""

    def encode(self, line: str, add_eos: bool = True, inference: bool = False) -> List[int]:
        raise NotImplementedError

    def decode(self, ids: Sequence[int], ignore_eos: bool = True) -> str:
        raise NotImplementedError

    def surface(self, ids: Sequence[int]) -> List[str]:
        """Per-token strings (for alignments / debugging)."""
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    @property
    def eos_id(self) -> int:
        return EOS_ID

    @property
    def unk_id(self) -> int:
        return UNK_ID


class DefaultVocab(VocabBase):
    """Word-level vocab from YAML/JSON/text (reference: default_vocab.cpp)."""

    def __init__(self, word2id: Dict[str, int]):
        self._w2i = dict(word2id)
        self._i2w: Dict[int, str] = {}
        for w, i in self._w2i.items():
            self._i2w[i] = w
        # ensure specials
        if self._w2i.get(DEFAULT_EOS_STR, EOS_ID) != EOS_ID or \
           self._w2i.get(DEFAULT_UNK_STR, UNK_ID) != UNK_ID:
            raise ValueError(
                f"Vocab must map {DEFAULT_EOS_STR}→{EOS_ID}, {DEFAULT_UNK_STR}→{UNK_ID}")
        self._w2i.setdefault(DEFAULT_EOS_STR, EOS_ID)
        self._w2i.setdefault(DEFAULT_UNK_STR, UNK_ID)
        self._i2w.setdefault(EOS_ID, DEFAULT_EOS_STR)
        self._i2w.setdefault(UNK_ID, DEFAULT_UNK_STR)
        self._size = max(self._i2w) + 1

    # -- IO -----------------------------------------------------------------
    @classmethod
    def load(cls, path: str, max_size: int = 0) -> "DefaultVocab":
        if path.endswith((".yml", ".yaml")):
            with open(path, "r", encoding="utf-8") as fh:
                m = yaml.safe_load(fh)
        elif path.endswith(".json"):
            with open(path, "r", encoding="utf-8") as fh:
                m = json.load(fh)
        else:  # plain text, one word per line
            m = {}
            with open(path, "r", encoding="utf-8") as fh:
                next_id = 2
                for line in fh:
                    w = line.rstrip("\n")
                    if not w or w in (DEFAULT_EOS_STR, DEFAULT_UNK_STR):
                        continue
                    m[w] = next_id
                    next_id += 1
            m[DEFAULT_EOS_STR] = EOS_ID
            m[DEFAULT_UNK_STR] = UNK_ID
        if max_size:
            m = {w: i for w, i in m.items() if i < max_size}
        return cls(m)

    def save(self, path: str) -> None:
        # Marian writes ids in value order; yaml map with sorted-by-id keys.
        with open(path, "w", encoding="utf-8") as fh:
            for i, w in sorted(self._i2w.items()):
                yaml.safe_dump({w: i}, fh, default_flow_style=False,
                               allow_unicode=True)

    @classmethod
    def build(cls, lines: Iterable[str], max_size: int = 0) -> "DefaultVocab":
        """Frequency-sorted vocab from raw text (marian-vocab equivalent:
        reference src/command/marian_vocab.cpp)."""
        counter: collections.Counter = collections.Counter()
        for line in lines:
            counter.update(line.split())
        words = [w for w, _ in sorted(counter.items(), key=lambda kv: (-kv[1], kv[0]))]
        if max_size:
            words = words[: max(0, max_size - 2)]
        m = {DEFAULT_EOS_STR: EOS_ID, DEFAULT_UNK_STR: UNK_ID}
        for j, w in enumerate(words):
            m[w] = j + 2
        return cls(m)

    # -- encode/decode ------------------------------------------------------
    def encode(self, line: str, add_eos: bool = True, inference: bool = False) -> List[int]:
        ids = [self._w2i.get(w, UNK_ID) for w in line.split()]
        if add_eos:
            ids.append(EOS_ID)
        return ids

    def decode(self, ids: Sequence[int], ignore_eos: bool = True) -> str:
        return " ".join(self.surface(ids, ignore_eos))

    def surface(self, ids: Sequence[int], ignore_eos: bool = True) -> List[str]:
        out = []
        for i in ids:
            if ignore_eos and i == EOS_ID:
                continue
            out.append(self._i2w.get(int(i), DEFAULT_UNK_STR))
        return out

    def __len__(self) -> int:
        return self._size

    def __getitem__(self, word: str) -> int:
        return self._w2i.get(word, UNK_ID)

    def id_to_word(self, i: int) -> str:
        return self._i2w.get(int(i), DEFAULT_UNK_STR)


def create_vocab(path: Optional[str], options=None, stream_index: int = 0,
                 train_paths: Optional[List[str]] = None,
                 max_size: int = 0) -> VocabBase:
    """Vocab factory (reference: Vocab::create). Dispatch on extension;
    builds the vocab from training data when the file does not exist."""
    if path and path.endswith(".spm"):
        from .spm_vocab import HAVE_SPM, SentencePieceVocab
        if os.path.exists(path):
            # dispatch an EXISTING model by content, not environment: a
            # BPE-fallback file must load as BPE even after the wheel
            # appears (else SentencePieceProcessor dies with an opaque
            # protobuf error on our JSON)
            with open(path, "rb") as fh:
                head = fh.read(64)
            if b"marian_tpu-bpe-v1" in head:
                from .bpe_vocab import BPEVocab
                return BPEVocab(path, options=options,
                                stream_index=stream_index)
        if HAVE_SPM:
            return SentencePieceVocab(path, options=options,
                                      stream_index=stream_index,
                                      train_paths=train_paths)
        # wheel absent: the in-repo BPE fallback keeps raw-text →
        # subword-vocab → train workflows alive (not byte-compatible
        # with real .spm binaries — bpe_vocab.py refuses those loudly)
        from .bpe_vocab import BPEVocab
        log.warn("sentencepiece package not installed — using the "
                 "in-repo BPE fallback for {} (SPM-format models are "
                 "not loadable without the wheel)", path)
        return BPEVocab(path, options=options, stream_index=stream_index,
                        train_paths=train_paths)
    if path and path.endswith(".fsv"):
        from .factored_vocab import FactoredVocab
        return FactoredVocab.load(path)
    if path and os.path.exists(path):
        return DefaultVocab.load(path, max_size=max_size)
    if path and train_paths:
        log.info("Building vocabulary {} from {}", path, ",".join(train_paths))

        def _lines():
            for tp in train_paths:
                with open(tp, "r", encoding="utf-8") as fh:
                    yield from (l.rstrip("\n") for l in fh)

        v = DefaultVocab.build(_lines(), max_size=max_size)
        v.save(path)
        return v
    raise FileNotFoundError(f"Vocabulary file {path} not found and no data to build it")
