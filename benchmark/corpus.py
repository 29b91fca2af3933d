"""Seeded sentences for every traffic mix: one length histogram, Zipf words.

The histogram is bench.py::_write_corpus's (log-normal mu 3.2 sigma 0.45,
4..63 words, mean ~28 — close to a WMT sentence-length histogram), taken
at its quantiles instead of sampled, so EVERY seed draws the same multiset
of lengths in another order: a seed changes which words and in which
order, never how much work a run holds. Words are Zipf-ranked draws over
the whole vocabulary. Target line = source line, so both sides land in
one width bucket (8, 16, 24, 32, 48, 64 with EOS). numpy + stdlib only.
"""

import json
import math
from statistics import NormalDist

import numpy as np


def sentence_lengths(hist, n, seed):
    """n lengths: the histogram's quantiles (i + 0.5) / n, permuted by seed."""
    nd = NormalDist(hist["mu"], hist["sigma"])
    lens = np.array([
        min(hist["max_words"], max(hist["min_words"],
                                   int(math.exp(nd.inv_cdf((i + 0.5) / n)))))
        for i in range(n)], dtype=np.int64)
    return np.random.RandomState(seed % (2 ** 31)).permutation(lens)


def word_ids(total, vocab_size, seed):
    """`total` ids in [2, vocab_size): Zipf over ranks (p ~ 1 / (rank + 10),
    flattened head so no word dominates), ids 0/1 are EOS/UNK."""
    ranks = np.arange(vocab_size - 2, dtype=np.float64)
    p = 1.0 / (ranks + 10.0)
    p /= p.sum()
    rs = np.random.RandomState((seed + 0x5EED) % (2 ** 31))
    return rs.choice(vocab_size - 2, size=total, p=p) + 2


def make_lines(hist, n, vocab_size, seed):
    """n sentences as text ('w<k>' words) plus their lengths in words."""
    lens = sentence_lengths(hist, n, seed)
    ids = word_ids(int(lens.sum()), vocab_size, seed)
    words = np.char.add("w", (ids - 2).astype(str))
    ends = np.cumsum(lens)
    lines = [" ".join(words[e - k:e]) for e, k in zip(ends, lens)]
    return lines, lens


def write_vocab(path, vocab_size):
    """Exactly vocab_size entries: </s>, <unk>, w0 .. w<V-3>."""
    vocab = {"</s>": 0, "<unk>": 1}
    vocab.update({f"w{i}": i + 2 for i in range(vocab_size - 2)})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(vocab, fh)


def write_parallel(lines, src_path, trg_path):
    text = "\n".join(lines) + "\n"
    for path in (src_path, trg_path):
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(text)


def width_of(n_words, buckets):
    """Padded width of a sentence of n_words (+ EOS) in the bucket table."""
    for b in buckets:
        if n_words + 1 <= b:
            return b
    raise ValueError(f"{n_words} words exceed the widest bucket {buckets[-1]}")
