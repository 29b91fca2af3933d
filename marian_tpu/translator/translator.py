"""The translation task driver — reference src/translator/translator.h ::
Translate<BeamSearch>::run.

Loads model(s) + vocabs + shortlist, batches input (maxi-batch length sort
for padding efficiency, like the decoder's --maxi-batch), runs the jitted
beam search batch by batch, and emits translations in input order.

The reference runs one host thread per GPU with per-thread graphs; here one
process drives the TPU (XLA pipelines batches via async dispatch), so the
ThreadPool collapses to a simple loop — the collector still guards ordering.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..common import logging as log
from ..common import io as mio
from ..data import (BatchGenerator, Corpus, TextInput, create_vocab,
                    parse_shortlist_options)
from ..models.encoder_decoder import create_model
from .beam_search import BeamSearch
from .output_collector import OutputCollector, OutputPrinter


class Translate:
    def __init__(self, options):
        self.options = options
        options.set("_translation_task", True)   # for --quiet-translation
        log.create_loggers(options)

        model_paths = list(options.get("models", [])) or [options.get("model")]
        self.params_list = []
        embedded_cfg = None
        first_names = None
        for mp in model_paths:
            params, cfg_yaml = mio.load_model(mp)
            # marian-conv int8 checkpoints: pair values+scales into QTensors
            from ..ops.quantization import wrap_quantized
            self.params_list.append(wrap_quantized(
                {k: jnp.asarray(v) for k, v in params.items()}))
            # ensemble scorers share ONE architecture (the jitted beam
            # steps each params dict through the same model): a mixed-arch
            # --models list must fail here with the file named, not as an
            # obscure shape error deep inside the first traced step.
            # Shapes, not just names: same-topology/different-dimension
            # mixes (dim-emb, vocab size) are the common accident.
            sig = {k: tuple(getattr(v, "shape", ()))
                   for k, v in self.params_list[-1].items()}
            if first_names is None:
                first_names = sig
            elif sig != first_names:
                diff = sorted(
                    set(sig) ^ set(first_names)
                    or {k for k in sig
                        if sig[k] != first_names.get(k)})[:5]
                raise ValueError(
                    f"--models ensemble members must share one "
                    f"architecture; {mp} differs from {model_paths[0]} "
                    f"(e.g. {diff}) — rescore n-best lists with "
                    f"marian-scorer to combine unlike models")
            if cfg_yaml and embedded_cfg is None:
                embedded_cfg = cfg_yaml
        # model architecture comes from the checkpoint-embedded config unless
        # --ignore-model-config (reference: translator.h config precedence)
        from ..models.encoder_decoder import apply_embedded_config
        self.options = apply_embedded_config(options, embedded_cfg)

        vocab_paths = list(self.options.get("vocabs", []))
        if not vocab_paths:
            raise ValueError("--vocabs required for translation")
        self.vocabs = [create_vocab(p, self.options, i)
                       for i, p in enumerate(vocab_paths)]
        self.src_vocab = self.vocabs[0]
        self.trg_vocab = self.vocabs[-1]
        # multi-source models (--type multi-transformer) take every vocab but
        # the last as a source stream, mirroring training (train.py)
        self.src_vocab_list = self.vocabs[:-1] if len(self.vocabs) > 2 \
            else [self.src_vocab]

        self.model = create_model(
            self.options,
            self.src_vocab_list if len(self.src_vocab_list) > 1
            else self.src_vocab,
            self.trg_vocab, inference=True)
        weights = self.options.get("weights", []) or None
        self.search = BeamSearch(self.model, self.params_list, weights,
                                 self.options, self.trg_vocab)
        self.shortlist_gen = parse_shortlist_options(
            self.options.get("shortlist", []), self.src_vocab, self.trg_vocab)
        self.printer = OutputPrinter(self.options, self.trg_vocab)
        # decode-side observability (serving/metrics.py — ISSUE 1): the
        # same metric types the server exposes, so a marian-server scrape
        # sees device-batch geometry (fill/waste over the BUCKETED padded
        # shape) alongside the scheduler's queueing series
        from ..serving import metrics as msm
        self._m_batches = msm.counter(
            "marian_translate_batches_total", "Device batches decoded")
        self._m_sentences = msm.counter(
            "marian_translate_sentences_total", "Sentences decoded")
        self._m_fill = msm.histogram(
            "marian_translate_batch_fill_ratio",
            "Real source tokens / padded device-batch capacity",
            buckets=msm.RATIO_BUCKETS)
        self._roofline_hint()

    def _roofline_hint(self):
        """One-time decode-defaults recommendation (the auto-tuner hook of
        VERDICT r3 #5): on a TPU whose beam step the analytic roofline
        puts in the weight-bound regime, say which off lever (int8 /
        shortlist) would pay and by how much."""
        cfg = getattr(self.model, "cfg", None)
        if cfg is None or not hasattr(cfg, "dim_ffn"):
            return                       # RNN family: no int8 decode path
        import jax
        kind = jax.devices()[0].device_kind
        from ..common.flops import decode_defaults_hint
        from ..ops.quantization import QTensor
        int8_on = any(isinstance(v, QTensor)
                      for v in self.params_list[0].values())
        hint = decode_defaults_hint(
            emb=int(cfg.dim_emb), ffn=int(cfg.dim_ffn),
            dec_depth=int(getattr(cfg, "dec_depth", 6)),
            vocab=len(self.trg_vocab),
            rows=int(self.options.get("mini-batch", 32) or 32)
            * int(self.options.get("beam-size", 12) or 12),
            device_kind=kind, int8_on=int8_on,
            shortlist_on=self.shortlist_gen is not None)
        if hint:
            log.info("{}", hint)

    def _input_corpus(self, lines: Optional[List[str]] = None):
        n_src = len(self.src_vocab_list)
        self._prefixes: Optional[List[List[int]]] = None
        force = bool(self.options.get("force-decode", False))
        if lines is not None:
            if n_src > 1:
                raise ValueError("multi-source decoding requires --input "
                                 "with one file per source stream")
            if force:
                raise ValueError("--force-decode needs --input files "
                                 "(source + target-prefix)")
            return TextInput([lines], [self.src_vocab], self.options)
        inputs = self.options.get("input", ["stdin"])
        paths = inputs if isinstance(inputs, list) else [inputs]
        n_expected = n_src + (1 if force else 0)
        if len(paths) != n_expected and (n_src > 1 or force):
            raise ValueError(
                f"model expects {n_expected} --input files "
                f"({n_src} source{' + target prefix' if force else ''}), "
                f"got {len(paths)}")
        streams = []
        for path in paths[:max(n_src, 1)]:
            if path in ("stdin", "-"):
                streams.append([l.rstrip("\n") for l in sys.stdin])
            else:
                with open(path, "r", encoding="utf-8") as fh:
                    streams.append([l.rstrip("\n") for l in fh])
        if force:
            # the last input file holds target PREFIXES, one per source
            # line (empty line = unconstrained); encoded without EOS so
            # the hypothesis continues after the prefix
            with open(paths[-1], "r", encoding="utf-8") as fh:
                self._prefixes = [
                    self.trg_vocab.encode(l.rstrip("\n"), add_eos=False)
                    if l.strip() else []
                    for l in fh]
            if len(self._prefixes) != len(streams[0]):
                raise ValueError(
                    f"--force-decode: prefix file has "
                    f"{len(self._prefixes)} lines but the source has "
                    f"{len(streams[0])} — one (possibly empty) prefix "
                    f"line per source sentence required")
        return TextInput(streams, self.src_vocab_list, self.options)

    def run(self, lines: Optional[List[str]] = None,
            stream=None) -> List[str]:
        corpus = self._input_corpus(lines)
        bg = BatchGenerator(
            corpus, None,
            mini_batch=int(self.options.get("mini-batch", 32) or 32),
            mini_batch_words=int(self.options.get("mini-batch-words", 0) or 0),
            maxi_batch=int(self.options.get("maxi-batch", 100) or 1),
            maxi_batch_sort=self.options.get("maxi-batch-sort", "src"),
            shuffle_batches=False, prefetch=True)
        out_path = self.options.get("output", "stdout")
        close = False
        if stream is None:
            if out_path in ("stdout", "-"):
                stream = sys.stdout
            else:
                stream = open(out_path, "w", encoding="utf-8")
                close = True
        collector = OutputCollector(stream)
        # return value is only materialized for library callers (lines=);
        # file/stdin translation streams through the collector with
        # O(one batch) memory — retaining every line of a corpus-sized
        # decode would grow RSS without bound
        keep_results = lines is not None
        by_sid: Dict[int, str] = {}
        # depth-1 decode pipeline (common/pipeline.py): dispatch batch
        # i+1's (async) beam search BEFORE collecting batch i, so host
        # n-best extraction + output writing overlap device beam steps
        # (the reference hides this host work behind a worker thread
        # pool; XLA async dispatch plays that role here)
        from ..common.pipeline import pipelined

        def _finalize(pbatch, handle):
            nbests = handle.collect()
            for row in range(pbatch.size):
                sid = int(pbatch.sentence_ids[row])
                text = self.printer.line(sid, nbests[row])
                if keep_results:
                    by_sid[sid] = text
                collector.write(sid, text)

        def _dispatch(batch):
            real = batch.size
            self._m_batches.inc()
            self._m_sentences.inc(real)
            self._m_fill.observe(
                batch.src_words
                / max(batch.src.batch_size * batch.src.batch_width, 1))
            if len(self.src_vocab_list) > 1:
                src_ids = tuple(sb.ids for sb in batch.sub)
                src_mask = tuple(sb.mask for sb in batch.sub)
            else:
                src_ids = batch.src.ids
                src_mask = batch.src.mask
            shortlist = None
            if self.shortlist_gen is not None:
                ids0 = src_ids[0] if isinstance(src_ids, tuple) else src_ids
                mask0 = src_mask[0] if isinstance(src_mask, tuple) else src_mask
                shortlist = self.shortlist_gen.generate(
                    np.unique(ids0[mask0 > 0]))
            prefix = None
            if self._prefixes is not None:
                plen = max([1] + [len(self._prefixes[int(s)])
                                  for s in batch.sentence_ids if s >= 0])
                prefix = np.full((batch.src.ids.shape[0], plen), -1,
                                 np.int32)
                for row in range(real):
                    sid = int(batch.sentence_ids[row])
                    pf = self._prefixes[sid]
                    prefix[row, :len(pf)] = pf
            return self.search.search_async(src_ids, src_mask,
                                            shortlist=shortlist,
                                            prefix=prefix)

        pipelined(bg, _dispatch, _finalize)
        collector.flush_remaining()
        if close:
            stream.close()
        # corpus order, like the written output (batches are length-sorted)
        return [by_sid[s] for s in sorted(by_sid)] if keep_results else []


def translate_main(options) -> None:
    from ..common.profiling import enable_compilation_cache
    enable_compilation_cache()
    Translate(options).run()
