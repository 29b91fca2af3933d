"""EncoderDecoder: the model-level API used by training and translation —
``build`` (teacher-forced loss graph), ``start_state``/``step`` (incremental
decoding). Rebuild of reference src/models/encoder_decoder.cpp and
src/models/costs.h (cost wrapping).

Where the reference assembles encoder/decoder objects and walks a tape, this
class closes a model *function family* (transformer or s2s) over a static
config; everything it returns is jit-compatible.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..layers.loss import RationalLoss, cross_entropy_loss, guided_alignment_loss
from ..obs import trace as obs_trace
from . import transformer as T

Params = Dict[str, jax.Array]


def _vocab_info(v):
    """Accept an int size, a VocabBase, or a list of either (multi-source);
    returns (size-or-tuple, FactorTables|None) (reference: models get vocab
    dims + factored-vocab handle from Vocab objects in model_factory.cpp)."""
    if isinstance(v, (tuple, list)):
        sizes, factors = zip(*[_vocab_info(x) for x in v])
        return tuple(sizes), tuple(factors)
    if isinstance(v, int):
        return v, None
    if getattr(v, "factored", False):
        from ..layers.logits import FactorTables
        return len(v), FactorTables.from_vocab(v)
    return len(v), None


class EncoderDecoder:
    def __init__(self, options, src_vocab, trg_vocab,
                 inference: bool = False):
        self.options = options
        self.model_type = options.get("type", "transformer")
        self.inference = inference
        self.label_smoothing = float(options.get("label-smoothing", 0.0) or 0.0)
        self._fused_ce_mode = str(options.get("fused-ce", "auto") or "auto")
        self.guided_weight = float(options.get("guided-alignment-weight", 0.1))
        self.multi_loss_type = str(options.get("multi-loss-type", "sum")
                                   or "sum")
        self.unlikelihood = bool(options.get("unlikelihood-loss", False))
        self.guided_cost = str(options.get("guided-alignment-cost", "ce"))
        ga = options.get("guided-alignment", "none")
        self.use_guided = bool(ga and ga != "none") and not inference
        src_vocab_size, src_factors = _vocab_info(src_vocab)
        trg_vocab_size, trg_factors = _vocab_info(trg_vocab)
        if self.model_type in ("transformer-lm", "lm-transformer", "lm") \
                and options.get("transformer-layer-plan", None):
            # a decoder-only stack whose layers differ: a per-layer plan
            # of (mixing, feed-forward) kinds, a function family of its own
            from . import layer_plan as P
            self.cfg = P.config_from_options(options, src_vocab_size,
                                             trg_vocab_size, inference,
                                             trg_factors=trg_factors)
            self._mod = P
        elif self.model_type in ("transformer", "multi-transformer",
                                 "transformer-lm", "lm-transformer", "lm"):
            seq_mesh = None
            if str(options.get("sequence-parallel", "none") or "none") != "none":
                from ..parallel import mesh as _mesh
                seq_mesh = _mesh.make_mesh(options)
            self.cfg = T.config_from_options(options, src_vocab_size,
                                             trg_vocab_size, inference,
                                             src_factors=src_factors,
                                             trg_factors=trg_factors,
                                             seq_mesh=seq_mesh)
            if self.cfg.ulr and self.cfg.n_encoders > 1:
                raise ValueError("--ulr does not support multi-source "
                                 "models (one query table, one source "
                                 "stream)")
            if self.cfg.ulr and not inference:
                # fixed ULR query/key tables feed init_params only; decode
                # reloads them from the checkpoint (self-contained)
                import os as _os
                import dataclasses as _dc
                from ..layers.embedding_io import (load_word2vec,
                                                   load_word2vec_raw)
                qf = str(options.get("ulr-query-vectors", "") or "")
                kf = str(options.get("ulr-keys-vectors", "") or "")
                if qf and kf and _os.path.exists(qf) and _os.path.exists(kf) \
                        and not isinstance(src_vocab, (int, tuple, list)) \
                        and hasattr(src_vocab, "__getitem__"):
                    _, keys = load_word2vec_raw(kf)
                    queries = load_word2vec(qf, src_vocab, keys.shape[1])
                    self.cfg = _dc.replace(self.cfg, ulr_queries=queries,
                                           ulr_keys=keys)
            self._mod = T
        elif self.model_type in ("s2s", "nematus", "amun", "multi-s2s",
                                 "char-s2s"):
            from . import s2s as S
            has_src_factors = (any(src_factors)
                               if isinstance(src_factors, (tuple, list))
                               else bool(src_factors))
            if has_src_factors:
                raise NotImplementedError(
                    "factored SOURCE vocabs are supported for transformer "
                    "models (the s2s family supports a factored target)")
            self.cfg = S.config_from_options(options, src_vocab_size,
                                             trg_vocab_size, inference,
                                             trg_factors=trg_factors)
            self._mod = S
        else:
            raise NotImplementedError(f"model type '{self.model_type}'")

    # -- parameters ---------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        return self._mod.init_params(self.cfg, key)

    @property
    def load_moved(self) -> Tuple[str, float]:
        """(suffix, rate) of the parameters that the step moves by their
        load signal and the optimizer leaves alone (parallel/zero.py::
        take_load_signals); rate 0.0 for every family but a layer plan
        with --plan-experts-bias-rate."""
        moved = getattr(self._mod, "load_moved", None)
        return moved(self.cfg) if moved else ("", 0.0)

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """Names of the counts `loss` returns as aux["counters"] (one lazy
        float32 vector): the family's own, summed over its layers, then
        the summed cost and the labels of each extra head it trains; ()
        for most families."""
        names = getattr(self._mod, "counter_names", None)
        return tuple(names(self.cfg) if names
                     else getattr(self._mod, "COUNTERS", ())) + tuple(
            f"{name}.{what}" for name in self._head_names
            for what in ("ce_sum", "labels"))

    @property
    def _head_names(self) -> Tuple[str, ...]:
        """The extra weighted heads this model's family trains beside the
        main one (`decode_train` then returns them last, as data)."""
        names = getattr(self._mod, "head_names", None)
        return tuple(names(self.cfg)) if names else ()

    @property
    def beam_carried_suffixes(self) -> Tuple[str, ...]:
        """Decode-state key suffixes that ride the beam (reordered by
        backpointers); model-family specific (KV caches vs RNN states)."""
        return self._mod.BEAM_CARRIED_SUFFIXES

    @property
    def fused_decode_reorder(self) -> bool:
        """True when the fused decode kernel owns the beam reorder of
        the self-attention caches: the beam search then passes pending
        backpointers into step() (beam_src) instead of gathering the
        cache leaves itself (ops/pallas/decode_attention.py)."""
        return self._mod is T and T.fused_decode_active(self.cfg)

    # -- training graph (reference: EncoderDecoder::build + costs.h) --------
    def loss(self, params: Params, batch: Dict[str, jax.Array],
             key: Optional[jax.Array] = None, train: bool = True
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Returns (ce_sum_plus_aux, aux dict with loss_sum/labels)."""
        with jax.named_scope("cast"):
            cparams = getattr(self._mod, "cast_params", T.cast_params)(
                params, self.cfg.compute_dtype)
        k_enc = jax.random.fold_in(key, 1) if key is not None else None
        k_dec = jax.random.fold_in(key, 2) if key is not None else None
        src_ids, src_mask = self._batch_sources(batch)
        moe = self._mod is T and getattr(self.cfg, "moe_experts", 0) > 0
        if moe:
            enc_out, moe_aux = self._mod.encode(self.cfg, cparams, src_ids,
                                                src_mask, train, k_enc,
                                                with_aux=True)
        else:
            enc_out = self._mod.encode(self.cfg, cparams, src_ids,
                                       src_mask, train, k_enc)
            moe_aux = None
        want_align = self.use_guided and "guided" in batch
        table = self._fused_ce_table(cparams)
        kw = {"return_hidden": True} if table is not None else {}
        if moe:
            kw["with_aux"] = True
        res = self._mod.decode_train(self.cfg, cparams, enc_out,
                                     src_mask, batch["trg_ids"],
                                     batch["trg_mask"], train, k_dec,
                                     return_alignment=want_align, **kw)
        parts = list(res) if isinstance(res, tuple) else [res]
        hidden = parts.pop(0)
        align = parts.pop(0) if want_align else None
        if moe:
            moe_aux = moe_aux + parts.pop(0)
        # a family that counts inside the step (its COUNTERS names the
        # entries) hands the counts back as one lazy vector
        counters = parts.pop(0) if hasattr(self._mod, "COUNTERS") else None
        heads = parts.pop(0) if self._head_names else ()
        fused = table is not None and not (self.unlikelihood
                                           and "data_weights" in batch)
        dw = batch.get("data_weights")
        if parts:
            # what is left is the main head's per-token weights, where
            # the family's objective weighs its labels (diffusion over
            # blocks: masked / t); a batch's own weights act beside them
            own = parts.pop(0)
            dw = own if dw is None else own * jnp.broadcast_to(
                dw.astype(own.dtype), own.shape)

        def cost(hidden, ids, mask, dw, was_projected=False):
            """Summed cost and label count of one head: its hidden states
            (or, `was_projected`, its logits) against ids under mask."""
            if fused:
                # output projection and loss are ONE streaming kernel here
                with jax.named_scope("loss"):
                    return self._fused_ce_loss(cparams, table, hidden, ids,
                                               mask, dw)
            if not was_projected:
                hidden = self._mod.output_logits(self.cfg, cparams, hidden)
            return cross_entropy_loss(hidden, ids, mask,
                                      self.label_smoothing, dw,
                                      unlikelihood=self.unlikelihood)
        rl = cost(hidden, batch["trg_ids"], batch["trg_mask"], dw,
                  was_projected=table is None)
        total = rl.loss_sum
        aux = {"ce_sum": rl.loss_sum, "labels": rl.labels}
        # a family's extra heads join as weighted sums over their own
        # labels; the label count stays the main head's
        sums = {name: jnp.zeros((2,), jnp.float32)
                for name in self._head_names}
        for head in heads:
            # a token's weight follows it to where it is the label
            hw = None if dw is None else jnp.roll(
                jnp.broadcast_to(dw, batch["trg_mask"].shape), -head.shift,
                axis=1)
            with jax.named_scope(head.name), \
                    jax.named_scope(f"{head.name}.loss"):
                hl = cost(head.hidden, head.ids, head.mask, hw)
            total = total + head.weight * hl.loss_sum
            sums[head.name] = sums[head.name] + jnp.stack(
                [hl.loss_sum, hl.labels])
        if sums:
            counters = jnp.concatenate(
                [counters] + [jax.lax.stop_gradient(v)
                              for v in sums.values()])
        if counters is not None:
            aux["counters"] = counters
        if moe and getattr(self.cfg, "moe_aux_weight", 0.0) > 0:
            # load-balance aux joins at label scale like the guided loss
            # (cost normalization divides by labels → effective weight is
            # moe_aux_weight per token)
            total = total + self.cfg.moe_aux_weight * moe_aux * rl.labels
            aux["moe_aux"] = moe_aux
        if want_align and align is not None:
            ga = guided_alignment_loss(align, batch["guided"],
                                       batch["trg_mask"], self.guided_cost)
            # --multi-loss-type combination of the partial losses
            # (reference: layers/loss.h MultiRationalLoss subclasses):
            # sum/scaled add the aux loss at the CE label count (scaled
            # multiplies by count_0/count_i — here both counts are the
            # target labels, so the factor is 1); mean adds the per-label
            # mean directly.
            if self.multi_loss_type == "mean":
                total = total + self.guided_weight * ga
            else:
                total = total + self.guided_weight * ga * rl.labels
            aux["guided"] = ga
        return total, aux

    # -- fused streaming CE (ops/pallas/fused_ce.py) ------------------------
    def _fused_ce_table(self, cparams):
        """[V, E] output table when the streaming fused CE applies, else None
        (→ dense logits + layers/loss.py). Applies for plain-tensor output
        projections of the transformer family; factored/quantized vocabs and
        non-TPU backends (unless --fused-ce on) use the dense path."""
        if self._fused_ce_mode == "off" \
                or not hasattr(self._mod, "_plain_output_table"):
            return None
        if self._fused_ce_mode == "auto" and jax.default_backend() != "tpu":
            return None
        cfg = self.cfg
        from ..ops.pallas.fused_ce import fused_available
        if not fused_available(int(cfg.dim_emb)):
            return None
        return self._mod._plain_output_table(cfg, cparams)

    def _fused_ce_loss(self, cparams, table, hidden, ids, mask, dw
                       ) -> RationalLoss:
        """Label-smoothed CE straight from decoder hidden states — logits
        blocks live only in VMEM (same numbers as cross_entropy_loss of
        output_logits; see fused_ce.py docstring for the algebra)."""
        from ..ops.pallas.fused_ce import fused_softmax_xent
        b, t, e = hidden.shape
        bias = cparams.get("decoder_ff_logit_out_b")
        bias = (bias.reshape(-1) if bias is not None       # --output-omit-bias
                else jnp.zeros((table.shape[0],), hidden.dtype))
        ce = fused_softmax_xent(
            hidden.reshape(b * t, e), table, bias,
            ids.reshape(-1), self.label_smoothing,
            interpret=None if self._fused_ce_mode == "auto" else
            (jax.default_backend() != "tpu"))
        ce = ce.reshape(b, t)
        w = mask.astype(jnp.float32)
        if dw is not None:
            w = w * jnp.broadcast_to(dw.astype(jnp.float32), w.shape)
        return RationalLoss(jnp.sum(ce * w),
                            jnp.sum(mask.astype(jnp.float32)))

    def _batch_sources(self, batch):
        """Collect source streams from a batch dict: 'src_ids'/'src_mask'
        plus 'src{i}_ids'/'src{i}_mask' for multi-source (i = 2..N)."""
        n = getattr(self.cfg, "n_encoders", 1)
        if n == 1:
            return batch["src_ids"], batch["src_mask"]
        ids = [batch["src_ids"]] + [batch[f"src{i}_ids"] for i in range(2, n + 1)]
        masks = [batch["src_mask"]] + [batch[f"src{i}_mask"] for i in range(2, n + 1)]
        return tuple(ids), tuple(masks)

    # -- incremental decoding (reference: startState/step) ------------------
    def encode_for_decode(self, params: Params, src_ids, src_mask):
        cparams = T.cast_params(params, self.cfg.compute_dtype)
        return self._mod.encode(self.cfg, cparams, src_ids, src_mask,
                                train=False, key=None)

    def start_state(self, params: Params, enc_out, src_mask, max_len: int,
                    want_alignment: bool = False):
        cparams = T.cast_params(params, self.cfg.compute_dtype)
        # transformer: alignment extraction keeps the unrolled decode
        # state; otherwise the scanned stacked caches apply
        return self._mod.init_decode_state(self.cfg, cparams, enc_out,
                                           src_mask, max_len,
                                           want_alignment=want_alignment)

    def start_paged_state(self, params: Params, enc_out, src_mask,
                          n_pages: int, page_len: int, max_pages: int):
        """Decode state over a paged KV pool (iteration-level batching;
        transformer family only — see T.init_paged_decode_state). The
        returned state's ``page_table``/``pos`` leaves are PER-ROW and
        owned by the caller's slot engine (translator/iteration.py)."""
        if self._mod is not T:
            raise ValueError("the paged KV pool is implemented for the "
                             "transformer family (s2s decoders keep "
                             "their recurrent states)")
        cparams = T.cast_params(params, self.cfg.compute_dtype)
        return T.init_paged_decode_state(self.cfg, cparams, enc_out,
                                         src_mask, n_pages, page_len,
                                         max_pages)

    def fork_paged_rows(self, state, src_mask, src_slots, dst_slots):
        """Copy a paged decode state's row-indexed leaves (cross-attn
        K/V) + source-mask rows between slots — the encoder-side half of
        a COW fork (beam hypothesis spread, prefix-cache follower); the
        decoder-side half is page-table aliasing in kv_pool.py."""
        if self._mod is not T:
            raise ValueError("paged-state forks are implemented for the "
                             "transformer family")
        return T.fork_paged_rows(state, src_mask, src_slots, dst_slots)

    def step(self, params: Params, state, prev_ids, src_mask,
             shortlist=None, return_alignment: bool = False,
             beam_src=None, fused_decode=None):
        cparams = T.cast_params(params, self.cfg.compute_dtype)
        # beam_src / fused_decode only exist for the transformer
        # family's fused decode kernel — passed through only when set,
        # so the s2s decode_step signature stays untouched
        kw = {}
        if beam_src is not None:
            kw["beam_src"] = beam_src
        if fused_decode is not None:
            kw["fused_decode"] = fused_decode
        return self._mod.decode_step(self.cfg, cparams, state, prev_ids,
                                     src_mask, shortlist, return_alignment,
                                     **kw)


def create_model(options, src_vocab, trg_vocab,
                 inference: bool = False):
    """Model factory (reference: src/models/model_factory.cpp ::
    models::createModelFromOptions). Vocab args may be int sizes or
    VocabBase objects (factored vocabs enable the factored softmax).
    --type bert / bert-classifier build the encoder-only BERT family
    (models/bert.py); everything else is an EncoderDecoder."""
    mtype = options.get("type", "transformer")
    if mtype in ("bert", "bert-classifier"):
        from .bert import BertModel
        label_vocab = trg_vocab if mtype == "bert-classifier" else None
        return BertModel(options, src_vocab, label_vocab, inference)
    return EncoderDecoder(options, src_vocab, trg_vocab, inference)


ARCH_KEY_PREFIXES = ("transformer", "enc-", "dec-", "dim-", "tied-",
                     "factors-", "lemma-", "input-types", "bert-", "char-",
                     "ulr", "plan-")
ARCH_KEYS = ("type", "skip", "layer-normalization", "right-left",
             "max-length")


def apply_embedded_config(options, config_yaml: Optional[str]):
    """Overlay the architecture part of a checkpoint's embedded
    special:model.yml onto runtime options (reference: model config loading
    in translator.h/rescorer.h; disabled by --ignore-model-config)."""
    if not config_yaml or options.get("ignore-model-config", False):
        return options
    import yaml as _yaml
    emb = _yaml.safe_load(config_yaml) or {}
    keys = [k for k in emb
            if k.startswith(ARCH_KEY_PREFIXES) or k in ARCH_KEYS]
    return options.with_(**{k: emb[k] for k in keys})


def batch_to_arrays(batch, compact: bool = False,
                    vocab_sizes=None) -> Dict[str, jnp.ndarray]:
    """CorpusBatch → dict of device arrays for the jitted loss. Extra
    source streams (multi-source) become src{i}_ids/src{i}_mask.

    ``compact=True`` slims the host→device transfer: token ids
    ship as uint16 when they fit, and the 0/1 float masks ship as per-row
    int32 LENGTHS (padding is terminal, so the mask is a prefix of ones)
    — ~4× fewer bytes per step. The jitted step rebuilds int32 ids and
    float masks on device (parallel/zero.py::expand_compact_batch).

    ``vocab_sizes`` (one size per stream, batch.sub order) makes the
    uint16 decision STATIC per run — required for stable jit signatures:
    a per-batch ids.max() gate would flip the key set (and force a full
    recompile) the first time a near-64k vocab's batch drew a high id.
    Without it the per-batch max is used (fine for fixed test vocabs).
    A mask that is not a prefix run (never produced by BatchGenerator)
    still falls back to the full form per-stream, loudly correct."""
    def stream(idx: int, prefix: str, sb) -> Dict[str, jnp.ndarray]:
        if compact:
            import numpy as np
            ids = np.asarray(sb.ids)
            mask = np.asarray(sb.mask)
            if vocab_sizes is not None:
                fits = int(vocab_sizes[idx]) <= 2 ** 16
            else:
                fits = ids.max(initial=0) < 2 ** 16
            lengths = mask.sum(axis=-1).astype(np.int32)
            prefix_run = (mask ==
                          (np.arange(mask.shape[-1]) <
                           lengths[..., None])).all()
            if fits and prefix_run:
                return {f"{prefix}_tok": jnp.asarray(
                            ids.astype(np.uint16)),
                        f"{prefix}_len": jnp.asarray(lengths)}
        return {f"{prefix}_ids": jnp.asarray(sb.ids),
                f"{prefix}_mask": jnp.asarray(sb.mask)}

    out = {}
    # host arrays become device arrays here: the step's H2D transfer
    with obs_trace.span("train.h2d") as sp:
        out.update(stream(0, "src", batch.src))
        out.update(stream(len(batch.sub) - 1, "trg", batch.trg))
        for i, sb in enumerate(batch.sub[1:-1], start=2):
            out.update(stream(i - 1, f"src{i}", sb))
        if batch.guided_alignment is not None:
            out["guided"] = jnp.asarray(batch.guided_alignment)
        if batch.data_weights is not None:
            out["data_weights"] = jnp.asarray(batch.data_weights)
        if sp:
            sp.set_attrs(rows=batch.batch_size,
                         src_width=batch.src.batch_width,
                         trg_width=batch.trg.batch_width,
                         bytes=sum(int(v.nbytes) for v in out.values()))
    return out
