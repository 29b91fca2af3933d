"""Batched beam search, jit-compiled with static shapes.

Rebuild of reference src/translator/beam_search.cpp :: BeamSearch::search and
translator/nth_element.cu (fused beam×vocab top-k). The reference purges
finished sentences from the batch (shapes shrink every few steps) and appends
to growing K/V tensors; under XLA both become masking over fixed shapes:

- state = (tokens [B,K,L], scores [B,K], finished [B,K], KV caches [B*K,...])
  inside a lax.while_loop over decode positions with an all-finished early
  exit — shapes never change, so ONE compiled program serves every batch of
  the same (B, Ts, L) bucket;
- the reference's NthElement GPU kernel is jax.lax.top_k over the flattened
  beam×vocab axis (XLA lowers to a TPU-native sort/top-k);
- finished beams are frozen by forcing their token distribution to
  {EOS: 0.0} so path scores stop changing;
- beam expansion at t=0 is masked to beam 0 (all beams start identical).

Semantics kept from the reference: Marian's score bookkeeping (cumulative
log-prob; length normalization score/len^alpha and word penalty applied when
ranking finished hypotheses), --allow-unk suppression, n-best, ensembles
(weighted log-prob sum across scorers), lexical shortlist (top-k runs in
shortlist coordinates, tokens mapped back through the per-batch index set).
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..data.vocab import EOS_ID, UNK_ID

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class BeamConfig:
    beam_size: int = 6
    normalize: float = 0.6          # length-normalization alpha (0 = off)
    word_penalty: float = 0.0
    allow_unk: bool = False
    max_length: int = 256           # decode cap L (static)
    n_best: int = 1
    return_alignment: bool = False
    # --output-sampling: () = off; ("full", temp) samples the full softmax;
    # ("topk", k, temp) restricts to the k most probable tokens first.
    # Each beam becomes an independent sample trajectory (gumbel-max over
    # the token log-probs — TPU-friendly: argmax, no host RNG in the loop).
    sampling: tuple = ()
    word_scores: bool = False       # --word-scores: per-token logP in n-best

    @classmethod
    def from_options(cls, options, max_length: int) -> "BeamConfig":
        norm = options.get("normalize", 0.0)
        if norm is True:
            norm = 1.0
        return cls(
            beam_size=int(options.get("beam-size", 6)),
            normalize=float(norm or 0.0),
            word_penalty=float(options.get("word-penalty", 0.0) or 0.0),
            allow_unk=bool(options.get("allow-unk", False)),
            max_length=max_length,
            n_best=int(options.get("beam-size", 6))
            if options.get("n-best", False) else 1,
            return_alignment=options.get("alignment", None) is not None,
            sampling=_parse_sampling(options.get("output-sampling", [])),
            word_scores=bool(options.get("word-scores", False)),
        )


def _parse_sampling(raw) -> tuple:
    """'full [temp]' / 'topk [k] [temp]' → normalized tuple (reference:
    --output-sampling in translator/sampling)."""
    if raw in (None, False, [], ""):
        return ()
    if raw is True:
        return ("full", 1.0)
    parts = [str(p) for p in (raw if isinstance(raw, list) else [raw])]
    mode = parts[0].lower()
    if mode == "full":
        temp = float(parts[1]) if len(parts) > 1 else 1.0
        return ("full", temp)
    if mode == "topk":
        n = int(parts[1]) if len(parts) > 1 else 10
        temp = float(parts[2]) if len(parts) > 2 else 1.0
        return ("topk", n, temp)
    raise ValueError(f"--output-sampling: unknown mode '{mode}' "
                     f"(expected full or topk)")


def _flatten_beams(x: jax.Array) -> jax.Array:
    return x.reshape((-1,) + x.shape[2:])


def _expand_to_beams(x, k: int):
    """[B, ...] → [B*K, ...] by repeat (encoder outputs shared per beam).
    Tuples (multi-source) are expanded leaf-wise."""
    if isinstance(x, (tuple, list)):
        return tuple(_expand_to_beams(e, k) for e in x)
    return jnp.repeat(x, k, axis=0)


def _first(x):
    """First stream of a possibly-multi-source input."""
    return x[0] if isinstance(x, (tuple, list)) else x


def _topk_rows(flat, k: int, mesh):
    """Per-row top-k. Under a 'data' decode mesh this runs per batch
    shard via shard_map: rows are independent, but XLA's TopK
    custom-call is opaque to GSPMD's partitioner, which otherwise
    ALL-GATHERS the sharded batch dim inside the decode loop — at
    transformer-big beam-6 scale that is ~50 MB of ICI traffic per
    step (caught by test_mesh_decode_is_collective_free)."""
    if mesh is None:
        return jax.lax.top_k(flat, k)
    nones = (None,) * (flat.ndim - 1)
    spec = P("data", *nones)
    return jax.shard_map(  # mtlint: ok -- k is --beam-size: a launch flag, one value per process
        lambda f: tuple(jax.lax.top_k(f, k)), mesh=mesh,
        in_specs=(spec,), out_specs=(spec, spec), check_vma=False)(flat)


def beam_search_jit(model, params_list: List[Dict[str, jax.Array]],
                    weights: Sequence[float], cfg: BeamConfig,
                    src_ids: jax.Array, src_mask: jax.Array,
                    shortlist: Optional[jax.Array] = None,
                    sample_key: Optional[jax.Array] = None,
                    prefix: Optional[jax.Array] = None,
                    mesh=None, allow_fused: bool = True):
    """The jittable core. Returns (tokens [B,K,L], raw_scores [B,K],
    lengths [B,K], norm_scores [B,K], alignments [B,K,L,Ts] or None,
    word_scores [B,K,L] — per-step chosen-token logP, --word-scores).

    params_list/weights: ensemble of scorers (reference: scorers.h); each
    scorer keeps its own decode state, log-probs are weight-summed.
    """
    b = _first(src_ids).shape[0]
    k = cfg.beam_size
    L = cfg.max_length
    bk = b * k

    # Fused decode kernel (ops/pallas/decode_attention.py): the beam
    # reorder of the self-attention caches is folded into the kernel's
    # cache READ — the loop carries the chosen backpointers as flat
    # source rows and hands them to the NEXT step instead of gathering
    # the cache leaves here. Caches lag the beam by exactly one step by
    # construction; every read goes through the pending map, so results
    # are identical (tests/test_decode_attention.py pins it). Gated off
    # under a decode mesh AND when the caller says the params/caches are
    # already device-sharded (allow_fused=False — TP/pipe-sharded
    # training params at a validation decode): the pallas call is opaque
    # to GSPMD, which would re-replicate the sharded caches around it —
    # those paths keep the manual shard_map'd flat gather
    # (collective-free pin).
    fused = (mesh is None and allow_fused
             and bool(getattr(model, "fused_decode_reorder", False)))

    # encoder once per scorer; expand rows to B*K (reference: startState then
    # flattened batch×beam decoding)
    src_mask_bk = _expand_to_beams(src_mask, k)
    states = []
    for params in params_list:
        enc = model.encode_for_decode(params, src_ids, src_mask)
        enc_bk = _expand_to_beams(enc, k)
        states.append(model.start_state(params, enc_bk, src_mask_bk, L,
                                        want_alignment=cfg.return_alignment))

    vocab = (shortlist.shape[0] if shortlist is not None
             else model.cfg.trg_vocab)

    tokens0 = jnp.zeros((b, k, L), jnp.int32)
    if cfg.sampling:
        # every beam is an independent sample — all start live at score 0
        scores0 = jnp.zeros((b, k), jnp.float32)
    else:
        scores0 = jnp.where(jnp.arange(k)[None, :] == 0, 0.0, NEG_INF
                            ).astype(jnp.float32).repeat(b, axis=0).reshape(b, k)
    finished0 = jnp.zeros((b, k), bool)
    lengths0 = jnp.zeros((b, k), jnp.int32)
    prev0 = jnp.zeros((bk, 1), jnp.int32)
    aligns0 = (jnp.zeros((b, k, L, _first(src_ids).shape[1]), jnp.float32)
               if cfg.return_alignment else jnp.zeros((0,), jnp.float32))

    def cond(carry):
        (t, _tokens, _scores, finished, _lengths, _prev, _states, _al,
         _ws, _src) = carry
        return jnp.logical_and(t < L, ~jnp.all(finished))

    def body(carry):
        (t, tokens, scores, finished, lengths, prev, states, aligns,
         wscores, src_rows) = carry
        # ensemble log-probs
        logp = None
        align_t = None
        new_states = []
        if fused:
            step_kw = {"beam_src": src_rows}
        elif getattr(model, "fused_decode_reorder", False):
            # mesh decode with the kernel's config gate on: force it
            # OFF inside the step too — the GSPMD-opaque pallas call
            # would re-replicate the sharded caches even with an
            # identity gather (the reorder itself already fell back to
            # the shard_map'd flat gather above)
            step_kw = {"fused_decode": False}
        else:
            step_kw = {}
        for params, st, w in zip(params_list, states, weights):
            if cfg.return_alignment:
                logits, st2, al = model.step(params, st, prev, src_mask_bk,
                                             shortlist=shortlist,
                                             return_alignment=True,
                                             **step_kw)
                align_t = al if align_t is None else align_t + al
            else:
                logits, st2 = model.step(params, st, prev, src_mask_bk,
                                         shortlist=shortlist, **step_kw)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = w * lp if logp is None else logp + w * lp
            new_states.append(st2)
        logp = logp.reshape(b, k, vocab)

        if not cfg.allow_unk and shortlist is None:
            logp = logp.at[:, :, UNK_ID].set(NEG_INF)

        # frozen finished beams: only EOS, with log-prob 0
        eos_onehot = jnp.where(jnp.arange(vocab)[None, None, :] == _eos_index(shortlist),
                               0.0, NEG_INF)
        logp = jnp.where(finished[:, :, None], eos_onehot, logp)

        if prefix is not None:
            # --force-decode: while t is inside a sentence's prefix, mask
            # the distribution to the forced token — it keeps its TRUE
            # model log-prob, so scores stay comparable after the prefix
            # ends (reference: forced decoding of given target prefixes).
            # prefix arrives padded to L with -1 (= unconstrained).
            ptok = jax.lax.dynamic_index_in_dim(prefix, t, axis=1,
                                                keepdims=False)   # [B]
            forced = ptok >= 0
            onehot_p = (jnp.arange(vocab)[None, None, :]
                        == jnp.maximum(ptok, 0)[:, None, None])
            gate = forced[:, None, None] & ~finished[:, :, None]
            logp = jnp.where(gate & ~onehot_p, NEG_INF, logp)

        if cfg.sampling:
            # --output-sampling: each beam samples its own next token via
            # gumbel-max (argmax of tempered log-probs + gumbel noise — no
            # categorical host round-trip; finished beams keep picking EOS
            # because their distribution is the {EOS: 0} onehot above)
            temp = float(cfg.sampling[-1])
            slp = logp / max(temp, 1e-6)
            if cfg.sampling[0] == "topk":
                n = min(int(cfg.sampling[1]), vocab)
                kth = _topk_rows(slp, n, mesh)[0][..., -1:]
                slp = jnp.where(slp < kth, NEG_INF, slp)
            g = jax.random.gumbel(jax.random.fold_in(sample_key, t),
                                  slp.shape, jnp.float32)
            tok_sl = jnp.argmax(slp + g, axis=-1).astype(jnp.int32)  # [B,K]
            top_scores = scores + jnp.take_along_axis(
                logp, tok_sl[..., None], axis=-1)[..., 0]
            beam_idx = jnp.broadcast_to(jnp.arange(k)[None, :], (b, k))
        else:
            combined = scores[:, :, None] + logp        # [B,K,V]
            flat = combined.reshape(b, k * vocab)
            top_scores, top_idx = _topk_rows(flat, k, mesh)  # [B,K]
            beam_idx = top_idx // vocab                 # [B,K] source beam
            tok_sl = top_idx % vocab                    # token in (shortlist) coords
        tok_full = (shortlist[tok_sl] if shortlist is not None
                    else tok_sl).astype(jnp.int32)

        # reorder beam-carried state by beam_idx
        def reorder(x):  # [B,K,...] gather along K
            return jnp.take_along_axis(
                x, beam_idx.reshape(beam_idx.shape + (1,) * (x.ndim - 2)), axis=1)

        tokens = reorder(tokens)
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, tok_full.astype(jnp.int32), t, axis=2)
        if cfg.word_scores:
            # per-word score = this step's cumulative minus the SOURCE
            # beam's previous cumulative (--word-scores output; frozen
            # beams pick EOS at logP 0 so their trace stops moving).
            # Gated: the [B,K,L] carry + per-step reorder/scatter are
            # dead weight for ordinary decodes (cf. aligns0)
            prev_sel = jnp.take_along_axis(scores, beam_idx, axis=1)
            wscores = reorder(wscores)
            wscores = jax.lax.dynamic_update_index_in_dim(
                wscores, top_scores - prev_sel, t, axis=2)
        was_finished = reorder(finished.astype(jnp.int32)).astype(bool)
        lengths = reorder(lengths)
        if cfg.return_alignment:
            aligns = reorder(aligns)
            al = align_t.reshape(b, k, -1)
            al = reorder(al)
            aligns = jax.lax.dynamic_update_index_in_dim(aligns, al, t, axis=2)

        now_eos = tok_full == _eos_token(shortlist)
        new_finished = was_finished | now_eos
        # length counts tokens incl. EOS (Marian hypothesis length)
        lengths = jnp.where(was_finished, lengths, t + 1)
        scores = top_scores

        # reorder each scorer's KV caches: rows are b*k, new row j takes
        # old row (batch*k + beam_idx). Implementations A/B'd on silicon
        # (r5, beam-6 transformer-big sent/s on v5e): flat LEADING-row
        # gather 88.5 — the only gather form the tiled cache layout runs
        # at bandwidth — vs one-hot matmul 61.6 (even unflattened, the
        # tiny-contraction dot relayouts the cache) vs take_along_axis
        # 46-53. The flat gather is opaque to GSPMD (it all-gathers the
        # whole cache per step under a decode mesh), so the mesh path
        # runs the SAME flat gather per batch shard inside a manual
        # 'data' shard_map — collective-free by construction
        # (test_mesh_decode_is_collective_free pins it).
        # MARIAN_BEAM_REORDER={gather,onehot,take} forces a form for
        # A/Bs (gather = the GSPMD-opaque global form, only meaningful
        # off-mesh).
        carried = model.beam_carried_suffixes
        reorder_impl = os.environ.get("MARIAN_BEAM_REORDER", "auto")

        def beam_rows(v, axis):
            shape = v.shape

            def split_rows():
                # [.., B*K, ..] -> [.., B, K, ..]: single-dim split,
                # layout-free (tiling lives on the last two dims)
                return v.reshape(shape[:axis] + (b, k) + shape[axis + 1:])

            def take():
                idx = beam_idx.reshape((1,) * axis + (b, k) +
                                       (1,) * (v.ndim - axis - 1))
                return jnp.take_along_axis(split_rows(), idx,
                                           axis=axis + 1).reshape(shape)

            if reorder_impl == "take" or (
                    reorder_impl == "onehot"
                    and not jnp.issubdtype(v.dtype, jnp.floating)):
                # take also covers integer carried state under the onehot
                # override: int x int einsum exactness is backend-
                # dependent; the gather forms are dtype-agnostic
                return take()

            def flat_gather(vv, idx):
                # rows (axis 0 or 1) indexed by a flat [rows] vector —
                # the ONLY gather form the tiled cache layout runs at
                # bandwidth (leading-row gather)
                bl = idx.shape[0]
                fs = (jnp.arange(bl)[:, None] * k + idx).reshape(-1)
                return vv[:, fs] if axis == 1 else vv[fs]

            if reorder_impl == "gather" or (mesh is None
                                            and reorder_impl != "onehot"):
                return flat_gather(v, beam_idx)
            if reorder_impl == "onehot":
                # one-hot matmul: exact (single 1.0 term per output, f32
                # MXU accumulation), partitionable — kept as an A/B
                # alternative; the shard_map gather below measured faster
                prec = (jax.lax.Precision.HIGHEST
                        if v.dtype == jnp.float32 else
                        jax.lax.Precision.DEFAULT)
                onehot = (beam_idx[:, :, None] ==
                          jnp.arange(k)[None, None, :]).astype(v.dtype)
                eq = "bij,bj...->bi..." if axis == 0 else "bij,lbj...->lbi..."
                return jnp.einsum(eq, onehot, split_rows(),
                                  precision=prec).reshape(shape)
            # decode mesh: the SAME fast flat gather, run PER BATCH SHARD
            # under a manual 'data' shard_map — beam_idx is batch-local
            # (source-beam index within each sentence's own beam), so the
            # local gather touches only local rows: collective-free by
            # construction (test_mesh_decode_is_collective_free), at the
            # single-device gather's measured speed per shard. Left to
            # GSPMD, the flat global gather all-gathers the entire cache
            # every step instead.
            row_axis_spec = ["data" if d == axis else None
                             for d in range(v.ndim)]
            spec_v = P(*row_axis_spec)
            return jax.shard_map(
                lambda vv, idx: flat_gather(vv, idx), mesh=mesh,
                in_specs=(spec_v, P("data")),
                out_specs=spec_v, check_vma=False)(v, beam_idx)

        def reorder_state(st):
            out = {}
            for key, v in st.items():
                if key == "pos":
                    out[key] = v
                elif fused and key.endswith(("_self_k", "_self_v")):
                    # fused decode kernel: the pending backpointers ride
                    # the carry and the NEXT step's cache read applies
                    # them — no gather here
                    out[key] = v
                elif key.endswith(carried):
                    # 'stack_*' = scanned decode caches [L, B*K, ...]:
                    # the batch axis is axis 1
                    out[key] = beam_rows(v, 1 if key.startswith("stack_")
                                         else 0)
                else:  # cross K/V / encoder context are beam-invariant
                    out[key] = v
            return out

        states2 = tuple(reorder_state(st) for st in new_states)
        prev = tok_full.reshape(bk, 1)
        if fused:
            src_rows = (jnp.arange(b, dtype=jnp.int32)[:, None] * k
                        + beam_idx.astype(jnp.int32)).reshape(bk)
        return (t + 1, tokens, scores, new_finished, lengths, prev, states2,
                aligns, wscores, src_rows)

    init = (jnp.zeros((), jnp.int32), tokens0, scores0, finished0, lengths0,
            prev0, tuple(states), aligns0,
            (jnp.zeros((b, k, L), jnp.float32) if cfg.word_scores
             else jnp.zeros((0,), jnp.float32)),
            # pending-backpointer carry: identity before the first top-k
            (jnp.arange(bk, dtype=jnp.int32) if fused
             else jnp.zeros((0,), jnp.int32)))
    (t, tokens, scores, finished, lengths, prev, states, aligns, wscores,
     _src) = jax.lax.while_loop(cond, body, init)

    # unfinished beams at L: length = L
    lengths = jnp.where(finished, lengths, L)
    norm = jnp.ones_like(scores)
    if cfg.normalize > 0:
        norm = jnp.power(lengths.astype(jnp.float32), cfg.normalize)
    norm_scores = scores / norm - cfg.word_penalty * lengths.astype(jnp.float32)
    return tokens, scores, lengths, norm_scores, \
        (aligns if cfg.return_alignment else None), \
        (wscores if cfg.word_scores else None)


def _eos_index(shortlist: Optional[jax.Array]):
    """Index of EOS in (shortlist) coordinates. The shortlist generator always
    places EOS_ID=0 at position 0 (sorted unique ids)."""
    return 0 if shortlist is not None else EOS_ID


def _eos_token(shortlist: Optional[jax.Array]):
    return EOS_ID


class BeamSearch:
    """Host-side wrapper: jit cache per (B, Ts, L) bucket, Histories out
    (reference: BeamSearch::search + translator.h per-batch loop)."""

    def __init__(self, model, params_list, weights: Optional[Sequence[float]],
                 options, trg_vocab):
        self.model = model
        self.params_list = params_list
        n = len(params_list)
        self.weights = list(weights) if weights else [1.0 / max(n, 1)] * n
        self.options = options
        self.trg_vocab = trg_vocab
        self.max_length_factor = float(options.get("max-length-factor", 3.0))
        self.max_length_cap = int(options.get("max-length", 1000))
        self._jitted = {}
        self._sample_calls = 0
        self._sample_seed = int(options.get("seed", 0) or 0) or 1234
        # Data-parallel decode: shard the batch dim over visible devices
        # (reference: translator.h round-robins batches over --devices GPU
        # workers, one model replica per device; the SPMD equivalent is
        # ONE jitted program with the batch sharded over a 'data' mesh —
        # GSPMD partitions every beam-search op along rows). --num-devices
        # caps the mesh; a single visible device means no mesh.
        # local (addressable) devices only: under multi-process (multihost)
        # each process decodes its own batches on its own chips — the same
        # per-worker decomposition as the reference's translator workers
        local = jax.local_devices()
        nd = int(options.get("num-devices", 0) or 0) or len(local)
        nd = max(1, min(nd, len(local)))
        self.mesh = None
        # sharded scorer params (TP/pipe training params at a validation
        # decode) also veto the fused decode kernel: its pallas call is
        # GSPMD-opaque and would all-gather the sharded caches per step
        self._sharded_params = any(self._mesh_sharded(p)
                                   for p in self.params_list)
        if nd > 1 and not self._sharded_params:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self.mesh = Mesh(np.array(local[:nd]), ("data",))
            rep = NamedSharding(self.mesh, PartitionSpec())

            def _replicate(v):
                # multiprocess: a GLOBAL-mesh array (training params at a
                # validation decode) cannot device_put onto the local
                # mesh directly — jax treats it as a cross-host transfer
                # even when a replica is addressable; hop via the local
                # replica on host
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    # the extracted local replica is a fully-addressable
                    # single-device array — replicating THAT is a
                    # device-to-device copy, no host round-trip
                    v = v.addressable_data(0)
                return jax.device_put(v, rep)

            # scorer params replicate to every device once, up front
            # (tree_map covers QTensor leaves)
            self.params_list = [jax.tree_util.tree_map(_replicate, p)
                                for p in self.params_list]

    @property
    def fused_decode_engaged(self) -> bool:
        """Whether beam_search_jit will actually run the fused decode
        kernel for this instance — the ONE place the gate's terms live
        (mirrored into beam_search_jit via mesh/allow_fused), so bench
        provenance fields cannot desynchronize from the compiled
        program."""
        return (self.mesh is None and not self._sharded_params
                and bool(getattr(self.model, "fused_decode_reorder",
                                 False)))

    @staticmethod
    def _mesh_sharded(params) -> bool:
        """True if any param leaf is already non-replicated device-sharded
        (TP/pipe-sharded training params reaching a validation decode):
        re-placing those replicated would materialize a full model copy
        per device mid-training — decode with them where they are
        instead (GSPMD handles sharded inputs without our mesh)."""
        for v in jax.tree_util.tree_leaves(params):
            sh = getattr(v, "sharding", None)
            if sh is not None and not getattr(sh, "is_fully_replicated",
                                              True):
                return True
        return False

    def _get_fn(self, cfg: BeamConfig, has_shortlist: bool):
        key = (cfg, has_shortlist)
        if key not in self._jitted:
            model, weights = self.model, tuple(self.weights)

            mesh = self.mesh
            allow_fused = not self._sharded_params

            def fn(params_list, src_ids, src_mask, shortlist=None,
                   sample_key=None, prefix=None):
                return beam_search_jit(model, list(params_list), weights, cfg,
                                       src_ids, src_mask, shortlist,
                                       sample_key=sample_key, prefix=prefix,
                                       mesh=mesh, allow_fused=allow_fused)

            self._jitted[key] = jax.jit(fn, static_argnames=())
        return self._jitted[key]

    def search_async(self, src_ids, src_mask,
                     shortlist=None, prefix=None) -> "_SearchHandle":
        """Dispatch one batch's beam search; returns a handle whose
        ``collect()`` blocks on the device result and extracts n-bests.
        src_ids/src_mask may be tuples of streams (multi-source).
        `prefix` [B, P] int32 (pad -1) force-decodes each sentence's
        target prefix (--force-decode)."""
        if prefix is not None and shortlist is not None:
            raise ValueError("--force-decode cannot be combined with a "
                             "lexical shortlist (prefix ids are full-vocab)")
        if getattr(self.model.cfg, "lm", False):
            raise ValueError("a decoder-only LM (--type transformer-lm) "
                             "has no source to translate; use "
                             "marian-scorer for LM scoring")
        if prefix is not None and getattr(self.model.cfg,
                                          "output_approx_knn", ()):
            raise ValueError("--force-decode cannot be combined with "
                             "--output-approx-knn (a forced token outside "
                             "the LSH candidate set would have no logit)")
        b, ts = _first(src_ids).shape
        n_rows = b
        if self.mesh is not None:
            # pad rows to a multiple of the mesh by REPLICATING row 0
            # (replicated rows decode safely — an all-zero mask row would
            # risk NaNs in fully-masked attention); extras drop at collect
            pad = (-b) % self.mesh.shape["data"]
            if pad:
                def _padrows(x):
                    if isinstance(x, (tuple, list)):
                        return tuple(_padrows(e) for e in x)
                    x = np.asarray(x)
                    return np.concatenate(
                        [x, np.repeat(x[:1], pad, axis=0)], axis=0)
                src_ids = _padrows(src_ids)
                src_mask = _padrows(src_mask)
                if prefix is not None:
                    prefix = _padrows(prefix)
                b += pad
        # static decode cap per source bucket (Marian: factor * src length)
        L = int(min(self.max_length_cap,
                    max(8, round(self.max_length_factor * ts))))
        if prefix is not None:
            plen = int(np.asarray(prefix).shape[1])
            # the forced prefix must fit under the cap with room to continue
            L = max(L, min(self.max_length_cap, plen + 8))
            if plen >= self.max_length_cap:
                raise ValueError(
                    f"--force-decode: prefix length {plen} exceeds "
                    f"--max-length {self.max_length_cap}")
        cfg = BeamConfig.from_options(self.options, L)
        sl_idx = jnp.asarray(shortlist.indices) if shortlist is not None else None
        fn = self._get_fn(cfg, sl_idx is not None)

        def _dev(x):
            if isinstance(x, (tuple, list)):
                return tuple(_dev(e) for e in x)
            x = jnp.asarray(x)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                spec = PartitionSpec("data", *([None] * (x.ndim - 1)))
                x = jax.device_put(x, NamedSharding(self.mesh, spec))
            return x

        sample_key = None
        if cfg.sampling:
            self._sample_calls += 1
            sample_key = jax.random.fold_in(
                jax.random.key(self._sample_seed), self._sample_calls)
        pfx = None
        if prefix is not None:
            # pad/crop to the decode cap with -1 (unconstrained past end)
            pfx = np.full((b, L), -1, np.int32)
            p = np.asarray(prefix)[:, :L]
            pfx[:, :p.shape[1]] = p
            pfx = _dev(pfx)       # same 'data' placement as its siblings
        args = (tuple(self.params_list), _dev(src_ids), _dev(src_mask))
        tokens, scores, lengths, norm_scores, aligns, wscores = fn(
            *args, shortlist=sl_idx, sample_key=sample_key, prefix=pfx)
        # device results stay lazy here — collect() forces them. Callers
        # that pipeline (translator driver) dispatch the NEXT batch's
        # search before collecting this one, so host n-best extraction
        # overlaps device beam steps (the role of the reference
        # translator's worker thread pool, played by XLA async dispatch).
        return _SearchHandle(tokens, scores, lengths, norm_scores, aligns,
                             wscores, cfg, self,
                             n_rows=n_rows if n_rows != b else None)

    def search(self, src_ids, src_mask,
               shortlist=None, prefix=None) -> List[List[dict]]:
        """Returns per-sentence n-best lists of dicts
        {tokens, score, norm_score, alignment}. src_ids/src_mask may be
        tuples of streams (multi-source). `prefix` [B, P] int32 (pad -1)
        force-decodes each sentence's target prefix (--force-decode)."""
        return self.search_async(src_ids, src_mask, shortlist=shortlist,
                                 prefix=prefix).collect()

    def _collect(self, tokens, scores, lengths, norm_scores, aligns,
                 cfg: BeamConfig, wscores=None) -> List[List[dict]]:  # noqa: C901
        b, k, L = tokens.shape
        out = []
        for i in range(b):
            order = np.argsort(-norm_scores[i])
            nbest = []
            for rank in range(min(cfg.n_best, k) if cfg.n_best > 1 else 1):
                j = order[rank]
                ln = int(lengths[i, j])
                toks = tokens[i, j, :ln].tolist()
                if toks and toks[-1] == EOS_ID:
                    toks = toks[:-1]
                entry = {
                    "tokens": toks,
                    "score": float(scores[i, j]),
                    "norm_score": float(norm_scores[i, j]),
                }
                if aligns is not None:
                    entry["alignment"] = aligns[i, j, :ln, :]
                if wscores is not None:
                    # per emitted token, incl. the EOS step (Marian's
                    # WordScores covers the terminating </s>)
                    entry["word_scores"] = [
                        float(x) for x in wscores[i, j, :ln]]
                nbest.append(entry)
            out.append(nbest)
        return out


class _SearchHandle:
    """Lazy result of one dispatched beam search. Holding it costs one
    batch's device output buffers; ``collect()`` forces the transfer and
    runs host n-best extraction. Depth-1 pipelining (dispatch batch i+1,
    then collect batch i) hides the host extraction of every batch but
    the last behind device compute."""

    def __init__(self, tokens, scores, lengths, norm_scores, aligns,
                 wscores, cfg, bs: "BeamSearch", n_rows: Optional[int] = None):
        self._dev = (tokens, scores, lengths, norm_scores, aligns, wscores)
        self._cfg = cfg
        self._bs = bs
        self._n = n_rows                 # original rows before mesh padding

    def collect(self) -> List[List[dict]]:
        tokens, scores, lengths, norm_scores, aligns, ws = self._dev

        def _h(x):
            if x is None:
                return None
            x = np.asarray(x)  # mtlint: ok -- collect() IS the designed sync boundary; depth-1 pipelining hides it behind the next batch's device work
            return x[:self._n] if self._n is not None else x

        return self._bs._collect(
            _h(tokens), _h(scores), _h(lengths), _h(norm_scores),
            _h(aligns) if aligns is not None else None, self._cfg,
            wscores=_h(ws) if self._cfg.word_scores else None)
