"""Decode benchmark: batched beam-6 translation throughput (sent/sec) —
BASELINE.json's second driver metric (training is measured by the
benchmark's `big.train` cell, BENCHMARK.json).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}; the
baseline field stays null (the empty reference mount ships no decode
number — SURVEY §6).

Drives the REAL translator path: Translator-style bucketed batches through
the jitted BeamSearch (ensemble-capable, KV-cached, scanned decoder stack),
on a freshly-initialized transformer-big. Sentence throughput counts real
input sentences; the beam-6/normalize-0.6 settings mirror Marian's
published decode configs.

Env knobs:
  MARIAN_DECBENCH_PRESET     big (default) | base | tiny (CPU smoke)
  MARIAN_DECBENCH_SENTS      sentences in the timed window (default 256)
  MARIAN_DECBENCH_INT8       int8-quantized decode (config #5)
  MARIAN_DECBENCH_SHORTLIST  lexical-shortlist decode: a synthetic binary
                             lexical table (clustered trg band → K=4096 of
                             the 32k vocab) through the REAL
                             LexicalShortlistGenerator.generate → beam
                             search in shortlist coordinates — the
                             reference's decode-speed headline combo
                             (intgemm + --shortlist). A/B stage (ISSUE
                             16): the IDENTICAL batches also run through
                             the full-vocab output GEMM (shortlist=None)
                             and the sibling full_vocab_sentences_per_sec
                             field records the pair — the output-
                             projection shrink isolated on one run
  MARIAN_DECBENCH_SSRU       SSRU decoder (--transformer-decoder-autoreg
                             rnn --dec-cell ssru): the reference's
                             production fast-decode architecture — no
                             self-attn KV cache; composes with INT8
  MARIAN_DECBENCH_BEAM       beam size (default 6; 1 = greedy — the
                             production student serving config)
  MARIAN_DECBENCH_BATCH      sentences per batch (default 64). The
                             weight-bound decode regime lives at small
                             row counts (batch×beam rows ≲ 64, where
                             DECODE_ROOFLINE predicts int8/shortlist
                             pay); batch 64 × beam 6 = 384 rows is
                             compute/cache-bound and measured those
                             levers FLAT — this knob reaches the
                             regime they were designed for
  MARIAN_DECBENCH_FUSED      --transformer-fused-decode-attention
                             on/off/auto (default auto = TPU only): the
                             Pallas fused beam-gather + cache-read
                             kernel (ops/pallas/decode_attention.py) —
                             the r5 while-body op-count lever
  MARIAN_DECBENCH_PAGED      paged stage (ISSUE 10): greedy decode over
                             the paged KV pool with rows as slots
                             (translator/greedy.py::greedy_decode_paged
                             — finished rows free their pages and LEAVE
                             the compiled step; active rows bucket).
                             A/B against the dense cache with the same
                             batches by also timing plain greedy_decode
                             (dense_sentences_per_sec field); forces
                             beam 1. step_ops reports the compiled
                             per-step program's op count for both paths
                             (the paged step has no while loop — its
                             analog of while_body_ops; CPU-interpret
                             caveat as for the fused stage). A bare
                             value > 1 overrides the page length
                             (default 16); rows come from
                             MARIAN_DECBENCH_BATCH like every stage
  MARIAN_DECBENCH_PAGED_BEAM paged_beam stage (ISSUE 12): copy-on-write
                             paged beam search (translator/
                             beam_iteration.py — full pages alias via
                             refcounts, only partial pages copy on
                             fork) A/B'd against the dense batched beam
                             search on IDENTICAL sentences
                             (dense_beam_sentences_per_sec field); beam
                             from MARIAN_DECBENCH_BEAM, a bare value
                             > 1 overrides the page length
  MARIAN_DECBENCH_PAGED_BEAM_SCAN
                             paged_beam_scan stage (ISSUE 18): the
                             fused on-device beam merge + multi-step
                             scanned rounds (--iteration-steps) A/B'd
                             against the single-step HOST-merge
                             baseline — the SAME PagedBeamEngine class,
                             IDENTICAL mixed-length sentences, merge=
                             "fused" vs merge="host"
                             (host_merge_sentences_per_sec field). The
                             row records token parity between the two
                             paths (every output string compared), both
                             sides' warm-block compile_s, and the fused
                             side's steady-window compile count
                             (steady_compiles — must be 0: the
                             closed-shape-set claim; a nonzero count or
                             a parity break poisons the row). Scanned
                             steps from MARIAN_DECBENCH_STEPS (default
                             4); beam from MARIAN_DECBENCH_BEAM; a bare
                             value > 1 overrides the page length
  MARIAN_DECBENCH_DEVICES    decode device count (default 1). Pinned to
                             ONE device because (a) the metric is
                             per-chip sent/s and every recorded row is
                             single-chip, and (b) a decode mesh vetoes
                             the fused kernel (GSPMD-opaque pallas
                             call), which would silently turn the
                             fused A/B into unfused-vs-unfused on a
                             multi-chip host
  MARIAN_DECBENCH_PROFILE    directory → jax.profiler trace of the
                             timed window

Every row reports ``while_body_ops``: the op count of the decode loop's
body in the COMPILED program (the largest while-body computation of the
optimized HLO). The r5 trace put the standard body at ~690 small ops ×
~4 µs dispatch each — the floor that made sent/s flat from 384 rows
down to 8; this field is how the fused kernel's reduction is tracked
per run instead of per profile session.

Every row also reports ``compile_s``: the backend-compile seconds the
stage's warm block actually paid, summed from the shared
``jax.monitoring`` backend-compile listener (common/jitwit.py — the
same event stream the perf plane's compile telemetry and the jit
retrace witness ride). A/B stages report the dense side separately
(``dense_compile_s`` / ``full_vocab_compile_s``): a paged-vs-dense
throughput pair is only comparable if neither side smuggled a
recompile into its warm. Null (not 0) when the listener is
unavailable or explicitly disarmed (``MARIAN_JITWIT=0``).
"""

import json
import os
import random
import re
import sys
import tempfile
import time


def _compiled_text(jitted, *args, **kwargs) -> "str | None":
    """Optimized HLO of the program the jit object's cache holds for
    these args (the warm call already populated it; on TPU the
    persistent XLA cache covers the AOT path). None when unavailable —
    op counts are reporting-only; the bench must not die for them."""
    try:
        return jitted.lower(*args, **kwargs).compile().as_text()
    except Exception as e:  # noqa: BLE001 — backend/AOT availability varies
        print(f"bench_decode: compiled-HLO op count unavailable: "
              f"{type(e).__name__}: {str(e)[:120]}", file=sys.stderr,
              flush=True)
        return None


def _computation_counts(txt: str):
    """(entry_name, {computation -> instruction count}) from HLO text.
    Computations open with `%name (params) -> type {` or `name (...) {`."""
    counts = {}
    entry = None
    current, n = None, 0
    for line in txt.splitlines():
        m = re.match(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{", line)
        if m:
            current, n = m.group(2), 0
            if m.group(1):
                entry = current
            continue
        if current is not None:
            if line.strip().startswith("}"):
                counts[current] = n
                current = None
            elif "=" in line:
                n += 1
    return entry, counts


def while_body_op_count(jitted, *args, **kwargs) -> "int | None":
    """Op count of the largest while-loop body in the compiled program:
    find each `while(...)` instruction's body= computation, count its
    instruction lines, return the max — the decode loop dominates every
    smaller scan/loop in the program."""
    txt = _compiled_text(jitted, *args, **kwargs)
    if txt is None:
        return None
    bodies = set(re.findall(r"body=%?([\w.\-]+)", txt))
    if not bodies:
        return None
    _, counts = _computation_counts(txt)
    hits = [v for k, v in counts.items() if k in bodies]
    return max(hits) if hits else None


def entry_op_count(jitted, *args, **kwargs) -> "int | None":
    """Op count of the compiled program's ENTRY computation — the paged
    stage's analog of while_body_ops: its per-step program has no while
    loop (the step loop lives on the host so rows can join/leave), so
    the whole entry IS the step body."""
    txt = _compiled_text(jitted, *args, **kwargs)
    if txt is None:
        return None
    entry, counts = _computation_counts(txt)
    return counts.get(entry)


def _warm_compile_s(window, armed: bool) -> "float | None":
    """Summed backend-compile seconds a stage's warm block paid, from a
    jitwit strict window (common/jitwit.py) over the jax.monitoring
    backend-compile event stream. None (not 0.0) when the listener is
    unavailable or disarmed — a zero-compile warm is a claim,
    an unobserved one is not."""
    if not armed:
        return None
    return round(sum(s for _site, s in window.compiles), 3)


def tristate_env(name: str):
    """Parse an on/off/auto A/B env knob; malformed values fall back to
    None (= model default) with a warning."""
    raw = os.environ.get(name)
    if not raw:
        return None
    v = raw.strip().lower()
    if v not in ("on", "off", "auto"):
        print(f"bench: bad {name}={raw!r} (want on/off/auto) — using "
              f"model default", file=sys.stderr, flush=True)
        return None
    return v


def main():
    preset = os.environ.get("MARIAN_DECBENCH_PRESET", "big")
    n_sents = int(os.environ.get("MARIAN_DECBENCH_SENTS", 256))
    cpu_smoke = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if cpu_smoke:
        if preset != "tiny":
            sys.exit("bench_decode: JAX_PLATFORMS=cpu runs the tiny smoke "
                     "preset only — a CPU number is not a device metric")
        from marian_tpu.common.hermetic import force_cpu_devices
        force_cpu_devices(1)
    import jax
    if not cpu_smoke and jax.default_backend() != "tpu":
        sys.exit(f"bench_decode: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}) — not falling back")
    import jax.numpy as jnp
    import numpy as np

    from marian_tpu.common.profiling import enable_compilation_cache
    enable_compilation_cache()
    # per-stage compile accounting (ISSUE 17 satellite): arm the jit
    # retrace witness's jax.monitoring listener so every stage's warm
    # block reports the backend-compile seconds it paid (compile_s and
    # the A/B siblings). setdefault respects an explicit
    # MARIAN_JITWIT=0; the listener re-checks the env per event.
    from marian_tpu.common import jitwit
    os.environ.setdefault(jitwit.ENV_VAR, "1")
    jw_armed = jitwit.install() and jitwit.enabled()
    from marian_tpu.common.options import Options
    from marian_tpu.data.vocab import DefaultVocab
    from marian_tpu.models.encoder_decoder import create_model
    from marian_tpu.translator.beam_search import BeamSearch

    if preset == "big":
        dims = dict(emb=1024, ffn=4096, heads=16, depth=6, vocab=32000)
        batch, src_len, max_len = 64, 32, 64
    elif preset == "base":
        dims = dict(emb=512, ffn=2048, heads=8, depth=6, vocab=32000)
        batch, src_len, max_len = 64, 32, 64
    else:
        dims = dict(emb=64, ffn=128, heads=4, depth=2, vocab=512)
        batch, src_len, max_len = 8, 12, 16
        n_sents = min(n_sents, 32)
    batch_env = os.environ.get("MARIAN_DECBENCH_BATCH")
    if batch_env:
        try:
            batch = max(1, int(batch_env))
        except ValueError:
            print(f"bench_decode: bad MARIAN_DECBENCH_BATCH={batch_env!r}"
                  f" — keeping {batch}", file=sys.stderr, flush=True)

    # MARIAN_DECBENCH_SSRU=1: the reference's production fast-decode
    # decoder (--transformer-decoder-autoreg rnn --dec-cell ssru, the
    # WNGT-2019 student config): the self-attention KV cache — whose
    # per-step reorder+read traffic dominates the standard decode step —
    # is replaced by one [B*K, d] recurrent state per layer
    ssru = bool(os.environ.get("MARIAN_DECBENCH_SSRU"))
    fused_env = tristate_env("MARIAN_DECBENCH_FUSED") or ""
    opts = Options({
        "type": "transformer",
        "dim-emb": dims["emb"], "transformer-dim-ffn": dims["ffn"],
        "transformer-heads": dims["heads"],
        "enc-depth": dims["depth"], "dec-depth": dims["depth"],
        "tied-embeddings-all": True, "transformer-ffn-activation": "relu",
        "precision": ["bfloat16", "float32"], "max-length": max_len,
        "seed": 17,
        **({"transformer-decoder-autoreg": "rnn", "dec-cell": "ssru"}
           if ssru else {}),
        **({"transformer-fused-decode-attention": fused_env}
           if fused_env else {}),
    })
    model = create_model(opts, dims["vocab"], dims["vocab"],
                         inference=True)
    params = model.init(jax.random.key(17))
    metric = "beam6_ssru_sentences_per_sec" if ssru \
        else "beam6_sentences_per_sec"
    if os.environ.get("MARIAN_DECBENCH_INT8"):
        # config #5 (int8 student decode): quantize offline like
        # marian-conv int8tpu, then pair values+scales into QTensor
        # leaves — only QTensors route the model through the int8
        # dot_general path (the same quantize→wrap the translator driver
        # does when loading an int8 checkpoint, translator.py:42)
        from marian_tpu.ops.quantization import (quantize_params,
                                                 wrap_quantized)
        params = wrap_quantized(
            {k: jnp.asarray(v)
             for k, v in quantize_params(params).items()})
        metric = metric.replace("sentences", "int8_sentences")
    # the REAL translator path: BeamSearch's jit cache + host-side
    # n-best extraction, exactly what marian_decoder runs per batch
    beam = int(os.environ.get("MARIAN_DECBENCH_BEAM", "6") or 6)
    if beam != 6:
        metric = metric.replace("beam6", f"beam{beam}")
    try:
        ndev = max(1, int(os.environ.get("MARIAN_DECBENCH_DEVICES", "1")))
    except ValueError:
        print(f"bench_decode: bad MARIAN_DECBENCH_DEVICES="
              f"{os.environ['MARIAN_DECBENCH_DEVICES']!r} — using 1",
              file=sys.stderr, flush=True)
        ndev = 1
    bopts = Options({"beam-size": beam, "normalize": 0.6,
                     "max-length": max_len, "seed": 17,
                     # single-device default: the metric is per-chip
                     # sent/s, and a decode mesh vetoes the fused
                     # kernel (see MARIAN_DECBENCH_DEVICES above)
                     "num-devices": ndev})
    vocab = DefaultVocab.build(
        [" ".join(f"w{i}" for i in range(dims["vocab"] - 2))])
    bs = BeamSearch(model, [params], None, bopts, vocab)

    sl_gen = None
    if os.environ.get("MARIAN_DECBENCH_SHORTLIST"):
        # Synthetic lexical table with a CLUSTERED target band: each src
        # word maps to 20 trg ids inside a 4000-id band, so a batch's
        # union stays ≤4096 and the per-batch shortlist K pins at one
        # static 4096 (k_multiple=4096 → one compiled shape). The output
        # matmul shrinks 32k→4k, the economics Marian's
        # --shortlist decode banks on.
        from marian_tpu.data.shortlist import LexicalShortlistGenerator
        band = 4000 if dims["vocab"] > 8000 else max(32, dims["vocab"] // 4)
        srcs, trgs, probs = [], [], []
        for s in range(2, dims["vocab"]):
            for j in range(20):
                srcs.append(s)
                trgs.append(2 + (s * 7 + j * 13) % band)
                probs.append(1.0 / (j + 1))
        slp = os.path.join(tempfile.mkdtemp(prefix="marian_decbench_"),
                           "lex.npz")
        np.savez(slp, srcs=np.array(srcs, np.int32),
                 trgs=np.array(trgs, np.int32),
                 probs=np.array(probs, np.float32))
        sl_gen = LexicalShortlistGenerator(
            slp, vocab, vocab, first=100, best=20,
            k_multiple=max(128, band + 96))
        metric = metric.replace("sentences", "shortlist_sentences")

    rng = random.Random(17)
    rs = np.random.RandomState(17)

    def make_batch():
        lens = [max(4, min(src_len, int(rng.lognormvariate(3.0, 0.4))))
                for _ in range(batch)]
        ids = np.zeros((batch, src_len), np.int32)
        mask = np.zeros((batch, src_len), np.float32)
        for i, n in enumerate(lens):
            ids[i, :n] = rs.randint(2, dims["vocab"], n)
            mask[i, :n] = 1.0
        return jnp.asarray(ids), jnp.asarray(mask)

    def shortlist_for(ids):
        if sl_gen is None:
            return None
        flat = [int(x) for x in np.asarray(ids).ravel() if x > 1]
        return sl_gen.generate(flat)

    paged_env = os.environ.get("MARIAN_DECBENCH_PAGED", "")
    if paged_env:
        # paged stage (ISSUE 10): greedy slot decode over the paged KV
        # pool A/B'd against the dense cache on the SAME batches; forces
        # beam 1 (the engine is greedy by design) and no shortlist
        if sl_gen is not None:
            print("bench_decode: MARIAN_DECBENCH_PAGED ignores the "
                  "shortlist stage", file=sys.stderr, flush=True)
        from marian_tpu.translator.greedy import (greedy_decode,
                                                  greedy_decode_paged)
        # "1"/"on"/"true" = enable with the default page length; a
        # bare number > 1 overrides it (rows: MARIAN_DECBENCH_BATCH)
        page_len = (int(paged_env) if paged_env.isdigit()
                    and int(paged_env) > 1 else 16)
        batches = [make_batch() for _ in range(max(1, n_sents // batch))]
        intro: dict = {}
        with jitwit.strict() as w_paged:
            greedy_decode_paged(
                model, params, *batches[0], max_len, page_len=page_len,
                introspect=intro)
        with jitwit.strict() as w_dense:
            greedy_decode(
                model, params, *batches[0], max_len, introspect=intro)

        t0 = time.perf_counter()
        for b_ids, b_mask in batches:
            greedy_decode_paged(model, params, b_ids, b_mask, max_len,
                                page_len=page_len)
        dt_paged = time.perf_counter() - t0
        t0 = time.perf_counter()
        for b_ids, b_mask in batches:
            greedy_decode(model, params, b_ids, b_mask, max_len)
        dt_dense = time.perf_counter() - t0
        # both loops end on host-side token fetches, so the residue
        # here should read ~0
        import jax as _jax
        t_sync = time.perf_counter()
        _jax.block_until_ready(_jax.numpy.zeros(()))
        final_sync_s = round(time.perf_counter() - t_sync, 3)
        sents = batch * len(batches)
        paged_counts = [c for c in (entry_op_count(fn, *args)
                                    for (kind, *_r), (fn, args)
                                    in intro.items()
                                    if kind == "paged_step")
                        if c is not None]
        # None (not 0) when the HLO text is unavailable — a zero-op
        # step is a claim, unavailability is not
        paged_ops = max(paged_counts) if paged_counts else None
        dense_ops = None
        if ("dense_step",) in intro:
            fn, args = intro[("dense_step",)]
            dense_ops = entry_op_count(fn, *args)
        result = {
            "metric": ("cpu_smoke_" if cpu_smoke else "") +
            "greedy_paged_sentences_per_sec",
            "value": round(sents / dt_paged, 2),
            "unit": "sent/sec",
            "vs_baseline": None,
            "chip": jax.devices()[0].device_kind,
            "preset": preset,
            "batch": batch,
            "beam": 1,
            "page_len": page_len,
            "dense_sentences_per_sec": round(sents / dt_dense, 2),
            # per-step compiled op counts (entry computation — the
            # paged step loop lives on the host, so there is no while
            # body; CPU-interpret numbers are NOT TPU claims, same
            # caveat as the fused stage)
            "step_ops": paged_ops,
            "dense_step_ops": dense_ops,
            "while_body_ops": None,
            # what each side's warm ACTUALLY compiled: the A/B is only
            # honest if neither path recompiles inside the timed loop,
            # and the warm cost here is the whole compile budget
            "compile_s": _warm_compile_s(w_paged, jw_armed),
            "dense_compile_s": _warm_compile_s(w_dense, jw_armed),
            "final_sync_s": final_sync_s,
        }
        print(json.dumps(result))
        return

    paged_beam_env = os.environ.get("MARIAN_DECBENCH_PAGED_BEAM", "")
    if paged_beam_env:
        # paged_beam stage (ISSUE 12): copy-on-write paged beam search
        # (translator/beam_iteration.py — full pages alias by refcount,
        # only partial pages copy on fork) A/B'd against the dense
        # batched beam search on IDENTICAL sentences. "1"/"on" = default
        # page length; a bare number > 1 overrides it.
        if sl_gen is not None:
            print("bench_decode: MARIAN_DECBENCH_PAGED_BEAM ignores the "
                  "shortlist stage", file=sys.stderr, flush=True)
        from marian_tpu.translator.beam_iteration import PagedBeamEngine
        page_len = (int(paged_beam_env) if paged_beam_env.isdigit()
                    and int(paged_beam_env) > 1 else 16)
        n_batches = max(1, n_sents // batch)
        texts = []
        for _ in range(n_batches):
            texts.append([
                " ".join(f"w{rs.randint(0, dims['vocab'] - 4)}"
                         for _ in range(max(4, min(
                             src_len - 1,
                             int(rng.lognormvariate(3.0, 0.4))))))
                for _ in range(batch)])
        engine = PagedBeamEngine(
            model, params, vocab, vocab, beam_size=beam, normalize=0.6,
            max_rows=batch * beam, page_len=page_len,
            src_len_cap=src_len, max_length_cap=max_len)
        with jitwit.strict() as w_paged:
            engine.decode_texts(texts[0])
        t0 = time.perf_counter()
        for chunk in texts:
            engine.decode_texts(chunk)
        dt_paged = time.perf_counter() - t0

        def dense_batch(chunk):
            # FIXED width (src_len), like make_batch: per-chunk widths
            # would mint a fresh jit compile (and a different decode
            # cap) per novel max length INSIDE the timed dense loop
            rows = [vocab.encode(t, add_eos=True, inference=True)
                    for t in chunk]
            ids = np.zeros((len(rows), src_len), np.int32)
            mask = np.zeros((len(rows), src_len), np.float32)
            for i, r in enumerate(rows):
                ids[i, :len(r)] = r
                mask[i, :len(r)] = 1.0
            return jnp.asarray(ids), jnp.asarray(mask)
        with jitwit.strict() as w_dense:
            bs.search(*dense_batch(texts[0]))
        t0 = time.perf_counter()
        for chunk in texts:
            bs.search(*dense_batch(chunk))
        dt_dense = time.perf_counter() - t0
        t_sync = time.perf_counter()
        jax.block_until_ready(jnp.zeros(()))
        final_sync_s = round(time.perf_counter() - t_sync, 3)
        sents = batch * len(texts)
        result = {
            "metric": ("cpu_smoke_" if cpu_smoke else "") +
            "paged_beam_sentences_per_sec",
            "value": round(sents / dt_paged, 2),
            "unit": "sent/sec",
            "vs_baseline": None,
            "chip": jax.devices()[0].device_kind,
            "preset": preset,
            "batch": batch,
            "beam": beam,
            "page_len": page_len,
            "dense_beam_sentences_per_sec": round(sents / dt_dense, 2),
            "compile_s": _warm_compile_s(w_paged, jw_armed),
            "dense_compile_s": _warm_compile_s(w_dense, jw_armed),
            "final_sync_s": final_sync_s,
        }
        print(json.dumps(result))
        return

    scan_env = os.environ.get("MARIAN_DECBENCH_PAGED_BEAM_SCAN", "")
    if scan_env:
        # paged_beam_scan stage (ISSUE 18): the fused on-device beam
        # merge + multi-step scanned rounds A/B'd against the HOST-merge
        # baseline — the same engine class on IDENTICAL mixed-length
        # sentences, so the pair isolates exactly what the tentpole
        # changed: log-softmax + k·k merge + page retable on device,
        # --iteration-steps decode steps per host sync vs one. Token
        # parity between the two paths is checked per row (the fused
        # merge claims bitwise-equal selection, not just equal speed).
        if sl_gen is not None:
            print("bench_decode: MARIAN_DECBENCH_PAGED_BEAM_SCAN ignores "
                  "the shortlist stage", file=sys.stderr, flush=True)
        from marian_tpu.translator.beam_iteration import PagedBeamEngine
        page_len = (int(scan_env) if scan_env.isdigit()
                    and int(scan_env) > 1 else 16)
        steps = max(1, int(os.environ.get("MARIAN_DECBENCH_STEPS", "4")
                           or 4))
        n_batches = max(1, n_sents // batch)
        texts = []
        for _ in range(n_batches):
            texts.append([
                " ".join(f"w{rs.randint(0, dims['vocab'] - 4)}"
                         for _ in range(max(4, min(
                             src_len - 1,
                             int(rng.lognormvariate(3.0, 0.4))))))
                for _ in range(batch)])

        def scan_engine(merge, steps_per_round):
            return PagedBeamEngine(
                model, params, vocab, vocab, beam_size=beam,
                normalize=0.6, max_rows=batch * beam, page_len=page_len,
                src_len_cap=src_len, max_length_cap=max_len,
                merge=merge, steps_per_round=steps_per_round)

        # fused side: warm the full compile-key grid (beam scan + the
        # pressure-fallback host jits), then decode the first chunk for
        # the parity record, then time the full set inside a STRICT
        # retrace window — the steady loop must compile NOTHING
        fused = scan_engine("fused", steps)
        with jitwit.strict() as w_fused:
            fused.warm_grid()
        parity_fused = fused.decode_texts(texts[0])
        with jitwit.strict() as w_steady:
            t0 = time.perf_counter()
            for chunk in texts:
                fused.decode_texts(chunk)
            dt_fused = time.perf_counter() - t0
        # host-merge baseline: same engine class, merge="host" (rounds
        # are single-step by construction — the host needs the sync)
        host = scan_engine("host", 1)
        with jitwit.strict() as w_host:
            host.warm_grid()
        parity_host = host.decode_texts(texts[0])
        t0 = time.perf_counter()
        for chunk in texts:
            host.decode_texts(chunk)
        dt_host = time.perf_counter() - t0
        t_sync = time.perf_counter()
        jax.block_until_ready(jnp.zeros(()))
        final_sync_s = round(time.perf_counter() - t_sync, 3)
        sents = batch * len(texts)
        parity_ok = parity_fused == parity_host
        steady_compiles = len(w_steady.compiles) if jw_armed else None
        result = {
            "metric": ("cpu_smoke_" if cpu_smoke else "") +
            "paged_beam_scan_sentences_per_sec",
            "value": round(sents / dt_fused, 2),
            "unit": "sent/sec",
            "vs_baseline": None,
            "chip": jax.devices()[0].device_kind,
            "preset": preset,
            "batch": batch,
            "beam": beam,
            "page_len": page_len,
            "steps_per_round": steps,
            "host_merge_sentences_per_sec": round(sents / dt_host, 2),
            "speedup_vs_host": round(dt_host / dt_fused, 2),
            "token_parity": parity_ok,
            "fused_fallback_rounds": fused._counters.get(
                "fused_fallback_rounds", 0),
            "compile_s": _warm_compile_s(w_fused, jw_armed),
            "host_compile_s": _warm_compile_s(w_host, jw_armed),
            # compiles the fused TIMED loop paid (strict window): any
            # nonzero here voids the closed-shape-set claim AND the
            # throughput pair, so it poisons the row below
            "steady_compiles": steady_compiles,
            "final_sync_s": final_sync_s,
        }
        if not parity_ok:
            bad = sum(1 for a, b in zip(parity_fused, parity_host)
                      if a != b)
            result["poisoned"] = True
            result["poisoned_reason"] = (
                f"token parity broke: {bad}/{len(parity_host)} sentences "
                f"differ between fused and host merge — the speedup is "
                f"measuring a different decode")
        elif steady_compiles:
            result["poisoned"] = True
            result["poisoned_reason"] = (
                f"{steady_compiles} compiles inside the fused timed "
                f"window — the warm grid missed a shape; the pair is "
                f"warm-vs-cold, not fused-vs-host")
        print(json.dumps(result))
        return

    if fused_env == "on":
        metric = metric.replace("sentences", "fused_sentences")

    # compile + warm
    ids, mask = make_batch()
    warm_sl = shortlist_for(ids)
    with jitwit.strict() as w_warm:
        bs.search(ids, mask, shortlist=warm_sl)

    # Whether the fused kernel ACTUALLY engaged for this run (the env
    # knob is a request; mesh/sharded-params/backend gates can veto it)
    fused_engaged = bs.fused_decode_engaged

    # while-body op count of the program the warm call just compiled:
    # re-lower through the SAME jit object (trace + persistent-cache
    # compile; cheap next to the timed window) and parse the body size.
    # Skipped under a decode mesh: lowering with plain uncommitted
    # arrays there would trace a SECOND, differently-sharded program —
    # an extra compile whose body is not the one being benched.
    body_ops = None
    if bs._jitted and bs.mesh is None:
        jitted = next(iter(bs._jitted.values()))
        sl_idx = jnp.asarray(warm_sl.indices) if warm_sl is not None else None
        body_ops = while_body_op_count(
            jitted, tuple(bs.params_list), jnp.asarray(ids),
            jnp.asarray(mask), shortlist=sl_idx, sample_key=None,
            prefix=None)
    print(f"bench_decode: while-body op count = {body_ops} "
          f"(fused requested={fused_env or 'auto'}, "
          f"engaged={fused_engaged})", file=sys.stderr, flush=True)

    batches = [make_batch() for _ in range(max(1, n_sents // batch))]
    # shortlist generation is host-side work the real translator does per
    # batch — keep it inside the timed window, like Marian does. The
    # depth-1 dispatch/collect pipeline is the translator driver's
    # (common/pipeline.py): host n-best extraction overlaps device beam
    # steps.
    from marian_tpu.common.pipeline import pipelined
    profile_dir = os.environ.get("MARIAN_DECBENCH_PROFILE")
    if profile_dir:
        os.makedirs(profile_dir, exist_ok=True)
        jax.profiler.start_trace(profile_dir)
    results = []
    t0 = time.perf_counter()
    pipelined(batches,
              lambda b: bs.search_async(b[0], b[1],
                                        shortlist=shortlist_for(b[0])),
              lambda b, h: results.append(h.collect()))
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()
        print(f"decode trace: tensorboard --logdir {profile_dir}",
              file=sys.stderr)
    nbests = results[-1]
    assert len(nbests) == batch
    sents = batch * len(batches)

    full_vocab_sps = None
    full_vocab_compile_s = None
    if sl_gen is not None:
        # shortlist A/B: the IDENTICAL batches back through the
        # full-vocab output GEMM (shortlist=None) — the pair isolates
        # the 32k→~4k output-projection shrink, which is the whole
        # economics --shortlist banks on. Kept OUT of the shortlisted
        # window above so the per-batch shortlist host work stays a
        # shortlist-side cost, as in the real translator.
        with jitwit.strict() as w_full:
            bs.search(ids, mask)
        full_vocab_compile_s = _warm_compile_s(w_full, jw_armed)
        t0 = time.perf_counter()
        pipelined(batches,
                  lambda b: bs.search_async(b[0], b[1]),
                  lambda b, h: h.collect())
        dt_full = time.perf_counter() - t0
        full_vocab_sps = round(sents / dt_full, 2)

    # the timed loops end on host-side n-best collects, so the residue
    # here should read ~0
    t_sync = time.perf_counter()
    jax.block_until_ready(jnp.zeros(()))
    final_sync_s = round(time.perf_counter() - t_sync, 3)
    result = {
        # a CPU number is never written under the device metric's name
        "metric": "cpu_smoke_" + metric if cpu_smoke else metric,
        "value": round(sents / dt, 2),
        "unit": "sent/sec",
        "vs_baseline": None,
        "chip": jax.devices()[0].device_kind,
        "platform": jax.devices()[0].platform,
        "preset": preset,
        "batch": batch,
        "beam": beam,
        "fused_decode": fused_env or "auto",
        "fused_decode_engaged": fused_engaged,
        "while_body_ops": body_ops,
        "compile_s": _warm_compile_s(w_warm, jw_armed),
        "final_sync_s": final_sync_s,
    }
    if full_vocab_sps is not None:
        result["full_vocab_sentences_per_sec"] = full_vocab_sps
        result["full_vocab_compile_s"] = full_vocab_compile_s
    print(json.dumps(result))


if __name__ == "__main__":
    main()
