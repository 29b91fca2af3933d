"""Benchmark: training throughput (src-tokens/sec/chip) — the driver's
headline metric (BASELINE.json north star: 180k src-tok/s/chip, v4).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Unlike a synthetic step-timing loop, this drives the REAL training path
(VERDICT r1 #8): GraphGroup.update over BatchGenerator-produced bucketed
batches from a synthetic mixed-length corpus at a memory-filling token
budget (--mini-batch-words), so host-side batch assembly, sharding,
donation, and the jitted fused step are all inside the measured window.
Throughput counts real (unpadded) source tokens, like Marian's words/s.

Reports ``mfu`` (analytic matmul FLOPs vs the chip's published bf16
peak — common/flops.py) next to ``vs_baseline``. Needs a TPU: without one
it fails rather than fall back — a CPU number is not a device metric.
The one exception is the ``tiny`` preset under an explicit
``JAX_PLATFORMS=cpu`` (tests/test_bench_smoke.py), whose row is named
``cpu_smoke_*`` and carries no baseline ratio.

Env knobs:
  MARIAN_BENCH_PRESET   big (default) | base | tiny (CPU smoke)
  MARIAN_BENCH_WORDS    token budget per batch (default 8192 for big)
  MARIAN_BENCH_PROFILE  directory → capture a jax.profiler trace of the
                        timed window (then: tensorboard --logdir <dir>)
  MARIAN_BENCH_BUCKETS  comma-separated bucket widths (default "full" =
                        the generator's 18-bucket table, the honest
                        length-mix config; "32,64" is the historical
                        2-bucket baseline leg)
  MARIAN_BENCH_SCAN     force --scan-layers on/off for an A/B (default:
                        model default)
  MARIAN_BENCH_SEQLEN   long-sequence stage: one bucket at exactly this
                        width, corpus lines at [s/2, s] words (doc-level
                        lengths; pairs with MARIAN_BENCH_FLASH for the
                        flash-attention A/B)
  MARIAN_BENCH_FLASH    force --transformer-flash-attention on/off/auto
  MARIAN_BENCH_PACKED   force --transformer-packed-attention on/off/auto
                        (r6 head-packed MXU kernel; auto = TPU only —
                        the packed_off ladder leg isolates its gain)
  MARIAN_BENCH_COMPACT  0 disables the uint16+lengths host→device
                        transfer (transfer_full A/B stage)
  MARIAN_BENCH_GRAD_DTYPE  --gradient-dtype. DEFAULT bfloat16 (the
                        bench measures the throughput config — bf16
                        backward grad writes + ZeRO-1 collectives;
                        rows carry grad_dtype provenance; the TRAINER
                        default stays float32). Set float32 for the
                        f32-pinned A/B legs
  MARIAN_BENCH_OPT_DTYPE  --optimizer-state-dtype (Adam first moment).
                        DEFAULT bfloat16 at the bench (trainer default
                        float32); rows carry opt_state_dtype
  MARIAN_BENCH_DISPATCH --dispatch-window: K full updates per jitted
                        dispatch (lax.scan over same-bucket batches) —
                        amortizes per-dispatch host latency over
                        K real updates. DEFAULT 8 (the bench measures
                        windowed; the TRAINER default stays K=1 because
                        K>1 quantizes save/validate/stop triggers to
                        window boundaries — see docs/PERFORMANCE.md
                        "dispatch-window default"). Set 1 for the
                        unwindowed A/B
"""

import json
import os
import random
import sys
import tempfile
import time

def _write_corpus(tmp, vocab_size, n_lines, seed=7, max_words=63):
    """Mixed-length synthetic parallel corpus (Zipf-ish lengths 4..64 by
    default, mean ~28 — matches a WMT-style length histogram closely
    enough to exercise the bucket table the way real data does). For the
    long-sequence stage (max_words >> 64, doc-level concatenation
    lengths) lines are drawn uniform in [max_words//2, max_words]."""
    rng = random.Random(seed)
    words = [f"w{i}" for i in range(vocab_size - 2)]  # EOS/UNK take 2 slots
    src_p = os.path.join(tmp, "b.src")
    trg_p = os.path.join(tmp, "b.trg")
    with open(src_p, "w") as fs, open(trg_p, "w") as ft:
        # line 0 mentions every word so the vocab covers all ids
        fs.write(" ".join(words) + "\n")
        ft.write(" ".join(words) + "\n")
        for _ in range(n_lines):
            if max_words > 64:
                n = rng.randint(max_words // 2, max_words)
                m = min(max_words, max(4, int(n * rng.uniform(0.9, 1.1))))
            else:
                n = min(max_words, max(4, int(rng.lognormvariate(3.2, 0.45))))
                m = min(max_words,
                        max(4, int(n * rng.uniform(0.8, 1.25))))
            fs.write(" ".join(rng.choice(words) for _ in range(n)) + "\n")
            ft.write(" ".join(rng.choice(words) for _ in range(m)) + "\n")
    return src_p, trg_p


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
    preset = os.environ.get("MARIAN_BENCH_PRESET", "big")
    profile_dir = os.environ.get("MARIAN_BENCH_PROFILE")
    cpu_smoke = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if cpu_smoke:
        if preset != "tiny":
            sys.exit("bench: JAX_PLATFORMS=cpu runs the tiny smoke preset "
                     "only — a CPU number is not a device metric")
        from marian_tpu.common.hermetic import force_cpu_devices
        force_cpu_devices(1)
    import jax
    if not cpu_smoke and jax.default_backend() != "tpu":
        sys.exit(f"bench: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}) — not falling back")

    from marian_tpu.common.profiling import enable_compilation_cache
    enable_compilation_cache()

    from marian_tpu.common.options import Options
    from marian_tpu.common import prng
    from marian_tpu.data import BatchGenerator, Corpus
    from marian_tpu.data.vocab import DefaultVocab
    from marian_tpu.models.encoder_decoder import batch_to_arrays, create_model
    from marian_tpu.training.graph_group import GraphGroup

    # Length buckets: every distinct (src_w, trg_w, rows) shape costs a
    # full XLA compile of the train step. The default since r4 is the
    # generator's FULL bucket table, the measured-best honest config
    # (+20% real-token throughput over the historical 2-bucket table's
    # padding tax); budget compile time accordingly on a cold cache
    # (MARIAN_BENCH_BUCKETS=32,64 pins the cheap table).
    # max-length 63 → crop to 63 tokens + EOS = width 64 exactly; corpus
    # lines are capped at 63 words so nothing falls past the last bucket
    # (bucket_length would jump to 512 → a surprise multi-minute compile)
    bucket_env = os.environ.get("MARIAN_BENCH_BUCKETS", "full")
    if bucket_env == "full":
        from marian_tpu.data.batch_generator import DEFAULT_LENGTH_BUCKETS
        buckets = DEFAULT_LENGTH_BUCKETS
    else:
        try:
            buckets = tuple(int(b) for b in bucket_env.split(",") if b)
            if not buckets:
                raise ValueError(bucket_env)
        except ValueError:
            print(f"bench: bad MARIAN_BENCH_BUCKETS={bucket_env!r} — "
                  f"falling back to 32,64", file=sys.stderr, flush=True)
            buckets = (32, 64)
        bucket_env = ",".join(str(b) for b in buckets)  # record parsed
    max_len = 63
    if preset == "big":
        dims = dict(emb=1024, ffn=4096, heads=16, depth=6, vocab=32000)
        words = int(os.environ.get("MARIAN_BENCH_WORDS", 8192))
        n_lines, steps, warmup = 3000, 30, 8
    elif preset == "base":
        dims = dict(emb=512, ffn=2048, heads=8, depth=6, vocab=32000)
        words = int(os.environ.get("MARIAN_BENCH_WORDS", 12288))
        n_lines, steps, warmup = 3000, 30, 8
    else:  # tiny CPU smoke
        dims = dict(emb=64, ffn=128, heads=4, depth=2, vocab=512)
        words = int(os.environ.get("MARIAN_BENCH_WORDS", 512))
        n_lines, steps, warmup = 200, 5, 2

    # MARIAN_BENCH_SEQLEN: long-sequence stage (doc-level concatenation
    # lengths — the long-context story measured, not just designed):
    # one bucket at exactly this width (rows crop to seqlen-1 + EOS),
    # corpus drawn at [s/2, s], token budget floored to ≥4 rows/batch.
    try:
        seqlen = int(os.environ.get("MARIAN_BENCH_SEQLEN", 0) or 0)
    except ValueError:
        print(f"bench: bad MARIAN_BENCH_SEQLEN="
              f"{os.environ['MARIAN_BENCH_SEQLEN']!r} — ignoring",
              file=sys.stderr, flush=True)
        seqlen = 0
    if seqlen > 64:
        max_len = seqlen - 1
        buckets = (seqlen,)
        bucket_env = str(seqlen)
        words = max(words, 4 * seqlen)
        n_lines = min(n_lines, 600)

    tmp = tempfile.mkdtemp(prefix="marian_bench_")
    src_p, trg_p = _write_corpus(tmp, dims["vocab"], n_lines,
                                 max_words=max_len)
    vsz = (dims["vocab"], dims["vocab"])  # static uint16 gate per stream

    fused_mode = os.environ.get("MARIAN_BENCH_FUSED", "tune")

    # bench defaults = the measured-best throughput config (r5 combined
    # legs: grad+moment bf16 stacked to 51,208 tok/s vs 49,640-50,351
    # headline) — the numeric levers Marian's own published speed numbers
    # also pull (fp16 training); every row carries grad_dtype/
    # opt_state_dtype provenance. TRAINER defaults stay f32/f32 —
    # users opt in (docs/PERFORMANCE.md "dispatch-window default" notes
    # the same bench-vs-trainer split for K).
    opt_dtype = os.environ.get("MARIAN_BENCH_OPT_DTYPE", "bfloat16")
    grad_dtype = os.environ.get("MARIAN_BENCH_GRAD_DTYPE", "bfloat16")
    # uint16-token + row-length host→device transfer (default on — A/B
    # with 0)
    compact = os.environ.get("MARIAN_BENCH_COMPACT", "1").strip().lower() \
        not in ("0", "false", "off", "no")
    remat = os.environ.get("MARIAN_BENCH_REMAT", "").strip().lower() \
        in ("1", "true", "on", "yes")
    stacked = os.environ.get("MARIAN_BENCH_STACKED", "").strip().lower() \
        in ("1", "true", "on", "yes")
    # --dispatch-window: K full updates per jitted dispatch (lax.scan) —
    # amortizes per-dispatch host latency over K real updates
    window = max(1, int(os.environ.get("MARIAN_BENCH_DISPATCH", "8") or 1))
    scan_env = os.environ.get("MARIAN_BENCH_SCAN")  # on/off A/B knob
    if scan_env:
        scan_env = {"on": "on", "1": "on", "true": "on",
                    "off": "off", "0": "off", "false": "off"}.get(
                        scan_env.strip().lower())
        if scan_env is None:
            print(f"bench: bad MARIAN_BENCH_SCAN="
                  f"{os.environ['MARIAN_BENCH_SCAN']!r} (want on/off) — "
                  f"using model default", file=sys.stderr, flush=True)
    flash_env = tristate_env("MARIAN_BENCH_FLASH")    # on/off/auto A/B
    packed_env = tristate_env("MARIAN_BENCH_PACKED")  # on/off/auto A/B
    opts = Options({
        "type": "transformer",
        **({"scan-layers": scan_env == "on"} if scan_env else {}),
        **({"dispatch-window": window} if window > 1 else {}),
        **({"transformer-flash-attention": flash_env} if flash_env else {}),
        **({"transformer-packed-attention": packed_env}
           if packed_env else {}),
        "dim-emb": dims["emb"], "transformer-dim-ffn": dims["ffn"],
        "transformer-heads": dims["heads"],
        "enc-depth": dims["depth"], "dec-depth": dims["depth"],
        "tied-embeddings-all": True,
        "transformer-ffn-activation": "relu",
        "precision": ["bfloat16", "float32"],
        "label-smoothing": 0.1, "cost-type": "ce-mean-words",
        "learn-rate": 2e-4, "lr-warmup": "8000", "lr-decay-inv-sqrt": ["8000"],
        "optimizer": "adam", "optimizer-params": [0.9, 0.98, 1e-9],
        "optimizer-state-dtype": opt_dtype,
        "gradient-dtype": grad_dtype,
        "gradient-checkpointing": remat,
        "stacked-params": stacked,
        "clip-norm": 0.0, "exponential-smoothing": 1e-4,
        "max-length": max_len, "max-length-crop": True,
        "mini-batch": 512, "mini-batch-words": words,
        "maxi-batch": 100, "maxi-batch-sort": "trg",
        "shuffle": "data", "seed": 1111,
    })

    vocab_lines = open(src_p).readline().split()
    vocab = DefaultVocab.build([" ".join(vocab_lines)])
    vocabs = [vocab, vocab]
    corpus = Corpus([src_p, trg_p], vocabs, opts)
    key = prng.root_key(1111)
    train_key = prng.stream(key, prng.STREAM_DROPOUT)

    def build_gg(fused: str) -> GraphGroup:
        o = opts.with_(**{"fused-ce": fused})
        model = create_model(o, len(vocab), len(vocab))
        gg = GraphGroup(model, o)
        gg.initialize(prng.stream(key, prng.STREAM_INIT))
        return gg

    if fused_mode == "tune" and jax.default_backend() == "tpu":
        # AutoTuner-style A/B: the streaming fused-CE kernel wins or loses
        # depending on chip generation and batch shape — time both on a
        # few real steps and keep the faster (reference: AutoTuner picking
        # kernel alternatives by measurement). Snapshot/restore the corpus
        # position so the timed window sees the same epoch regardless of
        # whether the probe ran (numbers stay comparable across
        # MARIAN_BENCH_FUSED settings).
        corpus_state = corpus.state.as_dict()
        probe = next(iter(BatchGenerator(corpus, opts, prefetch=False,
                                         length_buckets=buckets)))
        corpus.restore(corpus_state)
        times = {}
        t_ab = time.perf_counter()
        for mode in ("on", "off"):
            g = build_gg(mode)
            arrays = batch_to_arrays(probe, compact=compact, vocab_sizes=vsz)
            for i in range(2):                       # compile + settle
                g.update(dict(arrays), i + 1, train_key)
            jax.block_until_ready(g.params)
            t0 = time.perf_counter()
            for i in range(6):
                g.update(dict(arrays), i + 3, train_key)
            jax.block_until_ready(g.params)
            times[mode] = time.perf_counter() - t0
            del g
            if mode == "on" and time.perf_counter() - t_ab > 300:
                # a slow cold compile: a second probe variant would
                # double that cost — keep the fused default rather than
                # risk the caller's whole time budget on the A/B
                print(f"fused-ce A/B skipped after "
                      f"{time.perf_counter() - t_ab:.0f}s cold compile "
                      f"→ on", file=sys.stderr, flush=True)
                times = None
                fused_mode = "on"
                break
        if times is not None:
            fused_mode = min(times, key=times.get)
            print(f"fused-ce A/B: on={times['on']:.3f}s "
                  f"off={times['off']:.3f}s → {fused_mode}", file=sys.stderr,
                  flush=True)
    elif fused_mode == "tune":
        fused_mode = "auto"

    gg = build_gg(fused_mode)

    n_chips = len(jax.devices())

    def batches():
        while True:
            for b in BatchGenerator(corpus, opts, prefetch=True,
                                    length_buckets=buckets):
                yield b

    gen = batches()
    # Pre-materialize the exact batches the timed window will run, then warm
    # every distinct bucket shape among them (plus `warmup` steady-state
    # repeats) so NO jit compilation lands inside the measurement. Host
    # per-step costs (array conversion, sharding, dispatch) stay inside the
    # window; raw corpus iteration is excluded — in real training it is
    # prefetch-overlapped (BatchGenerator(prefetch=True)).
    timed_batches = [next(gen) for _ in range(steps)]
    step = 0
    by_shape = {}
    for b in timed_batches:
        by_shape.setdefault(b.shape_key(), b)
    print(f"warming {len(by_shape)} shapes: {sorted(by_shape)}",
          file=sys.stderr, flush=True)
    for sk, b in by_shape.items():
        t0 = time.perf_counter()
        gg.update(batch_to_arrays(b, compact=compact, vocab_sizes=vsz),
                  step + 1, train_key)
        jax.block_until_ready(gg.params)
        print(f"  shape {sk}: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
        step += 1
    # dispatch plan: with --dispatch-window, stable-sort the timed batches
    # by bucket shape and group runs of K — full windows go through ONE
    # jitted dispatch (update_window), stragglers singly. Total tokens and
    # batch population are identical to the unwindowed run.
    if window > 1:
        order = sorted(range(len(timed_batches)),
                       key=lambda j: (str(timed_batches[j].shape_key()), j))
        timed_batches = [timed_batches[j] for j in order]
        plan, run_ = [], []
        for b in timed_batches:
            if run_ and (b.shape_key() != run_[0].shape_key()
                         or len(run_) == window):
                plan.append(run_)
                run_ = []
            run_.append(b)
        if run_:
            plan.append(run_)
        for sk in sorted({g[0].shape_key() for g in plan
                          if len(g) == window}):
            b = by_shape[sk]
            arrays = batch_to_arrays(b, compact=compact, vocab_sizes=vsz)
            t0 = time.perf_counter()
            gg.update_window([dict(arrays) for _ in range(window)],
                             step + 1, train_key)
            jax.block_until_ready(gg.params)
            print(f"  window[{window}] shape {sk}: "
                  f"{time.perf_counter() - t0:.1f}s",
                  file=sys.stderr, flush=True)
            step += window
    else:
        plan = [[b] for b in timed_batches]
    for _ in range(warmup):
        b = timed_batches[step % len(timed_batches)]
        gg.update(batch_to_arrays(b, compact=compact, vocab_sizes=vsz),
                  step + 1, train_key)
        step += 1
    jax.block_until_ready(gg.params)

    if profile_dir:
        os.makedirs(profile_dir, exist_ok=True)
        jax.profiler.start_trace(profile_dir)

    # Timed window, in chunks fenced by a value fetch. The only pipeline
    # cost is the in-flight latency of the chunk's last step — noise
    # against ~100ms steps × CHUNK.
    from marian_tpu.common.flops import (peak_bf16_flops,
                                         transformer_train_flops)
    CHUNK = 5
    src_tokens = flops = 0.0
    dt = 0.0
    i = 0
    last_out = None
    while i < len(plan):
        chunk = plan[i:i + CHUNK]        # CHUNK dispatches, not batches
        t0 = time.perf_counter()
        for grp in chunk:
            if window > 1 and len(grp) == window:
                outs = gg.update_window(
                    [batch_to_arrays(b, compact=compact, vocab_sizes=vsz)
                     for b in grp],
                    step + 1, train_key)
                last_out = outs[-1]
                step += window
            else:
                for b in grp:
                    last_out = gg.update(
                        batch_to_arrays(b, compact=compact,
                                        vocab_sizes=vsz),
                        step + 1, train_key)
                    step += 1
        # per-chunk hardened sync: fetch a metric VALUE, not just
        # block_until_ready(params). The r4 transfer_full row (MFU 1.79,
        # above physical peak) showed this backend's block_until_ready
        # can return early on SOME input paths — and the full int32+f32
        # transfer leg is exactly the path the compact default never
        # exercises, so the under-sync only surfaced there. A scalar
        # value fetch cannot lie: it requires the chunk's last update to
        # have executed, regardless of input dtype path. Rows carry
        # `sync` provenance so a row timed any other way is identifiable.
        if last_out is not None:
            float(last_out.loss_sum)
        else:  # pragma: no cover — plan is never empty
            jax.block_until_ready(gg.params)
        dt += time.perf_counter() - t0
        for grp in chunk:
            for b in grp:
                src_tokens += b.src_words  # real (mask-counted) src tokens
                flops += transformer_train_flops(
                    dims["emb"], dims["ffn"], dims["depth"], dims["depth"],
                    dims["vocab"], b.src_words, b.words,
                    b.src.batch_width, b.trg.batch_width)
        i += CHUNK

    # Residue check: the per-chunk value fetches above already fenced
    # every chunk inside dt, so this final sync should measure ~0 —
    # anything else means work escaped a chunk fence and the row's
    # final_sync_s says so. Fences on the PARAMS, not loss_sum: the last
    # chunk already materialized loss_sum's host value, so re-fetching
    # that same array would be a host cache hit that can never block.
    # Runs BEFORE stop_trace: trace collection blocks, and pending work
    # draining inside it would escape both dt and the residue.
    t_sync = time.perf_counter()
    jax.block_until_ready(gg.params)
    sync_residue = time.perf_counter() - t_sync
    dt += sync_residue

    if profile_dir:
        jax.profiler.stop_trace()
        print(f"profile trace: tensorboard --logdir {profile_dir}",
              file=sys.stderr)

    chip_kind = jax.devices()[0].device_kind
    peak = peak_bf16_flops(chip_kind)
    mfu = round(flops / dt / max(n_chips, 1) / peak, 4) if peak else None
    tok_per_sec_chip = src_tokens / dt / max(n_chips, 1)
    baseline = 180_000.0  # north-star src-tok/s/chip (BASELINE.json)
    result = {
        # a CPU number is never written under the device metric's name
        "metric": ("cpu_smoke_src_tokens_per_sec" if cpu_smoke
                   else "train_src_tokens_per_sec_per_chip"),
        "value": round(tok_per_sec_chip, 1),
        "unit": "src-tokens/sec/chip",
        "vs_baseline": (None if cpu_smoke
                        else round(tok_per_sec_chip / baseline, 4)),
        "mfu": mfu,
        "chip": chip_kind,
        "platform": jax.devices()[0].platform,
        "n_chips": n_chips,
        "flops_per_src_token": round(flops / max(src_tokens, 1.0)),
        "buckets": bucket_env,
        "fused_ce": fused_mode,
        "scan_layers": scan_env or "default",
        "opt_state_dtype": opt_dtype,
        "grad_dtype": grad_dtype,
        "remat": remat,
        "stacked_params": stacked,
        "words_budget": words,
        "dispatch_window": window,
        # sync provenance (r6, transfer_full close-out): every timed
        # chunk is fenced by a metric-VALUE fetch, input-dtype-path
        # independent; final_sync_s is the residue past the last fence
        "sync": "value-fetch-per-chunk",
        "final_sync_s": round(sync_residue, 3),
        "compact_transfer": compact,
        "seqlen": max_len + 1,
        "flash": flash_env or "default",
        "packed_attn": packed_env or "default",
    }
    if mfu is not None and mfu > 0.95:
        # faster than the chip's physical peak = the measurement lied
        # somewhere; poison the row visibly rather than publish it
        result["suspect"] = "mfu>0.95: impossible — sync/accounting bug"
    print(json.dumps(result))


if __name__ == "__main__":
    main()
