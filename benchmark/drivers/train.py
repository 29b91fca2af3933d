"""Train cells: the trainer's own loop objects, wired as training/train.py
wires them (Corpus -> BatchGenerator with its prefetch thread ->
batch_to_arrays -> GraphGroup.update -> Scheduler.update), in this
process, at the trainer's defaults, on a corpus made from the seed.

Dispatch stays asynchronous, as in the trainer: the loop syncs only where
the trainer does, at the Scheduler's display boundary (every `sync_every`
updates, its one deferred cost fetch), and every timestamp of the window
is taken with the device drained. The window is one whole epoch of a
corpus sized to last about --seconds: the same batches for every seed, in
another order. The rate is the epoch's real target labels over the
window's own length.
"""

import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import corpus, jaxside, kernel_costs

def compare_with_reference(ctx, chk, model, params, batch):
    """Set-up, before any update: the program's cost of one of the
    corpus' own batches, dropout off, against the plain float32 reference
    beside the configuration (the same `model.loss` the train step
    differentiates, with its kernels, in its compute type).

    With fresh weights every token costs about ln V, so the batch's mean
    cost alone would hide most faults. The program's loss takes per-token
    weights (`data_weights`), so the check also reads `projections`
    seeded +-1 weightings of the per-token costs and compares each with
    the reference's: their RMS difference over the spread of the
    reference's per-token costs is the relative error of a token's cost.
    The reference runs in chunks of `chunk_tokens` so that its float32
    logits never set the process's peak memory."""
    import jax
    import jax.numpy as jnp
    ref = ctx.cell.reference
    ids_s, mask_s = np.asarray(batch.src.ids), np.asarray(batch.src.mask)
    ids_t, mask_t = np.asarray(batch.trg.ids), np.asarray(batch.trg.mask)
    rows, width = ids_t.shape
    chunk = max(1, int(chk["chunk_tokens"]) // width)
    pad = -rows % chunk

    def padded(a):
        return np.pad(a, ((0, pad), (0, 0)))
    token_costs = jax.jit(lambda p, a, b, c, d: ref.token_costs(
        p, ctx.dims, a, b, c, d))
    ce = np.concatenate([
        np.asarray(token_costs(params, *(padded(a)[i:i + chunk] for a in
                                          (ids_s, mask_s, ids_t, mask_t))))
        for i in range(0, rows + pad, chunk)])[:rows]
    real = mask_t > 0
    labels = float(real.sum())
    rs = np.random.RandomState(ctx.seed % (2 ** 31))
    signs = rs.choice((-1.0, 1.0), size=(int(chk["projections"]), rows,
                                        width)).astype(np.float32)
    weights = np.concatenate([np.ones((1, rows, width), np.float32), signs])
    arrays = {"src_ids": jnp.asarray(ids_s), "src_mask": jnp.asarray(mask_s),
              "trg_ids": jnp.asarray(ids_t), "trg_mask": jnp.asarray(mask_t)}
    loss = jax.jit(lambda p, b, w: model.loss(
        p, dict(b, data_weights=w), None, False)[0])
    got = np.array([float(loss(params, arrays, jnp.asarray(w)))
                    for w in weights])
    want = np.array([float((ce * real * w).sum(dtype=np.float64))
                     for w in weights])
    cost_rel = abs(got[0] - want[0]) / want[0]
    spread = float(np.sqrt(np.square(ce[real] - ce[real].mean()).sum()))
    token_rel = float(np.sqrt(np.mean(np.square(got[1:] - want[1:])))) \
        / spread
    ctx.note(f"reference check on a [{rows}, {width}] batch: cost "
             f"{got[0] / labels:.5f} against the reference's "
             f"{want[0] / labels:.5f} (relative {cost_rel:.2e}, limit "
             f"{chk['cost_rtol']}); a token's cost differs by "
             f"{token_rel:.2e} of the tokens' spread (limit "
             f"{chk['token_rtol']})")
    problems = []
    if not cost_rel <= chk["cost_rtol"]:
        problems.append(f"the batch's cost differs from the reference's "
                        f"by {cost_rel:.2e}")
    if not token_rel <= chk["token_rtol"]:
        problems.append(f"per-token costs differ from the reference's by "
                        f"{token_rel:.2e} of their spread")
    return problems


# the compiled step must hold these; the traced run totals their time
KERNELS = ("packed_attention_fwd", "packed_attention_bwd",
           "fused_ce_fwd", "fused_ce_dx", "fused_ce_dw")


def run(ctx):
    jaxside.lift_cache_cap()
    ir_dir = jaxside.arm_ir_dump()
    import jax
    devs = jaxside.require_devices(ctx.cell.chips, ctx.rehearse)
    jaxside.enable_cache()
    compiles = jaxside.CompileLog()

    from marian_tpu.common import prng
    from marian_tpu.common.config_parser import parse_options
    from marian_tpu.data import BatchGenerator, Corpus, create_vocab
    from marian_tpu.models.encoder_decoder import (batch_to_arrays,
                                                   create_model)
    from marian_tpu.training.graph_group import GraphGroup
    from marian_tpu.training.scheduler import Scheduler
    from marian_tpu.training.training_state import TrainingState

    traffic, dims = ctx.cell.traffic, ctx.dims
    chips = len(devs)
    sync_every = int(traffic["sync_every"])
    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        # -- data and options, from the seed --------------------------------
        n_lines = int(traffic["lines_per_second"] * ctx.seconds) \
            if not ctx.rehearse else 600
        words = traffic["mini_batch_words_per_chip"] * chips \
            if not ctx.rehearse else 256
        lines, _ = corpus.make_lines(traffic["lengths"], n_lines,
                                     dims["vocab"], ctx.seed)
        vocab_path = os.path.join(work, "vocab.json")
        src, trg = os.path.join(work, "c.src"), os.path.join(work, "c.trg")
        corpus.write_vocab(vocab_path, dims["vocab"])
        corpus.write_parallel(lines, src, trg)
        argv = (list(ctx.cell.config["task_flags"]) + ctx.tiny_flags
                + list(traffic["trainer_flags"])
                + ["--train-sets", src, trg, "--vocabs", vocab_path,
                   vocab_path, "--model", os.path.join(work, "model.npz"),
                   "--mini-batch-words", str(words),
                   "--disp-freq", f"{sync_every}u",
                   "--seed", str(ctx.program_seed), "--quiet",
                   "--devices"] + [str(i) for i in range(chips)])
        opts = parse_options(argv, mode="training")
        vocabs = [create_vocab(vocab_path, opts, i, [p])
                  for i, p in enumerate((src, trg))]
        data = Corpus([src, trg], vocabs, opts)
        model = create_model(opts, vocabs[0], vocabs[-1])
        ctx.check_dims(model.cfg, len(vocabs[-1]))
        gg = GraphGroup(model, opts)
        key = prng.root_key(ctx.program_seed)
        # the program's own initialiser, on the device, in one jitted call
        init_key = prng.stream(key, prng.STREAM_INIT)
        gg.initialize(init_key, jax.jit(model.init)(init_key))
        state = TrainingState(seed=ctx.program_seed)
        scheduler = Scheduler(opts, state)
        train_key = prng.stream(key, prng.STREAM_DROPOUT)
        compact = bool(opts.get("compact-transfer", True))
        vsz = [len(v) for v in vocabs]
        outs = []            # (lazy loss_sum, labels, lazy skipped) per update

        ann = jax.profiler.TraceAnnotation

        def update(batch):
            """One update as train.py's loop makes it; returns the host
            clock after the dispatch and after the bookkeeping."""
            with ann("bench.dispatch"):
                out = gg.update(batch_to_arrays(batch, compact=compact,
                                                vocab_sizes=vsz),
                                state.batches + 1, train_key)
            t_dispatched = time.perf_counter()
            with ann("bench.host"):
                outs.append((out.loss_sum, batch.words, out.skipped))
                scheduler.update(out.loss_sum, batch.words, batch.size,
                                 src_words=batch.src_words,
                                 lr=gg.schedule.host_lr(state.batches + 1),
                                 skipped=out.skipped)
            return t_dispatched, time.perf_counter()

        # -- every shape this corpus makes; the reference check; warm-up -----
        snapshot = data.state.as_dict()
        shapes = {}
        for b in BatchGenerator(data, opts, prefetch=False):
            shapes.setdefault(b.shape_key(), b)
        data.restore(snapshot)
        problems = compare_with_reference(
            ctx, traffic["reference_check"], model, gg.export_params(),
            shapes[min(shapes)])
        for sk in sorted(shapes):
            update(shapes[sk])
            jax.block_until_ready(gg.params)
        ctx.note(f"warmed {len(shapes)} step shapes: {sorted(shapes)}; "
                 f"{compiles.total_s():.1f}s in {len(compiles.events)} "
                 f"compiles or cache loads")

        # steady clocks before the window: each shape once more
        for sk in sorted(shapes):
            update(shapes[sk])
        jax.block_until_ready(gg.params)

        # -- the window: ONE whole epoch of the corpus --------------------------
        # Every seed's corpus holds the same multiset of lengths, so an
        # epoch is the same batches in another order: a fixed amount of
        # work, sized by `lines_per_second` to last about --seconds. (A
        # window cut by the clock held a seed-dependent mix of widths and
        # spread by 1.7 %; PERF.md, Findings.)
        spans = {"data_wait_s": 0.0, "dispatch_s": 0.0, "host_s": 0.0}
        tw = jaxside.TraceWindow(ctx.trace, ctx.trace_after_s,
                                 ctx.trace_for_s)
        traced_work, in_window = [], []
        wall0, t0 = time.time(), time.perf_counter()
        ctx.window_opens()
        bg = iter(BatchGenerator(data, opts))    # reads, shuffles, sorts
        while True:
            ta = time.perf_counter()
            with ann("bench.data"):
                batch = next(bg, None)
            if batch is None:
                break
            tb = time.perf_counter()
            tc, td = update(batch)
            spans["data_wait_s"] += tb - ta
            spans["dispatch_s"] += tc - tb
            spans["host_s"] += td - tc
            in_window.append(batch)
            if tw.state == "on":
                traced_work.append({
                    "rows": batch.batch_size,
                    "src_width": batch.src.batch_width,
                    "trg_width": batch.trg.batch_width})
            if state.batches % sync_every == 0:
                # the Scheduler's display just fetched the cost: drained
                tw.tick(time.perf_counter(), t0)
        jax.block_until_ready(gg.params)
        tw.stop()
        t1, wall1 = time.perf_counter(), time.time()
        window_s = t1 - t0 - tw.overhead_s

        # -- outside the window: counts, costs, checks --------------------------
        first_in = len(outs) - len(in_window)
        costs = [float(np.asarray(l)) / max(w, 1) for l, w, _ in outs]
        skipped = sum(int(np.asarray(s)) for _, _, s in outs
                      if s is not None)
        finite = all(np.isfinite(c) for c in costs)
        q = max(1, len(costs) // 4)
        falling = float(np.mean(costs[-q:])) < float(np.mean(costs[:q]))
        kernels = jaxside.kernels_dumped(ir_dir)
        missing = [k for k in KERNELS if k not in kernels] \
            if devs[0].platform == "tpu" else []
        n_compiles = compiles.count_between(wall0, wall1)
        real = sum(b.words for b in in_window)
        padded = sum(b.trg.batch_size * b.trg.batch_width
                     for b in in_window)
        flops = sum(kernel_costs.train_step_flops(
            dims, b.src_words, b.words, b.src.batch_width,
            b.trg.batch_width) for b in in_window)
        if not finite:
            problems.append("a cost is not finite")
        if skipped:
            problems.append(f"{skipped} updates skipped")
        if not falling:
            problems.append(f"cost did not fall: first quarter "
                            f"{np.mean(costs[:q]):.4f}, last "
                            f"{np.mean(costs[-q:]):.4f}")
        if missing:
            problems.append(f"kernels missing from the compiled step: "
                            f"{missing}")
        if n_compiles:
            problems.append(f"{n_compiles} compiles inside the window")
        ctx.note(f"{len(in_window)} updates in {window_s:.3f}s; cost "
                 f"{costs[0]:.4f} (first warmed) -> {costs[-1]:.4f}; "
                 f"first-quarter mean {np.mean(costs[:q]):.4f}, last "
                 f"{np.mean(costs[-q:]):.4f}; window starts at update "
                 f"{first_in}")
        device = jaxside.device_info(devs)
        peaks = ctx.peaks(device["kind"])
        values = dict(spans, window_s=window_s, updates=len(in_window),
                      real_tokens=real, padded_tokens=padded,
                      window_compiles=n_compiles)
        if peaks:
            values["mfu_pct"] = 100.0 * flops / window_s / chips \
                / peaks["bf16_flops_per_s"]
        return {
            "correct": not problems, "problems": problems,
            "attempted": len(in_window),
            "failed": skipped + sum(1 for c in costs[first_in:]
                                    if not np.isfinite(c)),
            "end_to_end": {"train_tok_s_chip": real / window_s / chips},
            "device": device,
            "obs": {"values": values,
                    "trace": tw.reduce(KERNELS),
                    "traced_work": traced_work},
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
