"""Train cells: the trainer's own loop objects, wired as training/train.py
wires them (Corpus -> BatchGenerator with its prefetch thread ->
batch_to_arrays -> GraphGroup.update -> Scheduler.update), in this
process, at the trainer's defaults, on a corpus made from the seed.

Dispatch stays asynchronous, as in the trainer: the loop syncs only where
the trainer does, at the Scheduler's display boundary (every `sync_every`
updates, its one deferred cost fetch), and every timestamp of the window
is taken with the device drained. The window is one whole epoch of a
corpus sized to last about --seconds, from the moment its first batch is
ready: the same batches for every seed, in another order. The rate is the
epoch's real target labels over the window's own length.

Whatever differs between two training configurations is read from the
configuration's file (CONFIG_KEYS; benchmark/manifest.py says what each
holds), so one driver serves them all.
"""

import itertools
import os
import re
import shutil
import tempfile
import time

import numpy as np

from benchmark import corpus, jaxside, manifest, trace_reduce

# what this driver reads from a configuration's file
CONFIG_KEYS = ("reference", "task_flags", "streams", "kernels",
               "train_flops", "built", "rehearse")


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


# positions of a batch that the placement's reference walks at once
PLACE_TOKENS = 8192
# the search stops once every group's share is this near the even share
PLACE_NEAR = 0.02


def choose_held(arrivals, held):
    """Which `held` experts a chip should hold so that it gets the EVEN
    share of the work (held / experts of the assignments: what a
    deployment's chips average), whoever sends it: `arrivals` [groups,
    experts] counts the assignments each group of positions made to each
    expert. With load[g, e] = an expert's arrivals over the mean
    expert's, the set starts as the experts nearest load 1 in their
    worst group (typical experts: low max over mean), and single swaps,
    the best first, bring every group's mean load over the set to 1: a
    swap is better if the worst group is nearer, or as near and the
    groups are nearer on average (a group that names few experts can
    only be an eighth off or on). Stops within PLACE_NEAR or where no
    swap is better. Returns (the experts, ascending; every group's mean
    load over them, 1.0 = the even share). Deterministic."""
    arrivals = np.asarray(arrivals, np.float64)
    arrivals = arrivals[arrivals.sum(axis=1) > 0]
    experts = arrivals.shape[1]
    load = arrivals * experts / arrivals.sum(axis=1, keepdims=True)
    chosen = np.argsort(np.abs(load - 1).max(axis=0),
                        kind="stable")[:held].copy()

    def off(mean_load):
        away = np.abs(mean_load - 1)
        return away.max(axis=0) + away.mean(axis=0)
    while np.abs(load[:, chosen].mean(axis=1) - 1).max() > PLACE_NEAR:
        rest = np.setdiff1d(np.arange(experts), chosen)
        swapped = (load[:, chosen].sum(axis=1)[:, None, None]
                   - load[:, chosen][:, :, None]
                   + load[:, rest][:, None, :]) / held    # [g, out, in]
        cost = off(swapped)
        out, into = np.unravel_index(np.argmin(cost), cost.shape)
        if cost[out, into] >= off(load[:, chosen].mean(axis=1)):
            break
        chosen[out] = rest[into]
    chosen = np.sort(chosen)
    return chosen, load[:, chosen].mean(axis=1)


def place_held_experts(ctx, params, shapes):
    """Between the initialiser and the first update: which experts this
    chip holds is decided by their LOAD, as a deployment decides it, not
    by which columns of a fresh router come first. For every layer that
    holds a share of its experts (`experts_held` < `experts`) the
    router's COLUMNS are permuted so that the held columns,
    [experts_first, experts_first + held), are experts whose arrivals
    sum to the even share (`choose_held`); nothing else changes, and
    relabelling experts is a symmetry of the layer (the expert matrices
    are independent draws). The arrivals are the configuration's own
    plain reference's (`routed_layers`), on the narrowest and the widest
    batch of the corpus in chunks of PLACE_TOKENS, each chunk a group;
    layer by layer in order, ONE pass: a layer is placed on the input
    that the layers placed before it give. Without it the cells' work
    followed the seed's draw of a router that is never trained (the
    held experts' list is computed in batches of half an even share, as
    many as it is long: marian_tpu/ops/experts.py::pool_rows, PR 50).
    The placement is made on the INITIALISER: the routers' inputs train
    from the first update on, so over the window a layer's held share
    still drifts from the even share (PERF.md 6, PR 43), and the work
    follows the lists' lengths; how the program computes a list of a
    given length is the program's, not the traffic's. Returns the
    parameters."""
    ref = ctx.cell.reference
    if not hasattr(ref, "routed_layers"):
        return params
    batches = []
    for key in sorted({min(shapes), max(shapes)}):
        ids = np.asarray(shapes[key].trg.ids)
        mask = np.asarray(shapes[key].trg.mask)
        rows = max(1, PLACE_TOKENS // ids.shape[1])
        batches += [(ids[i:i + rows], mask[i:i + rows])
                    for i in range(0, len(ids), rows)]
    first = int(ctx.dims["experts_first"])
    walk = ref.routed_layers(params, ctx.dims, batches)
    try:
        name, arrivals = next(walk)
        while True:
            router = params[name]
            held = params[name[:-len("router")] + "Wg"].shape[0]
            experts = router.shape[1]
            if held < experts:
                arrivals = np.asarray(arrivals)
                was = arrivals[:, first:first + held].sum() \
                    / arrivals.sum() * experts / held
                chosen, shares = choose_held(arrivals, held)
                rest = np.setdiff1d(np.arange(experts), chosen)
                router = router[:, np.concatenate(
                    [rest[:first], chosen, rest[first:]])]
                params = dict(params, **{name: router})
                ctx.note(f"placed {name}: the held experts' share of the "
                         f"arrivals {was:.3f} -> {shares.mean():.3f} even "
                         f"shares (groups {shares.min():.3f} to "
                         f"{shares.max():.3f}), "
                         f"columns {chosen.tolist()}")
            name, arrivals = walk.send(router)
    except StopIteration:
        return params


def kernels_in_step(cell, dumped):
    """Which of the configuration's kernel FAMILIES the compiled step
    holds: `dumped` is every kernel name in the step programs JAX handed
    the compiler (jaxside.kernels_dumped), and a kernel belongs to the
    longest family its name holds. Returns (a note, problems).

    A kernel's presence describes the path and proves nothing about the
    result, so an absent family is REPORTED, not demanded (PR 51): which
    kernels a step runs is the program's to change. One rule stays, the
    one by which a claim is judged: a family that a roofline metric of
    the cell totals may leave the step only where the cell also reports
    the whole step's share of the chip's peak, a per-layer metric with
    `mfu` as a part of its name that moves the same end-to-end metric;
    its roofline then falls silent (the reader returns nothing) and the
    step's share still bounds the claim. A cell that reports no such
    share would lose its only reading of that work without a trace."""
    families = tuple(cell.config["kernels"])
    held = {}
    for k in sorted(dumped):
        held.setdefault(trace_reduce.kernel_of(k, families), []).append(k)
    absent = [f for f in families if f not in held]
    step_shares = {m["moves"] for m in cell.per_layer
                   if "mfu" in re.split(r"[._-]", m["name"])}
    unbounded = sorted({
        f for m in cell.per_layer if m["moves"] not in step_shares
        for f in manifest.load_layer_metric(m["name"], cell.root)["args"]
        .get("kernels", ()) if f in absent})

    def label(f):
        return f if f in families else \
            f"{f or 'no family'} (not the configuration's)"
    note = "kernels in the step programs: " + ("; ".join(
        f"{label(f)}: {', '.join(ks)}" for f, ks in held.items()) or "none")
    if absent:
        note += (f"; ABSENT of the configuration's families: "
                 f"{', '.join(absent)} (reported, not demanded: a roofline "
                 f"of an absent family reads nothing)")
    problems = [f"kernels missing from the compiled step: {unbounded} "
                f"(a roofline totals them, and the cell reports no `mfu` "
                f"share of the whole step beside it)"] if unbounded else []
    return note, problems


def build_program(ctx, work, chips):
    """Data and options from the seed, and the program's model under them,
    as training/train.py builds them: (options, vocabularies, corpus,
    model). The corpus' files go to `work`."""
    from marian_tpu.common.config_parser import parse_options
    from marian_tpu.data import Corpus, create_vocab
    from marian_tpu.models.encoder_decoder import create_model
    traffic, dims, config = ctx.cell.traffic, ctx.dims, ctx.cell.config
    n_lines = int(traffic["lines_per_second"] * ctx.seconds) \
        if not ctx.rehearse else 600
    words = traffic["mini_batch_words_per_chip"] * chips \
        if not ctx.rehearse else 256
    lines, _ = corpus.make_lines(traffic["lengths"], n_lines,
                                 dims["vocab"], ctx.seed)
    vocab_path = os.path.join(work, "vocab.json")
    # 2 streams: source and target files holding the same lines, a
    # vocabulary each; 1: one file, one vocabulary, lines as documents
    sets = [os.path.join(work, f"c.{i}")
            for i in range(int(config["streams"]))]
    corpus.write_vocab(vocab_path, dims["vocab"])
    corpus.write_streams(lines, sets)
    argv = (list(config["task_flags"]) + ctx.tiny_flags
            + list(traffic["trainer_flags"])
            + ["--train-sets"] + sets
            + ["--vocabs"] + [vocab_path] * len(sets)
            + ["--model", os.path.join(work, "model.npz"),
               "--mini-batch-words", str(words),
               "--disp-freq", f"{int(traffic['sync_every'])}u",
               "--seed", str(ctx.program_seed), "--quiet",
               "--devices"] + [str(i) for i in range(chips)])
    opts = parse_options(argv, mode="training")
    vocabs = [create_vocab(vocab_path, opts, i, [p])
              for i, p in enumerate(sets)]
    data = Corpus(sets, vocabs, opts)
    model = create_model(opts, vocabs[0], vocabs[-1])
    ctx.check_built(dict(vars(model.cfg), vocab=len(vocabs[-1])))
    return opts, vocabs, data, model


def corpus_shapes(data, opts):
    """Every batch shape this corpus makes, with the first batch of each;
    the corpus is left where it was."""
    from marian_tpu.data import BatchGenerator
    snapshot = data.state.as_dict()
    shapes = {}
    for b in BatchGenerator(data, opts, prefetch=False):
        shapes.setdefault(b.shape_key(), b)
    data.restore(snapshot)
    return shapes


def run(ctx):
    jaxside.lift_cache_cap()
    ir_dir = jaxside.arm_ir_dump()
    import jax
    devs = jaxside.require_devices(ctx.cell.chips, ctx.rehearse)
    jaxside.enable_cache()
    compiles = jaxside.CompileLog()

    from marian_tpu.common import prng
    from marian_tpu.data import BatchGenerator
    from marian_tpu.models.encoder_decoder import batch_to_arrays
    from marian_tpu.training.graph_group import GraphGroup
    from marian_tpu.training.scheduler import Scheduler
    from marian_tpu.training.training_state import TrainingState

    traffic, dims, config = ctx.cell.traffic, ctx.dims, ctx.cell.config
    # kernel families: the traced run totals each one's device time
    families = tuple(config["kernels"])
    step_flops = manifest.load_cost(config["train_flops"], ctx.cell.root)
    chips = len(devs)
    sync_every = int(traffic["sync_every"])
    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        opts, vocabs, data, model = build_program(ctx, work, chips)
        gg = GraphGroup(model, opts)
        key = prng.root_key(ctx.program_seed)
        shapes = corpus_shapes(data, opts)
        # the program's own initialiser, on the device, in one jitted call;
        # the held experts placed by load
        init_key = prng.stream(key, prng.STREAM_INIT)
        gg.initialize(init_key, place_held_experts(
            ctx, jax.jit(model.init)(init_key), shapes))
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

        # -- the reference check; warm-up of every shape ------------------------
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
        # The window opens when the epoch's first batch is READY: before it
        # the generator reads, shuffles and sorts its first maxi-batch on
        # the host (~1 s of big.train's ~15; the later ones are made behind
        # the updates by the prefetch thread), and how long that takes on a
        # host whose cores are shared swung the rate by 1.4 % from run to
        # run (PERF.md 6, PR 43). It happens once an epoch, which for a
        # user lasts hours: it is set-up here.
        stall = time.perf_counter()
        bg = iter(BatchGenerator(data, opts))
        bg = itertools.chain([next(bg, None)], bg)
        stall = time.perf_counter() - stall
        wall0, t0 = time.time(), time.perf_counter()
        ctx.window_opens()
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
        dumped = jaxside.kernels_dumped(ir_dir)
        n_compiles = compiles.count_between(wall0, wall1)
        real = sum(b.words for b in in_window)
        padded = sum(b.trg.batch_size * b.trg.batch_width
                     for b in in_window)
        flops = sum(step_flops(dims, b.src_words, b.words,
                               b.src.batch_width, b.trg.batch_width)
                    for b in in_window)
        if not finite:
            problems.append("a cost is not finite")
        if skipped:
            problems.append(f"{skipped} updates skipped")
        if not falling:
            problems.append(f"cost did not fall: first quarter "
                            f"{np.mean(costs[:q]):.4f}, last "
                            f"{np.mean(costs[-q:]):.4f}")
        if devs[0].platform == "tpu":   # elsewhere kernels are interpreted
            note, missing = kernels_in_step(ctx.cell, dumped)
            ctx.note(note)
            problems += missing
        if n_compiles:
            problems.append(f"{n_compiles} compiles inside the window")
        ctx.note(f"{len(in_window)} updates in {window_s:.3f}s (waiting for "
                 f"a batch {spans['data_wait_s']:.3f}s, dispatching "
                 f"{spans['dispatch_s']:.3f}s; the epoch's first batch took "
                 f"{stall:.3f}s before it); cost "
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
            # ONE rate, under every name the cell reports it by
            # (BENCHMARK.json keeps it twice where a tighter bound holds:
            # `train_tok_s_chip.<class of cells>`)
            "end_to_end": {m["name"]: real / window_s / chips
                           for m in ctx.cell.end_to_end
                           if m["name"].split(".")[0] == "train_tok_s_chip"},
            "device": device,
            "obs": {"values": values,
                    "trace": tw.reduce(families),
                    "traced_work": traced_work},
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
