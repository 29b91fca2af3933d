"""The expert layer alone on the chip: `jit(grad)` of `ops/experts.py ::
held_experts` at one update's shapes of the four plan cells, lists of
0.6 / 0.9 / 1.2 / 1.6 even shares drawn by a seeded `idx`, at several
batch sizes (`rows`) of its loop beside the one `pool_rows` gives; and one
grouped matmul at a batch's rows against the same matmul at six batches'.

    chiprun -- python3 scripts/experts_microbench.py [--cells sdar kimi]
        [--parent .parent] [--out chiprun_out/experts]

`--parent DIR` times the `held_experts` of another checkout's
`marian_tpu/ops/experts.py` beside it (one that still takes `pool=`: the
1.5-share pool that always ran, PR 50's table). A program serves every
list (the loop's trip count is traced), so a cell costs one compile for
each batch size. Device time comes from a profiler trace of ten calls
(benchmark/trace_reduce.py), the host's clock over ten more beside it; one
JSON line per measurement with the compiler's temporaries. Refuses to time
anything but a TPU.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.trace_reduce import find_xplane, reduce_trace
from marian_tpu.ops import experts as X

CALLS = 10
# (tokens an update, model width, expert width, held, experts, top k)
CELLS = {"sdar": (32768, 2048, 768, 16, 128, 8),
         "joyai": (16384, 2048, 768, 16, 256, 8),
         "kimi": (16384, 2304, 1024, 8, 256, 8),
         "trinity": (16384, 2048, 1024, 8, 128, 8)}
LISTS = (0.6, 0.9, 1.2, 1.6)


def draw_idx(rng, tokens, top_k, held, experts, shares):
    """idx [tokens, top_k]: every token's picks distinct, each naming a
    held expert (0 .. held - 1) with probability shares * held / experts."""
    mine = np.minimum(rng.binomial(top_k, shares * held / experts, tokens),
                      held)
    here = np.argsort(rng.random((tokens, held)), axis=1)[:, :top_k]
    there = held + np.argsort(rng.random((tokens, experts - held)),
                              axis=1)[:, :top_k]
    slot = np.arange(top_k)[None, :]
    pad = np.zeros((tokens, max(top_k - held, 0)), here.dtype)
    here = np.concatenate([here, pad], axis=1)
    return np.where(slot < mine[:, None], here, there).astype(np.int32)


def device_ms(fn, args, trace_dir):
    """(device ms a call by the trace, its largest ops, host-clock ms a
    call): ten calls under the profiler, then ten without."""
    jax.block_until_ready(fn(*args))
    with jax.profiler.trace(trace_dir):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - start) / CALLS * 1e3
    got = reduce_trace(find_xplane(trace_dir)) or {
        "busy_s": float("nan"), "device_ops": []}
    shutil.rmtree(trace_dir, ignore_errors=True)     # ~1 MB each
    return got["busy_s"] / CALLS * 1e3, got["device_ops"][:5], wall


def layer_grad(held_experts, **how):
    """The layer's forward and backward as a cell's step runs them: the
    gradient of the tokens and the three stacks, the router's weights
    not trained through a share."""
    def loss(x, wg, wu, wd, mask, idx, w, cot):
        y, counters = held_experts(x, mask, idx, w, wg, wu, wd, 0, **how)
        return jnp.sum(y.astype(jnp.float32) * cot), counters
    # the loss's value too, or the forward's loop is dead code
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                      has_aux=True))


def parent_module(root):
    spec = importlib.util.spec_from_file_location(
        "parent_experts", os.path.join(root, "marian_tpu", "ops",
                                       "experts.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def one_matmul(out, report):
    """`jax.lax.ragged_dot` of m rows [m, 2048] against 16 even groups of
    [2048, 768]: the rate a row at one batch's rows and at six batches'."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (16, 2048, 768), jnp.bfloat16)
    for m in (2048, 4096, 8192, 49152):
        x = jax.random.normal(key, (m, 2048), jnp.bfloat16)
        groups = jnp.full((16,), m // 16, jnp.int32)
        fn = jax.jit(lambda x, w, g: jax.lax.ragged_dot(
            x, w, g, preferred_element_type=jnp.float32))
        ms, _, wall = device_ms(fn, (x, w, groups),
                                os.path.join(out, f"ragged_dot.{m}"))
        report({"what": "ragged_dot", "rows": m, "groups": 16,
                "device_ms": round(ms, 4), "host_clock_ms": round(wall, 4),
                "us_a_1k_rows": round(ms / m * 1e6, 2),
                "tflop_s": round(2 * m * 2048 * 768 / ms / 1e9, 1)})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/experts")
    ap.add_argument("--cells", nargs="+", default=list(CELLS),
                    choices=list(CELLS))
    ap.add_argument("--rows", nargs="+", type=int, default=[],
                    help="batch sizes beside pool_rows' own and its half "
                    "and double")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--no-matmul", action="store_true")
    ap.add_argument("--seed", type=int, default=1)
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"a {dev.platform} times nothing the trainer pays for")
    os.makedirs(opts.out, exist_ok=True)
    with open(os.path.join(opts.out, "table.jsonl"), "a") as table:
        measure(opts, dev, table)


def measure(opts, dev, table):
    def report(line):
        table.write(json.dumps(dict(line, device=dev.device_kind)) + "\n")
        table.flush()
        line.pop("counters", None)
        line["device_ops_s"] = [[op, round(s, 4)] for op, s
                                in line.pop("device_ops_s", [])[:3]]
        print(json.dumps(line), flush=True)

    if not opts.no_matmul:
        one_matmul(opts.out, report)
    parent = parent_module(opts.parent) if opts.parent else None
    for cell in opts.cells:
        tokens, d, f, held, experts, top_k = CELLS[cell]
        share = tokens * top_k * held // experts
        own = X.pool_rows(tokens, top_k, held, experts)
        keys = jax.random.split(jax.random.PRNGKey(opts.seed), 6)
        bf16 = jnp.bfloat16
        x = jax.random.normal(keys[0], (tokens, d), bf16)
        wg, wu = (jax.random.normal(k, (held, d, f), bf16) * 0.02
                  for k in keys[1:3])
        wd = jax.random.normal(keys[3], (held, f, d), bf16) * 0.02
        w = jax.nn.softmax(jax.random.normal(keys[4], (tokens, top_k)), -1)
        cot = jax.random.normal(keys[5], (tokens, d), jnp.float32)
        mask = jnp.ones((tokens,), jnp.float32)
        rng = np.random.default_rng(opts.seed)
        lists = {s: jnp.asarray(draw_idx(rng, tokens, top_k, held, experts,
                                         s)) for s in LISTS}
        forms = [("parent", layer_grad(
            parent.held_experts, pool=parent.pool_rows(
                tokens, top_k, held, experts)))] if parent else []
        forms += [(f"rows={r}", layer_grad(X.held_experts, rows=r))
                  for r in sorted({own // 2, own, 2 * own, *opts.rows})]
        base = {}
        for form, fn in forms:
            args = (x, wg, wu, wd, mask)
            compiled = fn.lower(*args, lists[LISTS[0]], w, cot).compile()
            temp = compiled.memory_analysis().temp_size_in_bytes
            for shares, idx in lists.items():
                ms, ops, wall = device_ms(
                    fn, (*args, idx, w, cot),
                    os.path.join(opts.out, f"{cell}.{form}.{shares}"))
                counters = dict(zip(
                    (parent if form == "parent" else X).COUNTERS,
                    np.asarray(fn(*args, idx, w, cot)[0][1]).tolist()))
                base.setdefault(shares, ms)
                report({
                    "cell": cell, "form": form, "own": form == f"rows={own}",
                    "list_shares": round(
                        counters["moe.assignments_held"] / share, 3),
                    "trips": counters.get("moe.pool_trips"),
                    "device_ms": round(ms, 3),
                    "over_first_form": round(ms / base[shares], 3),
                    "host_clock_ms": round(wall, 3),
                    "temp_mb": round(temp / 1e6, 1),
                    "counters": counters, "device_ops_s": ops})


if __name__ == "__main__":
    main()
