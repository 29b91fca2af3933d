"""The layer plan's rotation alone on the chip: `models/layer_plan.py ::
_rotate` (the pair's other channel from a signed-permutation matmul fused
with the turn) against the roll form it replaced, forward and VJP, at one
update's rows of width 4096 / 8192 of the two call sites: `gqa.rope`
(q [4,32,8192,128] + k [4,4,8192,128], half-split pairs) and `mla.rope`
(q [4,32,4096,192] turned from channel 128 on + the shared key [4,4096,64],
interleaved pairs); first that the two agree there.

    chiprun -- python3 scripts/rope_microbench.py [--out chiprun_out/rope]

Device time comes from a profiler trace of ten calls of each program
(benchmark/trace_reduce.py), the host's clock over ten more beside it; one
JSON line per measurement with the compiler's temporaries. Refuses to time
anything but a TPU.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.trace_reduce import find_xplane, reduce_trace
from marian_tpu.models.layer_plan import _rotate, rope_angles

CALLS = 10
HBM_BYTES_S = 819e9                      # benchmark/peaks.json, v5e


def roll_form(x, angles, pairing="interleaved"):
    """`_rotate` as it was before PR 42."""
    f = x.astype(jnp.float32)
    dim = x.shape[-1]
    if pairing == "half":
        first = jnp.arange(dim) < dim // 2
        other = jnp.where(first, -1.0, 1.0) * jnp.roll(f, dim // 2, axis=-1)
    else:
        even = jnp.arange(dim) % 2 == 0
        other = jnp.where(even, -jnp.roll(f, -1, axis=-1),
                          jnp.roll(f, 1, axis=-1))
    return (f * jnp.cos(angles) + other * jnp.sin(angles)).astype(x.dtype)


def gqa_site(new):
    def fn(q, k):
        angles = jnp.tile(rope_angles(4096, 128, 1e6, "half"), (2, 1))
        turn = _rotate if new else roll_form
        return turn(q, angles, "half"), turn(k, angles, "half")
    return fn, ((4, 32, 8192, 128), (4, 4, 8192, 128))


def mla_site(new):
    def fn(q, shared):
        angles = rope_angles(4096, 64, 32e6)
        if new:
            return _rotate(q, angles, start=128), _rotate(shared, angles)
        return (jnp.concatenate([q[..., :128],
                                 roll_form(q[..., 128:], angles)], axis=-1),
                roll_form(shared, angles))
    return fn, ((4, 32, 4096, 192), (4, 4096, 64))


def vjp_of(fn):
    def back(a, b, ga, gb):
        return jax.vjp(fn, a, b)[1]((ga, gb))
    return back


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
    return got["busy_s"] / CALLS * 1e3, got["device_ops"][:4], wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/rope")
    ap.add_argument("--dtype", default="bfloat16")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"a {dev.platform} times nothing the trainer pays for")
    dtype = jnp.dtype(opts.dtype)
    for site, make in (("gqa.rope", gqa_site), ("mla.rope", mla_site)):
        _, shapes = make(True)
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        args = [jax.random.normal(k, s, jnp.float32).astype(dtype)
                for k, s in zip(keys, shapes + shapes)]
        least_ms = 2 * sum(a.nbytes for a in args[:2]) / HBM_BYTES_S * 1e3
        for which, wrap, n in (("forward", lambda f: f, 2),
                               ("vjp", vjp_of, 4)):
            outs = {}
            for form in ("roll", "matmul"):
                fn = jax.jit(wrap(make(form == "matmul")[0]))
                compiled = fn.lower(*args[:n]).compile()
                ms, ops, wall = device_ms(
                    fn, args[:n],
                    os.path.join(opts.out, f"{site}.{which}.{form}"))
                outs[form] = fn(*args[:n])
                print(json.dumps({
                    "site": site, "pass": which, "form": form,
                    "dtype": opts.dtype, "device_ms": round(ms, 4),
                    "host_clock_ms": round(wall, 4),
                    "read_write_once_ms": round(least_ms, 4),
                    "temp_mb": round(compiled.memory_analysis()
                                     .temp_size_in_bytes / 1e6, 1),
                    "device_ops_s": ops,
                    "device": dev.device_kind}), flush=True)
            off = [float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max())
                   for a, b in zip(outs["roll"], outs["matmul"])]
            print(json.dumps({"site": site, "pass": which,
                              "max_abs_difference": off}), flush=True)


if __name__ == "__main__":
    main()
