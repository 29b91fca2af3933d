"""KDA's chunk preparation alone on the chip: the Pallas pair
(ops/pallas/kda_prep.py) against XLA's ops/kda.py :: chunk_terms, forward
and forward + backward under jax.grad, at the cell's widest and
narrowest head-group shapes; first that the two agree there.

    chiprun -- python3 scripts/kda_prep_bench.py [--heads 1 2 4]

Prints one JSON line per measurement; refuses to time anything but a TPU.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from marian_tpu.ops import kda
from marian_tpu.ops.pallas.kda_prep import kda_chunk_terms

SHAPES = ((16, 4, 1024, 128), (2, 4, 8192, 128))
SCALE = 128 ** -0.5


def inputs(seed, shape, strong=False):
    b, h, t, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], shape))
    k = kda.l2_normalize(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -jnp.exp(jax.random.normal(ks[3], shape) * (2.0 if strong else 1.0)
                 + (2.0 if strong else -2.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, g, beta


def loss_of(fn):
    return lambda *a: sum(jnp.sum(x * x) for x in fn(*a, SCALE))


def agree(shape, strong):
    args = inputs(3, shape, strong)
    out = {}
    pairs = zip("qg wk wv kd gc p".split(),
                jax.jit(lambda *a: kda_chunk_terms(*a, SCALE))(*args),
                jax.jit(lambda *a: kda.chunk_terms(*a, SCALE))(*args))
    for name, got, want in pairs:
        out[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    grads = lambda fn: jax.jit(jax.grad(                     # noqa: E731
        loss_of(fn), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, got, want in zip("dq dk dv dg db".split(),
                               grads(kda_chunk_terms),
                               grads(kda.chunk_terms)):
        out[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    return out


def seconds(fn, args, repeats=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, nargs="+", default=[4],
                    help="heads a grid step")
    opts = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("no TPU: a time from another device is not a measurement")
    say = lambda **kw: print(json.dumps(                      # noqa: E731
        {"device": device.device_kind, **kw}), flush=True)
    for strong in (False, True):
        say(what="max error over the term's largest value, pair vs XLA",
            shape=[2, 4, 256, 128], strong=strong,
            **agree((2, 4, 256, 128), strong))
    for shape in SHAPES:
        args = inputs(7, shape)
        chunks = shape[0] * shape[1] * shape[2] // kda.CHUNK
        forms = {"xla": kda.chunk_terms}
        for h in opts.heads:
            forms[f"pair-heads{h}"] = (
                lambda *a, h=h: kda_chunk_terms(*a, heads=h))
        for name, fn in forms.items():
            fwd = seconds(jax.jit(lambda *a, fn=fn: fn(*a, SCALE)), args)
            both = seconds(jax.jit(jax.grad(
                loss_of(fn), argnums=(0, 1, 2, 3, 4))), args)
            say(shape=list(shape), form=name, fwd_ms=fwd * 1e3,
                fwd_bwd_ms=both * 1e3, fwd_us_chunk_head=fwd * 1e6 / chunks,
                fwd_bwd_us_chunk_head=both * 1e6 / chunks)


if __name__ == "__main__":
    main()
