"""Per-shape GEMM micro-benchmark: forward vs VJP-transposed orientations.

The r4/r5 traces put the step's backward dots at ~2.5x forward time
against a 2:1 FLOP ratio, with the residual unexplained after the
f32-cotangent fix. This times each HOT dot of the bench transformer-big
step in isolation — the forward orientation and BOTH backward
orientations exactly as the VJP emits them — at the bench's dominant
batch shape, and prints achieved TFLOP/s vs chip peak per shape. If a
specific orientation runs slow, the fix is mechanical (emit the
transposed product and relayout after, or flip contracting dims).

  fwd: y[M,N]  = dot(x[M,K], w[K,N], contract K)
  dx : dx[M,K] = dot(g[M,N], w[K,N], contract N)   (both contract dim 1)
  dW : dW[K,N] = dot(x[M,K], g[M,N], contract M)   (both contract dim 0)

Usage: python scripts/gemm_microbench.py            # TPU
       JAX_PLATFORMS=cpu python scripts/gemm_microbench.py tiny
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(thunk):
    t0 = time.perf_counter()
    thunk()
    return time.perf_counter() - t0


def main():
    tiny = len(sys.argv) > 1 and sys.argv[1] == "tiny"
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" or tiny:
        from marian_tpu.common.hermetic import force_cpu_devices
        force_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    from marian_tpu.common.flops import peak_bf16_flops
    from marian_tpu.common.profiling import enable_compilation_cache
    enable_compilation_cache()

    # no peak on CPU (the tiny smoke): the "% of peak" column reads 0
    peak = peak_bf16_flops(jax.devices()[0].device_kind) or 0

    # bench transformer-big at the dominant full-bucket row count
    # ((48,48,176) -> 8448 rows)
    rows = 64 if tiny else 8448
    d, f, v = (64, 128, 512) if tiny else (1024, 4096, 32000)
    bases = [("logits", rows, d, v), ("ffn_W1", rows, d, f),
             ("ffn_W2", rows, f, d), ("attn_qkv(g3)", rows, d, 3 * d),
             ("attn_out", rows, d, d)]

    key = jax.random.key(0)
    reps = 3 if tiny else 1000

    def make_fn(dims, out_dtype, n, batch=((), ())):
        # the REP LOOP runs IN-JIT (one dispatch): host-side per-dispatch
        # latency swamps sub-ms kernels if each rep is its own dispatch.
        # The iteration-indexed
        # perturbation of `a` (one cheap elementwise pass) stops XLA
        # hoisting the loop-invariant dot out of the fori_loop.
        def loop(a, b):
            # every iteration's FULL output feeds the next iteration's
            # input through a scalar mean: no element is dead (fetching
            # out[0,0] alone lets XLA DCE the GEMM down to a dot
            # product — measured, embarrassingly), no hoisting (carry-
            # dependent input), and the mean fuses into the dot epilogue
            def body(i, a_c):
                out = jax.lax.dot_general(
                    a_c, b, (dims, batch),
                    preferred_element_type=out_dtype)
                s = (out.astype(jnp.float32).mean() * 1e-9).astype(
                    a_c.dtype)
                return a_c + s
            return jax.lax.fori_loop(0, n, body, a).ravel()[0]
        return jax.jit(loop)

    fwd = make_fn(((1,), (0,)), jnp.bfloat16, reps)
    dx_fn = make_fn(((1,), (1,)), jnp.bfloat16, reps)
    dw_fn = make_fn(((0,), (0,)), jnp.float32, reps)

    # a scalar-value fetch is the sync; with reps=1000 the loop body
    # dominates, and the null-call overhead (min of 3) is subtracted out
    null = jax.jit(lambda: jnp.zeros((), jnp.float32))
    float(null())
    overhead = min(_timed(lambda: float(null())) for _ in range(3))

    def timeit(fn, a, b):
        float(fn(a, b))             # warm
        best = min(_timed(lambda: float(fn(a, b))) for _ in range(3))
        return max(best - overhead, 1e-9) / reps

    # attention score/apply einsums: batched per-head dots with a dh=64
    # contraction — the suspected <=50%-MXU-tiling shapes (r4 trace:
    # ~14ms/step). b=176 rows/bucket at 16 heads, T=48.
    bh, t, dh = (4, 8, 16) if tiny else (176 * 16, 48, 64)
    scores = make_fn(((2,), (2,)), jnp.float32, reps,
                     batch=((0,), (0,)))    # [bh,T,dh]x[bh,T,dh]->[bh,T,T]
    apply_ = make_fn(((2,), (1,)), jnp.float32, reps,
                     batch=((0,), (0,)))    # [bh,T,T]x[bh,T,dh]->[bh,T,dh]

    def bench_batched(label, fn, ashape, bshape, fl):
        a = jax.random.normal(key, ashape, jnp.bfloat16)
        b = jax.random.normal(key, bshape, jnp.bfloat16)
        dt = timeit(fn, a, b)
        tf = fl / dt / 1e12
        pk = f"{100 * fl / dt / peak:5.1f}" if peak else "  n/a"
        print(f"{label:16s} {dt * 1e3:8.3f} {tf:7.2f} {pk}", flush=True)

    print(f"{'shape':16s} {'ms':>8s} {'TF/s':>7s} {'%peak':>6s}")
    k1, k2 = jax.random.split(key)
    for label, m, kk, n in bases:
        x = jax.random.normal(k1, (m, kk), jnp.bfloat16)
        w = jax.random.normal(k2, (kk, n), jnp.bfloat16)
        g = jax.random.normal(k2, (m, n), jnp.bfloat16)
        fl = 2.0 * m * kk * n
        for tag, fn, a, b in (("fwd", fwd, x, w),
                              ("dx", dx_fn, g, w),
                              ("dW", dw_fn, x, g)):
            dt = timeit(fn, a, b)
            tf = fl / dt / 1e12
            pk = f"{100 * fl / dt / peak:5.1f}" if peak else "  n/a"
            print(f"{label + '.' + tag:16s} {dt * 1e3:8.3f} {tf:7.2f} {pk}",
                  flush=True)
    bench_batched("attn_scores", scores, (bh, t, dh), (bh, t, dh),
                  2.0 * bh * t * t * dh)
    bench_batched("attn_apply", apply_, (bh, t, t), (bh, t, dh),
                  2.0 * bh * t * t * dh)


if __name__ == "__main__":
    main()
