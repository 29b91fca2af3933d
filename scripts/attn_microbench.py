"""Attention-kernel microbench: packed vs unpacked MXU share at NMT shapes.

The r5 GEMM truth table (scripts/gemm_microbench.py, docs/PERFORMANCE.md)
measured the dense score/apply einsums at 21.7%/30.6% of peak — the
dh=64 x T=48-64 tile-geometry cap the packed kernel
(ops/pallas/packed_attention.py) exists to fix. This script prints the
packed-vs-unpacked table for that regime: per shape, forward (and
optionally fwd+bwd) wall time for the dense einsum path and the packed
kernel, achieved matmul FLOP/s, and the share of the chip's bf16 peak.

Same in-jit timing discipline as gemm_microbench.py: the candidate runs
inside a fori_loop with full-output liveness so XLA cannot DCE it and
the host sync round-trip amortizes over ITERS real invocations.

The last columns are the packed kernel alone: device time of
`packed_attention_fwd` / `_bwd` in a profiler trace of a few calls of
jit(grad), the tiles a call takes (64 // T rows share one, PR 31; "of"
the tiles at one row each) and the us a tile costs — the law the
kernel's time follows (PERF.md section 6, PRs 26 and 31). The default
shapes are big.train's six batches (4096 words at widths 8 to 64), one
shape past 64 positions and one at dh 32.

Run it on the chip:

    python scripts/attn_microbench.py            # fwd table
    MARIAN_ATTNBENCH_BWD=1 python scripts/attn_microbench.py
    MARIAN_ATTNBENCH_SHAPES=2,16,48,64 python scripts/attn_microbench.py
                                                 # one b,h,t,dh override
    MARIAN_ATTNBENCH_SHAPES='512,16,8,64;64,16,64,64' ...
                                                 # several, ';' between

On CPU this degrades to a correctness-checked wall-time table (the MXU
share column reads n/a): interpret-mode Pallas is not a performance
path, so CPU numbers say nothing about the kernel — run on silicon.
"""

import glob
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _peak_flops(kind: str):
    from marian_tpu.common.flops import peak_bf16_flops
    return peak_bf16_flops(kind)


def _timed(loop_fn, q, k, v, iters):
    """Time ONE jitted dispatch of `loop_fn` (which runs the candidate
    `iters` times inside a fori_loop) and return seconds per iteration.
    Sync is a scalar VALUE fetch — the only hard sync this backend
    honors."""
    float(loop_fn(q, k, v))                  # compile + warm
    t0 = time.perf_counter()
    float(loop_fn(q, k, v))
    return (time.perf_counter() - t0) / iters


def _make_loop(fn, iters, grad):
    """In-jit timing discipline (same as gemm_microbench.py): `iters`
    invocations inside ONE dispatch, the candidate's FULL output fed
    back through a scalar mean into the next iteration's input — no
    dead elements for DCE, no loop-invariant hoisting, and the per-call
    dispatch floor is paid once instead of per sample."""
    import jax
    import jax.numpy as jnp

    def loop(q, k, v):
        def body(i, q_c):
            out = fn(q_c, k, v)
            if grad:
                s = sum((g.astype(jnp.float32).mean() for g in out),
                        jnp.float32(0.0))
            else:
                s = out.astype(jnp.float32).mean()
            return q_c + (s * 1e-9).astype(q_c.dtype)
        return jax.lax.fori_loop(0, iters, body, q).ravel()[0] \
            .astype(jnp.float32)
    return jax.jit(loop)


def _kernel_us(grad_fn, q, k, v, calls=5):
    """Device us a call of packed_attention_fwd and _bwd: their ops on
    the chip in a profiler trace of `calls` calls of the jitted gradient
    (which runs one of each), read as `profile_summary` reads a trace."""
    import jax

    from marian_tpu.cli.profile_summary import device_ops, read_xspace

    fn = jax.jit(grad_fn)
    jax.block_until_ready(fn(q, k, v))       # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                jax.block_until_ready(fn(q, k, v))
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        ops = [op for device in device_ops(read_xspace(path))
               for op in device]
    return tuple(sum(end - start for start, end, (name, _) in ops
                     if kernel in name) / 1e6 / calls
                 for kernel in ("packed_attention_fwd",
                                "packed_attention_bwd"))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from marian_tpu.ops.attention import dense_attention
    from marian_tpu.ops.pallas.packed_attention import (pack_group,
                                                        packed_attention,
                                                        rows_a_tile)

    bwd = bool(os.environ.get("MARIAN_ATTNBENCH_BWD"))
    shapes = [(512, 16, 8, 64), (256, 16, 16, 64), (168, 16, 24, 64),
              (128, 16, 32, 64), (80, 16, 48, 64), (64, 16, 64, 64),
              (32, 16, 128, 64), (128, 8, 32, 32)]
    override = os.environ.get("MARIAN_ATTNBENCH_SHAPES")
    if override:
        try:
            parsed = [tuple(int(x) for x in one.split(","))
                      for one in override.split(";") if one.strip()]
            if not parsed or any(len(one) != 4 for one in parsed):
                raise ValueError(override)
            shapes = parsed
        except ValueError:
            print(f"attn_microbench: bad MARIAN_ATTNBENCH_SHAPES="
                  f"{override!r} (want b,h,t,dh[;b,h,t,dh...]) — using "
                  f"the default set", file=sys.stderr, flush=True)

    kind = jax.devices()[0].device_kind
    peak = _peak_flops(kind)
    on_tpu = jax.default_backend() == "tpu"
    mode = "fwd+bwd" if bwd else "fwd"
    print(f"# attention microbench ({mode}) on {kind}"
          f"{'' if on_tpu else '  [CPU: interpret mode, MXU share n/a]'}")
    print(f"{'shape (b,h,t,dh)':>20} {'g':>2} {'dense ms':>9} "
          f"{'packed ms':>10} {'speedup':>8} {'dense MXU%':>11} "
          f"{'packed MXU%':>12} {'kernel fwd/bwd us':>18} "
          f"{'tiles (of)':>12} {'us a tile':>12}")

    rng = np.random.RandomState(0)
    for (b, h, t, dh) in shapes:
        q = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, t, dh), jnp.bfloat16)
        mask = jnp.ones((b, t), jnp.float32)
        g = pack_group(h, dh)
        # useful FLOPs: fwd = 2 same-size matmuls (score + apply) at
        # 2*b*h*t*t*dh each; bwd adds the 4 backward orientations
        # (dp, dq, dk, dv) of the same size → fwd+bwd = 6 dots = 3x fwd.
        # The packed bwd also RECOMPUTES the score dot (flash-style, no
        # saved stats), which this count deliberately excludes — the
        # column reads achieved USEFUL-FLOP rate, recompute is overhead.
        flops = 4.0 * b * h * t * t * dh * (1.0 if not bwd else 3.0)
        iters = 20 if not on_tpu else 200

        def loss_dense(q, k, v):
            return (dense_attention(
                q, k, v, mask=mask[:, None, None, :]) ** 2).sum()

        def loss_packed(q, k, v):
            return (packed_attention(q, k, v, kv_mask=mask) ** 2).sum()

        if bwd:
            dense_fn = jax.grad(loss_dense, argnums=(0, 1, 2))
            packed_fn = jax.grad(loss_packed, argnums=(0, 1, 2))
        else:
            def dense_fn(q, k, v):
                return dense_attention(q, k, v,
                                       mask=mask[:, None, None, :])

            def packed_fn(q, k, v):
                return packed_attention(q, k, v, kv_mask=mask)

        td = _timed(_make_loop(dense_fn, iters, bwd), q, k, v, iters)
        tp = _timed(_make_loop(packed_fn, iters, bwd), q, k, v, iters)

        def share(dt):
            if not (peak and on_tpu):
                return "n/a"
            return f"{100.0 * flops / dt / peak:.1f}"

        tiles = b // rows_a_tile(b, t, t) * (h // g)
        kernel = a_tile = "n/a"
        if on_tpu:
            fwd_us, bwd_us = _kernel_us(
                jax.grad(loss_packed, argnums=(0, 1, 2)), q, k, v)
            kernel = f"{fwd_us:.0f}/{bwd_us:.0f}"
            a_tile = f"{fwd_us / tiles:.3f}/{bwd_us / tiles:.3f}"
        print(f"{str((b, h, t, dh)):>20} {g:>2} {td * 1e3:>9.3f} "
              f"{tp * 1e3:>10.3f} {td / tp:>8.2f} {share(td):>11} "
              f"{share(tp):>12} {kernel:>18} "
              f"{f'{tiles} ({b * (h // g)})':>12} {a_tile:>12}")


if __name__ == "__main__":
    main()
