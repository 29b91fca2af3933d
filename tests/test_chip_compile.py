"""The Pallas kernels compiled for the real chip, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
described v5e (`jax.experimental.topologies`), so these cases run under
conftest's forced-CPU platform and cost no chip time. Interpret-mode
parity (test_*_attention.py, test_kv_pool.py, test_fused_ce.py) says a
kernel computes the right thing; this file says Mosaic accepts it —
block shapes against the (8, 128) tiling rule, VMEM/SMEM budgets,
primitives the TPU lowering implements — at transformer-big widths
(16 heads x dh 64, emb 1024, vocab 32000, bf16) and at the shapes the
main path produces: training length buckets 32/64, the packed cap (where
a cell falls to part of a row's heads) and the benchmark cell's six
batches at 4096 words, flash at 2048, dense beam decode at 64 sentences x beam 6, the paged
engine at its smallest and largest row bucket and at its SMEM row bound.

One case is no kernel: the layer plan's rotation, XLA's own, compiled as
its two call sites use it, for what the compiler makes of it (one fusion
a tensor, no float32 piece of the tensor in HBM).

Nothing runs: a passing compile is not a chip run (chip_smoke.py is).
The file sorts early on purpose — tier-1 runs into its wall-clock box
and sheds whatever sorts late.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs off /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from marian_tpu.models.layer_plan import _rotate, rope_angles
from marian_tpu.ops import auto_tuner
from marian_tpu.ops.experts import held_experts, pool_rows
from marian_tpu.ops.pallas import kv_pool
from marian_tpu.ops.pallas.decode_attention import decode_attention
from marian_tpu.ops.pallas.flash_attention import flash_attention
from marian_tpu.ops.pallas.fused_ce import fused_softmax_xent
from marian_tpu.ops.pallas.kda_chunk import kda_state_carry
from marian_tpu.ops.pallas.kda_prep import kda_chunk_terms
from marian_tpu.ops.pallas.packed_attention import packed_attention

H, DH, EMB, VOCAB = 16, 64, 1024, 32000     # transformer-big
DT = jnp.bfloat16
PAGE_LEN = kv_pool.DEFAULT_PAGE_LEN
MAX_LEN = 256                               # iteration.py's output cap
PAGES_ROW = MAX_LEN // PAGE_LEN
PACKED_CAP = auto_tuner.packed_attention_max_t(DH)


@pytest.fixture(scope="session")
def chip():
    """One described v5e chip's sharding (session-scoped: describing the
    topology loads libtpu once)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this machine
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip (the next run warns and
    recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


_PACKED = (packed_attention, "packed_attention_fwd", "packed_attention_bwd")
_FLASH = (flash_attention, "flash_attention_fwd", "flash_attention_dq",
          "flash_attention_dkv")


def _attn(kernel, b, tq, tk, causal, grad):
    """(callable, shapes, pallas_call names) for a self-/cross-attention
    call with a key padding mask: forward, or the summed-output gradient
    wrt q/k/v (which holds the forward and the backward kernels)."""
    fn, fwd_name, *bwd_names = kernel

    def fwd(q, k, v, m):
        return fn(q, k, v, kv_mask=m, causal=causal, interpret=False)

    def loss(q, k, v, m):
        return fwd(q, k, v, m).astype(jnp.float32).sum()

    shapes = [((b, H, tq, DH), DT), ((b, H, tk, DH), DT),
              ((b, H, tk, DH), DT), ((b, tk), jnp.float32)]
    if grad:
        return (jax.grad(loss, argnums=(0, 1, 2)), shapes,
                [fwd_name] + bwd_names)
    return fwd, shapes, [fwd_name]


def _latent_attn(b, t):
    """The layer plan's latent attention at its published head widths:
    32 heads, keys of 128 + 64 against values of 128, causal, with the
    forward and both backward kernels."""
    def loss(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m, causal=True,
                               interpret=False).astype(jnp.float32).sum()
    shapes = [((b, 32, t, 192), DT), ((b, 32, t, 192), DT),
              ((b, 32, t, 128), DT), ((b, t), jnp.float32)]
    return jax.grad(loss, argnums=(0, 1, 2)), shapes, list(_FLASH[1:])


def _block_diffusion_attn(b, t):
    """The layer plan's grouped-query attention under the block rule at
    its published head widths: 32 query heads on 4 key/value heads of
    128, the doubled row of 2t indices, blocks of 4, with the forward and
    both backward kernels."""
    from marian_tpu.ops.pallas.flash_attention import BlockDiffusion

    def loss(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m,
                               causal=BlockDiffusion(t, 4),
                               interpret=False).astype(jnp.float32).sum()
    shapes = [((b, 32, 2 * t, 128), DT), ((b, 4, 2 * t, 128), DT),
              ((b, 4, 2 * t, 128), DT), ((b, 2 * t), jnp.float32)]
    return jax.grad(loss, argnums=(0, 1, 2)), shapes, list(_FLASH[1:])


def _window_attn(b, t, window=2048):
    """The layer plan's grouped-query attention under a sliding window at
    its published head widths: 32 query heads on 4 key/value heads of
    128, the last 2048 keys, with the forward and both backward
    kernels."""
    from marian_tpu.ops.pallas.flash_attention import Window

    def loss(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m, causal=Window(window),
                               interpret=False).astype(jnp.float32).sum()
    shapes = [((b, 32, t, 128), DT), ((b, 4, t, 128), DT),
              ((b, 4, t, 128), DT), ((b, t), jnp.float32)]
    return jax.grad(loss, argnums=(0, 1, 2)), shapes, list(_FLASH[1:])


def _grouped_attn_64(b, t):
    """The layer plan's causal grouped-query attention at heads of 64:
    32 query heads on 8 key/value heads, with the forward and both
    backward kernels (`pick_blocks`' dh <= 64 branch)."""
    def loss(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m, causal=True,
                               interpret=False).astype(jnp.float32).sum()
    shapes = [((b, 32, t, 64), DT), ((b, 8, t, 64), DT),
              ((b, 8, t, 64), DT), ((b, t), jnp.float32)]
    return jax.grad(loss, argnums=(0, 1, 2)), shapes, list(_FLASH[1:])


def _kda_carry(b, heads, chunks):
    """The delta rule's state carry (chunks of 64, 128 x 128 state a
    head, float32), forward and backward."""
    def loss(*terms):
        return kda_state_carry(*terms, interpret=False).sum()
    f32 = jnp.float32
    wide = ((b, heads, chunks, 64, 128), f32)
    shapes = [wide, wide, wide, wide, ((b, heads, chunks, 1, 128), f32),
              ((b, heads, chunks, 64, 64), f32)]
    return (jax.grad(loss, argnums=tuple(range(6))), shapes,
            ["kda_chunk_fwd", "kda_chunk_bwd"])


def _kda_prep(b, heads, t):
    """The delta rule's chunk preparation (the WY transform and the
    pairwise decays of 64 positions, 128 channels a head, float32),
    forward and backward."""
    def loss(*inputs):
        # squares: a linear loss would leave the forward nothing to do
        return sum((x * x).sum() for x in kda_chunk_terms(
            *inputs, 128 ** -0.5, interpret=False))
    f32 = jnp.float32
    wide = ((b, heads, t, 128), f32)
    return (jax.grad(loss, argnums=tuple(range(5))),
            [wide, wide, wide, wide, ((b, heads, t), f32)],
            ["kda_prep_fwd", "kda_prep_bwd"])


def _held_experts(tokens, d, f, held, experts):
    """The layer plan's held experts at a cell's sizes (top 8), the loop
    of `pool_rows`' batches: its grouped matmuls must stay the chip's own
    ragged dot, not a dense matmul an expert under a mask."""
    def loss(x, w, wg, wu, wd, idx):
        y, _ = held_experts(x, jnp.ones((tokens,), jnp.float32), idx, w,
                            wg, wu, wd, 0,
                            pool_rows(tokens, 8, held, experts))
        return y.astype(jnp.float32).sum()
    shapes = [((tokens, d), DT), ((tokens, 8), jnp.float32),
              ((held, d, f), DT), ((held, d, f), DT),
              ((held, f, d), DT), ((tokens, 8), jnp.int32)]
    return (jax.grad(loss, argnums=(0, 2, 3, 4)), shapes,
            ["ragged-dot-none"])


def _decode(rows, per_row_pos):
    def f(q, kn, vn, ck, cv, pos, src):
        return decode_attention(q, kn, vn, ck, cv, pos, src_rows=src,
                                interpret=False)
    new = ((rows, H, 1, DH), DT)
    cache = ((rows, H, MAX_LEN, DH), DT)
    pos = ((rows,) if per_row_pos else (), jnp.int32)
    return (f, [new, new, new, cache, cache, pos, ((rows,), jnp.int32)],
            ["decode_attention"])


def _paged(rows):
    def f(q, kn, vn, pk, pv, table, pos):
        return kv_pool.paged_decode_attention(q, kn, vn, pk, pv, table, pos,
                                              interpret=False)
    new = ((rows, H, 1, DH), DT)
    pool = ((rows * PAGES_ROW + 1, H, PAGE_LEN, DH), DT)
    return (f, [new, new, new, pool, pool,
                ((rows, PAGES_ROW), jnp.int32), ((rows,), jnp.int32)],
            ["paged_decode_attention"])


def _xent(n, grad):
    def fwd(x, w, b, y):
        return fused_softmax_xent(x, w, b, y, label_smoothing=0.1,
                                  interpret=False)

    def loss(x, w, b, y):
        return fwd(x, w, b, y).sum()

    shapes = [((n, EMB), DT), ((VOCAB, EMB), DT), ((VOCAB,), jnp.float32),
              ((n,), jnp.int32)]
    if grad:
        return (jax.grad(loss, argnums=(0, 1, 2)), shapes,
                ["fused_ce_fwd", "fused_ce_dx", "fused_ce_dw"])
    return fwd, shapes, ["fused_ce_fwd"]


_ROWS_LO, _ROWS_HI = kv_pool.ROW_BUCKETS[0], kv_pool.ROW_BUCKETS[-1]
_ROWS_BOUND = kv_pool.paged_kernel_max_rows(PAGES_ROW)

CASES = {
    # trainer: encoder self (mask), decoder self (causal), cross (tq != tk)
    "packed-fwd-t32-mask": lambda: _attn(_PACKED, 8, 32, 32, False, False),
    "packed-grad-t32-causal": lambda: _attn(_PACKED, 8, 32, 32, True, True),
    "packed-fwd-t64-causal": lambda: _attn(_PACKED, 8, 64, 64, True, False),
    "packed-grad-t64-cross": lambda: _attn(_PACKED, 8, 64, 32, False, True),
    f"packed-fwd-t{PACKED_CAP}-mask":
        lambda: _attn(_PACKED, 2, PACKED_CAP, PACKED_CAP, False, False),
    f"packed-grad-t{PACKED_CAP}-causal":
        lambda: _attn(_PACKED, 2, PACKED_CAP, PACKED_CAP, True, True),
    # big.train's own batches (rows x width at 4096 words), at their own
    # width: a cell is a block of rows x all 16 heads, the rows a divisor
    # of the batch's; a tile takes 64 // width of them, their 8- to
    # 48-row slabs set one under the other in VMEM and stored back in
    # parts
    "packed-grad-512x8-mask": lambda: _attn(_PACKED, 512, 8, 8, False, True),
    "packed-grad-256x16-causal":
        lambda: _attn(_PACKED, 256, 16, 16, True, True),
    "packed-grad-168x24-mask":
        lambda: _attn(_PACKED, 168, 24, 24, False, True),
    "packed-grad-128x32-causal":
        lambda: _attn(_PACKED, 128, 32, 32, True, True),
    "packed-grad-80x48-mask": lambda: _attn(_PACKED, 80, 48, 48, False, True),
    "packed-grad-64x64-causal":
        lambda: _attn(_PACKED, 64, 64, 64, True, True),
    # cross attention inside one tile, Tq != Tk: two rows of 24 queries
    # against their 16 keys each; four of 16 against 8
    "packed-grad-168x24x16-cross":
        lambda: _attn(_PACKED, 168, 24, 16, False, True),
    "packed-grad-256x16x8-cross":
        lambda: _attn(_PACKED, 256, 16, 8, False, True),
    # cross attention past one pad (Tq 72 -> 128, Tk 40 -> 64), 45 rows:
    # no multiple of 8, cells of 5 or 3
    "packed-grad-45x72x40-cross":
        lambda: _attn(_PACKED, 45, 72, 40, False, True),
    # the encoder at decode time: fewer rows than a cell holds
    "packed-fwd-3x20-mask": lambda: _attn(_PACKED, 3, 20, 20, False, False),
    "flash-fwd-t2048": lambda: _attn(_FLASH, 1, 2048, 2048, True, False),
    "flash-grad-t2048": lambda: _attn(_FLASH, 1, 2048, 2048, True, True),
    # the layer plan's cell (kimi-linear.train-docs8k): 16384 tokens as 16
    # rows of 1024 and as 2 of 8192; KDA's heads come 4 at a time
    "flash-grad-192x128-16x1024": lambda: _latent_attn(16, 1024),
    "flash-grad-192x128-2x8192": lambda: _latent_attn(2, 8192),
    # block-diffusion training (sdar.train-docs8k): the same rows, doubled
    "flash-grad-block-diffusion-16x1024":
        lambda: _block_diffusion_attn(16, 1024),
    "flash-grad-block-diffusion-2x8192":
        lambda: _block_diffusion_attn(2, 8192),
    # window layers over long documents (trinity-mini.train-docs16k): the
    # narrowest and the widest batch of 16384 tokens
    "flash-grad-window-4x4096": lambda: _window_attn(4, 4096),
    "flash-grad-window-1x16384": lambda: _window_attn(1, 16384),
    # causal attention at heads of 64, 4 query heads a key/value head
    # (lfm2.train-docs8k): the narrowest and the widest batch
    "flash-grad-gqa64-16x1024": lambda: _grouped_attn_64(16, 1024),
    "flash-grad-gqa64-2x8192": lambda: _grouped_attn_64(2, 8192),
    "kda-carry-grad-16x1024": lambda: _kda_carry(16, 4, 16),
    "kda-carry-grad-2x8192": lambda: _kda_carry(2, 4, 128),
    "kda-prep-grad-16x1024": lambda: _kda_prep(16, 4, 1024),
    "kda-prep-grad-2x8192": lambda: _kda_prep(2, 4, 8192),
    # an odd number of heads: one to a tile, [64, 64] matrices
    "kda-prep-grad-3-heads": lambda: _kda_prep(2, 3, 256),
    # the four plan cells' widest update: kimi-linear, joyai-flash, sdar
    # (the doubled row), trinity-mini
    "held-experts-grad-16384": lambda: _held_experts(16384, 2304, 1024, 8,
                                                     256),
    "held-experts-grad-16384-16-of-256": lambda: _held_experts(
        16384, 2048, 768, 16, 256),
    "held-experts-grad-32768-16-of-128": lambda: _held_experts(
        32768, 2048, 768, 16, 128),
    "held-experts-grad-16384-8-of-128": lambda: _held_experts(
        16384, 2048, 1024, 8, 128),
    # offline decoder: 64 sentences x beam 6, scalar and per-row positions
    "decode-r384-scalar-pos": lambda: _decode(64 * 6, False),
    "decode-r384-row-pos": lambda: _decode(64 * 6, True),
    # paged server: smallest row bucket x beam 1 / 6, the largest, and the
    # SMEM bound the engine checks at start-up (one row more is refused)
    f"paged-r{_ROWS_LO}": lambda: _paged(_ROWS_LO),
    f"paged-r{_ROWS_LO * 6}": lambda: _paged(_ROWS_LO * 6),
    f"paged-r{_ROWS_HI}": lambda: _paged(_ROWS_HI),
    f"paged-r{_ROWS_BOUND}-bound": lambda: _paged(_ROWS_BOUND),
    # E and V are the widths; N is shrunk for compile time — the gradient
    # case keeps one real row block (block_n 1024) and holds the forward too
    "xent-fwd": lambda: _xent(256, False),
    "xent-grad": lambda: _xent(1024, True),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes, kernels = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # a named pallas_call's op_name is ".../<name>/pallas_call", wrapped
    # as "jvp(<name>)" / "transpose(jvp(<name>))" under differentiation
    found = {part for op_name in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
        for part in re.split(r"[/()]", op_name)}
    missing = [k for k in kernels if k not in found]
    assert not missing, (
        f"{missing} not among the compiled program's Pallas kernels — "
        f"something gave way to a reference")


def _rotated_gqa(q, k):
    """`_gqa`'s call of the turn: 32 + 4 whole heads of 128 in half-split
    pairs, the angles tiled over the doubled row."""
    angles = jnp.tile(rope_angles(q.shape[2] // 2, 128, 1e6, "half"), (2, 1))
    return _rotate(q, angles, "half"), _rotate(k, angles, "half")


def _rotated_mla(q, shared):
    """`_mla`'s: the 192-wide query turned in place from channel 128 on,
    the 64 shared key channels whole, interleaved pairs."""
    angles = rope_angles(q.shape[2], 64, 32e6)
    return _rotate(q, angles, start=128), _rotate(shared, angles)


ROTATIONS = {
    "gqa.rope-2x2048": (_rotated_gqa, ((2, 32, 2048, 128), (2, 4, 2048, 128))),
    "mla.rope-2x1024": (_rotated_mla, ((2, 32, 1024, 192), (2, 1024, 64))),
}


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "vjp"])
@pytest.mark.parametrize("site", ROTATIONS)
def test_the_rotation_is_one_pass_for_v5e(chip, site, backward):
    """The turn and its VJP, as the layer plan's two call sites use them,
    read and write their tensor once: the compiled program's temporaries
    stay under the query's own bytes (a roll along the channels was slices
    XLA did not fuse, 3.5 x them: PERF.md 6, PR 42) and no instruction of
    its entry computation yields a float32 array of a quarter of the
    query's elements or more (the roll's were float32 halves, or 63 + 1
    of 64 channels, of the whole tensor, in HBM)."""
    fn, shapes = ROTATIONS[site]
    if backward:
        turn = fn

        def fn(a, b, ga, gb):
            return jax.vjp(turn, a, b)[1]((ga, gb))
    args = [jax.ShapeDtypeStruct(s, DT, sharding=chip)
            for s in (shapes * 2 if backward else shapes)]
    compiled = jax.jit(fn).lower(*args).compile()
    query = math.prod(shapes[0])
    assert compiled.memory_analysis().temp_size_in_bytes < query * 2
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    # "%name = <type or tuple of types> op(operands...)": the types alone
    yields = re.findall(r"^\s*(?:ROOT )?\S+ = (.*?) [\w-]+\(", entry, re.M)
    wide = [held for held in yields
            for dims in re.findall(r"\bf32\[([\d,]+)\]", held)
            if math.prod(map(int, dims.split(","))) * 4 >= query]
    assert not wide, wide
