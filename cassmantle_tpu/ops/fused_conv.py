"""Pallas TPU fused GroupNorm+SiLU+conv3x3 for the UNet residual hot loop.

Why this exists (docs/PERF_NOTES.md "What the table says" #1/#3 and
VERDICT r5 "Next round" #2): 3x3 convolutions are ~45% of the SD1.5 UNet's
analytic FLOPs and, until this op, had zero conv-side optimization. On TPU
the convolution is a fusion ROOT for XLA — the GroupNorm affine and SiLU
feeding each ResBlock conv are materialized to HBM before the conv reads
them back, so every norm+act+conv sequence pays an extra round trip of the
level's full activation tensor (20 MB at the 64x64x320 level, x2 convs
x ~8 blocks x 100 CFG forwards per image). This kernel computes

    conv3x3(silu(x * a + b)) + bias        (NHWC, stride 1, SAME)

in one pass: x stays in HBM and each grid program DMAs just its row tile
(plus one halo row above/below) into VMEM, normalizes+activates it there,
and runs the 3x3 conv as nine shifted (TH*W, C) x (C, F) MXU matmuls
accumulated in fp32 — the im2col-free formulation that keeps the lane
dimension on channels, which is exactly the layout the UNet already uses
everywhere (NHWC end to end; models/unet.py docstring). The normalized
tensor never exists in HBM.

The three levers this module lands, per the round-6 plan:

1. **Fusion** — one HBM read of x (row tiles + 2 halo rows), one HBM
   write of the conv output; the GN affine (computed per-(batch,channel)
   in fp32 by ``layers.GroupNorm32(return_affine=True)``, the numerically
   sensitive reduction) stays outside the kernel, so the kernel itself is
   exact up to matmul ordering.
2. **NHWC layout pinning** — both the kernel and the ``lax`` reference
   path fix ``dimension_numbers=("NHWC", "HWIO", "NHWC")`` explicitly,
   so no flax/XLA default change can silently insert transposes around
   the hot loop.
3. **MXU channel padding** (``pad_to``) — SD1.5's 320/960-channel levels
   fill 2.5/7.5 128-lane MXU tiles; rounding the contraction and output
   channel dims up to a ``pad_to`` multiple (zeros feed zeros, the pad
   output slice is dropped) trades a few % nominal FLOPs for full tile
   occupancy. 640/1280/2560 are already lane-aligned and pad to
   themselves.

Block sizing is adaptive (``_choose_blocks``): the row-tile height and
output-channel block shrink together until the per-program working set
fits the VMEM budget, so every SD1.5-512 ResBlock shape (64x64x320
through 8x8x2560 skip-concats) and the SDXL-1024 128x128 levels dispatch
to the kernel rather than silently falling back.

Parity pinning: ``gn_silu_conv3x3_reference`` is the pure-lax
implementation of the same contract; ``tests/test_fused_conv.py`` pins
the Pallas kernel against it (interpret mode on CPU, so tier-1 tests
execute the real kernel — DMA halo logic included) across shapes
including the padded-channel case and a multi-row-tile case, plus an
end-to-end tiny-pipeline flag-on/flag-off comparison.

Dispatch mirrors ops/flash_attention.py: ``fused_conv_ok`` gates on
shapes/VMEM, interpret mode auto-selects off-TPU, and the
``CASSMANTLE_NO_FUSED_CONV`` env var is the operator kill switch that
reverts every site to the XLA path without a config edit.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu

# Per-program VMEM budget for the block chooser below (raw + normalized
# scratch, fp32 temporaries, double-buffered weight/output blocks, fp32
# accumulator), and the scoped-VMEM limit the kernel asks Mosaic for.
# The compiler's default scope is 16 MiB, which the 2560-channel
# skip-concat sites cannot meet at any lane-aligned F block (one
# double-buffered (3, 3, 2560, 128) bf16 weight block is 11.8 MB), so
# the kernel raises the limit and the chooser keeps a quarter of it as
# slack for what the estimate does not see (reshape copies of the nine
# shifted patches).
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
VMEM_BUDGET_BYTES = 24 * 1024 * 1024

# Row-tile and output-channel block candidates, widest first. A tile
# must divide the corresponding dim (Pallas grids are exact); the
# chooser walks these until the working set fits.
_BLOCK_H_CANDIDATES = (32, 16, 8, 4, 2)
_BLOCK_F_CANDIDATES = (256, 128)


def kill_switch_set() -> bool:
    """Operator kill switch (same parse as the flash-cross switch in
    ops/attention.py): any truthy CASSMANTLE_NO_FUSED_CONV reverts every
    fused-conv site to the XLA reference path."""
    return os.environ.get("CASSMANTLE_NO_FUSED_CONV", "").lower() \
        not in ("", "0", "false", "no", "off")


def describe(unet_cfg) -> str:
    """One-line conv-side execution-strategy description for pipeline
    startup logs (serving/pipeline.py, serving/sdxl.py): makes the
    A/B arm visible in serving logs the way lm_int8 logs its footprint.
    Empty when the fused path is off."""
    if not getattr(unet_cfg, "fused_conv", False):
        return ""
    pad = getattr(unet_cfg, "conv_pad_to", 0)
    mode = "kill-switched to XLA" if kill_switch_set() else "active"
    return (f"fused_conv: GroupNorm+SiLU+conv3x3 Pallas path {mode}"
            + (f", channels padded to multiples of {pad}" if pad else ""))


def round_up(n: int, mult: int) -> int:
    """n rounded up to a multiple of ``mult`` (mult<=0 -> n unchanged)."""
    if mult <= 0:
        return n
    return ((n + mult - 1) // mult) * mult


def _vmem_bytes(th: int, w: int, c: int, bf: int, itemsize: int) -> int:
    raw = (th + 2) * w * c * itemsize          # DMA'd rows (tile + halo)
    xn = (th + 2) * (w + 2) * c * itemsize     # normalized, W-padded
    # the affine+SiLU runs in fp32 on the whole tile: the upcast rows
    # and the activated rows are both live on the kernel's VMEM stack
    # (Mosaic counted 19.6 MB for the 64x64 960->320 site, which the
    # estimate without them put at 10.5 MB)
    fp32_tmp = 2 * (th + 2) * w * c * 4
    k_blk = 9 * c * bf * itemsize
    out_blk = th * w * bf * itemsize
    acc = th * w * bf * 4
    return raw + xn + fp32_tmp + 2 * (k_blk + out_blk) + acc


def _choose_blocks(h: int, w: int, c: int, f: int, itemsize: int):
    """(row-tile height, output-channel block) fitting the VMEM budget,
    or None when no candidate combination fits. Largest tiles first:
    fewer grid programs amortize per-program setup; shrinking TH first
    keeps the MXU's N dimension wide as long as possible."""
    th_cands = [t for t in _BLOCK_H_CANDIDATES if h % t == 0 and t < h]
    if h <= _BLOCK_H_CANDIDATES[0]:
        th_cands.insert(0, h)
    bf_cands = [b for b in _BLOCK_F_CANDIDATES if f % b == 0]
    if f <= 512:
        bf_cands.insert(0, f)
    for bf in bf_cands:
        for th in th_cands:
            if _vmem_bytes(th, w, c, bf, itemsize) <= VMEM_BUDGET_BYTES:
                return th, bf
    return None


def fused_conv_ok(x: jax.Array, kernel: jax.Array) -> bool:
    """Shapes the kernel handles profitably (others -> XLA reference).

    Requires NHWC x (B, H, W, C) and HWIO kernel (3, 3, C, F), stride-1
    SAME — the only conv shape in the ResBlock hot loop — and a
    (row-tile, F-block) combination whose working set fits the VMEM
    budget. With the adaptive chooser this holds for every SD1.5-512
    ResBlock shape (64x64x320..8x8x2560) and the SDXL-1024 128x128
    levels; exotic shapes fall back to the reference."""
    if x.ndim != 4 or kernel.ndim != 4:
        return False
    b, h, w, c = x.shape
    kh, kw, kc, f = kernel.shape
    if (kh, kw) != (3, 3) or kc != c:
        return False
    if h < 3 or w < 3:
        return False  # border taps would cross the whole image
    return _choose_blocks(h, w, c, f, x.dtype.itemsize) is not None


def gn_silu_conv3x3_reference(
    x: jax.Array,          # (B, H, W, C) activations
    a: jax.Array,          # (B, C) fp32 GroupNorm affine scale (inv*gamma)
    b: jax.Array,          # (B, C) fp32 GroupNorm affine shift
    kernel: jax.Array,     # (3, 3, C, F) HWIO conv weights
    bias: jax.Array,       # (F,)
) -> jax.Array:
    """Pure-lax reference for the fused contract, layout-pinned NHWC/HWIO.

    Matches the unfused module path bit-for-bit in spirit: the affine
    applies as one FMA in the activation dtype (exactly what
    ``layers._GroupNormCore`` does), SiLU in the activation dtype, and
    the conv computes in the activation dtype like ``nn.Conv(dtype=...)``.
    """
    dt = x.dtype
    h = x * a[:, None, None, :].astype(dt) + b[:, None, None, :].astype(dt)
    h = jax.nn.silu(h)
    out = jax.lax.conv_general_dilated(
        h, kernel.astype(dt), window_strides=(1, 1),
        padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out + bias.astype(dt)[None, None, None, :]


def _fused_kernel(x_hbm, a_ref, b_ref, k_ref, bias_ref, o_ref,
                  raw_ref, xn_ref, sems, *,
                  th: int, w: int, nh: int):
    """One (batch, row-tile, F-block) program.

    At f-block 0 the program DMAs its row tile plus one halo row
    above/below from HBM (x never materializes normalized), applies the
    GN affine + SiLU in fp32, and writes the result into zero-bordered
    VMEM scratch; the F axis is sequential, so later F blocks of the
    same tile reuse the scratch. Then nine shifted MXU matmuls
    accumulate the conv in fp32.
    """
    bi = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _load_and_normalize():
        row0 = i * th
        # main rows -> raw[1 : th+1]
        main = pltpu.make_async_copy(
            x_hbm.at[bi, pl.ds(row0, th)],
            raw_ref.at[pl.ds(1, th)], sems.at[0])
        main.start()

        @pl.when(i > 0)
        def _top():
            top = pltpu.make_async_copy(
                x_hbm.at[bi, pl.ds(row0 - 1, 1)],
                raw_ref.at[pl.ds(0, 1)], sems.at[1])
            top.start()
            top.wait()

        @pl.when(i < nh - 1)
        def _bottom():
            bot = pltpu.make_async_copy(
                x_hbm.at[bi, pl.ds(row0 + th, 1)],
                raw_ref.at[pl.ds(th + 1, 1)], sems.at[2])
            bot.start()
            bot.wait()

        main.wait()
        xv = raw_ref[:].astype(jnp.float32)             # (TH+2, W, C)
        av = a_ref[:].astype(jnp.float32)               # (1, 1, C)
        bv = b_ref[:].astype(jnp.float32)
        xn = xv * av + bv
        xn = xn * jax.nn.sigmoid(xn)                    # SiLU, fp32
        xn_ref[:] = jnp.zeros(xn_ref.shape, xn_ref.dtype)
        xn_ref[:, 1:w + 1, :] = xn.astype(xn_ref.dtype)

        # image-edge halo rows are SAME zero padding, not data (the raw
        # rows there were never DMA'd — whatever the scratch held must
        # not leak through silu(affine(.)) into the border taps)
        @pl.when(i == 0)
        def _zero_top():
            xn_ref[0:1, :, :] = jnp.zeros(
                (1,) + xn_ref.shape[1:], xn_ref.dtype)

        @pl.when(i == nh - 1)
        def _zero_bottom():
            xn_ref[th + 1:th + 2, :, :] = jnp.zeros(
                (1,) + xn_ref.shape[1:], xn_ref.dtype)

    bf = k_ref.shape[-1]
    acc = jnp.zeros((th * w, bf), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = xn_ref[dy:dy + th, dx:dx + w, :]
            patch = patch.reshape(th * w, patch.shape[-1])
            acc += jax.lax.dot_general(
                patch, k_ref[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    acc += bias_ref[0].astype(jnp.float32)[None, :]
    o_ref[0] = acc.reshape(th, w, bf).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_h", "block_f"))
def _fused_bhwc(x, a, b, kernel, bias, interpret: bool,
                block_h: int, block_f: int):
    """(B, H, W, C) fused GN-affine+SiLU+conv3x3 -> (B, H, W, F)."""
    bsz, h, w, c = x.shape
    f = kernel.shape[-1]
    nh = h // block_h
    nf = f // block_f
    grid = (bsz, nh, nf)
    kern = functools.partial(_fused_kernel, th=block_h, w=w, nh=nh)
    compiler_params = pltpu.CompilerParams(
        # batch rows independent; row tiles independent; the F axis
        # reuses each tile's normalized scratch sequentially
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )
    flops = 2 * bsz * h * w * 9 * c * f
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),         # x stays in HBM
            # (B, 1, C): a (1, C) block of a (B, C) array breaks the
            # TPU rule that a block's last two dims divide (8, 128) or
            # equal the array's
            pl.BlockSpec((1, 1, c), lambda bi, i, j: (bi, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda bi, i, j: (bi, 0, 0)),
            pl.BlockSpec((3, 3, c, block_f), lambda bi, i, j: (0, 0, 0, j)),
            pl.BlockSpec((1, block_f), lambda bi, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_h, w, block_f),
                               lambda bi, i, j: (bi, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, w, f), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_h + 2, w, c), x.dtype),      # raw rows
            pltpu.VMEM((block_h + 2, w + 2, c), x.dtype),  # silu(gn(x))
            pltpu.SemaphoreType.DMA((3,)),
        ],
        compiler_params=compiler_params,
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=(bsz * h * w * (c + f) + 9 * c * f)
            * x.dtype.itemsize,
            transcendentals=bsz * h * w * c,  # the sigmoid
        ),
        interpret=interpret,
        name="fused_conv3x3",
    )(x, a[:, None, :], b[:, None, :], kernel, bias)


def _pad_last(t: jax.Array, to: int) -> jax.Array:
    pad = to - t.shape[-1]
    if pad == 0:
        return t
    widths = [(0, 0)] * (t.ndim - 1) + [(0, pad)]
    return jnp.pad(t, widths)


def gn_silu_conv3x3(
    x: jax.Array,          # (B, H, W, C)
    a: jax.Array,          # (B, C) fp32 GroupNorm affine scale
    b: jax.Array,          # (B, C) fp32 GroupNorm affine shift
    kernel: jax.Array,     # (3, 3, C, F) HWIO
    bias: jax.Array,       # (F,)
    *,
    pad_to: int = 0,
    interpret=None,
) -> jax.Array:
    """Fused ``conv3x3(silu(gn_affine(x))) + bias`` with dispatch.

    ``pad_to`` > 0 rounds the C and F channel dims up to that multiple
    (zero channels: a zero input channel contributes silu(0)=0 through
    zero kernel rows; pad output channels are sliced off) so the MXU
    contraction/output tiles fill — the 320->384 / 960->1024 trade at
    SD1.5's non-aligned levels. Shapes the kernel can't take, or a set
    CASSMANTLE_NO_FUSED_CONV, fall back to the layout-pinned lax
    reference (still one call site, so the A/B stays honest).
    """
    if interpret is None:
        interpret = not on_tpu()
    c = x.shape[-1]
    f = kernel.shape[-1]
    cp = round_up(c, pad_to)
    fp = round_up(f, pad_to)
    if kill_switch_set():
        return gn_silu_conv3x3_reference(x, a, b, kernel, bias)
    xp = _pad_last(x, cp)
    kp = kernel
    if cp != c:
        kp = jnp.pad(kp, ((0, 0), (0, 0), (0, cp - c), (0, 0)))
    kp = _pad_last(kp, fp)
    if not fused_conv_ok(xp, kp):
        return gn_silu_conv3x3_reference(x, a, b, kernel, bias)
    blocks = _choose_blocks(x.shape[1], x.shape[2], cp, fp,
                            x.dtype.itemsize)
    ap = _pad_last(a, cp)
    bp = _pad_last(b, cp)
    biasp = _pad_last(bias, fp).astype(jnp.float32)[None, :]
    out = _fused_bhwc(
        xp, ap.astype(jnp.float32), bp.astype(jnp.float32),
        kp.astype(x.dtype), biasp,
        bool(interpret), blocks[0], blocks[1],
    )
    return out[..., :f]
