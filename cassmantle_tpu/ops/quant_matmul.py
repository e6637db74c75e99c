"""Pallas int8×int8→int32 matmul + conv3x3 kernels for W8A8 serving.

Why this exists (ISSUE 20; ROADMAP item 4; PAPERS.md Efficient Diffusion
survey): the repo's weights-only w8a16 path (ops/quant.py) halves weight
HBM reads but the MXU still multiplies bf16 and activations still move at
full width. W8A8 closes both gaps: weights AND activations are int8 in
HBM/VMEM, the MXU runs its int8 mode (2× the bf16 MAC rate on v5e-class
chips), and the int32 accumulator is rescaled to fp in a fused epilogue —
per-output-channel weight scale × per-tensor (or per-token, LM) activation
scale, exactly the symmetric scheme ops/quant.py pins algebraically:

    x ≈ s_a · X8,  W ≈ W8 ⊙ s_w   ⇒   x @ W ≈ (X8 @ W8)_i32 · s_a ⊙ s_w

Two kernels, mirroring the repo's Pallas conventions (ops/fused_conv.py):

- ``int8_matmul``: (M, K) × (K, N) grid over (M-tile, N-tile), whole-K
  blocks, int32 MXU accumulation, epilogue = row-scale × col-scale ×
  acc + bias. Per-token activation scales are just a non-constant row
  scale — same kernel, no second code path.
- ``int8_conv3x3``: stride-1 SAME NHWC conv as nine shifted (H·W, C) ×
  (C, F) int8 matmuls per (batch, F-block) program — the im2col-free
  formulation of fused_conv.py, minus the in-kernel GN/SiLU (see below).

The fused GN+SiLU+conv path gets its int8 variant via
``gn_silu_conv3x3_w8a8``: the GN affine + SiLU + activation-quantize
chain runs as one XLA elementwise fusion that WRITES int8 (half the HBM
bytes the bf16 path writes), and the conv reads int8. The normalized
tensor does hit HBM here — unlike the fp fused kernel — because dynamic
per-tensor scaling needs a global absmax before quantizing; with static
calibrated scales the write is still int8-wide, so the traffic trade is
(½·write + ½·read) vs the fp kernel's (0·write + 1·read): even, while
the MXU rate doubles. docs/PERF_NOTES.md "Quantized serving accounting"
carries the full byte math.

fp8 rides the same interface: ``quantize_act``/``quantize_tensor_act``
accept fp8 dtypes (e4m3 grid, ops/quant.py), and the dense/conv entry
points dispatch fp8 leaves to an XLA dot that uses native fp8 MXU
support where the hardware has it (v5p+) and fp32 upcast where it
doesn't — so flipping a pipeline to fp8 is a dtype argument, not a
rewrite.

Parity pinning: ``*_reference`` functions compute the SAME integer math
in plain lax (int32 accumulation, identical epilogue order), and
tests/test_w8a8.py pins kernel-vs-reference in interpret mode on CPU —
tier-1 executes the real kernels, channel padding included.

Dispatch: interpret mode auto-selects off-TPU; shapes whose working set
misses the VMEM budget fall back to the reference (still int8 math, XLA
lowered); the serving-level ``CASSMANTLE_NO_W8A8`` kill switch is read
at pipeline BUILD time (serving/pipeline.py) — reverting bit-exactly to
the fp path requires never having quantized the weights, so the switch
gates the load-time tree transform, not this module's call sites.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu
from cassmantle_tpu.ops.quant import (
    ActQTensor,
    act_absmax,
    act_scale_from_absmax,
    quantize_act,
)

# Per-program VMEM budget, inside Mosaic's default 16 MiB scope.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

# int8 MXU tiling: 32 sublanes × 128 lanes is the minimum int8 tile, so
# every padded dim is a multiple of these.
_SUBLANE = 32
_LANE = 128

_BLOCK_M = 128
_BLOCK_N = 128
_CONV_F_CANDIDATES = (256, 128, 64, 32)


def w8a8_disabled() -> bool:
    """Operator kill switch (same parse as CASSMANTLE_NO_FUSED_CONV).
    Consulted at pipeline BUILD time: with the switch set the loaders
    never quantize, modules take the plain branch, and serving is
    bit-exactly the pre-w8a8 path — which is the whole point of a
    quantization kill switch (an already-int8 tree can't round-trip
    back)."""
    return os.environ.get("CASSMANTLE_NO_W8A8", "").lower() \
        not in ("", "0", "false", "no", "off")


def describe(calibrated: bool, sites: int) -> str:
    """One-line w8a8 execution-strategy description for pipeline startup
    logs (the fused_conv.describe pattern)."""
    scales = "static calibrated" if calibrated else "dynamic absmax"
    return (f"w8a8: int8 Pallas matmul/conv active at {sites} sites, "
            f"{scales} activation scales")


def round_up(n: int, mult: int) -> int:
    if mult <= 0:
        return n
    return ((n + mult - 1) // mult) * mult


def _pad_dim(t: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - t.shape[axis]
    if pad == 0:
        return t
    widths = [(0, 0)] * t.ndim
    widths[axis] = (0, pad)
    return jnp.pad(t, widths)


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

def _matmul_blocks(mp: int, kp: int, np_: int):
    """(M-block, N-block) fitting the VMEM budget, or None."""
    bm = _BLOCK_M if mp >= _BLOCK_M else mp
    bn = _BLOCK_N if np_ >= _BLOCK_N else np_
    while bm >= _SUBLANE:
        used = (bm * kp            # x block, int8
                + kp * bn          # w block, int8
                + bm * bn * 4      # int32/fp32 accumulator
                + 2 * bm * bn * 4  # double-buffered output blocks
                + bm * 4 + 2 * bn * 4 * 2)  # scales + bias
        if used <= VMEM_BUDGET_BYTES:
            return bm, bn
        bm //= 2
    return None


def int8_matmul_ok(m: int, k: int, n: int) -> bool:
    """Shapes the Pallas kernel handles (others → lax reference, same
    integer math)."""
    mp = round_up(m, _SUBLANE)
    kp = round_up(k, _LANE)
    np_ = round_up(n, _LANE)
    return _matmul_blocks(mp, kp, np_) is not None


def _matmul_kernel(x_ref, w_ref, sr_ref, sc_ref, bias_ref, o_ref):
    acc = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * sr_ref[:] * sc_ref[:]
    out = out + bias_ref[:]
    o_ref[:] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "bm", "bn"))
def _matmul_padded(x_q, w_q, row_scale, col_scale, bias, out_dtype,
                   interpret: bool, bm: int, bn: int):
    mp, kp = x_q.shape
    np_ = w_q.shape[-1]
    grid = (mp // bm, np_ // bn)
    flops = 2 * mp * kp * np_
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=mp * kp + kp * np_
            + mp * np_ * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x_q, w_q, row_scale, col_scale, bias)


def int8_matmul_reference(x_q, w_q, row_scale, col_scale, bias,
                          out_dtype=jnp.float32):
    """Pure-lax reference: identical int32 accumulation and epilogue
    order as the kernel (parity is near-bitwise; fp32 epilogue rounding
    is the only freedom)."""
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * row_scale * col_scale
    out = out + bias
    return out.astype(out_dtype)


def int8_matmul(x_q, w_q, row_scale, col_scale, bias=None,
                out_dtype=jnp.float32, interpret=None):
    """(M, K) int8 × (K, N) int8 → (M, N) ``out_dtype`` with the scaled
    epilogue ``acc_i32 · row_scale · col_scale + bias``.

    ``row_scale`` is (M, 1) fp32 (per-token activation scales, or a
    broadcast per-tensor scalar), ``col_scale`` (1, N) fp32 (per-output-
    channel weight scale, activation scale may be pre-folded in). Pads
    M/K/N up to int8 MXU tiles (zero int8 pads contribute zero to the
    int32 dot; pad rows/cols are sliced off).
    """
    if interpret is None:
        interpret = not on_tpu()
    m, k = x_q.shape
    n = w_q.shape[-1]
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    bias = bias.astype(jnp.float32).reshape(1, n)
    row_scale = jnp.broadcast_to(
        jnp.asarray(row_scale, jnp.float32), (m, 1))
    col_scale = jnp.asarray(col_scale, jnp.float32).reshape(1, n)
    mp = round_up(m, _SUBLANE)
    kp = round_up(k, _LANE)
    np_ = round_up(n, _LANE)
    blocks = _matmul_blocks(mp, kp, np_)
    if blocks is None:
        return int8_matmul_reference(x_q, w_q, row_scale, col_scale,
                                     bias, out_dtype)
    bm, bn = blocks
    # re-pad so the grid tiles exactly (Pallas grids are exact)
    mp = round_up(mp, bm)
    np_ = round_up(np_, bn)
    xq = _pad_dim(_pad_dim(x_q, 0, mp), 1, kp)
    wq = _pad_dim(_pad_dim(w_q, 0, kp), 1, np_)
    sr = _pad_dim(row_scale, 0, mp)
    sc = _pad_dim(col_scale, 1, np_)
    bp = _pad_dim(bias, 1, np_)
    out = _matmul_padded(xq, wq, sr, sc, bp, jnp.dtype(out_dtype),
                         bool(interpret), bm, bn)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# w8a8 dense entry point (QDense in models/layers.py dispatches here)
# ---------------------------------------------------------------------------

def _dense_scales(x, q: ActQTensor, per_token: bool):
    """(quantized activations, row_scale (M,1)) for a flattened (M, K)
    activation block."""
    qdtype = q.data.dtype
    if per_token or q.act_scale is None:
        scale = act_scale_from_absmax(
            act_absmax(x, per_token=per_token), qdtype)
    else:
        scale = q.act_scale
    x_q = quantize_act(x, scale, qdtype)
    row = jnp.asarray(scale, jnp.float32)
    if row.ndim:
        row = row.reshape(x.shape[0], 1)          # per-token (M, 1)
    row = jnp.broadcast_to(row, (x.shape[0], 1))  # per-tensor scalar
    return x_q, row


def w8a8_dense(x, q: ActQTensor, bias=None, out_dtype=None,
               per_token: bool = False, interpret=None):
    """Dense layer on a quantized leaf: quantize activations (static
    calibrated scale when the leaf carries one, dynamic absmax
    otherwise; per-token row scales for the LM path), run the int8
    kernel, epilogue in fp32, cast to ``out_dtype`` (default: x.dtype).

    fp8 leaves take the XLA-dot path: native fp8 MXU where hardware
    supports it (TPU), fp32 upcast elsewhere — same interface either
    way."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = q.data.shape[-1]
    x2 = x.reshape(-1, k)
    col_scale = q.scale.reshape(1, n)
    if jnp.dtype(q.data.dtype) != jnp.int8:   # fp8 leaf
        qdtype = q.data.dtype
        if per_token or q.act_scale is None:
            a_scale = act_scale_from_absmax(
                act_absmax(x2, per_token=per_token), qdtype)
        else:
            a_scale = q.act_scale
        x_q = quantize_act(x2, a_scale, qdtype)
        compute = qdtype if on_tpu() else jnp.float32
        acc = jax.lax.dot_general(
            x_q.astype(compute), q.data.astype(compute),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out = acc * jnp.asarray(a_scale, jnp.float32).reshape(-1, 1) \
            * col_scale
        if bias is not None:
            out = out + bias.astype(jnp.float32).reshape(1, n)
        return out.astype(out_dtype).reshape(lead + (n,))
    x_q, row_scale = _dense_scales(x2, q, per_token)
    if int8_matmul_ok(x2.shape[0], k, n):
        out = int8_matmul(x_q, q.data, row_scale, col_scale, bias,
                          out_dtype=out_dtype, interpret=interpret)
    else:
        b = jnp.zeros((1, n), jnp.float32) if bias is None \
            else bias.astype(jnp.float32).reshape(1, n)
        out = int8_matmul_reference(x_q, q.data, row_scale, col_scale,
                                    b, out_dtype)
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# int8 conv3x3 (stride-1 SAME, NHWC) + the fused GN+SiLU int8 variant
# ---------------------------------------------------------------------------

def _conv_blocks(h: int, w: int, c: int, f: int):
    """Output-channel block for the whole-image conv program, or None
    when even the smallest block misses the VMEM budget."""
    cands = [b for b in _CONV_F_CANDIDATES if f % b == 0]
    if f <= 512 and f not in cands:
        cands.insert(0, f)
    for bf in cands:
        used = ((h + 2) * (w + 2) * c       # padded int8 image
                + 9 * c * bf                # int8 kernel block
                + h * w * bf * 4            # int32/fp32 accumulator
                + 2 * h * w * bf * 4        # double-buffered out blocks
                + 4 * bf * 2)               # scale + bias rows
        if used <= VMEM_BUDGET_BYTES:
            return bf
    return None


def int8_conv_ok(x_q: jax.Array, kernel: jax.Array) -> bool:
    """NHWC (B, H, W, C) int8 × HWIO (3, 3, C, F) int8, whole image per
    program. Covers every SD1.5-512 and SDXL-1024 ResBlock shape (the
    int8 image is small: 128·128·320 ≈ 5 MB); misses fall back to the
    lax reference."""
    if x_q.ndim != 4 or kernel.ndim != 4:
        return False
    b, h, w, c = x_q.shape
    kh, kw, kc, f = kernel.shape
    if (kh, kw) != (3, 3) or kc != c:
        return False
    if h < 3 or w < 3:
        return False
    return _conv_blocks(h, w, c, f) is not None


def _conv_kernel(x_ref, k_ref, sc_ref, bias_ref, o_ref, *,
                 h: int, w: int):
    c = x_ref.shape[-1]
    bf = k_ref.shape[-1]
    acc = jnp.zeros((h * w, bf), jnp.int32)
    for dy in range(3):
        for dx in range(3):
            patch = x_ref[0, dy:dy + h, dx:dx + w, :]
            patch = patch.reshape(h * w, c)
            acc += jax.lax.dot_general(
                patch, k_ref[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
    out = acc.astype(jnp.float32) * sc_ref[:]
    out = out + bias_ref[:]
    o_ref[0] = out.reshape(h, w, bf).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "bf"))
def _conv_padded(x_q, kernel, col_scale, bias, out_dtype,
                 interpret: bool, bf: int):
    bsz, hp, wp, c = x_q.shape
    h, w = hp - 2, wp - 2
    f = kernel.shape[-1]
    grid = (bsz, f // bf)
    kern = functools.partial(_conv_kernel, h=h, w=w)
    flops = 2 * bsz * h * w * 9 * c * f
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda bi, j: (bi, 0, 0, 0)),
            pl.BlockSpec((3, 3, c, bf), lambda bi, j: (0, 0, 0, j)),
            pl.BlockSpec((1, bf), lambda bi, j: (0, j)),
            pl.BlockSpec((1, bf), lambda bi, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, h, w, bf),
                               lambda bi, j: (bi, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, w, f), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=bsz * hp * wp * c + 9 * c * f
            + bsz * h * w * f * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="int8_conv3x3",
    )(x_q, kernel, col_scale, bias)


def int8_conv3x3_reference(x_q, kernel, col_scale, bias,
                           out_dtype=jnp.float32):
    """Pure-lax reference with the kernel's exact integer math: SAME
    zero padding, nine shifted int8 dots accumulated in int32, fp32
    epilogue."""
    b, h, w, c = x_q.shape
    f = kernel.shape[-1]
    xp = jnp.pad(x_q, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((b, h, w, f), jnp.int32)
    for dy in range(3):
        for dx in range(3):
            patch = jax.lax.dynamic_slice(
                xp, (0, dy, dx, 0), (b, h, w, c))
            acc += jax.lax.dot_general(
                patch, kernel[dy, dx],
                (((3,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
    out = acc.astype(jnp.float32) * col_scale.reshape(1, 1, 1, f)
    out = out + bias.astype(jnp.float32).reshape(1, 1, 1, f)
    return out.astype(out_dtype)


def int8_conv3x3(x_q, kernel, col_scale, bias, out_dtype=jnp.float32,
                 interpret=None):
    """(B, H, W, C) int8 NHWC conv with (3, 3, C, F) int8 HWIO weights,
    stride-1 SAME, epilogue ``acc_i32 · col_scale + bias`` (col_scale =
    activation scale × per-channel weight scale, pre-folded fp32
    (F,))."""
    if interpret is None:
        interpret = not on_tpu()
    f = kernel.shape[-1]
    col = jnp.asarray(col_scale, jnp.float32).reshape(1, f)
    b = bias.astype(jnp.float32).reshape(1, f)
    if not int8_conv_ok(x_q, kernel):
        return int8_conv3x3_reference(x_q, kernel, col, b, out_dtype)
    bf = _conv_blocks(x_q.shape[1], x_q.shape[2], x_q.shape[3], f)
    xp = jnp.pad(x_q, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return _conv_padded(xp, kernel, col, b, jnp.dtype(out_dtype),
                        bool(interpret), bf)


def gn_silu_conv3x3_w8a8(
    x: jax.Array,          # (B, H, W, C) activations
    a: jax.Array,          # (B, C) fp32 GroupNorm affine scale
    b: jax.Array,          # (B, C) fp32 GroupNorm affine shift
    q: ActQTensor,         # (3, 3, C, F) quantized HWIO conv weights
    bias: jax.Array,       # (F,)
    *,
    pad_to: int = 0,
    interpret=None,
) -> jax.Array:
    """int8 variant of the fused GN+SiLU+conv contract
    (ops/fused_conv.py): GN affine + SiLU + quantize fuse into one XLA
    elementwise pass writing int8, then the int8 Pallas conv. Static
    calibrated activation scale when the leaf carries one, dynamic
    global absmax otherwise. ``pad_to`` rounds C/F up exactly like the
    fp kernel (int8 zero pads are exact zeros through the integer
    dot)."""
    dt = x.dtype
    h = x * a[:, None, None, :].astype(dt) + b[:, None, None, :].astype(dt)
    h = jax.nn.silu(h)
    qdtype = q.data.dtype
    if q.act_scale is None:
        a_scale = act_scale_from_absmax(act_absmax(h), qdtype)
    else:
        a_scale = q.act_scale
    f = q.data.shape[-1]
    col_scale = (jnp.asarray(a_scale, jnp.float32)
                 * q.scale.reshape(f))
    if jnp.dtype(qdtype) != jnp.int8:   # fp8 leaf → XLA dot path
        h_q = quantize_act(h, a_scale, qdtype)
        compute = qdtype if on_tpu() else jnp.float32
        out = jax.lax.conv_general_dilated(
            h_q.astype(compute), q.data.astype(compute),
            window_strides=(1, 1), padding=((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
        )
        out = out * col_scale.reshape(1, 1, 1, f) \
            + bias.astype(jnp.float32).reshape(1, 1, 1, f)
        return out.astype(dt)
    h_q = quantize_act(h, a_scale, jnp.int8)
    c = h_q.shape[-1]
    cp = round_up(c, pad_to)
    fp = round_up(f, pad_to)
    hq = _pad_dim(h_q, -1, cp)
    kq = q.data
    if cp != c:
        kq = jnp.pad(kq, ((0, 0), (0, 0), (0, cp - c), (0, 0)))
    kq = _pad_dim(kq, -1, fp)
    colp = _pad_dim(col_scale.reshape(1, f), -1, fp).reshape(fp)
    biasp = _pad_dim(bias.astype(jnp.float32).reshape(1, f),
                     -1, fp).reshape(fp)
    out = int8_conv3x3(hq, kq, colp, biasp, out_dtype=dt,
                       interpret=interpret)
    return out[..., :f]


def gn_silu_conv3x3_w8a8_reference(x, a, b, q: ActQTensor, bias):
    """Whole-contract lax reference (quantize + integer conv + epilogue,
    no Pallas) for parity tests."""
    dt = x.dtype
    h = x * a[:, None, None, :].astype(dt) + b[:, None, None, :].astype(dt)
    h = jax.nn.silu(h)
    if q.act_scale is None:
        a_scale = act_scale_from_absmax(act_absmax(h), q.data.dtype)
    else:
        a_scale = q.act_scale
    h_q = quantize_act(h, a_scale, q.data.dtype)
    f = q.data.shape[-1]
    col = (jnp.asarray(a_scale, jnp.float32)
           * q.scale.reshape(f)).reshape(1, f)
    return int8_conv3x3_reference(
        h_q, q.data, col, bias, out_dtype=dt)
