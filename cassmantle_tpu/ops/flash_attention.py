"""Pallas TPU flash attention: blockwise online-softmax, O(N) memory.

The UNet's self-attention over image tokens is the framework's "long
sequence" axis (SURVEY.md §5.7): 4,096 tokens at 512² latents, 16k+ at
SDXL-1024. This kernel tiles Q into VMEM blocks and streams K/V blocks
through the grid's innermost dimension, keeping the running max/denominator
(online softmax) in fp32 scratch — attention never materializes the (S, S)
score matrix in HBM.

Layout: callers pass q/k/v as (..., S, H, D); the wrapper folds batch×heads
into the leading grid dimension. Scores accumulate in fp32 on the MXU
(``preferred_element_type``); probabilities are cast back to the value dtype
for the P·V matmul so both matmuls hit the MXU in bf16 on TPU.

Dispatch rules (``flash_attention_ok``): self-attention (no mask), sequence
divisible into blocks, head_dim bounded. Cross-attention with ragged
S_k (the UNet's text context, S_k=77) takes :func:`flash_cross_attention`:
K/V pad to one 128-wide block and the kernel masks the pad columns via a
static ``kv_len`` — the score matrix (4096×77 per head at 512² level 0,
materialized to HBM on the XLA path) never leaves VMEM. Tiny text-model
sequences stay on the XLA path where fusion is already optimal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu

# 1024-blocks: a round-1 builder's note put them ~2x faster than 512 at
# the UNet's level-0 site (S=4096, d=40, bh=64) on v5e (unverified, no
# ledger line): fewer grid programs amortize the per-program MXU setup
# over more work. (1024, 40)-bf16 q/k/v tiles plus two
# (1024, 1024)-fp32 intermediates stay well inside VMEM. Env-tunable so
# a hardware window can sweep block sizes without an edit-reinstall
# cycle (tools/profile_unet.py A/Bs per-resolution; each sweep point is
# its own process, so import-time read is right).
import os as _os

def _block_env(name: str, default: int) -> int:
    v = int(_os.environ.get(name, str(default)))
    if v < 128 or v % 128:
        # fail at import, not mid-sweep: 0 would ZeroDivision in the
        # dispatch gate, negatives slip through it into a negative
        # Pallas grid, and non-lane-multiples can't tile the MXU
        raise ValueError(f"{name}={v}: need a positive multiple of 128")
    return v


BLOCK_Q = _block_env("CASSMANTLE_FLASH_BLOCK_Q", 1024)
BLOCK_K = _block_env("CASSMANTLE_FLASH_BLOCK_K", 1024)
MAX_HEAD_DIM = 256
_NEG_INF = -1e30


def flash_attention_ok(q: jax.Array, k: jax.Array) -> bool:
    """Shapes the kernel handles profitably (others -> XLA path)."""
    sq, sk, d = q.shape[-3], k.shape[-3], q.shape[-1]
    return (
        sq % BLOCK_Q == 0
        and sk % BLOCK_K == 0
        and sq >= BLOCK_Q
        and sk >= BLOCK_K
        and d <= MAX_HEAD_DIM
        and q.ndim >= 4
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, num_k_blocks: int, block_k: int,
                  kv_len: int = 0):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # (BQ, D)
    k = k_ref[0]                      # (BK, D)
    v = v_ref[0]                      # (BK, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (BQ, BK) fp32

    if kv_len:  # static: ragged K/V padded into the last block
        col = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
               + k_idx * block_k)
        s = jnp.where(col < kv_len, s, _NEG_INF)

    m_prev = m_ref[:, :1]             # (BQ, 1)
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)   # (BQ, 1)
    p = jnp.exp(s - m_new)            # (BQ, BK) fp32
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                  # (BQ, D) fp32
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(k_idx == num_k_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "interpret", "block_q", "block_k", "kv_len"))
def _flash_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                interpret: bool, block_q: int = BLOCK_Q,
                block_k: int = BLOCK_K, kv_len: int = 0) -> jax.Array:
    """(BH, S, D) flash attention. ``kv_len`` > 0 marks K/V as padded to
    the block grid with only the first kv_len columns valid."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k

    grid = (bh, nq, nk)
    kernel = functools.partial(_flash_kernel, scale=scale, num_k_blocks=nk,
                               block_k=block_k, kv_len=kv_len)
    # Only the k-block axis carries state (online-softmax scratch); the
    # batch*heads and q-block axes are embarrassingly parallel.
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )
    flops = 2 * 2 * bh * sq * sk * d  # QK^T + PV
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=compiler_params,
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=(2 * bh * sq * d + 2 * bh * sk * d) * 2,
            transcendentals=bh * sq * sk,
        ),
        interpret=interpret,
        # what a device trace calls the kernel (the HLO instruction and
        # a scope of its op_name), whatever the wrapper is named
        name="flash_attention",
    )(q, k, v)


def _fold_heads(t, s, d):
    t = jnp.moveaxis(t, -2, -3)                   # (..., H, S, D)
    return t.reshape((-1, s, d))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale=None, interpret=None,
                    block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K) -> jax.Array:
    """(..., S, H, D) self-attention via the Pallas kernel.

    ``block_q``/``block_k`` override the default tiles — the wide-head
    dispatch (``flash_wide_ok``) shrinks them so fat single-head VMEM
    working sets (the VAE mid-block's D=512) still fit."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not on_tpu()

    *batch, sq, h, d = q.shape
    sk = k.shape[-3]

    qf = _fold_heads(q, sq, d)
    kf, vf = _fold_heads(k, sk, d), _fold_heads(v, sk, d)
    out = _flash_bhsd(qf, kf, vf, float(scale), bool(interpret),
                      block_q=block_q, block_k=block_k)
    out = out.reshape(tuple(batch) + (h, sq, d))
    return jnp.moveaxis(out, -3, -2)              # (..., S, H, D)


# Wide-head self-attention: the VAE mid block attends single-head over
# H·W image tokens at the FULL channel width (D = 512 at production
# geometry) — S hits 16,384 at SDXL's 128² latent, where the XLA path
# materializes a 16k×16k fp32 score matrix (1 GB per image) in HBM. The
# main kernel's 1024-tiles would blow VMEM at D=512 (two (BQ, BK) fp32
# intermediates + three (BK, D) operand tiles), so this dispatch runs
# the SAME kernel at 512-blocks: ~5 MB/program working set, scores
# never leave VMEM. Gated to D above MAX_HEAD_DIM so it can't shadow
# the tuned main path.
WIDE_BLOCK = 512
MAX_WIDE_HEAD_DIM = 512


def flash_wide_ok(q: jax.Array, k: jax.Array) -> bool:
    """Self-attention shapes for the wide-head (VAE mid-block) variant:
    D past the main kernel's bound but within the 512-block VMEM
    budget, and a sequence that tiles into 512-blocks."""
    sq, sk, d = q.shape[-3], k.shape[-3], q.shape[-1]
    return (
        sq == sk
        and sq % WIDE_BLOCK == 0
        and sq >= WIDE_BLOCK
        and MAX_HEAD_DIM < d <= MAX_WIDE_HEAD_DIM
        and q.ndim >= 4
    )


# Cross-attention K/V blocks: the text context is short (77 for CLIP), so
# one lane-width block holds it after padding; queries keep large blocks.
CROSS_BLOCK_K = 128
MAX_CROSS_KV = 1024


def flash_cross_ok(q: jax.Array, k: jax.Array) -> bool:
    """Ragged-K/V shapes worth padding into the kernel: long aligned
    query axis (image tokens), short unaligned context. The XLA path
    for these materializes a (S_q, S_k) score matrix per head in HBM;
    here it stays in VMEM."""
    sq, sk, d = q.shape[-3], k.shape[-3], q.shape[-1]
    return (
        sq % BLOCK_Q == 0
        and sq >= BLOCK_Q
        and 0 < sk <= MAX_CROSS_KV
        and d <= MAX_HEAD_DIM
        and q.ndim >= 4
        # anything the plain kernel takes (sk in full BLOCK_K blocks)
        # stays there; this path covers every remaining short-context
        # shape, aligned-to-128 included (pad=0, kv_len exact)
        and not flash_attention_ok(q, k)
    )


def flash_cross_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          scale=None, interpret=None) -> jax.Array:
    """(..., S_q, H, D) x (..., S_k, H, D) cross-attention with ragged
    S_k: K/V zero-pad to the block width and the kernel masks pad
    columns via the static ``kv_len`` (exact — pad keys get -inf scores
    before the online softmax, so they contribute nothing)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not on_tpu()

    *batch, sq, h, d = q.shape
    sk = k.shape[-3]
    pad = (-sk) % CROSS_BLOCK_K
    widths = [(0, 0)] * (k.ndim - 3) + [(0, pad), (0, 0), (0, 0)]
    kp = jnp.pad(k, widths)
    vp = jnp.pad(v, widths)

    qf = _fold_heads(q, sq, d)
    kf, vf = _fold_heads(kp, sk + pad, d), _fold_heads(vp, sk + pad, d)
    out = _flash_bhsd(qf, kf, vf, float(scale), bool(interpret),
                      block_k=CROSS_BLOCK_K, kv_len=sk)
    out = out.reshape(tuple(batch) + (h, sq, d))
    return jnp.moveaxis(out, -3, -2)
