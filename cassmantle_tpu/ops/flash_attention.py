"""Pallas TPU flash attention: blockwise softmax in VMEM, O(N) memory.

The UNet's self-attention over image tokens is the framework's "long
sequence" axis (SURVEY.md §5.7): 4,096 tokens at 512² latents, 16k+ at
SDXL-1024. This kernel tiles Q into VMEM blocks against K/V blocks, so
attention never materializes the (S, S) score matrix in HBM. K/V of up to
4,096 tokens sit whole in one block and each head's softmax is whole;
longer K/V stream through the grid's innermost dimension with the running
max/denominator (online softmax) in fp32 scratch.

Layout: callers pass q/k/v as (..., S, H, D), which is a free reshape of
what the projections produce and consume, (B, S, H·D) — and that is what
the kernel reads and writes. One grid program holds a (block, H·D) tile
of q, k, v and o and loops the H heads inside, each head a static D-lane
slice of the tile: no head-folding transpose exists beside the kernel,
and loads and stores fill the lanes (320 of 384 at SD1.5's level 0, where
a (B·H, S, 40) array filled 40 of 128). Scores accumulate in fp32 on the
MXU (``preferred_element_type``) from operands in their own dtype, the
softmax statistics are fp32, and probabilities are cast back to the value
dtype for the P·V matmul so both matmuls hit the MXU in bf16 on TPU. The
softmax scale is applied to the query tile, not to the scores (one more
rounding of q in its own dtype; exact at D = 64, where it is 1/8).

What the chip said (one v5e, bf16, CFG batch 2, ``tools/flash_timing.py``;
PERF.md section 5, PR 27). The kernel this replaced folded the heads into
the batch, (B·H, S, D) at 1024² blocks: 1.089 ms a call at level 0 of
SD1.5 (4096 tokens, 8 heads of 40) with its transposes. This one: 0.988
with the old blocks and arithmetic (the heads' chains in one basic block
do NOT interleave by themselves; issuing the next head's QK^T ahead of
this head's softmax read 0.930); 0.891 with K/V whole in one block (no
running state to keep, rescale or store); 0.790 with the scale on the
query tile — the VPU's passes over the (BQ, BK) score tile are what a
call costs, and that is one fewer of about six. The MXU's own floor at
D = 40 is 0.70 (each matmul fills 40 of its 128 rows or columns).

Dispatch (``flash_plan``): no mask, a query axis that tiles, and K/V that
either tile (self-attention, ``flash_self``) or are short enough to pad
into 128-wide blocks with the pad columns masked by a static ``kv_len``
(the UNet's text context, S_k=77: ``flash_cross``) — the score matrix
(4096×77 per head at 512² level 0, materialized to HBM on the XLA path)
never leaves VMEM. The VAE mid block's single 512-wide head is the same
kernel at H=1. Tiny text-model sequences stay on the XLA path where
fusion is already optimal.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu

_NEG_INF = -1e30
_LANES = 128
#: shortest query axis worth a kernel launch, and what it must tile into
MIN_SEQ = 512
#: ragged K/V no longer than this pad into 128-wide blocks (flash_cross)
MAX_CROSS_KV = 1024
#: widest H·D tile the kernel's VMEM plan covers (SDXL level 2: 20 x 64)
MAX_WIDTH = 1280
#: a head's running max / denominator live in one lane of a 128-lane row
MAX_HEADS = _LANES
#: K/V of up to this many elements a batch row stay whole in VMEM (SDXL
#: level 1: 4096 x 640, 5 MB each in bf16, double-buffered)
WHOLE_KV_ELEMENTS = 4096 * 640
#: one head's fp32 score tile, block_q x block_k
SCORE_TILE_ELEMENTS = 512 * 1024

#: scoped VMEM the kernel may ask for: whole K/V, double-buffered (up to
#: 4 x 5 MB), beside a head's fp32 score tile and its probabilities pass
#: the compiler's 16 MiB default; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


class FlashPlan(NamedTuple):
    """How the kernel takes one attention site: ``kind`` names the
    dispatch branch (``attention.dispatch``'s ``path`` label), the
    blocks tile S_q and S_k (ragged K/V: S_k zero-padded up to a whole
    number of ``block_k``)."""

    kind: str
    block_q: int
    block_k: int


def _largest_block(seq: int, cap: int) -> int:
    """Largest power of two from 128 up that divides ``seq`` and is at
    most ``cap``."""
    block = _LANES
    while block * 2 <= cap and seq % (block * 2) == 0:
        block *= 2
    return block


def flash_plan(q: jax.Array, k: jax.Array) -> Optional[FlashPlan]:
    """The kernel's plan for q (..., S_q, H, D) against k (..., S_k, H, D),
    or None for shapes it does not take profitably (-> XLA path)."""
    if q.ndim < 4:
        return None
    sq, heads, d = q.shape[-3:]
    sk = k.shape[-3]
    width = heads * d
    if sq < MIN_SEQ or sq % MIN_SEQ or heads > MAX_HEADS or width > MAX_WIDTH:
        return None
    if sk >= MIN_SEQ and sk % MIN_SEQ == 0:
        kind, padded = "flash_self", sk
    elif 0 < sk <= MAX_CROSS_KV:
        kind, padded = "flash_cross", -(-sk // _LANES) * _LANES
    else:
        return None
    # Blocks as one v5e chip timed them (PERF.md section 5, PR 27). K/V
    # whole in one block wherever they fit: the softmax is then whole,
    # no running state is kept or rescaled, and K/V are fetched once a
    # batch row (level-0 self-attention, 4096 x 8 x 40: 0.89 ms a call
    # against 0.99 at 1024 x 1024 blocks). Past that, 1024-blocks under
    # the online softmax.
    if padded * width <= WHOLE_KV_ELEMENTS:
        block_k = padded
    else:
        block_k = _largest_block(padded, 1024)
    # a 2 MB fp32 score tile a head, but no fewer than 256 query rows a
    # step (128 rows read 0.93 ms against 0.87 at level 0: each K/V
    # weight tile then streams too few rows) and no more than 1024
    block_q = _largest_block(
        sq, min(1024, max(256, SCORE_TILE_ELEMENTS // block_k)))
    return FlashPlan(kind, block_q, block_k)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *state, scale: float,
                  heads: int, head_dim: int, num_k_blocks: int,
                  block_k: int, kv_len: int):
    """One (block_q, H·D) query tile against one (block_k, H·D) K/V tile,
    head by head. With a single K/V block the softmax is whole and no
    running state exists; otherwise ``state`` is (running max, running
    denominator, accumulators), head h's statistics in lane h."""
    k_idx = pl.program_id(2)
    online = num_k_blocks > 1
    if online:
        m_ref, l_ref, acc_ref = state

        @pl.when(k_idx == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    valid = None
    if kv_len:  # static: ragged K/V zero-padded into the last block
        col = (jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[1], block_k), 1) + k_idx * block_k)
        valid = col < kv_len

    for h in range(heads):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        # the scale goes onto the (BQ, D) query tile, in fp32 and back to
        # the operand dtype, not onto the (BQ, BK) scores: one VPU pass
        # over the score tile less, for one more rounding of q
        q = (q_ref[0, :, lanes].astype(jnp.float32) * scale).astype(
            q_ref.dtype)                  # (BQ, D)
        k = k_ref[0, :, lanes]            # (BK, D)
        v = v_ref[0, :, lanes]            # (BK, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                  # (BQ, BK) fp32
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)

        m_new = jnp.max(s, axis=-1, keepdims=True)     # (BQ, 1)
        if online:
            m_prev = m_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)            # (BQ, BK) fp32
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                  # (BQ, D) fp32
        if online:
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[:, h:h + 1] = m_new
            l_ref[:, h:h + 1] = l_ref[:, h:h + 1] * alpha + l_new
        else:
            o_ref[0, :, lanes] = (pv / l_new).astype(o_ref.dtype)

    if online:
        @pl.when(k_idx == num_k_blocks - 1)
        def _finish():
            for h in range(heads):
                lanes = slice(h * head_dim, (h + 1) * head_dim)
                o_ref[0, :, lanes] = (
                    acc_ref[h] / l_ref[:, h:h + 1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "interpret", "block_q", "block_k", "kv_len"))
def _flash_bsw(q: jax.Array, k: jax.Array, v: jax.Array, heads: int,
               scale: float, interpret: bool, block_q: int, block_k: int,
               kv_len: int = 0) -> jax.Array:
    """(B, S, H·D) flash attention, the heads side by side in the minor
    dimension. ``kv_len`` > 0 marks K/V as padded to the block grid with
    only the first kv_len keys valid."""
    b, sq, width = q.shape
    sk = k.shape[1]
    d = width // heads
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, heads=heads, head_dim=d,
        num_k_blocks=nk, block_k=block_k, kv_len=kv_len)
    state = [] if nk == 1 else [
        pltpu.VMEM((block_q, _LANES), jnp.float32),    # running max
        pltpu.VMEM((block_q, _LANES), jnp.float32),    # running denom
        pltpu.VMEM((heads, block_q, d), jnp.float32),  # accumulators
    ]
    return pl.pallas_call(
        kernel,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, width), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, width), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, width),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, width), q.dtype),
        scratch_shapes=state,
        # Only the k-block axis carries state (online-softmax scratch);
        # the batch and q-block axes are embarrassingly parallel.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * b * sq * sk * width,  # QK^T + PV
            # q and o once; K/V once a q block, or once a batch row
            # where they are one block that stays put
            bytes_accessed=(2 * b * sq * width + 2 * b * sk * width
                            * (nq if nk > 1 else 1)) * q.dtype.itemsize,
            transcendentals=b * heads * sq * sk,
        ),
        interpret=interpret,
        # what a device trace calls the kernel (the HLO instruction and
        # a scope of its op_name), whatever the wrapper is named
        name="flash_attention",
    )(q, k, v)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale=None, interpret=None,
                    plan: Optional[FlashPlan] = None) -> jax.Array:
    """(..., S_q, H, D) x (..., S_k, H, D) attention via the Pallas
    kernel, for every shape ``flash_plan`` takes: self-attention, and
    cross-attention with ragged S_k, where K/V zero-pad to the block
    width and the kernel masks pad columns via the static ``kv_len``
    (exact — pad keys get -inf scores before the softmax, so they
    contribute nothing). ``plan`` overrides the shape rule's blocks."""
    if plan is None:
        plan = flash_plan(q, k)
    if plan is None:
        raise ValueError(
            f"no flash plan for q {q.shape} against k {k.shape}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not on_tpu()

    *batch, sq, h, d = q.shape
    sk = k.shape[-3]
    # (..., S, H, D) -> (B, S, H·D): the projections' own layout
    q, k, v = (t.reshape((-1, t.shape[-3], h * d)) for t in (q, k, v))
    pad = -sk % plan.block_k
    if pad:
        k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (k, v))
    out = _flash_bsw(q, k, v, h, float(scale), bool(interpret),
                     plan.block_q, plan.block_k, kv_len=sk if pad else 0)
    return out.reshape(tuple(batch) + (sq, h, d))
