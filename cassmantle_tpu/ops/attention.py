"""Attention dispatch: XLA reference path + Pallas flash-attention kernel.

Every attention site in the model zoo (UNet spatial transformers, CLIP/GPT-2
/MiniLM text blocks) funnels through :func:`multi_head_attention`, so the
Pallas kernel swap happens in exactly one place. The reference has no
attention code at all — its models live behind the HF Inference API
(reference backend.py:240-295) — so this op is the heart of the "replace the
remote API with local TPU compute" north star.

Dispatch policy:
- TPU + no mask + a shape ``flash_plan`` takes (a query axis long enough
  to tile; K/V that tile or are short enough to pad) → Pallas flash
  attention (blockwise online-softmax, O(N) memory;
  ops/flash_attention.py), per batch shard inside a
  :func:`batch_sharded_kernels` region;
- otherwise → jnp.einsum attention, which XLA fuses well on its own.
Every site counts itself under ``attention.dispatch{path=...}`` when its
program is traced.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from cassmantle_tpu.ops.platform import on_tpu
from cassmantle_tpu.utils.logging import metrics

# When set, every attention site uses the plain XLA path — used when
# tracing for a non-TPU device (e.g. CPU-side param init) while the default
# backend is TPU.
_FORCE_XLA = contextvars.ContextVar("cassmantle_force_xla", default=False)

# When set to (mesh, axis_name, batch_axis), CAUSAL self-attention sites
# run sequence-parallel over that mesh axis (zigzag ring schedule). The
# caller owns the data layout: sequences must already be zigzag-permuted
# (parallel/ring.py) and stay permuted through the whole network.
_CONTEXT_PARALLEL = contextvars.ContextVar(
    "cassmantle_context_parallel", default=None
)

# When set to (mesh, batch_axis), the flash kernels traced inside run
# per batch shard under shard_map. A Mosaic kernel cannot be partitioned
# by GSPMD ("Mosaic kernels cannot be automatically partitioned"), so a
# jit whose inputs arrive batch-sharded — the dp serving mesh,
# serving/pipeline.py::dp_sharded_sampler — has to hand each device its
# own rows explicitly. Everything around the kernels stays GSPMD's.
_BATCH_SHARDED = contextvars.ContextVar(
    "cassmantle_batch_sharded_kernels", default=None
)


@contextlib.contextmanager
def xla_only():
    token = _FORCE_XLA.set(True)
    try:
        yield
    finally:
        _FORCE_XLA.reset(token)


@contextlib.contextmanager
def context_parallel(mesh, axis_name: str = "sp",
                     batch_axis: Optional[str] = "dp"):
    """Route every causal self-attention traced inside this context
    through the sequence-parallel zigzag ring over ``mesh[axis_name]``
    (the long-context trace context; see parallel/lm_train.py)."""
    token = _CONTEXT_PARALLEL.set((mesh, axis_name, batch_axis))
    try:
        yield
    finally:
        _CONTEXT_PARALLEL.reset(token)


@contextlib.contextmanager
def batch_sharded_kernels(mesh, batch_axis: str = "dp"):
    """Trace context for a jit whose batch is sharded over
    ``mesh[batch_axis]``: every flash-kernel site traced inside runs
    under ``shard_map`` on its local rows (attention never mixes batch
    rows, so the result is the unsharded one). The batch at each site
    must divide by the axis size."""
    token = _BATCH_SHARDED.set((mesh, batch_axis))
    try:
        yield
    finally:
        _BATCH_SHARDED.reset(token)


def _flash_per_batch_shard(kernel, q, k, v):
    """``kernel(q, k, v)``, per batch shard where a
    :func:`batch_sharded_kernels` region is active."""
    ctx = _BATCH_SHARDED.get()
    if ctx is None:
        return kernel(q, k, v)
    mesh, batch_axis = ctx
    rows = P(batch_axis)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(rows, rows, rows), out_specs=rows,
        check_vma=False,
    )(q, k, v)


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention. q: (..., Sq, H, D), k/v: (..., Sk, H, D).

    ``mask`` broadcasts against (..., H, Sq, Sk); True = attend.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    if mask is not None:
        big_neg = jnp.finfo(logits.dtype).min
        logits = jnp.where(mask, logits, big_neg)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights = weights.astype(v.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", weights, v)


def multi_head_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    causal: bool = False,
) -> jax.Array:
    """Attention entry point used by all models.

    Shapes: q (..., Sq, H, D); k, v (..., Sk, H, D); returns (..., Sq, H, D).
    ``causal=True`` (with no explicit mask) lets this layer own the
    triangular masking — and, inside a :func:`context_parallel` region,
    dispatch to sequence-parallel zigzag ring attention instead of ever
    materializing the (S, S) mask.
    """
    if causal and mask is None and q.shape == k.shape:
        cp = _CONTEXT_PARALLEL.get()
        if cp is not None and q.ndim == 4:
            from cassmantle_tpu.parallel.ring import (
                zigzag_sharded_attention,
            )

            mesh, axis_name, batch_axis = cp
            _count_dispatch("ring")
            return zigzag_sharded_attention(
                q, k, v, mesh, axis_name=axis_name, scale=scale,
                batch_axis=batch_axis,
            )
    if causal and mask is None:
        # bottom-right-aligned band: when s_q != s_k (cached decode, where
        # the queries are the LAST s_q positions of the sequence), query i
        # attends keys [0, s_k - s_q + i]; reduces to plain tril at
        # s_q == s_k
        s_q, s_k = q.shape[-3], k.shape[-3]
        assert s_q <= s_k, (
            f"causal decode needs s_q <= s_k, got {s_q} > {s_k}"
        )
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
    if _FORCE_XLA.get():
        use_flash = False
    if use_flash is None:
        use_flash = on_tpu() and mask is None
    if use_flash and mask is None:
        from cassmantle_tpu.ops.flash_attention import (
            flash_attention,
            flash_plan,
        )

        # one kernel for every shape it takes: self-attention over image
        # tokens (the VAE mid block's single 512-wide head included) and
        # ragged-S_k cross-attention (UNet text context, S_k=77: K/V pad
        # into the kernel, pad columns masked)
        plan = flash_plan(q, k)
        if plan is not None and not (
                plan.kind == "flash_cross" and _no_flash_cross()):
            _count_dispatch(plan.kind)
            return _flash_per_batch_shard(
                partial(flash_attention, scale=scale, plan=plan), q, k, v)
    _count_dispatch("xla")
    return xla_attention(q, k, v, mask=mask, scale=scale)


def _no_flash_cross() -> bool:
    """CASSMANTLE_NO_FLASH_CROSS=1 is the operator kill switch — one env
    var reverts every ragged cross-attention site to the XLA path if the
    kernel misbehaves there on some TPU generation, without touching the
    self-attention sites."""
    return os.environ.get("CASSMANTLE_NO_FLASH_CROSS", "").lower() not in (
        "", "0", "false", "no", "off")


def _count_dispatch(path: str) -> None:
    """``attention.dispatch{path=...}``: one count a site each time a
    program is traced (this code runs at trace time only), so a reader
    sees how many sites of a program took the kernel, by kind, and how
    many fell to XLA."""
    metrics.inc("attention.dispatch", labels={"path": path})
