"""int8 quantization for serving: weights-only (w8a16) AND full W8A8.

The reference rents its LLM (Mistral-7B-Instruct, reference backend.py:25)
so it never faces the on-box memory/bandwidth question. Serving that model
locally does: 7B bf16 params are ~14 GB — at the edge of one v5e chip's
16 GB HBM before activations — and single-stream greedy decode is
weight-streaming-bound, so weight bytes ARE the step time. Per-channel
symmetric int8 storage halves both.

Design (TPU-first):
- ``QTensor``: a registered pytree (int8 data + per-out-channel fp32
  scale). Param trees keep their exact structure; only large matmul
  kernels are swapped for QTensors, so one tree works for any model.
- w8a16 (``quantize_tree`` + ``quantized_apply``): dequantization
  happens INSIDE the jitted computation (``dequantize_tree`` at the top
  of the wrapped apply): HBM holds int8, and XLA fuses the
  ``convert+scale`` producer into each kernel's consumer ops, upcasting
  tiles in VMEM rather than materializing a persistent bf16 copy of the
  weights.
- W8A8 (``ActQTensor`` + ``w8a8_tree_host``; ISSUE 20): selected
  kernel leaves become ``ActQTensor`` (int8 data + per-out-channel fp32
  weight scale + an optional STATIC per-tensor activation scale from
  the committed calibration artifact, parallel/calibrate.py). The
  module code at w8a8-capable sites (models/layers.py ``QDense``, the
  fused-conv glue) branches on ``isinstance(kernel, ActQTensor)`` and
  dispatches the int8×int8→int32 Pallas kernels (ops/quant_matmul.py)
  — the MXU runs int8, activations move at int8 width, and the scales
  fold into the int32→fp epilogue. Quantize-once-at-load is the
  contract: per-call weight requantization inside a dispatch path is a
  recompile/bandwidth cliff and is lint-pinned
  (analysis/recompile.py ``quant-in-dispatch``).
- Per-OUTPUT-channel scales (last axis): row x @ W column j sees one
  scale s_j, preserving matmul semantics exactly:
  x @ (s ⊙ W8) == (x @ W8) ⊙ s.
- Symmetric (no zero-point): zero-points force an extra correction
  matmul; absmax/127 keeps the kernel a pure dot.

Embeddings, norms, biases, and small kernels stay in the storage dtype —
they're a rounding error of the footprint and disproportionately
quality-sensitive.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from cassmantle_tpu.ops.platform import host_cpu_device


class QTensor(NamedTuple):
    """int8 data + broadcastable fp32 scale. A pytree by construction."""

    data: jax.Array    # int8, original shape
    scale: jax.Array   # fp32, shape broadcastable to data (per out-channel)

    @property
    def shape(self):
        return self.data.shape

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return (self.data.astype(jnp.float32) * self.scale).astype(dtype)


def quantize_tensor(w: jax.Array, axis: int = -1) -> QTensor:
    """Symmetric per-channel int8: scale = absmax/127 along all axes
    except ``axis`` (the output-feature axis, kept per-channel)."""
    w32 = jnp.asarray(w, jnp.float32)
    reduce_axes = tuple(i for i in range(w32.ndim)
                        if i != (axis % w32.ndim))
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    data = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(data=data, scale=scale)


def default_predicate(path: tuple, leaf: Any) -> bool:
    """Quantize large matmul kernels only: param named 'kernel' with
    >=2 dims and enough elements to matter. Embeddings (named
    'embedding'), norms ('scale'/'bias'), and tiny projections pass
    through."""
    name = str(path[-1]) if path else ""
    return (
        "kernel" in name
        and hasattr(leaf, "ndim") and leaf.ndim >= 2
        and leaf.size >= 1 << 16
    )


def _walk(tree: Any, fn: Callable[[tuple, Any], Any], path: tuple = ()):
    if isinstance(tree, dict):
        return {k: _walk(v, fn, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


def quantize_tree(
    params: Any,
    predicate: Optional[Callable[[tuple, Any], bool]] = None,
) -> Any:
    """Swap selected leaves of a param tree for QTensors (same structure
    otherwise). Works on the plain-dict trees flax produces. The default
    predicate is resolved at call time (module attribute) so policy is
    overridable in one place."""
    if predicate is None:
        predicate = default_predicate

    def visit(path, leaf):
        if predicate(path, leaf):
            return quantize_tensor(leaf)
        return leaf

    return _walk(params, visit)


def quantize_tree_host(
    params: Any,
    predicate: Optional[Callable[[tuple, Any], bool]] = None,
) -> Any:
    """quantize_tree pinned to host CPU — the form to use as a loader
    ``transform`` (models/weights.py): quantizing BEFORE device placement
    keeps peak HBM at the int8 footprint. Quantizing after would hold the
    full fp tree and the int8 tree resident together, which is exactly
    what breaks a 7B-class model on a 16 GB chip."""
    with jax.default_device(host_cpu_device()):
        return quantize_tree(params, predicate)


def dequantize_tree(params: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse of quantize_tree — call INSIDE jit so XLA fuses the
    upcast into each kernel's consumers (int8 stays the HBM format)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.dequantize(dtype) if isinstance(leaf, QTensor)
        else leaf,
        params,
        is_leaf=lambda x: isinstance(x, QTensor),
    )


def quantized_apply(apply_fn: Callable, dtype=jnp.bfloat16) -> Callable:
    """Wrap ``apply_fn(params, *args, **kw)`` to accept a quantized tree:
    the returned function dequantizes first, so it drops into any
    call site that jits apply (decode prefill/step, pipelines)."""
    def wrapped(params, *args, **kwargs):
        return apply_fn(dequantize_tree(params, dtype), *args, **kwargs)

    return wrapped


_Q8_SUFFIX = ".q8"
_SCALE_SUFFIX = ".q8_scale"


def save_quantized(params: Any, path: str) -> None:
    """Persist a (possibly quantized) tree as flat safetensors: each
    QTensor becomes two entries, '<path>.q8' (int8) and
    '<path>.q8_scale' (fp32) — so a 7B-class model quantizes ONCE
    offline (tools/quantize_weights.py) and every later boot loads int8
    straight from disk, no fp pass, half the read bytes."""
    import os

    import numpy as np
    from safetensors import numpy as st_numpy

    flat: dict = {}

    def visit(path_t, leaf):
        key = "/".join(str(p) for p in path_t)
        if isinstance(leaf, QTensor):
            flat[key + _Q8_SUFFIX] = np.asarray(leaf.data)
            flat[key + _SCALE_SUFFIX] = np.asarray(
                leaf.scale, dtype=np.float32)
        else:
            flat[key] = np.asarray(leaf)
        return leaf

    _walk(params, visit)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    st_numpy.save_file(flat, path)


def load_quantized(path: str) -> Any:
    """Inverse of :func:`save_quantized`: rebuilds the tree with
    QTensor leaves (host arrays; push with tree_map(jnp.asarray, .))."""
    from cassmantle_tpu.models.weights import load_safetensors, set_in_tree

    flat = load_safetensors(path)
    tree: dict = {}
    for key, value in flat.items():
        if key.endswith(_SCALE_SUFFIX):
            continue
        if key.endswith(_Q8_SUFFIX):
            base = key[: -len(_Q8_SUFFIX)]
            set_in_tree(tree, base,
                        QTensor(data=value,
                                scale=flat[base + _SCALE_SUFFIX]))
        else:
            set_in_tree(tree, key, value)
    return tree


def tree_nbytes(params: Any) -> int:
    """HBM footprint of a (possibly quantized) tree, in bytes."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += getattr(leaf, "nbytes", 0)
    return total


def quantization_error(w: jax.Array, axis: int = -1) -> float:
    """Relative L2 reconstruction error (diagnostics/tests)."""
    q = quantize_tensor(w, axis)
    w32 = jnp.asarray(w, jnp.float32)
    err = jnp.linalg.norm(q.dequantize(jnp.float32) - w32)
    return float(err / (jnp.linalg.norm(w32) + 1e-9))


# ---------------------------------------------------------------------------
# W8A8: activation quantization + the serving tree transform (ISSUE 20)
# ---------------------------------------------------------------------------

#: int8 symmetric range. 127 (not 128) keeps the grid symmetric so
#: negation is exact and no zero-point correction term is needed.
ACT_QMAX = 127.0

#: fp8 e4m3 finite max — the "127" of the fp8 grid when hardware
#: supports fp8 matmuls behind the same interface (ops/quant_matmul.py).
FP8_E4M3_MAX = 448.0

#: absmax floor when computing activation scales: an all-zero
#: activation tensor (padded slot, masked batch row) must not produce a
#: 0 scale and a NaN-producing divide.
_ACT_EPS = 1e-8


def qmax_for(dtype) -> float:
    """Largest representable magnitude of the quantized grid."""
    if jnp.dtype(dtype) == jnp.int8:
        return ACT_QMAX
    return FP8_E4M3_MAX


def act_absmax(x: jax.Array, per_token: bool = False) -> jax.Array:
    """absmax statistic for activation scaling: a scalar (per-tensor,
    image pipelines) or shape (..., 1) reduced over the feature axis
    (per-token, the LM path — decode activations are outlier-heavy per
    position, so per-token scales cost one row-max and buy back most of
    the quality)."""
    x32 = jnp.abs(x.astype(jnp.float32))
    if per_token:
        return jnp.max(x32, axis=-1, keepdims=True)
    return jnp.max(x32)


def act_scale_from_absmax(absmax, dtype=jnp.int8) -> jax.Array:
    """absmax → symmetric scale on the target grid (int8 or fp8)."""
    return jnp.maximum(jnp.asarray(absmax, jnp.float32), _ACT_EPS) \
        / qmax_for(dtype)


def quantize_act(x: jax.Array, scale: jax.Array,
                 dtype=jnp.int8) -> jax.Array:
    """Quantize activations with a precomputed scale. int8 rounds and
    clips; fp8 just scales and casts (the fp8 grid rounds in hardware).
    Stays pure elementwise so XLA fuses it into the producer (GN/SiLU/
    norm epilogue) — the quantized tensor is written to HBM at one byte
    per element, never at full width."""
    x32 = x.astype(jnp.float32) / scale
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.clip(jnp.round(x32), -ACT_QMAX, ACT_QMAX) \
            .astype(jnp.int8)
    return jnp.clip(x32, -FP8_E4M3_MAX, FP8_E4M3_MAX).astype(dtype)


class ActQTensor(NamedTuple):
    """A w8a8 weight leaf: int8 data + per-out-channel fp32 weight scale
    + optional STATIC per-tensor activation scale for this site (fp32
    scalar from the calibration artifact; ``None`` selects dynamic
    in-graph absmax scaling).

    Deliberately a distinct type from :class:`QTensor`: w8a16 trees are
    dequantized wholesale before apply (modules never see them), while
    ActQTensor leaves flow INTO apply and module code branches on them
    (models/layers.py ``QDense``). ``act_scale=None`` vs an array
    changes the pytree structure — that choice is fixed per pipeline
    build (calibrated or not), so bucket jits see one stable structure
    and never recompile over it."""

    data: jax.Array                    # int8 (or fp8), original shape
    scale: jax.Array                   # fp32 weight scale, per out-channel
    act_scale: Optional[jax.Array]     # fp32 scalar static act scale | None

    @property
    def shape(self):
        return self.data.shape

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return (self.data.astype(jnp.float32) * self.scale).astype(dtype)


def quantize_tensor_act(w: jax.Array, axis: int = -1,
                        act_scale: Optional[jax.Array] = None,
                        dtype=jnp.int8) -> ActQTensor:
    """quantize_tensor, but produce a w8a8 leaf (optionally carrying the
    site's static activation scale)."""
    if jnp.dtype(dtype) == jnp.int8:
        q = quantize_tensor(w, axis)
        data, scale = q.data, q.scale
    else:
        w32 = jnp.asarray(w, jnp.float32)
        reduce_axes = tuple(i for i in range(w32.ndim)
                            if i != (axis % w32.ndim))
        absmax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / FP8_E4M3_MAX, 1.0)
        data = jnp.clip(w32 / scale, -FP8_E4M3_MAX,
                        FP8_E4M3_MAX).astype(dtype)
    if act_scale is not None:
        act_scale = jnp.asarray(act_scale, jnp.float32)
    return ActQTensor(data=data, scale=scale, act_scale=act_scale)


#: Module names whose 'kernel' param is a w8a8-capable DENSE site: the
#: QDense instances in models/layers.py (attention q/k/v/qkv/kv/out
#: projections, transformer MLP fc1/fc2, GEGLU proj/out). The names are
#: a whitelist on purpose — plain nn.Dense sites (time embeds,
#: SpatialTransformer proj_in/proj_out, heads) would crash on a
#: quantized leaf, so the predicate must only ever select sites whose
#: module code branches on ActQTensor.
W8A8_DENSE_MODULES = frozenset(
    {"q", "k", "v", "qkv", "kv", "out", "proj", "fc1", "fc2"})

#: Module names whose 'kernel' is a w8a8-capable 3x3 CONV site: the
#: Conv3x3Params sites consumed by the fused GN+SiLU+conv glue
#: (models/layers.py fused_gn_silu_conv3x3). 1x1 skips, conv_in/out and
#: up/downsamplers are plain nn.Conv and stay fp.
W8A8_CONV_MODULES = frozenset({"conv1", "conv2"})

#: Minimum element count for a kernel to be worth quantizing — same
#: rationale as default_predicate. Tests override via the ``min_size``
#: argument (tiny-geometry kernels are below any sensible floor).
W8A8_MIN_SIZE = 1 << 16


def w8a8_default_predicate(path: tuple, leaf: Any,
                           min_size: int = W8A8_MIN_SIZE) -> bool:
    """True for kernel leaves at w8a8-capable sites (see the module
    whitelists above)."""
    if not path or str(path[-1]) != "kernel":
        return False
    if not hasattr(leaf, "ndim") or leaf.size < min_size:
        return False
    parent = str(path[-2]) if len(path) >= 2 else ""
    if leaf.ndim == 2 and parent in W8A8_DENSE_MODULES:
        return True
    return (leaf.ndim == 4 and leaf.shape[:2] == (3, 3)
            and parent in W8A8_CONV_MODULES)


def site_key(path: tuple) -> str:
    """Calibration-artifact key for a kernel param path: the module
    path, '/'-joined — identical to the key ``note_act_stat`` records
    (flax ``self.path`` of the owning module). A leading ``params``
    segment (the flax variable-collection root present in full
    variable trees but not in module paths) is stripped so both sides
    derive the same key."""
    parts = [str(p) for p in path[:-1]]
    if parts and parts[0] == "params":
        parts = parts[1:]
    return "/".join(parts)


def w8a8_tree(params: Any,
              act_scales: Optional[dict] = None,
              predicate: Optional[Callable[[tuple, Any], bool]] = None,
              dtype=jnp.int8) -> Any:
    """Swap w8a8-capable kernel leaves for ActQTensors. ``act_scales``
    maps site keys (:func:`site_key`) to calibrated absmax floats; sites
    present in the map get a STATIC activation scale folded in, absent
    sites fall back to dynamic in-graph scaling. One tree transform =
    quantize-once-at-load; never call this per dispatch (lint-pinned:
    analysis/recompile.py quant-in-dispatch)."""
    if predicate is None:
        predicate = w8a8_default_predicate

    def visit(path, leaf):
        if not predicate(path, leaf):
            return leaf
        a_scale = None
        if act_scales is not None:
            absmax = act_scales.get(site_key(path))
            if absmax is not None:
                a_scale = act_scale_from_absmax(absmax, dtype)
        return quantize_tensor_act(leaf, act_scale=a_scale, dtype=dtype)

    return _walk(params, visit)


def w8a8_tree_host(params: Any,
                   act_scales: Optional[dict] = None,
                   predicate: Optional[Callable] = None,
                   dtype=jnp.int8) -> Any:
    """w8a8_tree pinned to host CPU — the loader-transform form (same
    peak-HBM argument as :func:`quantize_tree_host`)."""
    with jax.default_device(host_cpu_device()):
        return w8a8_tree(params, act_scales, predicate, dtype)


def w8a8_site_count(params: Any) -> int:
    """Number of ActQTensor leaves in a tree (diagnostics/tests)."""
    count = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, ActQTensor)):
        if isinstance(leaf, ActQTensor):
            count += 1
    return count


def w8a8_calibrated(params: Any) -> bool:
    """True when any ActQTensor leaf carries a STATIC activation scale
    (i.e. the tree was built against a matching calibration artifact;
    dynamic-absmax trees have ``act_scale=None`` everywhere)."""
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, ActQTensor)):
        if isinstance(leaf, ActQTensor) and leaf.act_scale is not None:
            return True
    return False


# -- calibration stat recorder ----------------------------------------------
# The calibration pass (parallel/calibrate.py) runs the UNMODIFIED fp
# path eagerly and collects per-site activation absmax through this
# thread-local sink. Module code at w8a8 sites calls note_act_stat with
# its flax path + the activation tensor; outside a collect_act_stats()
# context that call is a single falsy attribute read — zero traced ops,
# zero serving cost. Inside, values are reduced to host floats, which
# is why calibration must run eagerly (a tracer is skipped, never
# synced — so the recorder can't accidentally introduce a host sync
# into a jitted serving path either).

_act_tls = threading.local()


def act_stats_active() -> bool:
    return getattr(_act_tls, "sink", None) is not None


@contextmanager
def collect_act_stats():
    """Context manager yielding a dict that fills with
    {site_key: absmax float} as fp forwards run eagerly inside it."""
    sink: dict = {}
    prev = getattr(_act_tls, "sink", None)
    _act_tls.sink = sink
    try:
        yield sink
    finally:
        _act_tls.sink = prev


def note_act_stat(site: str, value: jax.Array) -> None:
    """Record max(|value|) for ``site`` into the active sink. No-op when
    no sink is active or under a trace (calibration is eager by
    contract)."""
    sink = getattr(_act_tls, "sink", None)
    if sink is None or isinstance(value, jax.core.Tracer):
        return
    # concrete array on host: float() here is a deliberate sync — this
    # only ever executes inside an eager calibration pass
    absmax = float(jnp.max(jnp.abs(value.astype(jnp.float32))))
    sink[site] = max(sink.get(site, 0.0), absmax)
