"""Analytic roofline cost model: FLOPs + HBM bytes per serving dispatch.

PERF_NOTES has carried analytic per-image TF figures since round 6
(510.6 vs 686.6 TF/image, "58% of ceiling") — but only as doc prose.
This module makes the analytic model a *runtime* object (ISSUE 14):

- :func:`trace_cost` derives FLOPs and an HBM-traffic proxy from a
  function's jaxpr (dot/conv only, scan trip counts multiplied) —
  shape-only, so it runs on any backend, against ``ShapeDtypeStruct``
  params, without executing anything. ``tools/profile_unet.py`` shares
  the same per-eqn math (:func:`eqn_flops`), so the profiler tables and
  the live attribution can never disagree.
- ``data/cost_model.json`` (written by ``tools/profile_unet.py
  --emit-cost-model``, drift-gated by tests/test_obs_device.py) is the
  committed artifact: per pipeline/stage/bucket analytic FLOPs + HBM
  bytes for the production configs, keyed by a config-digest signature.
- :func:`flops_per_item` is what the serving pipelines call per
  dispatch variant: committed entry when the runtime signature matches
  the artifact (production configs — no tracing at startup), else a
  trace-once of the pipeline's OWN jitted impl (exact for any config,
  brownout tiers included — the jaxpr is the truth), cached
  process-wide. The result feeds ``block_timer(flops_est=...)``
  (utils/profiling.py): stage spans gain ``flops_est`` attrs and
  ``pipeline.mxu_utilization`` / ``request.device_flops`` report
  measured-vs-ceiling live (docs/PERF_NOTES.md "Reading the roofline
  live").

The HBM-bytes figure is a roofline *proxy* — operand + result buffer
bytes of every counted op, ignoring XLA fusion (which keeps most
intermediates out of HBM). It upper-bounds true traffic and is emitted
for the artifact's roofline arithmetic, not for live attribution.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import threading
from typing import Callable, Dict, Optional, Tuple

from cassmantle_tpu.utils.logging import get_logger

log = get_logger("costmodel")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COST_MODEL_PATH = os.path.join(_REPO_ROOT, "data", "cost_model.json")

#: Peak dense bf16 FLOP/s of one chip, keyed by the ``device_kind`` jax
#: reports, each with its source. The only table of peaks in the repo:
#: a device that is not here has no utilization, not a default one.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 per chip",
    },
}


class UnknownDeviceKind(LookupError):
    """A TPU whose ``device_kind`` has no row in :data:`CHIP_PEAKS`."""


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak bf16 FLOP/s for a ``device_kind``; raises
    :class:`UnknownDeviceKind` for one the table does not hold."""
    try:
        return float(CHIP_PEAKS[device_kind]["bf16_flops"])
    except KeyError:
        raise UnknownDeviceKind(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; "
            f"add it to obs/costmodel.py CHIP_PEAKS with its source "
            f"(known: {sorted(CHIP_PEAKS)})") from None


def chip_peak_flops() -> Optional[float]:
    """Peak FLOP/s the ``pipeline.mxu_utilization`` gauge divides by:
    the attached TPU's row of :data:`CHIP_PEAKS` (an unknown kind
    raises), or None off-TPU — a CPU run has no MXU to be utilized, so
    it renders no gauge."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return peak_flops_for_kind(device.device_kind)


# -- per-eqn analytic math (shared with tools/profile_unet.py) -------------

def eqn_flops(eqn) -> float:
    """Analytic FLOPs of one jaxpr eqn: 2·M·N·K for ``dot_general``,
    2·out·C_in·prod(kernel) for ``conv_general_dilated``, 0 otherwise.
    Shape-derived — backend-independent."""
    name = eqn.primitive.name
    if name == "dot_general":
        dims = eqn.params["dimension_numbers"]
        (lc, _), _ = dims
        a = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        k = math.prod(a[i] for i in lc) or 1
        return 2.0 * math.prod(out) * k
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        rhs_spec = dn.rhs_spec  # (out_c, in_c, *spatial)
        cin = rhs[rhs_spec[1]]
        spatial = [rhs[i] for i in rhs_spec[2:]]
        return 2.0 * math.prod(out) * cin * math.prod(spatial)
    return 0.0


def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    try:
        itemsize = dtype.itemsize
    except AttributeError:  # pragma: no cover - exotic avals
        return 0.0
    return float(math.prod(shape) * itemsize)


def eqn_hbm_bytes(eqn) -> float:
    """HBM-traffic proxy for a counted eqn: operand + result buffer
    bytes (reads + the write). Ignores fusion — an upper bound."""
    if eqn.primitive.name not in ("dot_general", "conv_general_dilated"):
        return 0.0
    total = sum(_aval_bytes(v.aval) for v in eqn.invars)
    total += sum(_aval_bytes(v.aval) for v in eqn.outvars)
    return total


def trace_cost(fn, *args) -> Tuple[float, float]:
    """(FLOPs, HBM-bytes proxy) of ``fn(*args)`` from its jaxpr.

    Scan bodies multiply by their trip count; pjit/cond/other
    sub-jaxprs recurse at the ambient multiplier (a ``while_loop`` body
    counts once — unknown trip count, documented undercount; none of
    the costed serving graphs contain one). Args may be concrete arrays
    or ``ShapeDtypeStruct``s — nothing executes."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    totals = [0.0, 0.0]

    def visit(jx, mult: float = 1.0) -> None:
        for eqn in jx.eqns:
            inner = mult
            if eqn.primitive.name == "scan":
                inner = mult * float(eqn.params.get("length", 1))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    visit(sub.jaxpr, inner)
                elif isinstance(sub, (list, tuple)):
                    for s in sub:
                        if hasattr(s, "jaxpr"):
                            visit(s.jaxpr, inner)
            totals[0] += eqn_flops(eqn) * mult
            totals[1] += eqn_hbm_bytes(eqn) * mult

    visit(jaxpr.jaxpr)
    return totals[0], totals[1]


def params_count(tree) -> int:
    """Total element count of a param pytree (host metadata only —
    works on device arrays, numpy, and ShapeDtypeStructs alike). The
    LM/scorer analytic model: dense decode costs 2·N FLOPs per token."""
    import jax

    return int(sum(
        math.prod(getattr(leaf, "shape", ()) or ())
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")))


def params_bytes(tree) -> int:
    """Total byte size of a param pytree — the per-token weight-read
    floor of an LM decode step (PERF_NOTES "LM decode accounting")."""
    import jax

    return int(sum(
        _aval_bytes(leaf)
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")))


# -- config signatures ------------------------------------------------------
# The committed artifact and the runtime pipeline must derive the SAME
# signature from the same config, or the match silently never fires —
# one definition here, used by --emit-cost-model and the pipelines.

def _digest(*parts) -> str:
    return hashlib.sha256("|".join(repr(p) for p in parts)
                          .encode()).hexdigest()[:16]


def _w8a8_effective(flag: bool) -> bool:
    """The ARMED w8a8 state for signature purposes: under the
    CASSMANTLE_NO_W8A8 kill switch a w8a8 config serves the fp path,
    and its dispatches must resolve the fp cost entry — same rationale
    as effective_sampler_cfg for the consistency kill switch."""
    if not flag:
        return False
    from cassmantle_tpu.ops.quant_matmul import w8a8_disabled

    return not w8a8_disabled()


def t2i_signature(cfg, sampler_cfg=None) -> str:
    """SD1.5 text→image dispatch signature: everything the analytic
    per-image FLOPs depend on (model archs + the sampler geometry —
    ``consistency`` included, since the few-step path runs num_steps
    direct forwards of the same UNet; the ARMED w8a8 state included,
    since quantized serving halves weight-side HBM bytes and the
    committed w8a8 variant carries its own roofline entry)."""
    s = sampler_cfg if sampler_cfg is not None else cfg.sampler
    m = cfg.models
    return _digest("t2i", m.unet.arch(), m.vae.arch(), m.clip_text,
                   s.image_size, s.num_steps, s.kind,
                   s.consistency, _w8a8_effective(m.unet_w8a8))


def sdxl_signature(cfg, sampler_cfg=None) -> str:
    s = sampler_cfg if sampler_cfg is not None else cfg.sampler
    m = cfg.models
    return _digest("sdxl", m.unet.arch(), m.vae.arch(), m.clip_text,
                   m.clip_text_2, s.image_size, s.num_steps, s.kind,
                   s.consistency, _w8a8_effective(m.unet_w8a8))


def lm_signature(mcfg, w8a8: bool = False) -> str:
    """Prompt-LM signature: the model config alone — decode FLOPs are
    2·N(params)·tokens regardless of sampler knobs. ``w8a8``: the
    ARMED lm_w8a8 state (the caller owns the ModelZooConfig; pass
    ``_w8a8_effective(models.lm_w8a8)``) — the quantized tree streams
    half the weight bytes per token, a separate committed entry."""
    return _digest("lm", mcfg, _w8a8_effective(w8a8))


def scorer_signature(mcfg, seq_len: int) -> str:
    return _digest("scorer", mcfg, seq_len)


# -- the committed artifact -------------------------------------------------

_model_lock = threading.Lock()
_model_cache: Optional[Dict] = None
_runtime_cache: Dict[Tuple[str, str], Optional[float]] = {}


def load_cost_model(path: Optional[str] = None) -> Dict:
    """The committed cost-model JSON ({} when absent/unreadable —
    attribution then falls back to trace-once, never crashes serving)."""
    global _model_cache
    if path is not None:  # explicit path: no process cache (tests)
        try:
            with open(path) as f:
                return json.load(f)
        except Exception:
            return {}
    with _model_lock:
        if _model_cache is None:
            try:
                with open(COST_MODEL_PATH) as f:
                    _model_cache = json.load(f)
            except Exception:
                _model_cache = {}
        return _model_cache


def committed_entry(kind: str, signature: str) -> Optional[Dict]:
    """The artifact's entry for this pipeline kind IF its signature
    matches the runtime config (production presets); None otherwise.
    Preset VARIANT entries (e.g. ``t2i_lcm`` — the same pipeline kind
    at a different committed sampler geometry) are found by signature
    scan, so the lcm preset resolves without tracing too: signatures
    are digests over the kind prefix + full config, so a cross-kind
    collision cannot occur."""
    pipelines = load_cost_model().get("pipelines", {})
    entry = pipelines.get(kind)
    if isinstance(entry, dict) and entry.get("signature") == signature:
        return entry
    for other in pipelines.values():
        if isinstance(other, dict) and \
                other.get("signature") == signature:
            return other
    return None


def flops_per_item(kind: str, signature: str,
                   tracer: Optional[Callable[[], float]] = None,
                   ) -> Optional[float]:
    """Per-item (image / token-batch row / encode row) analytic FLOPs
    for a dispatch variant:

    1. the committed ``data/cost_model.json`` entry when the runtime
       signature matches (production configs — zero tracing cost);
    2. else ``tracer()`` — the caller traces its OWN jitted impl
       (exact for brownout tiers), cached process-wide by
       ``(kind, signature)``;
    3. else None — the dispatch simply carries no cost attribution
       (attribution must never break serving).
    """
    key = (kind, signature)
    with _model_lock:
        if key in _runtime_cache:
            return _runtime_cache[key]
    entry = committed_entry(kind, signature)
    value: Optional[float] = None
    if entry is not None:
        try:
            value = float(entry["flops_per_item"])
        except (KeyError, TypeError, ValueError):
            value = None
    if value is None and tracer is not None:
        try:
            value = float(tracer())
        except Exception:
            log.exception("cost-model trace failed for %s; dispatches "
                          "carry no FLOPs attribution", kind)
            value = None
    with _model_lock:
        _runtime_cache[key] = value
    return value


def reset_runtime_cache() -> None:
    """Test seam: drop trace-once results (and the artifact cache)."""
    global _model_cache
    with _model_lock:
        _runtime_cache.clear()
        _model_cache = None
