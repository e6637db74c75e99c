"""Persistent XLA compile cache + big-model param cache locations.

First XLA compiles of the production models are expensive (the SD1.5
sampler alone takes minutes); both the serving pipelines and bench
enable the on-disk compile cache so every later process reuses them.

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set (jax reads it into
``jax_compilation_cache_dir`` itself) no code here sets a directory;
when it is not, the cache is ``<checkout>/.jax_cache`` — a fixed path,
because the path is part of what a later process must agree on to hit.

Cache EFFECTIVENESS is exported (ISSUE 14): jax announces
persistent-cache traffic via ``jax.monitoring`` events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``), and a
listener registered at :func:`enable_compile_cache` mirrors the
process-lifetime totals into the ``jit.cache_hits`` / ``jit.cache_misses``
gauges — so a worker whose cold start burned minutes recompiling
(cache volume lost, key churn from a config change) is attributable
from `/metrics` instead of from a hunch.

Semantics caveat (re-read in jax 0.9.0's ``compilation_cache.
put_executable_and_time``): the ``cache_misses`` event fires only
for misses whose compile was WRITTEN BACK to the cache — compiles
under ``jax_persistent_cache_min_compile_time_secs`` (1.0 s here) or
the min entry size never record a miss. So the pair counts *the
expensive traffic the cache exists for*: hits = expensive compiles it
absorbed, misses = expensive compiles it could not. A cold start made
of sub-second compiles legitimately shows 0/0 — read beside
``jit.compiles``/``jit.compile_s`` (utils/jit_sentinel.py), which
count every compile and what each cost, for the full picture.
"""

from __future__ import annotations

import hashlib
import os
import threading

from cassmantle_tpu.utils.logging import metrics

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")
PARAM_CACHE_DIR = os.environ.get(
    "CASSMANTLE_PARAM_CACHE", os.path.join(_REPO_ROOT, ".param_cache")
)

_enabled = False
_listener_lock = threading.Lock()
_listener_armed = False
_cache_events = {"hits": 0, "misses": 0}

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_cache_event(event: str, **_kw) -> None:
    """jax.monitoring listener: mirror persistent-cache traffic into
    gauges. Must never raise — it runs inside jax's compile path."""
    try:
        if event == _HIT_EVENT:
            _cache_events["hits"] += 1
            metrics.gauge("jit.cache_hits", float(_cache_events["hits"]))
        elif event == _MISS_EVENT:
            _cache_events["misses"] += 1
            metrics.gauge("jit.cache_misses",
                          float(_cache_events["misses"]))
    except Exception:  # pragma: no cover - defensive
        pass


def _arm_cache_listener() -> None:
    global _listener_armed
    with _listener_lock:
        if _listener_armed:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_cache_event)
        _listener_armed = True


def cache_event_counts() -> dict:
    """Process-lifetime persistent-cache hit/miss totals (what the
    gauges mirror) — test/debug seam."""
    return dict(_cache_events)


def enable_compile_cache() -> None:
    global _enabled
    # every pipeline/scorer boot passes through here: piggyback the
    # opt-in jit compile-count sentinel (utils/jit_sentinel.py) so
    # CASSMANTLE_JIT_SENTINEL=1 needs no per-pipeline wiring
    from cassmantle_tpu.utils.jit_sentinel import maybe_enable_from_env

    maybe_enable_from_env()
    # ...and the cache hit/miss listener, so cold-start compile cost is
    # attributable per worker without per-pipeline wiring either
    _arm_cache_listener()
    if _enabled:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _enabled = True


# Bump when a module's param STRUCTURE changes without a config change
# (the digest below only sees the config repr) — a stale cached init
# tree would otherwise load with missing/extra leaves and fail at apply.
# v2: UNet attention out-projections gained their published bias.
_PARAM_SCHEMA_VERSION = 4  # v4: fused qkv in UNet + CLIP/MiniLM


def param_cache_path(name: str, cfg) -> str:
    """Stable cache file name for (model name, config, schema)."""
    digest = hashlib.sha256(
        f"v{_PARAM_SCHEMA_VERSION}:{cfg!r}".encode()).hexdigest()[:16]
    return os.path.join(PARAM_CACHE_DIR, f"{name}-{digest}.safetensors")
