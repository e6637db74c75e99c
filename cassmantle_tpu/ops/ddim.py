"""DDIM sampler as a jit-compiled ``lax.scan``.

Replaces the reference's remote txt2img call (backend.py:270-295) with an
on-device denoise loop: the entire 50-step trajectory compiles to ONE XLA
computation — no host round-trips between steps, no data-dependent Python
control flow (SURVEY.md §7 stage 3). Classifier-free guidance runs the
conditional and unconditional halves in a single 2B batch so the UNet's
matmuls stay large for the MXU.

Schedule: Stable Diffusion's "scaled linear" beta schedule (1000 train
steps), strided to ``num_steps`` inference steps; eta=0 (deterministic DDIM)
by default, eta>0 adds the stochastic DDPM-style term.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp



def alpha_bars_full(
    num_train_steps: int = 1000,
    beta_start: float = 0.00085,
    beta_end: float = 0.012,
):
    """ᾱ_t for SD's scaled-linear beta schedule, fp64 numpy (host-side).

    The single source of the schedule constants — every sampler kind
    (DDIM here, Euler/DPM++ in ops/samplers.py) derives from this so
    they all integrate the same discretization of the same ODE.
    """
    import numpy as np

    betas = np.linspace(beta_start**0.5, beta_end**0.5, num_train_steps,
                        dtype=np.float64) ** 2
    return np.cumprod(1.0 - betas)


def strided_timesteps(num_steps: int, num_train_steps: int = 1000):
    """Descending int32 inference timesteps, diffusers "leading" spacing
    (t = i·stride)."""
    import numpy as np

    stride = num_train_steps // num_steps
    return (np.arange(num_steps) * stride)[::-1].astype(np.int32)


@dataclasses.dataclass(frozen=True)
class DDIMSchedule:
    """Precomputed per-inference-step coefficients (host-side, tiny)."""

    timesteps: jnp.ndarray        # (T,) int32, descending
    alpha_bars: jnp.ndarray       # (T,) float32 ᾱ_t
    alpha_bars_prev: jnp.ndarray  # (T,) float32 ᾱ_{t-1}

    @staticmethod
    def create(
        num_steps: int,
        num_train_steps: int = 1000,
        beta_start: float = 0.00085,
        beta_end: float = 0.012,
        start: int = 0,
    ) -> "DDIMSchedule":
        """``start`` > 0 drops the first inference steps (img2img tails)."""
        import numpy as np

        ab_full = alpha_bars_full(num_train_steps, beta_start, beta_end)
        ts = strided_timesteps(num_steps, num_train_steps)[start:]
        ab = ab_full[ts].astype(np.float32)
        ab_prev = np.concatenate(
            [ab_full[ts[1:]], [1.0]]
        ).astype(np.float32)
        return DDIMSchedule(
            timesteps=jnp.asarray(ts),
            alpha_bars=jnp.asarray(ab),
            alpha_bars_prev=jnp.asarray(ab_prev),
        )


def ddim_update(x, eps, a_t, a_prev):
    """One deterministic DDIM transition x_t -> x_{t-1} (eta = 0)."""
    x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
    dir_xt = jnp.sqrt(1.0 - a_prev) * eps
    return jnp.sqrt(a_prev) * x0 + dir_xt


def ddim_sample_deepcache(
    denoise_full: Callable,       # (x, t) -> (eps, deep_features)
    denoise_shallow: Callable,    # (x, t, deep_features) -> eps
    latents: jax.Array,
    schedule: DDIMSchedule,
) -> jax.Array:
    """DDIM with deep-feature reuse (DeepCache-style serving): steps run
    in pairs — a FULL UNet pass whose deepest-levels output is cached,
    then a SHALLOW pass (level-0 blocks only) reusing it. Deep
    activations vary slowly across adjacent steps, so quality stays
    near the full trajectory at ~60% of the compute (models/unet.py
    documents the split). Deterministic (eta=0); even step count
    required."""
    n = schedule.timesteps.shape[0]
    assert n % 2 == 0, f"deepcache pairing needs an even step count, got {n}"

    def pack(a):
        return a.reshape(n // 2, 2)

    @jax.named_scope("denoise_step")
    def pair_step(x, per):
        t, a_t, a_prev = per
        eps, deep = denoise_full(x, t[0])
        x = ddim_update(x, eps, a_t[0], a_prev[0])
        eps = denoise_shallow(x, t[1], deep)
        x = ddim_update(x, eps, a_t[1], a_prev[1])
        return x, None

    final, _ = jax.lax.scan(
        pair_step, latents,
        (pack(schedule.timesteps), pack(schedule.alpha_bars),
         pack(schedule.alpha_bars_prev)),
    )
    return final


# -- encoder propagation (Faster Diffusion, PAPERS.md) -----------------------
#
# The UNet's ENCODER (conv_in + down levels + mid block) drifts slowly
# across adjacent denoise steps; the decoder (up path) is what turns the
# current x_t into eps. Encoder propagation runs the full UNet only at
# KEY steps, captures the encoder feature cache (skip stack + up-path
# entry, models/unet.py ``return_skips``), and at the propagated steps
# in between runs ONLY the decoder against that cache. Because the
# decoder never reads x_t (x_t enters the UNet solely through the
# encoder), every propagated eps in a segment depends only on the cache
# and its own timestep — so a whole segment's decoder passes stack into
# ONE batched forward (the paper's parallel-decoder follow-on win).


def encprop_disabled() -> bool:
    """Operator kill switch (docs/DEPLOY.md §6): any truthy
    CASSMANTLE_NO_ENCPROP reverts encprop-configured serving to full
    forwards at every step (read at pipeline trace time, like
    CASSMANTLE_NO_FUSED_CONV — set it before serving starts)."""
    import os

    return os.environ.get("CASSMANTLE_NO_ENCPROP", "").lower() \
        not in ("", "0", "false", "no", "off")


def encprop_key_indices(num_steps: int, stride: int,
                        dense_steps: int = 0):
    """Key-step indices for an encprop schedule: the first
    ``dense_steps`` positions are ALL keys (encoder features drift
    fastest early in sampling, per Faster Diffusion — denser keys
    there), then every ``stride``-th step. Step 0 is always a key (the
    first propagated step needs a cache to exist). Host-side numpy; the
    single source of the key/propagated split — the sampler engine,
    the pipelines' accounting counters, and the cost model in
    tools/profile_unet.py all derive from it."""
    import numpy as np

    assert stride >= 1, f"encprop stride must be >= 1, got {stride}"
    assert 0 <= dense_steps <= num_steps, (
        f"dense_steps {dense_steps} outside [0, {num_steps}]")
    dense = list(range(dense_steps))
    rest = list(range(dense_steps, num_steps, stride))
    return np.asarray(dense + rest, dtype=np.int64)


def _encprop_plan(num_steps: int, stride: int, dense_steps: int):
    """(dense prefix length, full-segment count, tail length): after the
    dense all-key prefix the remaining steps split into segments of
    exactly ``stride`` (key + stride-1 propagated) plus one shorter
    tail segment for the remainder."""
    rest = num_steps - dense_steps
    return dense_steps, rest // stride, rest % stride


def encprop_step_counts(num_steps: int, stride: int, dense_steps: int,
                        deepcache: bool = False):
    """(key, shallow, propagated) step counts for a schedule — the
    accounting the ``pipeline.encprop_*`` diagnosis counters report.
    Without deepcache, shallow is 0 and every non-key step is a
    decoder-only propagated forward; in the composed loop the SECOND
    step of each (length ≥ 2) segment is a DeepCache shallow pass
    (fresh level-0 encoder, reads x_t — NOT a decoder-only forward),
    so it must not be counted as propagated."""
    keys = len(encprop_key_indices(num_steps, stride, dense_steps))
    shallow = 0
    if deepcache:
        _, nseg, tail = _encprop_plan(num_steps, stride, dense_steps)
        shallow = (nseg if stride >= 2 else 0) + (1 if tail >= 2 else 0)
    return keys, shallow, num_steps - keys - shallow


def encprop_sample(
    spec: dict,
    denoise_key: Callable,      # (x, t) -> (eps, skips_cache[, deep])
    denoise_prop: Callable,     # (skips_cache, ts (P,)) -> (P, B, ...) eps
    latents: jax.Array,
    stride: int,
    dense_steps: int = 0,
    denoise_shallow: Optional[Callable] = None,
    batch_props: bool = True,
) -> jax.Array:
    """Generic encoder-propagation sampling engine, parameterized by a
    solver ``spec`` so DDIM/Euler/DPM++(2M) share one loop:

    - ``spec["timesteps"]``: (T,) int32 descending;
    - ``spec["coefs"]``: tuple of (T,) per-step coefficient arrays;
    - ``spec["init"](latents) -> carry`` (tuple of latent-shaped arrays);
    - ``spec["x_for"](carry, coefs_i) -> x`` the denoiser input;
    - ``spec["update"](carry, eps, coefs_i) -> carry``;
    - ``spec["final"](carry) -> x0`` latents.

    The loop runs as two ``lax.scan``s — the dense all-key prefix, then
    uniform (key + stride-1 propagated) segments — plus an unrolled
    tail for the remainder, so compile cost stays one key body + one
    segment body regardless of step count (never 50 unrolled UNets).
    At stride 1 every step is a key step and the math reduces exactly
    to the plain sampler's scan (the stride-1 bit-parity bar,
    tests/test_encprop.py).

    ``denoise_shallow`` composes DeepCache: when given, ``denoise_key``
    must also return the deep cache, the SECOND step of each segment
    runs as a DeepCache shallow pass (fresh level-0 encoder + cached
    deep activation — it still sees x_t), and only the remaining steps
    propagate. Deep-cache refreshes then happen exactly at encoder key
    steps (deep cache keys ⊆ encoder keys).

    ``batch_props=False`` runs each propagated step as its own
    single-timestep decoder call — the reference arm of the
    batched-decoder equivalence test."""
    ts = spec["timesteps"]
    coefs = tuple(spec["coefs"])
    n = int(ts.shape[0])
    dense, nseg, tail = _encprop_plan(n, stride, dense_steps)

    def coefs_at(arrs, i):
        return tuple(a[i] for a in arrs)

    def key_step(carry, t, coefs_i):
        out = denoise_key(spec["x_for"](carry, coefs_i), t)
        eps, cache, rest = out[0], out[1], out[2:]
        return spec["update"](carry, eps, coefs_i), cache, rest

    def prop_updates(carry, cache, seg_ts, seg_coefs, start):
        """Advance positions ``start..len-1`` of a segment off one
        batched decoder forward (or per-step forwards when unbatched)."""
        p = seg_ts.shape[0] - start
        if p <= 0:
            return carry
        if batch_props:
            eps_all = denoise_prop(cache, seg_ts[start:])
        for j in range(p):
            if not batch_props:
                eps = denoise_prop(cache, seg_ts[start + j:start + j + 1])[0]
            else:
                eps = eps_all[j]
            carry = spec["update"](
                carry, eps, coefs_at(seg_coefs, start + j))
        return carry

    def segment(carry, seg_ts, seg_coefs):
        carry, cache, rest = key_step(carry, seg_ts[0], coefs_at(seg_coefs, 0))
        start = 1
        if denoise_shallow is not None and seg_ts.shape[0] > 1:
            eps = denoise_shallow(
                spec["x_for"](carry, coefs_at(seg_coefs, 1)),
                seg_ts[1], rest[0])
            carry = spec["update"](carry, eps, coefs_at(seg_coefs, 1))
            start = 2
        return prop_updates(carry, cache, seg_ts, seg_coefs, start)

    carry = spec["init"](latents)
    if dense:
        @jax.named_scope("denoise_step")
        def dense_body(c, per):
            t, coefs_i = per[0], per[1:]
            c, _, _ = key_step(c, t, coefs_i)
            return c, None

        carry, _ = jax.lax.scan(
            dense_body, carry, (ts[:dense],) + tuple(a[:dense] for a in coefs))
    if nseg:
        stop = dense + nseg * stride

        def pack(a):
            return a[dense:stop].reshape(nseg, stride)

        @jax.named_scope("denoise_step")
        def seg_body(c, per):
            seg_ts, seg_coefs = per[0], per[1:]
            return segment(c, seg_ts, seg_coefs), None

        carry, _ = jax.lax.scan(
            seg_body, carry, (pack(ts),) + tuple(pack(a) for a in coefs))
    if tail:
        lo = n - tail
        carry = segment(carry, ts[lo:], tuple(a[lo:] for a in coefs))
    return spec["final"](carry)


def ddim_spec(schedule: DDIMSchedule) -> dict:
    """DDIM solver spec for :func:`encprop_sample` — the per-step
    arithmetic is :func:`ddim_update` verbatim, so a stride-1 encprop
    trajectory is bit-identical to :func:`ddim_sample` at eta 0."""
    return {
        "timesteps": schedule.timesteps,
        "coefs": (schedule.alpha_bars, schedule.alpha_bars_prev),
        "init": lambda latents: (latents,),
        "x_for": lambda carry, coefs_i: carry[0],
        "update": lambda carry, eps, coefs_i: (
            ddim_update(carry[0], eps, coefs_i[0], coefs_i[1]),),
        "final": lambda carry: carry[0],
    }


def ddim_sample_encprop(
    denoise_key: Callable,
    denoise_prop: Callable,
    latents: jax.Array,
    schedule: DDIMSchedule,
    stride: int,
    dense_steps: int = 0,
    denoise_shallow: Optional[Callable] = None,
    batch_props: bool = True,
) -> jax.Array:
    """DDIM with encoder propagation (deterministic, eta=0): full UNet
    forwards only at the key steps of
    :func:`encprop_key_indices`(T, stride, dense_steps); propagated
    steps run the decoder alone against the cached encoder features,
    batched per segment. See :func:`encprop_sample`."""
    return encprop_sample(
        ddim_spec(schedule), denoise_key, denoise_prop, latents,
        stride, dense_steps, denoise_shallow=denoise_shallow,
        batch_props=batch_props)


def ddim_sample(
    denoise: Callable[[jax.Array, jax.Array], jax.Array],
    latents: jax.Array,
    schedule: DDIMSchedule,
    eta: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Run the full DDIM loop as a lax.scan.

    ``denoise(x_t, t)`` predicts noise ε for the (already guided) batch.
    ``latents`` is x_T ~ N(0, I). Returns x_0-schedule-final latents.
    """
    if eta > 0.0 and rng is None:
        raise ValueError("eta > 0 requires an rng key")
    noise_rng = rng if rng is not None else jax.random.PRNGKey(0)

    @jax.named_scope("denoise_step")
    def step(carry, per_step):
        x, key = carry
        t, a_t, a_prev = per_step
        eps = denoise(x, t)
        x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
        sigma = eta * jnp.sqrt(
            (1.0 - a_prev) / (1.0 - a_t)
        ) * jnp.sqrt(1.0 - a_t / a_prev)
        dir_xt = jnp.sqrt(jnp.maximum(1.0 - a_prev - sigma**2, 0.0)) * eps
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, x.shape, dtype=x.dtype)
        x_prev = jnp.sqrt(a_prev) * x0 + dir_xt + sigma * noise
        return (x_prev, key), None

    (final, _), _ = jax.lax.scan(
        step,
        (latents, noise_rng),
        (schedule.timesteps, schedule.alpha_bars, schedule.alpha_bars_prev),
    )
    return final


def _cfg_context(context, uncond_context, addition_embeds,
                 uncond_addition_embeds):
    """Stack the unconditional and conditional conditioning into the 2B
    CFG batch (shared by every CFG denoiser variant)."""
    full_context = jnp.concatenate([uncond_context, context], axis=0)
    full_addition = None
    if addition_embeds is not None:
        uncond_add = (uncond_addition_embeds
                      if uncond_addition_embeds is not None
                      else jnp.zeros_like(addition_embeds))
        full_addition = jnp.concatenate([uncond_add, addition_embeds], axis=0)
    return full_context, full_addition


def _cfg_double(x, t):
    """(x, t) -> the duplicated (x2, t2) the 2B CFG batch consumes."""
    x2 = jnp.concatenate([x, x], axis=0)
    t2 = jnp.full((2 * x.shape[0],), t, dtype=jnp.int32)
    return x2, t2


def _cfg_guide(eps, guidance_scale):
    eps_uncond, eps_cond = jnp.split(eps, 2, axis=0)
    return eps_uncond + guidance_scale * (eps_cond - eps_uncond)


def make_cfg_denoiser(
    unet_apply: Callable,
    params,
    context: jax.Array,          # (B, S, D) conditional text states
    uncond_context: jax.Array,   # (B, S, D) unconditional ("") states
    guidance_scale: float,
    addition_embeds: Optional[jax.Array] = None,         # (B, A) SDXL
    uncond_addition_embeds: Optional[jax.Array] = None,  # (B, A) SDXL
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Classifier-free guidance denoiser: one 2B-batch UNet call per step.

    For SDXL, ``addition_embeds`` carries the pooled-text + time-ids
    micro-conditioning vector; it rides the same 2B batch as the context.
    """
    full_context, full_addition = _cfg_context(
        context, uncond_context, addition_embeds, uncond_addition_embeds)

    def denoise(x, t):
        x2, t2 = _cfg_double(x, t)
        if full_addition is None:
            eps = unet_apply(params, x2, t2, full_context)
        else:
            eps = unet_apply(params, x2, t2, full_context, full_addition)
        return _cfg_guide(eps, guidance_scale)

    return denoise


def make_slot_denoiser(
    unet_apply: Callable,
    guidance_scale: float,
) -> Callable:
    """CFG denoiser for the staged step-level serving loop
    (serving/stages.py): conditioning arrives as per-slot ARGUMENTS
    (slot contents change between steps, so nothing can be closed over)
    and the timestep is a per-slot ``(C,)`` vector — each slot sits at
    its own schedule position. Otherwise the arithmetic is exactly
    :func:`make_cfg_denoiser`'s 2C-batch CFG, so a solo slot's
    trajectory matches the monolithic scan bit for bit (the rows of the
    CFG batch are computation-independent)."""

    def denoise(params, x, t, context, uncond_context,
                addition_embeds=None, uncond_addition_embeds=None):
        full_context, full_addition = _cfg_context(
            context, uncond_context, addition_embeds,
            uncond_addition_embeds)
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t, t], axis=0)
        if full_addition is None:
            eps = unet_apply(params, x2, t2, full_context)
        else:
            eps = unet_apply(params, x2, t2, full_context, full_addition)
        return _cfg_guide(eps, guidance_scale)

    return denoise


def make_cfg_denoiser_pair(
    unet_apply: Callable,
    params,
    context: jax.Array,
    uncond_context: jax.Array,
    guidance_scale: float,
    addition_embeds: Optional[jax.Array] = None,
    uncond_addition_embeds: Optional[jax.Array] = None,
) -> Tuple[Callable, Callable]:
    """CFG denoiser pair for deep-feature reuse: ``full(x, t)`` returns
    (guided eps, deep features of the 2B CFG batch); ``shallow(x, t,
    deep)`` reuses them. The cache rides the same cond+uncond batch, so
    both guidance halves reuse their own deep features. SDXL
    micro-conditioning rides along exactly as in make_cfg_denoiser."""
    full_context, full_addition = _cfg_context(
        context, uncond_context, addition_embeds, uncond_addition_embeds)

    def denoise_full(x, t):
        x2, t2 = _cfg_double(x, t)
        eps, deep = unet_apply(params, x2, t2, full_context,
                               full_addition, None, True)
        return _cfg_guide(eps, guidance_scale), deep

    def denoise_shallow(x, t, deep):
        x2, t2 = _cfg_double(x, t)
        eps = unet_apply(params, x2, t2, full_context, full_addition, deep)
        return _cfg_guide(eps, guidance_scale)

    return denoise_full, denoise_shallow


def _tile_rows(t: jax.Array, p) -> jax.Array:
    """Tile a (B, ...) tensor to (P*B, ...) — row b of copy p lands at
    p*B + b, matching ``jnp.repeat(ts, B)`` timestep ordering."""
    return jnp.tile(t, (p,) + (1,) * (t.ndim - 1))


def make_cfg_denoiser_encprop(
    unet_apply: Callable,
    params,
    context: jax.Array,
    uncond_context: jax.Array,
    guidance_scale: float,
    addition_embeds: Optional[jax.Array] = None,
    uncond_addition_embeds: Optional[jax.Array] = None,
    deepcache: bool = False,
) -> Tuple[Callable, Callable, Optional[Callable]]:
    """CFG denoiser triple for encoder propagation:

    - ``key(x, t)`` — full forward; returns (guided eps, encoder cache
      [, deep cache when ``deepcache``]). The cache rides the 2B
      cond+uncond batch, so both guidance halves propagate their own
      encoder features.
    - ``prop(cache, ts)`` — ONE batched decoder forward for a whole
      propagated segment: the 2B cache rows tile P× along batch
      (copy p = timestep ts[p] for every row), the decoder runs once at
      (P*2B), and the result unstacks to per-step guided eps (P, B,
      H, W, C). Exact relative to P single-step decoder calls — batch
      rows are computation-independent (the batched-decoder equivalence
      bar, tests/test_encprop.py).
    - ``shallow(x, t, deep)`` — the DeepCache shallow pass for the
      composed loop; None unless ``deepcache``.
    """
    full_context, full_addition = _cfg_context(
        context, uncond_context, addition_embeds, uncond_addition_embeds)

    def denoise_key(x, t):
        x2, t2 = _cfg_double(x, t)
        if deepcache:
            eps, deep, cache = unet_apply(
                params, x2, t2, full_context, full_addition, None, True,
                None, True)
            return _cfg_guide(eps, guidance_scale), cache, deep
        eps, cache = unet_apply(
            params, x2, t2, full_context, full_addition, None, False,
            None, True)
        return _cfg_guide(eps, guidance_scale), cache

    def denoise_prop(cache, ts):
        p = ts.shape[0]
        b2 = full_context.shape[0]                     # 2B CFG batch
        skips, up_entry = cache
        tiled = (tuple(_tile_rows(s, p) for s in skips),
                 _tile_rows(up_entry, p))
        t_all = jnp.repeat(ts.astype(jnp.int32), b2)   # (P*2B,)
        ctx_all = _tile_rows(full_context, p)
        add_all = (None if full_addition is None
                   else _tile_rows(full_addition, p))
        eps = unet_apply(params, None, t_all, ctx_all, add_all, None,
                         False, tiled)
        eps = eps.reshape((p, b2) + eps.shape[1:])
        eps_uncond, eps_cond = jnp.split(eps, 2, axis=1)
        return eps_uncond + guidance_scale * (eps_cond - eps_uncond)

    denoise_shallow = None
    if deepcache:
        def denoise_shallow(x, t, deep):
            x2, t2 = _cfg_double(x, t)
            eps = unet_apply(params, x2, t2, full_context, full_addition,
                             deep)
            return _cfg_guide(eps, guidance_scale)

    return denoise_key, denoise_prop, denoise_shallow


def initial_latents(
    rng: jax.Array, batch: int, image_size: int, vae_scale: int = 8,
    channels: int = 4,
) -> jax.Array:
    h = w = image_size // vae_scale
    return jax.random.normal(rng, (batch, h, w, channels), dtype=jnp.float32)
