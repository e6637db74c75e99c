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
from typing import Callable, Optional

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


def initial_latents(
    rng: jax.Array, batch: int, image_size: int, vae_scale: int = 8,
    channels: int = 4,
) -> jax.Array:
    h = w = image_size // vae_scale
    return jax.random.normal(rng, (batch, h, w, channels), dtype=jnp.float32)
