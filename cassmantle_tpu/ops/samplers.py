"""Diffusion samplers beyond DDIM: Euler and DPM-Solver++(2M).

The reference's hosted SDXL endpoint (backend.py:270-295) exposes no
sampler choice; serving locally we can trade steps for latency — DPM++(2M)
at 20-25 steps matches 50-step DDIM quality, roughly halving image latency
on the same chip. All samplers here keep the DDIM contract from ops/ddim.py:

- ``denoise(x_t, t) -> eps`` with x_t in VP space (unit-variance latents),
  ``t`` an int train-timestep — so the CFG denoiser and the UNet are shared
  unchanged across samplers;
- the full trajectory is ONE ``lax.scan`` under jit: per-step coefficients
  are precomputed host-side into fixed-shape arrays (no data-dependent
  control flow, no recompiles per step).

Schedules use SD's scaled-linear betas with "leading" uniform timestep
spacing (t = i·stride, the same spacing DDIMSchedule.create uses, so all
sampler kinds integrate the same discretization of the same ODE).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from cassmantle_tpu.ops.ddim import (
    DDIMSchedule,
    alpha_bars_full as _alpha_bars,
    ddim_sample,
    strided_timesteps as _strided_timesteps,
)

SAMPLER_KINDS = ("ddim", "euler", "dpmpp_2m")

#: PRNG seed of the deterministic re-noise ladder multistep consistency
#: sampling uses between f-evaluations: step noise is
#: ``normal(fold_in(PRNGKey(seed), t), latent_row_shape)`` — a pure
#: function of the TIMESTEP, shared across batch rows. That makes the
#: sampler deterministic (no carried key chain), batch-invariant (a
#: request's trajectory does not depend on what it batched with), and
#: replayable at step granularity by the staged slot stepper (each slot
#: folds its own current timestep), which is what lets few-step
#: requests ride the continuous-batching path (eta>0-style carried
#: chains cannot — see make_slot_sampler's rejection).
CONSISTENCY_NOISE_SEED = 0x1C3


def consistency_disabled() -> bool:
    """Operator kill switch (docs/DEPLOY.md §6): any truthy
    CASSMANTLE_NO_CONSISTENCY reverts consistency-configured serving to
    the TEACHER path — the plain configured sampler kind at
    ``SamplerConfig.consistency_teacher_steps`` — bit-exactly (read at
    pipeline build/trace time: set it before serving starts)."""
    import os

    return os.environ.get("CASSMANTLE_NO_CONSISTENCY", "").lower() \
        not in ("", "0", "false", "no", "off")


def consistency_boundary(sigma, sigma_min, sigma_data: float = 0.5):
    """The consistency-model boundary-condition parameterization
    (c_skip, c_out) at noise level ``sigma`` (k-space,
    sqrt((1-ᾱ)/ᾱ)): f(x, σ) = c_skip(σ)·x + c_out(σ)·x0_pred(x, σ).
    At σ = σ_min this is EXACTLY (1, 0) — f is the identity at the
    clean boundary, the constraint that makes the distilled student a
    consistency function rather than a free-form few-step net.

    Written with ``** 0.5`` (not jnp.sqrt) so host-side schedule
    precomputation stays numpy even when it happens inside a jit trace
    (run_cfg_denoise builds the schedule at pipeline trace time) while
    the SAME expression serves traced sigmas in the distillation
    step."""
    c_skip = sigma_data**2 / ((sigma - sigma_min) ** 2 + sigma_data**2)
    c_out = (sigma_data * (sigma - sigma_min)
             / (sigma**2 + sigma_data**2) ** 0.5)
    return c_skip, c_out


def consistency_renoise(t, shape, dtype=jnp.float32):
    """The deterministic per-step re-noise draw (see
    CONSISTENCY_NOISE_SEED): one latent ROW of noise keyed on the
    timestep, broadcast across the batch. Shared verbatim by the
    monolithic scan, the slot stepper, and the reference loop in
    tests/test_samplers.py."""
    key = jax.random.fold_in(
        jax.random.PRNGKey(CONSISTENCY_NOISE_SEED), t)
    return jax.random.normal(key, shape, dtype)


@dataclasses.dataclass(frozen=True)
class ConsistencySchedule:
    """Few-step consistency/LCM sampling schedule, all step math
    precomputed host-side. Timesteps are drawn FROM THE TEACHER SOLVER
    DISCRETIZATION — the same ``strided_timesteps(teacher_steps)`` grid
    ``ConsistencyDistillTrainer`` trains on (the LCM recipe: the student
    only ever sees schedule positions of the teacher's ODE
    discretization, so serving must query exactly those points, never
    interpolate past them). Within that grid the selection is TRAILING
    (start at the grid's noisiest point, stride down, never reach the
    grid's final t=0 entry) so the LAST f-evaluation sits at a genuinely
    noisy timestep and its output IS the final x0 — touching t=0 would
    spend the final UNet forward evaluating f where the boundary
    condition makes it the identity."""

    timesteps: jnp.ndarray        # (T,) int32 descending, last > 0
    alpha_bars: jnp.ndarray       # (T,) float32 ᾱ at each f-eval step
    alpha_bars_next: jnp.ndarray  # (T,) ᾱ of the re-noise target; last=1
    c_skip: jnp.ndarray           # (T,) boundary coefficients
    c_out: jnp.ndarray            # (T,)

    @staticmethod
    def create(num_steps: int, teacher_steps: int = 50,
               num_train_steps: int = 1000,
               sigma_data: float = 0.5) -> "ConsistencySchedule":
        from cassmantle_tpu.ops.ddim import strided_timesteps

        assert num_steps >= 1
        ab_full = _alpha_bars(num_train_steps)
        # the trainer's grid, minus its final t=0 point (the trainer
        # never queries the student there — skip ≥ 1 — and f is the
        # identity there by the boundary condition)
        grid = strided_timesteps(teacher_steps, num_train_steps)[:-1]
        assert num_steps <= len(grid), (
            f"consistency needs num_steps {num_steps} <= "
            f"teacher_steps-1 = {len(grid)} (the student is only "
            f"trained on the teacher discretization's query points)")
        ts = grid[(len(grid) // num_steps)
                  * np.arange(num_steps)].astype(np.int32)
        ab = ab_full[ts]
        ab_next = np.concatenate([ab[1:], [1.0]])
        sigma = np.sqrt((1.0 - ab) / ab)
        sigma_min = float(np.sqrt((1.0 - ab_full[0]) / ab_full[0]))
        c_skip, c_out = consistency_boundary(sigma, sigma_min, sigma_data)
        f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))  # noqa: E731
        return ConsistencySchedule(
            timesteps=jnp.asarray(ts), alpha_bars=f32(ab),
            alpha_bars_next=f32(ab_next), c_skip=f32(c_skip),
            c_out=f32(c_out))


def consistency_sample(
    denoise: Callable[[jax.Array, jax.Array], jax.Array],
    latents: jax.Array,
    schedule: ConsistencySchedule,
) -> jax.Array:
    """Multistep consistency sampling: per step, ONE UNet forward maps
    the current state straight to an x0 estimate through the boundary
    parameterization, then the state re-noises to the next (lower)
    evaluation timestep — num_steps total UNet forwards per image,
    which is the whole point (docs/PERF_NOTES.md "Few-step
    accounting"). ``latents`` standard normal (VP convention, same as
    every other sampler); one ``lax.scan``, deterministic (see
    consistency_renoise). The final step's ᾱ_next is 1.0, so its
    update reduces exactly to the x0 estimate."""

    @jax.named_scope("denoise_step")
    def step(x, per):
        t, ab, ab_next, c_skip, c_out = per
        eps = denoise(x, t)
        x0 = (x - jnp.sqrt(1.0 - ab) * eps) / jnp.sqrt(ab)
        f = c_skip * x + c_out * x0
        noise = consistency_renoise(t, x.shape[1:], x.dtype)
        x = jnp.sqrt(ab_next) * f + jnp.sqrt(1.0 - ab_next) * noise
        return x, None

    final, _ = jax.lax.scan(
        step, latents,
        (schedule.timesteps, schedule.alpha_bars,
         schedule.alpha_bars_next, schedule.c_skip, schedule.c_out),
    )
    return final


def make_consistency_sampler(num_steps: int, teacher_steps: int = 50):
    """num_steps (1–8) -> ``sample(denoise, latents, rng=None)`` — the
    few-step counterpart of :func:`make_sampler` (rng accepted for
    signature parity and ignored: the re-noise ladder is deterministic
    by construction). ``teacher_steps`` is the solver discretization
    the student was distilled on (``SamplerConfig.
    consistency_teacher_steps``) — the grid the schedule queries."""
    schedule = ConsistencySchedule.create(num_steps, teacher_steps)

    def sample(denoise, latents, rng=None):
        return consistency_sample(denoise, latents, schedule)

    return sample


@dataclasses.dataclass(frozen=True)
class EulerSchedule:
    """k-diffusion sigma ladder; x evolves in k-space (x_vp * sqrt(1+s²))."""

    timesteps: jnp.ndarray   # (T,) int32 descending
    sigmas: jnp.ndarray      # (T+1,) float32, sigmas[-1] == 0

    @staticmethod
    def create(num_steps: int, start: int = 0) -> "EulerSchedule":
        """``start`` > 0 drops the first steps (img2img tails)."""
        ab = _alpha_bars()
        ts = _strided_timesteps(num_steps)[start:]
        sig = np.sqrt((1.0 - ab[ts]) / ab[ts])
        sig = np.concatenate([sig, [0.0]]).astype(np.float32)
        return EulerSchedule(timesteps=jnp.asarray(ts),
                             sigmas=jnp.asarray(sig))


def euler_sample(
    denoise: Callable[[jax.Array, jax.Array], jax.Array],
    latents: jax.Array,
    schedule: EulerSchedule,
    prescaled: bool = False,
) -> jax.Array:
    """Deterministic Euler solver over the k-diffusion ODE.

    ``latents`` is standard normal (VP convention, same as ddim_sample)
    and gets scaled by sigma_max here — unless ``prescaled``, in which
    case the caller already built the k-space state (img2img tails).
    Returns VP-space x_0 latents.
    """
    x = latents if prescaled else latents * schedule.sigmas[0]

    @jax.named_scope("denoise_step")
    def step(x, per_step):
        t, sigma, sigma_next = per_step
        x_vp = x / jnp.sqrt(1.0 + sigma * sigma)
        eps = denoise(x_vp, t)
        # k-diffusion derivative for eps-prediction is eps itself
        x = x + (sigma_next - sigma) * eps
        return x, None

    final, _ = jax.lax.scan(
        step, x,
        (schedule.timesteps, schedule.sigmas[:-1], schedule.sigmas[1:]),
    )
    return final  # sigma -> 0 lands in VP space already


@dataclasses.dataclass(frozen=True)
class DPMppSchedule:
    """DPM-Solver++(2M) with all step math precomputed host-side.

    Update (data-prediction form): x <- c_skip·x + c_d0·m0 + c_d1·m1
    where m0/m1 are this/previous step's predicted x0. First and last
    steps are first-order (c_d1 = 0) — the standard multistep warmup and
    ``lower_order_final`` boundary handling, which also keeps every
    coefficient finite (the final step's h is infinite only in the
    analytic form; here it resolves to c_skip=0, c_d0=1).
    """

    timesteps: jnp.ndarray  # (T,) int32 descending
    alphas: jnp.ndarray     # (T,) sqrt(abar) at each step (for x0 recovery)
    sigmas: jnp.ndarray     # (T,) sqrt(1-abar)
    c_skip: jnp.ndarray     # (T,)
    c_d0: jnp.ndarray       # (T,)
    c_d1: jnp.ndarray       # (T,)

    @staticmethod
    def create(num_steps: int, start: int = 0) -> "DPMppSchedule":
        """``start`` > 0 drops the first steps (img2img tails); the
        first kept step is automatically first-order (its h_prev is
        undefined), which is exactly the multistep warmup."""
        ab = _alpha_bars()
        ts = _strided_timesteps(num_steps)[start:]
        alpha = np.sqrt(ab[ts])
        sigma = np.sqrt(1.0 - ab[ts])
        # targets: step i maps state at ts[i] -> ts[i+1] (final -> clean)
        alpha_next = np.concatenate([alpha[1:], [1.0]])
        sigma_next = np.concatenate([sigma[1:], [0.0]])
        lam = np.log(alpha) - np.log(sigma)
        with np.errstate(divide="ignore"):
            lam_next = np.log(alpha_next) - np.log(
                np.where(sigma_next > 0, sigma_next, 1e-300)
            )
        h = lam_next - lam                       # (T,), last is huge/inf
        h_prev = np.concatenate([[np.nan], h[:-1]])
        em1 = np.where(np.isfinite(h), np.expm1(-h), -1.0)  # exp(-h)-1

        # 2M correction weight 1/(2·r0) with r0 = h_prev/h, i.e. h/(2·h_prev)
        with np.errstate(divide="ignore", invalid="ignore"):
            inv2r = h / (2.0 * h_prev)
            inv2r = np.where(np.isfinite(inv2r), inv2r, 0.0)
        first_order = np.zeros(len(ts), dtype=bool)
        first_order[0] = True                    # multistep warmup
        first_order[-1] = True                   # lower_order_final
        inv2r = np.where(first_order, 0.0, inv2r)

        c_skip = np.where(sigma > 0, sigma_next / sigma, 0.0)
        c_d0 = -alpha_next * em1 * (1.0 + inv2r)
        c_d1 = alpha_next * em1 * inv2r
        f32 = lambda a: jnp.asarray(a.astype(np.float32))  # noqa: E731
        return DPMppSchedule(
            timesteps=jnp.asarray(ts), alphas=f32(alpha), sigmas=f32(sigma),
            c_skip=f32(c_skip), c_d0=f32(c_d0), c_d1=f32(c_d1),
        )


def dpmpp_2m_sample(
    denoise: Callable[[jax.Array, jax.Array], jax.Array],
    latents: jax.Array,
    schedule: DPMppSchedule,
) -> jax.Array:
    """DPM-Solver++(2M): 2nd-order multistep in data-prediction form.

    ``latents`` standard normal; x stays in VP space throughout.
    """

    @jax.named_scope("denoise_step")
    def step(carry, per_step):
        x, m1 = carry
        t, alpha, sigma, c_skip, c_d0, c_d1 = per_step
        eps = denoise(x, t)
        m0 = (x - sigma * eps) / alpha
        x = c_skip * x + c_d0 * m0 + c_d1 * m1
        return (x, m0), None

    (final, _), _ = jax.lax.scan(
        step, (latents, jnp.zeros_like(latents)),
        (schedule.timesteps, schedule.alphas, schedule.sigmas,
         schedule.c_skip, schedule.c_d0, schedule.c_d1),
    )
    return final


def make_slot_sampler(kind: str, num_steps: int, eta: float = 0.0,
                      teacher_steps: int = 50):
    """Step-granular counterpart of :func:`make_sampler` for the staged
    serving path (serving/stages.py): instead of one ``lax.scan``
    position shared by the whole batch, every slot carries its OWN step
    index and the per-step coefficients gather per slot — so requests
    can sit at different schedule positions inside one fixed-capacity
    step dispatch.

    Returns ``(prepare, slot_step, num_steps)``:

    - ``prepare(latents) -> (x, aux)`` maps standard-normal latents to
      the solver-space entry state (identity for DDIM/DPM++, the
      sigma-max scale for Euler) plus the per-slot auxiliary state
      (DPM++'s multistep history m1; zeros where the solver has none);
    - ``slot_step(denoise, x, aux, idx) -> (x', aux')`` advances every
      slot one step: ``x``/``aux`` are ``(C, H, W, Ch)``, ``idx`` is
      ``(C,)`` int32 (each slot's current step), and ``denoise(x, t)``
      receives the per-slot int timestep vector ``t``.

    The per-slot arithmetic is EXACTLY the matching ``make_sampler``
    scan body (same schedule arrays, same expressions), so a solo
    staged trajectory is bit-identical to the monolithic scan — the
    staged-vs-monolithic parity bar (tests/test_stages.py). Only
    deterministic samplers qualify: ``eta > 0`` draws per-step noise
    from a carried key chain that step-boundary admission cannot
    replay, so it stays monolithic.
    """
    if eta != 0.0:
        raise ValueError(
            "staged serving needs a deterministic sampler (eta=0); "
            "eta>0 carries a per-step noise key chain that step-level "
            "admission cannot replay")

    def _b(a):  # (C,) -> (C, 1, 1, 1) for latent broadcasting
        return a[:, None, None, None]

    if kind == "ddim":
        schedule = DDIMSchedule.create(num_steps)

        def prepare(latents):
            return latents, jnp.zeros_like(latents)

        def slot_step(denoise, x, aux, idx):
            t = schedule.timesteps[idx]
            a_t = _b(schedule.alpha_bars[idx])
            a_prev = _b(schedule.alpha_bars_prev[idx])
            eps = denoise(x, t)
            # ddim_sample's step body with eta pinned to 0: sigma is
            # exactly zero, so the stochastic term vanishes and the
            # remaining expressions are kept verbatim for bit parity
            x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
            sigma = 0.0 * jnp.sqrt(
                (1.0 - a_prev) / (1.0 - a_t)
            ) * jnp.sqrt(1.0 - a_t / a_prev)
            dir_xt = jnp.sqrt(
                jnp.maximum(1.0 - a_prev - sigma**2, 0.0)) * eps
            return jnp.sqrt(a_prev) * x0 + dir_xt, aux

        return prepare, slot_step, num_steps

    if kind == "euler":
        eschedule = EulerSchedule.create(num_steps)

        def prepare(latents):
            return latents * eschedule.sigmas[0], jnp.zeros_like(latents)

        def slot_step(denoise, x, aux, idx):
            t = eschedule.timesteps[idx]
            sigma = _b(eschedule.sigmas[idx])
            sigma_next = _b(eschedule.sigmas[idx + 1])
            x_vp = x / jnp.sqrt(1.0 + sigma * sigma)
            eps = denoise(x_vp, t)
            return x + (sigma_next - sigma) * eps, aux

        return prepare, slot_step, num_steps

    if kind == "dpmpp_2m":
        dschedule = DPMppSchedule.create(num_steps)

        def prepare(latents):
            # the multistep history m1 enters zero, exactly as
            # dpmpp_2m_sample's scan carry initializes it
            return latents, jnp.zeros_like(latents)

        def slot_step(denoise, x, aux, idx):
            t = dschedule.timesteps[idx]
            alpha = _b(dschedule.alphas[idx])
            sigma = _b(dschedule.sigmas[idx])
            c_skip = _b(dschedule.c_skip[idx])
            c_d0 = _b(dschedule.c_d0[idx])
            c_d1 = _b(dschedule.c_d1[idx])
            eps = denoise(x, t)
            m0 = (x - sigma * eps) / alpha
            # first/last-step first-order handling rides the
            # precomputed coefficients (c_d1 = 0 there), so a slot
            # admitted mid-flight warms up exactly like a fresh scan
            return c_skip * x + c_d0 * m0 + c_d1 * aux, m0

        return prepare, slot_step, num_steps

    if kind == "consistency":
        # the few-step student rides the staged continuous-batching
        # path: each slot folds its OWN timestep into the deterministic
        # re-noise ladder, so the per-slot arithmetic is exactly
        # consistency_sample's scan body and a solo staged trajectory
        # is bit-identical to the monolithic scan
        cschedule = ConsistencySchedule.create(num_steps, teacher_steps)

        def prepare(latents):
            return latents, jnp.zeros_like(latents)

        def slot_step(denoise, x, aux, idx):
            t = cschedule.timesteps[idx]
            ab = _b(cschedule.alpha_bars[idx])
            ab_next = _b(cschedule.alpha_bars_next[idx])
            c_skip = _b(cschedule.c_skip[idx])
            c_out = _b(cschedule.c_out[idx])
            eps = denoise(x, t)
            x0 = (x - jnp.sqrt(1.0 - ab) * eps) / jnp.sqrt(ab)
            f = c_skip * x + c_out * x0
            noise = jax.vmap(
                lambda ti: consistency_renoise(ti, x.shape[1:], x.dtype)
            )(t)
            return jnp.sqrt(ab_next) * f + \
                jnp.sqrt(1.0 - ab_next) * noise, aux

        return prepare, slot_step, num_steps

    raise ValueError(f"unknown sampler kind {kind!r}; "
                     f"choose from {SAMPLER_KINDS} or 'consistency'")


def make_img2img_sampler(kind: str, num_steps: int, start: int,
                         eta: float = 0.0):
    """Tail sampling from schedule position ``start`` (img2img).

    Returns ``(prepare, sample)``: ``prepare(x0_latents, noise)`` builds
    the solver-space state at the start step (VP for DDIM/DPM++, k-space
    for Euler); ``sample(denoise, x, rng)`` runs the remaining steps and
    returns x0 latents. Every kind integrates the same ODE as its full-
    schedule counterpart in :func:`make_sampler`.
    """
    ab = _alpha_bars()
    ts = _strided_timesteps(num_steps)
    a0 = float(ab[ts[start]])
    if kind == "euler":
        es = EulerSchedule.create(num_steps, start)
        sigma0 = float(np.sqrt((1.0 - a0) / a0))

        def prepare(x0, noise):
            return x0 + sigma0 * noise          # k-space

        def sample(denoise, x, rng=None):
            return euler_sample(denoise, x, es, prescaled=True)

        return prepare, sample

    def prepare(x0, noise):                      # VP space
        return jnp.sqrt(a0) * x0 + jnp.sqrt(1.0 - a0) * noise

    if kind == "ddim":
        ds = DDIMSchedule.create(num_steps, start=start)

        def sample(denoise, x, rng=None):
            return ddim_sample(denoise, x, ds, eta=eta, rng=rng)

        return prepare, sample
    if kind == "dpmpp_2m":
        ps = DPMppSchedule.create(num_steps, start)

        def sample(denoise, x, rng=None):
            return dpmpp_2m_sample(denoise, x, ps)

        return prepare, sample
    raise ValueError(f"unknown sampler kind {kind!r}; "
                     f"choose from {SAMPLER_KINDS}")


def make_sampler(kind: str, num_steps: int, eta: float = 0.0):
    """(kind, steps) -> ``sample(denoise, latents, rng) -> x0 latents``.

    ``latents`` standard normal in every case, so pipelines switch
    samplers by config without touching their latent setup.
    """
    if kind == "ddim":
        schedule = DDIMSchedule.create(num_steps)

        def sample(denoise, latents, rng=None):
            return ddim_sample(denoise, latents, schedule, eta=eta, rng=rng)

        return sample
    if kind == "euler":
        eschedule = EulerSchedule.create(num_steps)

        def sample(denoise, latents, rng=None):
            return euler_sample(denoise, latents, eschedule)

        return sample
    if kind == "dpmpp_2m":
        dschedule = DPMppSchedule.create(num_steps)

        def sample(denoise, latents, rng=None):
            return dpmpp_2m_sample(denoise, latents, dschedule)

        return sample
    raise ValueError(f"unknown sampler kind {kind!r}; "
                     f"choose from {SAMPLER_KINDS}")
