"""Sampler family tests (ops/samplers.py): Euler and DPM-Solver++(2M)
against the DDIM baseline.

Key correctness property: for the probability-flow ODE with a *consistent*
epsilon field — denoise(x_t, t) returning exactly the eps that places x_t
on the trajectory of a fixed x0 — every solver must recover x0 (the ODE's
solution keeps x0 invariant). This validates coefficients, spacing, and
VP/k-space conversions without any model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.ops.ddim import DDIMSchedule
from cassmantle_tpu.ops.samplers import (
    SAMPLER_KINDS,
    ConsistencySchedule,
    DPMppSchedule,
    EulerSchedule,
    _alpha_bars,
    consistency_boundary,
    consistency_renoise,
    make_consistency_sampler,
    make_sampler,
    make_slot_sampler,
)

X0 = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 3))
AB = jnp.asarray(_alpha_bars().astype(np.float32))


def consistent_denoise(x, t):
    """eps such that x = sqrt(ab)*x0 + sqrt(1-ab)*eps."""
    ab = AB[t]
    return (x - jnp.sqrt(ab) * X0) / jnp.sqrt(1.0 - ab)


@pytest.mark.parametrize("kind", SAMPLER_KINDS)
def test_solver_recovers_x0_under_consistent_field(kind):
    sample = make_sampler(kind, 25)
    noise = jax.random.normal(jax.random.PRNGKey(1), X0.shape)
    out = sample(consistent_denoise, noise)
    np.testing.assert_allclose(np.asarray(out), np.asarray(X0),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("kind", ("euler", "dpmpp_2m"))
def test_solver_jits_and_is_deterministic(kind):
    sample = make_sampler(kind, 8)
    noise = jax.random.normal(jax.random.PRNGKey(2), X0.shape)
    f = jax.jit(lambda n: sample(consistent_denoise, n))
    a, b = f(noise), f(noise)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(a)).all()


def test_dpmpp_schedule_coefficients_finite():
    s = DPMppSchedule.create(20)
    for name in ("alphas", "sigmas", "c_skip", "c_d0", "c_d1"):
        arr = np.asarray(getattr(s, name))
        assert np.isfinite(arr).all(), name
    # final step: c_skip 0 (sigma_next = 0), first-order (c_d1 = 0)
    assert np.asarray(s.c_skip)[-1] == 0.0
    assert np.asarray(s.c_d1)[-1] == 0.0
    assert np.asarray(s.c_d1)[0] == 0.0  # multistep warmup


def test_dpmpp_2m_interior_coefficients_match_formula():
    """Regression for the 2M correction weight: for an interior step,
    c_d0/c_d1 must equal the DPM-Solver++(2M) formula with weight
    1/(2·r0), r0 = h_prev/h (computed independently here)."""
    steps = 10
    s = DPMppSchedule.create(steps)
    ab = _alpha_bars()
    ts = np.asarray(s.timesteps)
    i = 5  # interior: not warmup, not final
    a = np.sqrt(ab[ts])
    sg = np.sqrt(1.0 - ab[ts])
    lam = np.log(a) - np.log(sg)
    a_next, sg_next = a[i + 1], sg[i + 1]
    lam_next = np.log(a_next) - np.log(sg_next)
    h = lam_next - lam[i]
    h_prev = lam[i] - lam[i - 1]
    r0 = h_prev / h
    em1 = np.expm1(-h)
    w = 1.0 / (2.0 * r0)
    np.testing.assert_allclose(
        float(np.asarray(s.c_d0)[i]), -a_next * em1 * (1.0 + w), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(np.asarray(s.c_d1)[i]), a_next * em1 * w, rtol=1e-5
    )
    np.testing.assert_allclose(
        float(np.asarray(s.c_skip)[i]), sg_next / sg[i], rtol=1e-5
    )


def test_euler_schedule_monotone():
    s = EulerSchedule.create(30)
    sig = np.asarray(s.sigmas)
    assert sig[-1] == 0.0
    assert (np.diff(sig) < 0).all()
    assert len(np.asarray(s.timesteps)) == 30


def curved_denoise(x, t):
    """eps field with t-dependent curvature (the consistent field is exact
    for every solver, so order-of-accuracy needs a curved target)."""
    ab = AB[t]
    x0_t = X0 * (1.0 + 0.3 * jnp.sin(t.astype(jnp.float32) / 150.0))
    return (x - jnp.sqrt(ab) * x0_t) / jnp.sqrt(1.0 - ab)


def test_solvers_converge_to_common_limit_with_order():
    """All solvers approach the same ODE solution as steps grow, and the
    2nd-order multistep beats 1st-order Euler at equal low step count."""
    noise = jax.random.normal(jax.random.PRNGKey(3), X0.shape)
    ref = make_sampler("ddim", 500)(curved_denoise, noise)

    def err(kind, steps):
        out = make_sampler(kind, steps)(curved_denoise, noise)
        return float(jnp.abs(out - ref).max())

    # convergence: error shrinks with more steps
    assert err("dpmpp_2m", 50) < err("dpmpp_2m", 10)
    assert err("euler", 50) < err("euler", 10)
    # order: 2nd-order multistep beats Euler at 10 steps
    assert err("dpmpp_2m", 10) < err("euler", 10)
    # all three agree at 50 steps to reasonable tolerance
    assert err("euler", 50) < 0.15 and err("dpmpp_2m", 50) < 0.15


def test_make_sampler_rejects_unknown():
    with pytest.raises(ValueError):
        make_sampler("plms", 10)


def test_pipeline_runs_with_each_sampler():
    """Tiny end-to-end: Text2ImagePipeline under each sampler kind."""
    import dataclasses

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    base = test_config()
    for kind in ("euler", "dpmpp_2m"):
        cfg = base.replace(
            sampler=dataclasses.replace(base.sampler, kind=kind)
        )
        pipe = Text2ImagePipeline(cfg)
        imgs = pipe.generate(["a red lighthouse"], seed=1)
        assert imgs.shape[-1] == 3 and imgs.dtype == np.uint8
        assert np.isfinite(imgs.astype(np.float32)).all()


# -- few-step consistency sampling (ISSUE 15) --------------------------------


def test_consistency_boundary_condition_at_sigma_min():
    """f(x, σ_min) = x EXACTLY: c_skip(σ_min) = 1 and c_out(σ_min) = 0
    — the boundary condition that makes the parameterization a
    consistency function. Away from the boundary both coefficients are
    strictly interior."""
    ab0 = _alpha_bars()[0]
    sigma_min = float(np.sqrt((1.0 - ab0) / ab0))
    c_skip, c_out = consistency_boundary(sigma_min, sigma_min)
    assert float(c_skip) == 1.0
    assert float(c_out) == 0.0
    c_skip, c_out = consistency_boundary(10.0 * sigma_min, sigma_min)
    assert 0.0 < float(c_skip) < 1.0 and float(c_out) > 0.0


@pytest.mark.parametrize("n", [1, 4, 8])
def test_consistency_schedule_trailing_spacing(n):
    """Grid alignment + trailing spacing: EVERY evaluation timestep is
    a point of the teacher solver discretization — the same
    ``strided_timesteps(teacher_steps)`` grid
    ``ConsistencyDistillTrainer`` trains on, so a really-distilled
    student is never queried at a noise level it never saw — the first
    f-eval sits at the grid's NOISIEST trained point and the last
    strictly above t=0 (the final UNet forward is a real prediction,
    never the boundary identity), with exactly ``n`` evaluation steps
    (the step-count accounting the `pipeline.consistency_steps` counter
    multiplies by) and a terminal re-noise target of ᾱ = 1 (the last
    update IS the x0 estimate)."""
    from cassmantle_tpu.ops.ddim import strided_timesteps

    teacher = 50
    s = ConsistencySchedule.create(n, teacher_steps=teacher)
    ts = np.asarray(s.timesteps)
    grid = strided_timesteps(teacher)
    assert len(ts) == n
    # queried points ⊆ the trainer's discretization, t=0 excluded
    assert set(ts.tolist()) <= set(grid[:-1].tolist())
    assert ts[0] == grid[0] and ts[-1] > 0
    assert (np.diff(ts) < 0).all() if n > 1 else True
    assert float(np.asarray(s.alpha_bars_next)[-1]) == 1.0
    for name in ("alpha_bars", "alpha_bars_next", "c_skip", "c_out"):
        assert np.isfinite(np.asarray(getattr(s, name))).all(), name
    # later (cleaner) steps lean more on the identity term
    assert (np.diff(np.asarray(s.c_skip)) > 0).all() if n > 1 else True


def _affine_denoise(x, t):
    """Works for both the scalar-t monolithic contract and the
    vector-t slot contract."""
    t_b = jnp.reshape(t.astype(jnp.float32), (-1,) + (1,) * (x.ndim - 1))
    return 0.1 * x + 0.01 * t_b


def test_consistency_sample_matches_reference_loop():
    """The scan executes EXACTLY num_steps f-evaluations at the
    schedule's timesteps with the boundary-parameterized update and the
    deterministic re-noise ladder — pinned against a hand-rolled host
    loop using the same published pieces (schedule arrays +
    consistency_renoise)."""
    n = 4
    s = ConsistencySchedule.create(n)
    lat = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 4))
    out = make_consistency_sampler(n)(_affine_denoise, lat)

    x = lat
    for i in range(n):
        t = s.timesteps[i]
        eps = _affine_denoise(x, t)
        ab = s.alpha_bars[i]
        x0 = (x - jnp.sqrt(1.0 - ab) * eps) / jnp.sqrt(ab)
        f = s.c_skip[i] * x + s.c_out[i] * x0
        noise = consistency_renoise(t, x.shape[1:], x.dtype)
        x = jnp.sqrt(s.alpha_bars_next[i]) * f + \
            jnp.sqrt(1.0 - s.alpha_bars_next[i]) * noise
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               atol=1e-5, rtol=1e-5)


def test_consistency_sample_deterministic_and_ignores_rng():
    sample = make_consistency_sampler(4)
    lat = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 8, 4))
    a = sample(_affine_denoise, lat)
    b = sample(_affine_denoise, lat, rng=jax.random.PRNGKey(99))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(a)).all()


def test_consistency_slot_sampler_bit_matches_monolithic():
    """The staged slot variant: a solo trajectory stepped one slot-step
    at a time (jitted, as the staged server dispatches it) is
    bit-identical to the jitted monolithic scan — the property that
    lets few-step requests ride step-level continuous batching."""
    n = 4
    lat = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 8, 4))
    ref = jax.jit(
        lambda l: make_consistency_sampler(n)(_affine_denoise, l))(lat)
    prepare, slot_step, steps = make_slot_sampler("consistency", n)
    assert steps == n
    x, aux = prepare(lat)
    jstep = jax.jit(
        lambda x, aux, idx: slot_step(_affine_denoise, x, aux, idx))
    for i in range(steps):
        x, aux = jstep(x, aux, jnp.array([i]))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(ref))


@pytest.fixture(scope="module")
def teacher_pipe():
    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    return Text2ImagePipeline(test_config())


def _lcm_tiny_cfg(num_steps=2):
    import dataclasses

    from cassmantle_tpu.config import test_config

    base = test_config()
    return base.replace(sampler=dataclasses.replace(
        base.sampler, consistency=True, num_steps=num_steps,
        consistency_teacher_steps=base.sampler.num_steps))


def test_consistency_kill_switch_reverts_bit_exact(teacher_pipe,
                                                   monkeypatch):
    """CASSMANTLE_NO_CONSISTENCY=1 reverts a consistency config to the
    TEACHER path bit-exactly (kind @ consistency_teacher_steps — here
    the module teacher pipe's own schedule), and the
    `pipeline.consistency_steps` counter goes quiet — the pinned
    regression contract of the kill switch."""
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline
    from cassmantle_tpu.utils.logging import metrics

    prompts = ["a quiet harbor at dawn"]
    reference = teacher_pipe.generate(prompts, seed=3)
    monkeypatch.setenv("CASSMANTLE_NO_CONSISTENCY", "1")
    off = Text2ImagePipeline(_lcm_tiny_cfg(),
                             share_params_with=teacher_pipe)
    before = metrics.counter_total("pipeline.consistency_steps")
    out = off.generate(prompts, seed=3)
    np.testing.assert_array_equal(out, reference)
    assert metrics.counter_total("pipeline.consistency_steps") == before
    monkeypatch.delenv("CASSMANTLE_NO_CONSISTENCY")
    on = Text2ImagePipeline(_lcm_tiny_cfg(),
                            share_params_with=teacher_pipe)
    live = on.generate(prompts, seed=3)
    assert not np.array_equal(live, reference)  # few-step path engaged
    assert metrics.counter_total("pipeline.consistency_steps") > before


def test_warmed_consistency_loop_never_recompiles(teacher_pipe):
    """Jit sentinel pinned on the warmed few-step serving loop: a
    second same-bucket generate must hit the jit cache with ZERO new
    compiles (the per-step re-noise fold is internal scan structure,
    never a fresh trace)."""
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline
    from cassmantle_tpu.utils import jit_sentinel

    pipe = Text2ImagePipeline(_lcm_tiny_cfg(),
                              share_params_with=teacher_pipe)
    pipe.generate(["a quiet harbor at dawn"], seed=5)   # warmup compile
    with jit_sentinel.no_new_compiles():
        pipe.generate(["a stormy night at sea"], seed=6)


def test_consistency_config_rejections():
    import dataclasses

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    base = test_config()

    def cfg(**kw):
        return base.replace(sampler=dataclasses.replace(
            base.sampler, consistency=True, **kw))

    with pytest.raises(AssertionError, match="few-step"):
        Text2ImagePipeline(cfg(num_steps=12))
    with pytest.raises(AssertionError, match="eta"):
        Text2ImagePipeline(cfg(num_steps=4, eta=0.5))
    with pytest.raises(AssertionError, match="consistency_teacher_steps"):
        # the teacher grid must be finer than the student schedule —
        # the student only trains on the teacher discretization
        Text2ImagePipeline(cfg(num_steps=4, consistency_teacher_steps=4))


def test_img2img_rejects_consistency(teacher_pipe):
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(_lcm_tiny_cfg(),
                              share_params_with=teacher_pipe)
    imgs = np.zeros((1, 64, 64, 3), dtype=np.uint8)
    with pytest.raises(NotImplementedError, match="consistency"):
        pipe.generate_img2img(imgs, ["a sketch"], strength=0.5)
