"""A second image trajectory, named by a test configuration: the
deterministic Euler solver of the k-diffusion ODE over the same
discretisation as the reference's DDIM (scaled-linear betas, "leading"
spacing), one guided forward a step."""

import numpy as np

from benchmarks.harness import reference as ref


def euler_trajectory(guided, x, sampler):
    """x_T (standard normal) -> x_0; the state lives in k-space, x_vp *
    sqrt(1 + sigma^2), and lands in VP space as sigma reaches 0."""
    ts, a_t, _ = ref.ddim_schedule(sampler["num_steps"])
    sigmas = np.concatenate([np.sqrt((1.0 - a_t) / a_t), [0.0]]).astype(
        np.float32)
    x = x * sigmas[0]
    for t, sigma, sigma_next in zip(ts, sigmas[:-1], sigmas[1:]):
        eps = guided(x / np.sqrt(1.0 + sigma * sigma), t)
        x = x + (sigma_next - sigma) * eps
    return x
