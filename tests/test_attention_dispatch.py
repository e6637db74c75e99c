"""How often the flash kernel engages, read from the program (ISSUE 27).

``ops/attention.py::multi_head_attention`` counts every site it dispatches
under ``attention.dispatch{path=...}`` while a program is traced. Each case
traces one model at its published widths (``jax.eval_shape``: shapes only,
nothing compiles or runs) with the platform question answered "tpu" here,
in the test, and reads the delta: the census of sites by path that the
benchmark's set-up reads on the chip and PERF.md reports. A site that
silently fell off the kernel (a shape rule that moved, a new mask) shows
as a moved count.
"""

import jax
import jax.numpy as jnp
import pytest

from cassmantle_tpu.config import FrameworkConfig, sdxl_config
from cassmantle_tpu.ops import attention
from cassmantle_tpu.utils.logging import metrics

PATHS = ("flash_self", "flash_cross", "xla", "ring")


def dispatch_counts():
    counters = metrics.dump_state()["counters"]
    return {path: sum(value for name, labels, value in counters
                      if name == "attention.dispatch"
                      and dict(labels).get("path") == path)
            for path in PATHS}


def traced_census(trace):
    before = dispatch_counts()
    trace()
    after = dispatch_counts()
    return {path: int(after[path] - before[path]) for path in PATHS
            if after[path] != before[path]}


def unet_trace(models, latent_hw, addition_dim=None):
    from cassmantle_tpu.models.unet import UNet

    unet = UNet(models.unet)
    args = [jnp.zeros((2, latent_hw, latent_hw, 4), jnp.bfloat16),
            jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 77, models.unet.context_dim), jnp.bfloat16)]
    if addition_dim:
        args.append(jnp.zeros((2, addition_dim), jnp.bfloat16))
    params = jax.eval_shape(unet.init, jax.random.PRNGKey(0), *args)
    return lambda: jax.eval_shape(unet.apply, params, *args)


def vae_trace(models, latent_hw):
    from cassmantle_tpu.models.vae import VAEDecoder

    vae = VAEDecoder(models.vae)
    lat = jnp.zeros((1, latent_hw, latent_hw, 4), jnp.float32)
    params = jax.eval_shape(vae.init, jax.random.PRNGKey(0), lat)
    return lambda: jax.eval_shape(vae.apply, params, lat)


CASES = {
    # SD1.5 at 512²: levels 0 and 1 (4096 and 1024 tokens) take the
    # kernel, 5 self and 5 cross sites each; level 2 and mid (256, 64
    # tokens) stay with XLA, 6 self and 6 cross
    "sd15_unet_512": (
        lambda: unet_trace(FrameworkConfig().models, 64),
        {"flash_self": 10, "flash_cross": 10, "xla": 12}),
    # ... and at 256² only level 0 (1024 tokens) tiles
    "sd15_unet_256": (
        lambda: unet_trace(FrameworkConfig().models, 32),
        {"flash_self": 5, "flash_cross": 5, "xla": 22}),
    "sd15_vae_512": (
        lambda: vae_trace(FrameworkConfig().models, 64),
        {"flash_self": 1}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_sites_by_dispatch_path(case, monkeypatch):
    build, expected = CASES[case]
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert traced_census(build()) == expected


def test_sdxl_unet_sites_by_dispatch_path(monkeypatch):
    """SDXL at 1024²: both attending levels (4096 tokens under 10 heads,
    1024 under 20) take the kernel at every transformer block; nothing is
    left to XLA but what a mask keeps there (none in the UNet)."""
    models = sdxl_config().models
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    census = traced_census(
        unet_trace(models, 128, models.unet.addition_embed_dim))
    assert census.get("xla", 0) == 0
    assert census["flash_self"] == census["flash_cross"] > 0


def test_off_the_chip_every_site_is_xla():
    """The same trace with the platform question answered truthfully
    (this is a CPU): no site takes the kernel."""
    census = traced_census(unet_trace(FrameworkConfig().models, 32))
    assert census == {"xla": 32}


def test_masked_and_causal_sites_count_as_xla_and_ring_as_ring(monkeypatch):
    """A mask keeps a site off the kernel whatever its shape; a causal
    site inside a ``context_parallel`` region counts as ``ring``."""
    import numpy as np
    from jax.sharding import Mesh

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    q = jnp.zeros((2, 1024, 2, 64))
    mask = jnp.ones((1024, 1024), bool)
    assert traced_census(lambda: jax.eval_shape(
        lambda q: attention.multi_head_attention(q, q, q, mask=mask),
        q)) == {"xla": 1}
    assert traced_census(lambda: jax.eval_shape(
        lambda q: attention.multi_head_attention(q, q, q, causal=True),
        q)) == {"xla": 1}

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "sp"))

    def ring(q):
        with attention.context_parallel(mesh, "sp", "dp"):
            return attention.multi_head_attention(q, q, q, causal=True)

    assert traced_census(lambda: jax.eval_shape(ring, q)) == {"ring": 1}
