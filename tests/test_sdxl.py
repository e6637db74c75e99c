"""SDXL pipeline tests: dual-tower conditioning, micro-conds, batch-DP.

The reference's image generator IS remote SDXL-base (backend.py:24,
270-295); these tests cover its local TPU replacement (serving/sdxl.py) at
tiny CPU dims — geometry, determinism, and data-parallel equivalence on
the virtual 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import (
    MeshConfig,
    test_sdxl_config as _tiny_sdxl_config,
)
from cassmantle_tpu.models.clip_text import ClipTextEncoder
from cassmantle_tpu.models.unet import UNet
from cassmantle_tpu.ops.ddim import make_cfg_denoiser
from cassmantle_tpu.parallel.mesh import make_mesh
from cassmantle_tpu.serving.sdxl import SDXLPipeline


@pytest.fixture(scope="module")
def cfg():
    return _tiny_sdxl_config()


@pytest.fixture(scope="module")
def pipe(cfg):
    return SDXLPipeline(cfg)


def test_clip_penultimate_output(cfg):
    m = cfg.models.clip_text
    enc = ClipTextEncoder(m)
    ids = jnp.arange(8, dtype=jnp.int32)[None, :] % m.vocab_size
    params = enc.init(jax.random.PRNGKey(0), ids)
    out = enc.apply(params, ids)
    assert out["penultimate"].shape == out["hidden"].shape
    # penultimate is pre-final-block, pre-LN: must differ from final hidden
    assert not np.allclose(np.asarray(out["penultimate"]),
                           np.asarray(out["hidden"]))


def test_sdxl_unet_micro_conditioning(cfg):
    m = cfg.models.unet
    unet = UNet(m)
    lat = jnp.zeros((2, 8, 8, 4))
    t = jnp.zeros((2,), jnp.int32)
    ctx = jnp.zeros((2, 8, m.context_dim))
    add = jnp.ones((2, m.addition_embed_dim))
    params = unet.init(jax.random.PRNGKey(0), lat, t, ctx, add)
    eps = unet.apply(params, lat, t, ctx, add)
    assert eps.shape == lat.shape
    # micro-conditioning must actually influence the output
    eps2 = unet.apply(params, lat, t, ctx, 2.0 * add)
    assert not np.allclose(np.asarray(eps), np.asarray(eps2))


def test_cfg_denoiser_with_additions(cfg):
    m = cfg.models.unet
    unet = UNet(m)
    lat = jnp.zeros((1, 8, 8, 4))
    t = jnp.zeros((1,), jnp.int32)
    ctx = jnp.zeros((1, 8, m.context_dim))
    add = jnp.ones((1, m.addition_embed_dim))
    params = unet.init(jax.random.PRNGKey(0), lat, t, ctx, add)
    denoise = make_cfg_denoiser(
        unet.apply, params, ctx, ctx, 5.0,
        addition_embeds=add, uncond_addition_embeds=add,
    )
    eps = denoise(lat, jnp.asarray(0, jnp.int32))
    assert eps.shape == lat.shape
    assert np.isfinite(np.asarray(eps)).all()


def test_sdxl_generate_shapes_and_determinism(pipe, cfg):
    imgs = pipe.generate(["a red lighthouse", "a green meadow"], seed=7)
    s = cfg.sampler.image_size
    assert imgs.shape == (2, s, s, 3)
    assert imgs.dtype == np.uint8
    again = pipe.generate(["a red lighthouse", "a green meadow"], seed=7)
    np.testing.assert_array_equal(imgs, again)
    other = pipe.generate(["a red lighthouse", "a green meadow"], seed=8)
    assert not np.array_equal(imgs, other)


def test_sdxl_prompt_changes_image(pipe):
    a = pipe.generate(["a red lighthouse"], seed=3)
    b = pipe.generate(["an ancient forest"], seed=3)
    assert not np.array_equal(a, b)


def test_sdxl_data_parallel_matches_single_device(cfg):
    single = SDXLPipeline(cfg)
    mesh = make_mesh(MeshConfig(dp=-1, tp=1, sp=1))
    assert mesh.shape["dp"] == len(jax.devices())
    dp_pipe = SDXLPipeline(cfg, mesh=mesh)
    # full dp-width batch so both runs draw identical initial latents
    prompts = [f"scene number {i}" for i in range(mesh.shape["dp"])]
    ref = single.generate(prompts, seed=5)
    out = dp_pipe.generate(prompts, seed=5)
    assert out.shape == ref.shape
    # same params (deterministic init) + same seed -> identical images up
    # to reduction-order effects; uint8 quantization absorbs those.
    mismatch = np.mean(ref.astype(np.int32) != out.astype(np.int32))
    assert mismatch < 0.02, f"{mismatch:.4f} of pixels differ"


def test_content_backend_uses_sdxl_with_dual_towers(cfg):
    from cassmantle_tpu.serving.pipeline import TPUContentBackend
    from cassmantle_tpu.serving.sdxl import SDXLPipeline

    backend = TPUContentBackend(cfg)
    assert isinstance(backend.t2i, SDXLPipeline)
    content = backend.generate_sync("The harbor at dawn", True)
    s = cfg.sampler.image_size
    assert content.image.shape == (s, s, 3)
    assert content.prompt_text


def test_sdxl_data_parallel_pads_partial_batch(cfg):
    mesh = make_mesh(MeshConfig(dp=-1, tp=1, sp=1))
    dp_pipe = SDXLPipeline(cfg, mesh=mesh)
    s = cfg.sampler.image_size
    out = dp_pipe.generate(["a", "b", "c"], seed=1)  # 3 pads to dp width
    assert out.shape == (3, s, s, 3)


def test_sdxl_shares_params_across_sampler_kinds(pipe, cfg):
    """A second SDXL pipeline that differs only in sampler holds the
    donor's trees (one SDXL weight set in HBM), and the shared
    run_cfg_denoise machinery serves the dual-tower pipeline under
    another sampler kind too."""
    import dataclasses

    prompts = ["a tower at dusk"]
    base = pipe.generate(prompts, seed=5)
    fast = SDXLPipeline(cfg.replace(sampler=dataclasses.replace(
        cfg.sampler, kind="dpmpp_2m", num_steps=4)),
        share_params_with=pipe)
    assert fast.unet_params is pipe.unet_params
    out = fast.generate(prompts, seed=5)
    assert out.shape == base.shape and out.dtype == np.uint8
    assert not np.array_equal(base, out)
