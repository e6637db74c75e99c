"""Parity pins for the fused GroupNorm+SiLU+conv3x3 Pallas path.

The kernel runs in interpret mode on CPU (ops/fused_conv.py dispatch), so
these tests execute the REAL kernel logic, not a stand-in: per-shape
parity against the pure-lax reference (padded-channel case included),
param-tree identity between the fused and unfused ResBlock, ResBlock
output parity, and an end-to-end tiny SD1.5 pipeline A/B with the config
flag on vs off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.ops.fused_conv import (
    fused_conv_ok,
    gn_silu_conv3x3,
    gn_silu_conv3x3_reference,
    round_up,
)

# (B, H, W, C, F, pad_to) — covers an aligned case, a pad-to-128 case
# (C and F both round up), a ragged/odd-geometry case with small pad,
# and a rectangular image.
SHAPES = [
    (2, 8, 8, 32, 64, 0),
    (1, 16, 16, 96, 96, 128),   # padded: 96 -> 128 on both C and F
    (2, 6, 10, 40, 72, 8),      # rectangular + odd channels, pad to 8
    (1, 12, 12, 64, 32, 0),     # F < C, shrinking conv
    (1, 64, 64, 40, 48, 0),     # multi-row-tile: exercises halo DMA
]


def _case(rng, b, h, w, c, f):
    x = jnp.asarray(rng.standard_normal((b, h, w, c)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((b, c)) * 0.5 + 1.0, jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, c)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 3, c, f)) * 0.05, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((f,)) * 0.1, jnp.float32)
    return x, a, bb, k, bias


@pytest.mark.parametrize("b,h,w,c,f,pad", SHAPES)
def test_kernel_matches_reference(b, h, w, c, f, pad):
    rng = np.random.default_rng(hash((b, h, w, c, f)) % 2**32)
    x, a, bb, k, bias = _case(rng, b, h, w, c, f)
    ref = gn_silu_conv3x3_reference(x, a, bb, k, bias)
    got = gn_silu_conv3x3(x, a, bb, k, bias, pad_to=pad, interpret=True)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_padding_is_exact():
    """Channel padding is a layout trade, never a numeric one: padded
    and unpadded dispatch agree to roundoff."""
    rng = np.random.default_rng(7)
    x, a, bb, k, bias = _case(rng, 2, 8, 8, 40, 72)
    plain = gn_silu_conv3x3(x, a, bb, k, bias, pad_to=0, interpret=True)
    padded = gn_silu_conv3x3(x, a, bb, k, bias, pad_to=128, interpret=True)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(plain),
                               atol=1e-5, rtol=1e-5)


def test_hot_shapes_dispatch_to_kernel():
    """The SD1.5-512 ResBlock shapes (64x64x320..8x8x2560 skip-concats,
    after pad-to-128) and the SDXL-1024 128x128 levels must all take
    the Pallas path — the whole point of the op; a silent fallback at
    the hot levels would make the sd15_fusedconv A/B measure nothing
    (this regression shipped once: a full-H block gate rejected every
    64x64 level)."""
    for h, w, c, f in [
        (64, 64, 384, 384), (64, 64, 1024, 384),   # SD1.5 level 0 (+concat)
        (32, 32, 640, 640), (32, 32, 1024, 640),
        (16, 16, 1280, 1280), (8, 8, 2560, 1280),
        # SDXL-1024: its widest skip-concats per level (960->320 at
        # 128x128, 1920->640 at 64x64, 2560->1280 at 32x32)
        (128, 128, 384, 384), (128, 128, 1024, 384),
        (64, 64, 1920, 640), (32, 32, 2560, 1280),
    ]:
        # ShapeDtypeStructs: the gate is shape/dtype-only, no data needed
        x = jax.ShapeDtypeStruct((1, h, w, c), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((3, 3, c, f), jnp.bfloat16)
        assert fused_conv_ok(x, k), (h, w, c, f)


def test_round_up():
    assert round_up(320, 128) == 384
    assert round_up(640, 128) == 640
    assert round_up(960, 128) == 1024
    assert round_up(7, 0) == 7


def test_dispatch_gate():
    """Shapes the kernel can't take fall back (and the fallback IS the
    reference, so the result is still correct)."""
    x = jnp.zeros((1, 2, 2, 8))          # too small for border taps
    k = jnp.zeros((3, 3, 8, 8))
    assert not fused_conv_ok(x, k)
    k5 = jnp.zeros((5, 5, 8, 8))
    assert not fused_conv_ok(jnp.zeros((1, 8, 8, 8)), k5)
    rng = np.random.default_rng(3)
    xs, a, bb, kk, bias = _case(rng, 1, 2, 2, 8, 8)
    out = gn_silu_conv3x3(xs, a, bb, kk, bias, interpret=True)
    ref = gn_silu_conv3x3_reference(xs, a, bb, kk, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_kill_switch(monkeypatch):
    rng = np.random.default_rng(5)
    x, a, bb, k, bias = _case(rng, 1, 8, 8, 32, 32)
    monkeypatch.setenv("CASSMANTLE_NO_FUSED_CONV", "1")
    out = gn_silu_conv3x3(x, a, bb, k, bias, pad_to=128, interpret=True)
    ref = gn_silu_conv3x3_reference(x, a, bb, k, bias)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_resblock_fused_param_tree_and_output_parity():
    """The fused ResBlock declares nn.Conv's EXACT param layout (same
    names, shapes, initializers, RNG folds) — checkpoints and the A/B
    share one tree — and reproduces the unfused outputs."""
    from cassmantle_tpu.models.unet import ResBlock

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 32))
    temb = jax.random.normal(jax.random.PRNGKey(2), (2, 16))
    plain = ResBlock(64, jnp.float32)
    fused = ResBlock(64, jnp.float32, fused_conv=True, conv_pad_to=128)
    p_plain = plain.init(rng, x, temb)
    p_fused = fused.init(rng, x, temb)
    assert (jax.tree_util.tree_structure(p_plain)
            == jax.tree_util.tree_structure(p_fused))
    jax.tree_util.tree_map(
        lambda u, v: np.testing.assert_array_equal(
            np.asarray(u), np.asarray(v)),
        p_plain, p_fused)
    o_plain = plain.apply(p_plain, x, temb)
    o_fused = fused.apply(p_plain, x, temb)  # the SAME tree drives both
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_plain),
                               atol=5e-5, rtol=1e-4)


def test_unet_flag_parity(cfg):
    """Whole-UNet forward with fused_conv on vs off, same params."""
    import dataclasses

    from cassmantle_tpu.models.unet import UNet

    ucfg = cfg.models.unet
    plain = UNet(ucfg)
    fused = UNet(dataclasses.replace(ucfg, fused_conv=True,
                                     conv_pad_to=128))
    lat = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 4))
    ts = jnp.asarray([10, 500])
    ctx = jax.random.normal(jax.random.PRNGKey(4),
                            (2, 8, ucfg.context_dim))
    params = plain.init(jax.random.PRNGKey(0), lat, ts, ctx)
    o_plain = plain.apply(params, lat, ts, ctx)
    o_fused = fused.apply(params, lat, ts, ctx)
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_plain),
                               atol=2e-4, rtol=1e-3)


def test_fused_vae_resblocks_numeric_parity(cfg):
    """VAEConfig.fused_conv routes every GN→SiLU→conv3x3 pair through
    the fused Pallas kernel (interpret mode on CPU — the real kernel)
    with an IDENTICAL param tree; decoder and encoder outputs must
    match the naive path."""
    import dataclasses

    from cassmantle_tpu.models.vae import VAEDecoder, VAEEncoder
    from cassmantle_tpu.models.weights import init_params

    vcfg = cfg.models.vae
    fused_cfg = dataclasses.replace(vcfg, fused_conv=True)
    assert fused_cfg.arch() == vcfg.arch()

    lat = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    dec = VAEDecoder(vcfg)
    params = init_params(dec, 3, lat)
    a = dec.apply(params, lat)
    b = VAEDecoder(fused_cfg).apply(params, lat)      # same tree
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)

    img = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
    enc = VAEEncoder(vcfg)
    eparams = init_params(enc, 4, img, jax.random.PRNGKey(2))
    ea = enc.apply(eparams, img, jax.random.PRNGKey(3))
    eb = VAEEncoder(fused_cfg).apply(eparams, img, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(ea), np.asarray(eb),
                               atol=2e-5, rtol=2e-5)


def test_fused_vae_kill_switch(cfg, monkeypatch):
    """CASSMANTLE_NO_FUSED_CONV covers the VAE sites too (one switch for
    every fused-conv site, UNet and VAE alike)."""
    import dataclasses

    from cassmantle_tpu.models.vae import VAEDecoder
    from cassmantle_tpu.models.weights import init_params

    vcfg = dataclasses.replace(cfg.models.vae, fused_conv=True)
    lat = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 4))
    dec = VAEDecoder(vcfg)
    params = init_params(dec, 3, lat)
    monkeypatch.setenv("CASSMANTLE_NO_FUSED_CONV", "1")
    a = dec.apply(params, lat)
    monkeypatch.delenv("CASSMANTLE_NO_FUSED_CONV")
    b = dec.apply(params, lat)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_pipeline_flag_parity(cfg):
    """End-to-end tiny SD1.5 pipeline: flag on vs off produce the same
    images within parity tolerance (uint8: tiny fp reorder deltas may
    flip a pixel value by ~1 step; the distributions must agree).

    Slow tier since round 25 (the default tier outgrew its 870s window
    again, same pressure as rounds 14/21): ~20s of paired pipeline
    compiles whose tier-1 coverage is duplicated — the unet-level flag
    parity above stays in the quick sweep, and the fused pipeline path
    is exercised end-to-end every tier-1 run by the w8a8 A/B tests
    (both arms of test_w8a8's pipeline tests run fused_conv=True)."""
    import dataclasses

    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe_off = Text2ImagePipeline(cfg)
    cfg_on = cfg.replace(models=dataclasses.replace(
        cfg.models, unet=dataclasses.replace(
            cfg.models.unet, fused_conv=True, conv_pad_to=128)))
    pipe_on = Text2ImagePipeline(cfg_on, share_params_with=pipe_off)
    prompts = ["a lighthouse over a stormy sea"]
    img_off = pipe_off.generate(prompts, seed=3)
    img_on = pipe_on.generate(prompts, seed=3)
    assert img_off.shape == img_on.shape
    diff = np.abs(img_off.astype(np.int32) - img_on.astype(np.int32))
    assert diff.max() <= 3, diff.max()
    assert diff.mean() < 0.1, diff.mean()
