"""SD autoencoder (VAE) — decoder is the serving hot path, encoder included
for completeness (img2img, tests).

Replaces the image-decoding tail of the reference's remote diffusion call
(backend.py:270-295): after the DDIM scan finishes, latents decode to pixels
on-device and only uint8 RGB crosses back to host.

NHWC, fp32 by default (the VAE is the most precision-sensitive stage; its
FLOPs are a rounding error next to 50 UNet steps — though at SDXL-1024 the
decode is 10.47 TF/image, which the decode-side kernels below attack).
Attention in the mid block is single-head over H·W tokens, routed through
ops.attention like every other attention site — on TPU that dispatches
the flash kernel (ops/flash_attention.py::flash_plan: a self-attention
site of one 512-wide head) instead of materializing the S=16,384 score
matrix in HBM at SDXL's 128² latent. ``VAEConfig.fused_conv`` additionally routes every
ResBlock's GN→SiLU→conv3x3 pair through the fused Pallas kernel.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import VAEConfig
from cassmantle_tpu.models.layers import (
    GroupNorm32,
    MultiHeadAttention,
    fused_gn_silu_conv3x3,
    nearest_upsample_2x,
)


class VAEResBlock(nn.Module):
    """GN/SiLU/conv3x3 x2 + skip — the VAE twin of the UNet ResBlock.

    ``fused_conv`` routes both norm+act+conv sequences through the same
    Pallas fused kernel the UNet hot loop uses (ops/fused_conv.py):
    GroupNorm statistics still reduce in fp32 here (``return_affine``,
    at the VAE's 1e-6 epsilon), and the normalize, SiLU, and 3x3 conv
    run as one kernel — the activated tensor never round-trips HBM,
    which at SDXL decode means the 1024² per-level activations. The
    param tree is IDENTICAL either way (Conv3x3Params declares
    nn.Conv's exact layout), so checkpoints and the init cache are
    shared and ``VAEConfig.arch()`` clears the flag for identity.
    """

    out_channels: int
    dtype: jnp.dtype
    fused_conv: bool = False

    def _gn_silu_conv(self, x, norm_name: str, conv_name: str):
        return fused_gn_silu_conv3x3(
            x, self.out_channels, self.dtype, norm_name, conv_name,
            epsilon=1e-6)

    @nn.compact
    def __call__(self, x):
        if self.fused_conv:
            h = self._gn_silu_conv(x, "norm1", "conv1")
        else:
            h = GroupNorm32(epsilon=1e-6, name="norm1")(x)
            h = nn.silu(h)
            h = nn.Conv(self.out_channels, (3, 3), padding=1,
                        dtype=self.dtype, name="conv1")(h)
        if self.fused_conv:
            h = self._gn_silu_conv(h, "norm2", "conv2")
        else:
            h = GroupNorm32(epsilon=1e-6, name="norm2")(h)
            h = nn.silu(h)
            h = nn.Conv(self.out_channels, (3, 3), padding=1,
                        dtype=self.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1),
                        dtype=self.dtype, name="skip")(x)
        return x + h


class VAEAttnBlock(nn.Module):
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        residual = x
        x = GroupNorm32(epsilon=1e-6, name="norm")(x)
        x = x.reshape(b, h * w, c)
        x = MultiHeadAttention(num_heads=1, dtype=self.dtype, name="attn")(x)
        return residual + x.reshape(b, h, w, c)


class VAEDecoder(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, latents: jax.Array) -> jax.Array:
        """(B, h, w, 4) scaled latents -> (B, 8h, 8w, 3) in [-1, 1]."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        z = (latents / cfg.scaling_factor).astype(dtype)
        z = nn.Conv(cfg.latent_channels, (1, 1), dtype=dtype,
                    name="post_quant_conv")(z)

        mults = cfg.channel_mults
        ch = cfg.base_channels * mults[-1]
        x = nn.Conv(ch, (3, 3), padding=1, dtype=dtype, name="conv_in")(z)
        x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                        name="mid_res_0")(x)
        x = VAEAttnBlock(dtype, name="mid_attn")(x)
        x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                        name="mid_res_1")(x)

        for i, mult in enumerate(reversed(mults)):
            lvl = len(mults) - 1 - i
            ch = cfg.base_channels * mult
            for blk in range(cfg.blocks_per_level + 1):
                x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                                name=f"up_{lvl}_res_{blk}")(x)
            if lvl != 0:
                x = nearest_upsample_2x(x)
                x = nn.Conv(ch, (3, 3), padding=1, dtype=dtype,
                            name=f"up_{lvl}_upsample")(x)

        x = GroupNorm32(epsilon=1e-6, name="norm_out")(x)
        x = nn.silu(x)
        x = nn.Conv(3, (3, 3), padding=1, dtype=jnp.float32,
                    name="conv_out")(x)
        return x.astype(jnp.float32)


class VAEEncoder(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, images: jax.Array, rng: jax.Array) -> jax.Array:
        """(B, H, W, 3) in [-1,1] -> sampled scaled latents (B, H/8, W/8, 4)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Conv(cfg.base_channels, (3, 3), padding=1, dtype=dtype,
                    name="conv_in")(images.astype(dtype))
        for lvl, mult in enumerate(cfg.channel_mults):
            ch = cfg.base_channels * mult
            for blk in range(cfg.blocks_per_level):
                x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                                name=f"down_{lvl}_res_{blk}")(x)
            if lvl != len(cfg.channel_mults) - 1:
                x = nn.Conv(ch, (3, 3), strides=(2, 2), padding=1,
                            dtype=dtype, name=f"down_{lvl}_downsample")(x)
        ch = cfg.base_channels * cfg.channel_mults[-1]
        x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                        name="mid_res_0")(x)
        x = VAEAttnBlock(dtype, name="mid_attn")(x)
        x = VAEResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                        name="mid_res_1")(x)
        x = GroupNorm32(epsilon=1e-6, name="norm_out")(x)
        x = nn.silu(x)
        moments = nn.Conv(cfg.latent_channels * 2, (3, 3), padding=1,
                          dtype=jnp.float32, name="conv_out")(x)
        moments = nn.Conv(cfg.latent_channels * 2, (1, 1), dtype=jnp.float32,
                          name="quant_conv")(moments)
        mean, logvar = jnp.split(moments, 2, axis=-1)
        logvar = jnp.clip(logvar, -30.0, 20.0)
        std = jnp.exp(0.5 * logvar)
        sample = mean + std * jax.random.normal(rng, mean.shape)
        return sample * cfg.scaling_factor


def postprocess_images(decoded: jax.Array) -> jax.Array:
    """[-1,1] float -> uint8 RGB, on device."""
    x = jnp.clip(decoded * 0.5 + 0.5, 0.0, 1.0)
    return jnp.round(x * 255.0).astype(jnp.uint8)
