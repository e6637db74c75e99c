"""Diffusion UNet (SD1.5 geometry by default, SDXL via UNetConfig.sdxl()).

This is the flagship TPU model: it replaces the reference's remote SDXL
Inference-API call (backend.py:270-295) with a local Flax module whose
denoise step runs as one jit'd XLA graph per DDIM step (ops/ddim.py wraps it
in a lax.scan).

TPU-first choices:
- NHWC layout end to end (XLA TPU-native conv layout; no transposes);
- bf16 params/activations with fp32 GroupNorm and fp32 softmax (via
  ops.attention), preserving image quality while feeding the MXU bf16;
- attention over image tokens (H·W up to 4096 at 512², 16k+ at SDXL-1024)
  goes through ops.attention → Pallas flash kernel on TPU;
- static shapes everywhere: the batch/resolution buckets come from
  ServingConfig, so XLA compiles once per bucket.

Structure matches Stable Diffusion's UNet so safetensors checkpoints map
1:1 (models/weights.py): conv_in → time-embed MLP → down levels (ResBlocks
+ spatial transformers + strided-conv downsample) → mid → up levels with
skip concatenation and nearest-neighbor upsample → GroupNorm/SiLU/conv_out.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import UNetConfig
from cassmantle_tpu.models.layers import (
    GEGLU,
    GroupNorm32,
    LayerNorm32,
    MultiHeadAttention,
    conv3x3_same,
    fused_gn_silu_conv3x3,
    nearest_upsample_2x,
    timestep_embedding,
)


class ResBlock(nn.Module):
    """GN/SiLU/conv3x3 x2 + time injection + skip.

    ``fused_conv`` routes both norm+act+conv sequences through the
    Pallas fused kernel (ops/fused_conv.py): GroupNorm statistics still
    reduce in fp32 here (``return_affine``), but the normalize, SiLU,
    and 3x3 conv run as one kernel so the activated tensor never
    round-trips HBM. The param tree is IDENTICAL either way
    (Conv3x3Params declares nn.Conv's exact kernel/bias layout), so
    checkpoints, the init cache, and the A/B share one tree;
    ``conv_pad_to`` additionally pads channel dims to MXU-friendly
    multiples inside the fused op (zero-fill, output sliced back).
    Unfused, both convolutions go through ``conv3x3_same``: ``nn.Conv``,
    or on the TPU under 8 batch rows the same products with H folded
    into the batch (models/layers.py::conv3x3_form).
    """

    out_channels: int
    dtype: jnp.dtype
    fused_conv: bool = False
    conv_pad_to: int = 0

    def _gn_silu_conv(self, x, norm_name: str, conv_name: str):
        return fused_gn_silu_conv3x3(
            x, self.out_channels, self.dtype, norm_name, conv_name,
            pad_to=self.conv_pad_to)

    @nn.compact
    def __call__(self, x, temb):
        if self.fused_conv:
            h = self._gn_silu_conv(x, "norm1", "conv1")
        else:
            h = GroupNorm32(name="norm1")(x)
            h = nn.silu(h)
            h = conv3x3_same(h, self.out_channels, self.dtype, "conv1")
        t = nn.Dense(self.out_channels, dtype=self.dtype,
                     name="time_proj")(nn.silu(temb))
        h = h + t[:, None, None, :]
        if self.fused_conv:
            h = self._gn_silu_conv(h, "norm2", "conv2")
        else:
            h = GroupNorm32(name="norm2")(h)
            h = nn.silu(h)
            h = conv3x3_same(h, self.out_channels, self.dtype, "conv2")
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1),
                        dtype=self.dtype, name="skip")(x)
        return x + h


class BasicTransformerBlock(nn.Module):
    num_heads: int
    context_dim: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, context):
        h = LayerNorm32(name="ln1")(x)
        # bias-free q/k/v but biased out-projection: the published UNet
        # layout (manifests unet_sd15/unet_sdxl: to_out.0 has a bias).
        # fused_qkv: one projection matmul per site instead of three
        # (converters concatenate to_q/to_k/to_v at load) — the UNet
        # only ever runs full forwards, never cached decode.
        x = x + MultiHeadAttention(
            num_heads=self.num_heads, dtype=self.dtype, use_bias=False,
            out_bias=True, fused_qkv=True, name="self_attn",
        )(h)
        h = LayerNorm32(name="ln2")(x)
        x = x + MultiHeadAttention(
            num_heads=self.num_heads, dtype=self.dtype, use_bias=False,
            out_bias=True, fused_qkv=True, name="cross_attn",
        )(h, context=context)
        h = LayerNorm32(name="ln3")(x)
        x = x + GEGLU(
            intermediate=x.shape[-1] * 4, dtype=self.dtype, name="ff"
        )(h)
        return x


class SpatialTransformer(nn.Module):
    """Flatten HW -> tokens, run transformer blocks with text cross-attn."""

    num_heads: int
    depth: int
    context_dim: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, context):
        b, h, w, c = x.shape
        residual = x
        # diffusers' Transformer2DModel hardcodes eps=1e-6 for this norm
        # (unlike the resblock norms at the 1e-5 norm_eps default)
        x = GroupNorm32(epsilon=1e-6, name="norm")(x)
        x = nn.Dense(c, dtype=self.dtype, name="proj_in")(x)
        x = x.reshape(b, h * w, c)
        for i in range(self.depth):
            x = BasicTransformerBlock(
                num_heads=self.num_heads, context_dim=self.context_dim,
                dtype=self.dtype, name=f"block_{i}",
            )(x, context)
        x = x.reshape(b, h, w, c)
        x = nn.Dense(c, dtype=self.dtype, name="proj_out")(x)
        return x + residual


class UNet(nn.Module):
    cfg: UNetConfig

    def _heads(self, channels: int) -> int:
        if self.cfg.num_heads is not None:
            return self.cfg.num_heads
        return max(1, channels // 64)  # SDXL convention: head_dim 64

    @nn.compact
    def __call__(
        self,
        latents: jax.Array,                  # (B, H, W, 4) noisy latents
        timesteps: jax.Array,                # (B,) int/float
        context: jax.Array,                  # (B, S, context_dim) text states
        addition_embeds: Optional[jax.Array] = None,  # SDXL micro-conds
    ) -> jax.Array:
        """Denoise forward: predicted noise ``eps`` (B, H, W, 4), float32."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        latents = latents.astype(dtype)
        context = context.astype(dtype)

        # -- time embedding ------------------------------------------------
        temb = timestep_embedding(timesteps, cfg.base_channels)
        temb = nn.Dense(cfg.time_embed_dim, dtype=dtype, name="time_fc1")(
            temb.astype(dtype))
        temb = nn.Dense(cfg.time_embed_dim, dtype=dtype, name="time_fc2")(
            nn.silu(temb))
        if cfg.addition_embed_dim and addition_embeds is not None:
            aemb = nn.Dense(cfg.time_embed_dim, dtype=dtype,
                            name="add_fc1")(addition_embeds.astype(dtype))
            aemb = nn.Dense(cfg.time_embed_dim, dtype=dtype,
                            name="add_fc2")(nn.silu(aemb))
            temb = temb + aemb

        levels = len(cfg.channel_mults)

        def res_block(ch: int, name: str) -> ResBlock:
            return ResBlock(ch, dtype, fused_conv=cfg.fused_conv,
                            conv_pad_to=cfg.conv_pad_to, name=name)

        x = nn.Conv(cfg.base_channels, (3, 3), padding=1,
                    dtype=dtype, name="conv_in")(latents)

        # -- down ----------------------------------------------------------
        skips = [x]
        for lvl in range(levels):
            ch = cfg.base_channels * cfg.channel_mults[lvl]
            for blk in range(cfg.blocks_per_level):
                x = res_block(ch, f"down_{lvl}_res_{blk}")(x, temb)
                if cfg.attention_levels[lvl] and cfg.transformer_depth[lvl]:
                    x = SpatialTransformer(
                        num_heads=self._heads(ch),
                        depth=cfg.transformer_depth[lvl],
                        context_dim=cfg.context_dim, dtype=dtype,
                        name=f"down_{lvl}_attn_{blk}",
                    )(x, context)
                skips.append(x)
            if lvl != levels - 1:
                x = nn.Conv(ch, (3, 3), strides=(2, 2), padding=1,
                            dtype=dtype, name=f"down_{lvl}_downsample")(x)
                skips.append(x)

        # -- mid -----------------------------------------------------------
        mid_ch = cfg.base_channels * cfg.channel_mults[-1]
        mid_depth = max(
            [d for lvl, d in enumerate(cfg.transformer_depth)
             if cfg.attention_levels[lvl]] or [1]
        )
        x = res_block(mid_ch, "mid_res_0")(x, temb)
        x = SpatialTransformer(
            num_heads=self._heads(mid_ch), depth=mid_depth,
            context_dim=cfg.context_dim, dtype=dtype, name="mid_attn",
        )(x, context)
        x = res_block(mid_ch, "mid_res_1")(x, temb)

        # -- up ------------------------------------------------------------
        for lvl in reversed(range(levels)):
            ch = cfg.base_channels * cfg.channel_mults[lvl]
            for blk in range(cfg.blocks_per_level + 1):
                skip = skips.pop()
                x = jnp.concatenate([x, skip], axis=-1)
                x = res_block(ch, f"up_{lvl}_res_{blk}")(x, temb)
                if cfg.attention_levels[lvl] and cfg.transformer_depth[lvl]:
                    x = SpatialTransformer(
                        num_heads=self._heads(ch),
                        depth=cfg.transformer_depth[lvl],
                        context_dim=cfg.context_dim, dtype=dtype,
                        name=f"up_{lvl}_attn_{blk}",
                    )(x, context)
            if lvl != 0:
                x = nearest_upsample_2x(x)
                x = conv3x3_same(x, ch, dtype, f"up_{lvl}_upsample")

        assert not skips, f"unconsumed skips: {len(skips)}"

        # -- out -----------------------------------------------------------
        x = GroupNorm32(name="norm_out")(x)
        x = nn.silu(x)
        x = nn.Conv(cfg.sample_channels, (3, 3), padding=1,
                    dtype=jnp.float32, name="conv_out")(x)
        return x.astype(jnp.float32)
