"""Mistral-7B-class causal LM (RoPE + GQA + sliding window + SwiGLU).

The reference's prompt model IS Mistral-7B-Instruct — it calls the hosted
HF Inference endpoint for it (reference backend.py:25, 240-268). This module
is the local TPU-native equivalent of that model family, exposing the same
``__call__`` / ``prefill`` / ``decode_step`` contract as GPT2LM so the
jitted greedy-decode scan (ops/decode.py) and the serving PromptGenerator
drive either family unchanged.

TPU-first choices:
- grouped-query attention: K/V projected at ``num_kv_heads`` and the cache
  stored at KV width (4x less HBM traffic per decode step at 7B scale than
  full-head caches); heads are repeated to query width only at the attention
  site, feeding the MXU full-width batched matmuls;
- RoPE computed in fp32 and applied pre-cache, so cached K is
  position-encoded once and decode steps touch only one new position;
- sliding-window attention expressed as a static band mask under jit —
  no dynamic shapes; the window is part of the compiled graph;
- RMSNorm/softmax accumulate fp32, matmuls run bf16 into the MXU.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import MistralConfig
from cassmantle_tpu.ops.attention import multi_head_attention


class RMSNorm(nn.Module):
    """Root-mean-square LayerNorm (no mean subtraction, no bias), fp32."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + self.epsilon)
        return (out * scale.astype(jnp.float32)).astype(orig_dtype)


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embedding. positions (...,S) -> two
    (..., S, head_dim/2) fp32 arrays."""
    half = head_dim // 2
    freqs = theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary position embedding, GPT-NeoX split-half convention (the
    Mistral/Llama family layout). x: (..., S, H, D); cos/sin (..., S, D/2)
    broadcast over heads."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over the head axis
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(orig_dtype)


def repeat_kv(kv: jax.Array, n_rep: int) -> jax.Array:
    """(..., S, KVH, D) -> (..., S, KVH*n_rep, D) by head repetition."""
    if n_rep == 1:
        return kv
    return jnp.repeat(kv, n_rep, axis=-2)


def band_mask(q_pos: jax.Array, k_pos: jax.Array,
              window: int) -> jax.Array:
    """Causal sliding-window mask: attend iff 0 <= q - k < window.

    q_pos (Sq,), k_pos (Sk,) -> bool (Sq, Sk). Static under jit.
    """
    diff = q_pos[:, None] - k_pos[None, :]
    return (diff >= 0) & (diff < window)


class MistralAttention(nn.Module):
    cfg: MistralConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, cos, sin, mask=None, kv_cache=None,
                 return_kv: bool = False, causal: bool = False):
        """GQA attention with RoPE applied to q/k before caching.

        Same cache contract as models/layers.py::MultiHeadAttention, but
        the cache holds ``num_kv_heads`` heads: decode mode takes
        ``kv_cache=(cache_k, cache_v, index)`` with cache_k/v shaped
        (B, max_len, KVH, D) and writes this call's (RoPE'd) k/v at
        ``index``.
        """
        cfg = self.cfg
        d = cfg.head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n * d, use_bias=False, dtype=self.dtype, name=name
        )
        b, s, _ = x.shape
        q = dense(cfg.num_heads, "q")(x).reshape(b, s, cfg.num_heads, d)
        k = dense(cfg.num_kv_heads, "k")(x).reshape(b, s, cfg.num_kv_heads, d)
        v = dense(cfg.num_kv_heads, "v")(x).reshape(b, s, cfg.num_kv_heads, d)

        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        kv_out = None
        if kv_cache is not None:
            cache_k, cache_v, index = kv_cache
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k.astype(cache_k.dtype), index, axis=-3
            )
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v.astype(cache_v.dtype), index, axis=-3
            )
            k, v = cache_k, cache_v
            kv_out = (cache_k, cache_v)
        elif return_kv:
            kv_out = (k, v)

        n_rep = cfg.num_heads // cfg.num_kv_heads
        out = multi_head_attention(
            q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), mask=mask,
            causal=causal,
        )
        out = out.reshape(b, s, cfg.num_heads * d)
        out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype,
                       name="out")(out)
        if kv_out is not None:
            return out, kv_out
        return out


class SwiGLU(nn.Module):
    """Mistral/Llama MLP: down(silu(gate(x)) * up(x))."""

    intermediate: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        g = nn.Dense(self.intermediate, use_bias=False, dtype=self.dtype,
                     name="gate")(x)
        u = nn.Dense(self.intermediate, use_bias=False, dtype=self.dtype,
                     name="up")(x)
        return nn.Dense(features, use_bias=False, dtype=self.dtype,
                        name="down")(nn.silu(g) * u)


class MistralBlock(nn.Module):
    cfg: MistralConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, cos, sin, mask=None, kv_cache=None,
                 return_kv: bool = False, causal: bool = False):
        h = RMSNorm(self.cfg.rms_eps, name="ln1")(x)
        attn_out = MistralAttention(self.cfg, self.dtype, name="attn")(
            h, cos, sin, mask=mask, kv_cache=kv_cache,
            return_kv=return_kv, causal=causal,
        )
        if kv_cache is not None or return_kv:
            a, kv = attn_out
        else:
            a, kv = attn_out, None
        x = x + a
        h = RMSNorm(self.cfg.rms_eps, name="ln2")(x)
        x = x + SwiGLU(self.cfg.intermediate_size, self.dtype,
                       name="mlp")(h)
        return x, kv


class MistralLM(nn.Module):
    """Causal LM with the GPT2LM serving contract (__call__/prefill/
    decode_step), so ops/decode.py::greedy_decode drives it unchanged."""

    cfg: MistralConfig

    @property
    def _dtype(self):
        return jnp.dtype(self.cfg.dtype)

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              dtype=self._dtype, name="embed")
        self.blocks = [
            MistralBlock(cfg, self._dtype, name=f"block_{i}")
            for i in range(cfg.num_layers)
        ]
        self.ln_f = RMSNorm(cfg.rms_eps, name="ln_f")
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=jnp.float32, name="lm_head")

    def _logits(self, hidden: jax.Array) -> jax.Array:
        # fp32 head keeps greedy argmax stable under bf16 activations
        return self.lm_head(hidden.astype(jnp.float32))

    def __call__(self, input_ids: jax.Array,
                 valid: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        """Plain forward: (B, S) [+ (B, S) validity] -> (B, S, V).

        Explicit (B, S) ``positions`` select the context-parallel form
        (zigzag-permuted data, parallel/lm_train.py): RoPE follows the
        per-token true positions, the mask is owned by the attention op
        (plain causal, dispatchable to the sharded zigzag ring), and the
        sequence must fit the sliding window — the band mask degenerates
        to causal there, which is what the zigzag kernel implements."""
        cfg = self.cfg
        _, s = input_ids.shape
        if positions is not None:
            assert valid is None, \
                "positions mode owns masking; pre-mask inputs instead"
            assert s <= cfg.sliding_window, (
                f"context-parallel Mistral needs seq {s} <= "
                f"sliding_window {cfg.sliding_window} (banded zigzag "
                f"attention not implemented)")
            mask = None
        else:
            positions = jnp.arange(s)
            mask = band_mask(
                positions, positions, cfg.sliding_window)[None, None]
            if valid is not None:
                mask = mask & valid[:, None, None, :]
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        x = self.embed(input_ids)
        for block in self.blocks:
            x, _ = block(x, cos, sin, mask=mask, causal=mask is None)
        return self._logits(self.ln_f(x))

    def prefill(
        self, input_ids: jax.Array, prompt_len: jax.Array, max_len: int
    ) -> Tuple[jax.Array, Tuple]:
        """Right-padded prompt forward seeding a ``max_len`` decode cache.

        Cache layout: per-layer (k, v), each (B, max_len, KVH, D) with
        RoPE already applied to K and positions >= P zero-filled.
        """
        cfg = self.cfg
        b, p = input_ids.shape
        assert p <= max_len
        positions = jnp.arange(p)
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        x = self.embed(input_ids)
        band = band_mask(positions, positions, cfg.sliding_window)
        valid = positions[None, :] < prompt_len[:, None]
        mask = band[None, None] & valid[:, None, None, :]
        cache = []
        for block in self.blocks:
            x, (k, v) = block(x, cos, sin, mask=mask, return_kv=True)
            pad = ((0, 0), (0, max_len - p), (0, 0), (0, 0))
            cache.append((jnp.pad(k, pad), jnp.pad(v, pad)))
        logits = self._logits(self.ln_f(x))
        last = jnp.take_along_axis(
            logits, (prompt_len - 1)[:, None, None], axis=1
        ).squeeze(1)
        return last, tuple(cache)

    def decode_step(
        self,
        token: jax.Array,      # (B,) ids for position ``index``
        index: jax.Array,      # scalar int32: the cache slot written
        cache: Tuple,
        valid: jax.Array,      # (B, max_len) cache validity incl. this step
        positions: jax.Array,  # (B, 1) each row's position id
    ) -> Tuple[jax.Array, Tuple]:
        """One cached decode step; the S=1 case of :meth:`decode_chunk`
        (one code path shared with the speculative verify forward).
        RoPE follows ``positions``; the sliding window is counted in
        cache slots, so the serving layer hands this family rows of one
        prompt bucket only, where the two agree
        (serving/pipeline.py ``LMFamily.mixed_buckets``).
        Returns (logits (B, V), new cache)."""
        logits, new_cache = self.decode_chunk(
            token[:, None], index, cache, valid, positions)
        return logits[:, 0], new_cache

    def decode_chunk(
        self,
        tokens: jax.Array,     # (B, S) ids for positions index..index+S-1
        index: jax.Array,      # scalar int32: cache position of tokens[:, 0]
        cache: Tuple,
        valid: jax.Array,      # (B, max_len) cache validity incl. the chunk
        positions: Optional[jax.Array] = None,  # (B, S); None: the slots
    ) -> Tuple[jax.Array, Tuple]:
        """Multi-token cached decode (the GPT2LM.decode_chunk contract):
        RoPE follows the true positions ``index + j`` and the sliding
        window is enforced per query inside the shared causal chunk
        mask — cache positions at or below ``index + j - window`` are
        never attended by query j. Returns (logits (B, S, V), new
        cache)."""
        from cassmantle_tpu.models.layers import chunk_causal_mask

        cfg = self.cfg
        _, s = tokens.shape
        mask = chunk_causal_mask(valid, index, s,
                                 window=cfg.sliding_window)
        if positions is None:
            positions = (index + jnp.arange(s))[None, :]
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        x = self.embed(tokens)
        new_cache = []
        for block, (ck, cv) in zip(self.blocks, cache):
            x, kv = block(x, cos, sin, mask=mask, kv_cache=(ck, cv, index))
            new_cache.append(kv)
        return self._logits(self.ln_f(x)), tuple(new_cache)
