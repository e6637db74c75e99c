"""GPT-2-class causal LM for prompt/story generation.

Replaces the reference's remote Mistral-7B Inference-API call
(backend.py:240-268): story episodes are generated locally by greedy decode
(ops/decode.py) over this module, 32-96 new tokens per round, matching the
reference's decode budget (backend.py:250-255).

Two call modes, one parameter set, all static shapes:
- ``prefill``: full forward over the right-padded prompt bucket; returns
  last-real-token logits plus every layer's k/v to seed a fixed-size decode
  cache.
- ``decode_step``: single-token step extending the cache; runs inside the
  sampler's lax.scan. The caller owns the cache-validity mask (right-padded
  prompt positions stay masked forever).
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import GPT2Config
from cassmantle_tpu.models.layers import (
    MultiHeadAttention,
    TransformerMLP,
    chunk_causal_mask,
)


class GPT2Block(nn.Module):
    cfg: GPT2Config
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, mask=None, kv_cache=None, return_kv=False,
                 causal=False):
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln1")(x)
        # act_per_token: under W8A8 (lm_w8a8) LM activations quantize
        # with per-token scales — decode activations are outlier-heavy
        # per position, and a row-max costs nothing against the matmul
        attn_out = MultiHeadAttention(
            num_heads=self.cfg.num_heads, dtype=self.dtype, name="attn",
            act_per_token=True,
        )(h, mask=mask, kv_cache=kv_cache, return_kv=return_kv,
          causal=causal)
        if kv_cache is not None or return_kv:
            a, kv = attn_out
        else:
            a, kv = attn_out, None
        x = x + a
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln2")(x)
        x = x + TransformerMLP(
            intermediate=self.cfg.hidden_size * 4, dtype=self.dtype,
            name="mlp", act_per_token=True,
        )(h)
        return x, kv


class GPT2LM(nn.Module):
    cfg: GPT2Config

    @property
    def _dtype(self):
        return jnp.dtype(self.cfg.dtype)

    def setup(self):
        dtype = self._dtype
        self.wte = nn.Embed(self.cfg.vocab_size, self.cfg.hidden_size,
                            dtype=dtype, name="wte")
        self.wpe = nn.Embed(self.cfg.max_positions, self.cfg.hidden_size,
                            dtype=dtype, name="wpe")
        self.blocks = [
            GPT2Block(self.cfg, dtype, name=f"block_{i}")
            for i in range(self.cfg.num_layers)
        ]
        self.ln_f = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln_f")

    def _logits(self, hidden: jax.Array) -> jax.Array:
        # weight-tied LM head (fp32 matmul keeps greedy argmax stable)
        emb = self.wte.embedding.astype(jnp.float32)
        return hidden.astype(jnp.float32) @ emb.T

    def __call__(self, input_ids: jax.Array,
                 valid: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        """Plain forward: (B, S) [+ optional (B, S) validity] -> (B, S, V).

        With ``valid=None`` the causal mask is owned by the attention op
        (never materialized here) — which also makes this forward
        context-parallel capable: under ``ops.attention.context_parallel``
        the attention runs sequence-sharded, and the caller supplies
        zigzag-permuted ``positions`` matching its permuted input_ids
        (parallel/lm_train.py)."""
        _, s = input_ids.shape
        if positions is None:
            positions = jnp.arange(s)[None, :]
        x = self.wte(input_ids) + self.wpe(positions)
        if valid is None:
            mask = None
        else:
            causal = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None]
            mask = causal & valid[:, None, None, :]
        for block in self.blocks:
            x, _ = block(x, mask=mask, causal=mask is None)
        return self._logits(self.ln_f(x))

    def prefill(
        self, input_ids: jax.Array, prompt_len: jax.Array, max_len: int
    ) -> Tuple[jax.Array, Tuple]:
        """Padded-prompt forward seeding a ``max_len`` decode cache.

        input_ids (B, P) right-padded, prompt_len (B,). Returns
        (last-real-token logits (B, V), cache tuple of per-layer (k, v)
        each (B, max_len, H, D) with positions >= P zero-filled).
        """
        b, p = input_ids.shape
        assert p <= max_len
        positions = jnp.arange(p)[None, :]
        x = self.wte(input_ids) + self.wpe(positions)
        causal = jnp.tril(jnp.ones((p, p), dtype=bool))
        valid = positions < prompt_len[:, None]
        mask = causal[None, None] & valid[:, None, None, :]
        cache = []
        for block in self.blocks:
            x, (k, v) = block(x, mask=mask, return_kv=True)
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
        """One greedy-decode step; the S=1 case of :meth:`decode_chunk`
        (one code path, so the speculative verify forward and the plain
        greedy scan run the exact same per-position computation). A row
        decoded in a wider prompt bucket's program than its own sits at
        a position below its slot (ops/decode.py ``position_offset``).
        Returns (logits (B, V), updated cache)."""
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
        """Multi-token cached decode: score S positions in ONE forward.

        The speculative-decode verify step (ops/decode.py): the chunk's
        k/v append into the cache at ``index..index+S-1`` (one
        dynamic-update-slice per layer — the chunk-append contract in
        models/layers.py) and each query j attends the cache under the
        shared causal chunk mask (``<= index + j``), so logits[:, j]
        equals what ``decode_step`` would produce after feeding
        tokens[:, :j+1] one at a time. One weight read serves all S
        positions — the whole point of drafting.

        Returns (logits (B, S, V), updated cache).
        """
        _, s = tokens.shape
        if positions is None:
            positions = (index + jnp.arange(s))[None, :]
        x = self.wte(tokens) + self.wpe(positions)
        mask = chunk_causal_mask(valid, index, s)
        new_cache = []
        for block, (ck, cv) in zip(self.blocks, cache):
            x, kv = block(x, mask=mask, kv_cache=(ck, cv, index))
            new_cache.append(kv)
        return self._logits(self.ln_f(x)), tuple(new_cache)
