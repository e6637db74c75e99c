"""LFM2-MoE-class causal LM: gated short convolutions among grouped-query
attention layers, a dense leading MLP and sigmoid-routed sparse experts.

Layer ``i`` mixes tokens by ``cfg.layer_types[i]``: ``conv`` is the short
convolution (two gates around a depthwise causal convolution of
``conv_L_cache`` taps: neither attention nor a recurrence, its only state
the last ``conv_L_cache - 1`` inputs), ``full_attention`` is grouped-query
attention with per-head RMSNorm on q and k and rotary over the whole head.
Its feed-forward is a dense SwiGLU MLP for ``i < num_dense_layers`` and
models/moe.py::HeldExperts after, under the family's routing rule:
sigmoid scores, the top-k taken on score + ``expert_bias``, the weights
the unbiased scores of the chosen over their sum + 1e-6, times
``routed_scaling_factor``; no shared expert. The embedding is tied: the
head reads it. It keeps the zoo LM contract (``prefill`` /
``decode_step``), so ops/decode.py's scan and PromptGenerator drive it
like the other families.

The cache is the tree models/qwen3_next.py describes, with a third kind
of entry: k/v of ``max_len`` positions for an attention layer, and for a
convolution layer its window alone, (B, conv_L_cache - 1, D) float32,
handed over by ``prefill`` as it stood at each row's own ``prompt_len``
(the convolution is causal, so pads change nothing before them). It
carries the expert layers' ``stats`` and the ``real`` rows likewise.

Precision as in models/qwen3_next.py: weights in ``cfg.dtype``, matmuls
read their operands in it and accumulate in float32; the residual stream,
the norms, the gates' products, the convolution and the router stay
float32.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import Lfm2MoeConfig
from cassmantle_tpu.models import qwen3_next
from cassmantle_tpu.models.moe import HeldExperts
from cassmantle_tpu.models.qwen3_next import (
    Linear,
    RMSNorm,
    cache_stats,  # noqa: F401  (the family's, by the same name)
    rotary,
    window_at,
    zero_stats,
)
from cassmantle_tpu.ops.attention import multi_head_attention

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, prompt_len=None, window=None):
        """x (B, S, D) -> (out (B, S, D), window). Prefill: ``window``
        None, and the one handed back is the gated inputs before
        ``prompt_len``; a step: ``window`` is the layer's cache entry and
        S = 1."""
        d, taps = self.cfg.hidden_size, self.cfg.conv_L_cache
        s = x.shape[1]
        gate_b, gate_c, inner = jnp.split(
            Linear(3 * d, self.dtype, name="in_proj")(x), 3, axis=-1)
        u = gate_b * inner
        conv = self.param("conv", nn.initializers.lecun_normal(),
                          (taps, d), F32).astype(F32)
        if window is None:
            padded, window = window_at(u, prompt_len, taps - 1)
        else:
            padded = jnp.concatenate([window, u], axis=1)
            window = padded[:, 1:]
        mixed = sum(conv[j] * padded[:, j:j + s] for j in range(taps))
        return (Linear(d, self.dtype, name="out_proj")(gate_c * mixed),
                window)


class GroupedQueryAttention(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, positions, mask, kv_cache=None, index=None):
        """x (B, S, D), mask (B, 1, S, S_k) -> (out, (k, v)). With
        ``kv_cache`` (k, v of (B, max_len, KVH, D)) this call's k/v are
        written at ``index`` and the whole cache is attended."""
        cfg = self.cfg
        h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        b, s, _ = x.shape
        q = Linear(h * d, self.dtype, name="q_proj")(x).reshape(b, s, h, d)
        k = Linear(kvh * d, self.dtype, name="k_proj")(x).reshape(
            b, s, kvh, d)
        v = Linear(kvh * d, self.dtype, name="v_proj")(x).reshape(
            b, s, kvh, d)
        q = rotary(RMSNorm(cfg.norm_eps, centred=False, name="q_norm")(q),
                   positions, d, cfg.rope_theta)
        k = rotary(RMSNorm(cfg.norm_eps, centred=False, name="k_norm")(k),
                   positions, d, cfg.rope_theta)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        if kv_cache is not None:
            k = jax.lax.dynamic_update_slice_in_dim(kv_cache[0], k, index,
                                                    axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(kv_cache[1], v, index,
                                                    axis=1)
        # KV head j serves query heads j*(H/KVH) .. (j+1)*(H/KVH)-1; q
        # stays float32, so the scores come out in it
        attn = multi_head_attention(
            q, *(jnp.repeat(t, h // kvh, axis=2) for t in (k, v)), mask=mask)
        out = Linear(cfg.hidden_size, self.dtype, name="out_proj")(
            attn.reshape(b, s, h * d))
        return out, (k, v)


class DenseMLP(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        width = self.cfg.intermediate_size
        gate = Linear(width, self.dtype, name="w1")(x)
        up = Linear(width, self.dtype, name="w3")(x)
        return Linear(self.cfg.hidden_size, self.dtype, name="w2")(
            nn.silu(gate) * up)


class Lfm2MoeLayer(nn.Module):
    cfg: Lfm2MoeConfig
    index: int
    dtype: jnp.dtype

    @property
    def full_attention(self) -> bool:
        return self.cfg.layer_types[self.index] == "full_attention"

    @nn.compact
    def __call__(self, x, real, dense_experts: bool, **mixer_args):
        """x (B, S, D) float32, real (B, S) -> (x, cache entry, stats)."""
        cfg = self.cfg
        h = RMSNorm(cfg.norm_eps, centred=False, name="operator_norm")(x)
        if self.full_attention:
            with jax.named_scope("gqa_attn"):
                mixed, entry = GroupedQueryAttention(
                    cfg, self.dtype, name="mixer")(h, **mixer_args)
        else:
            with jax.named_scope("short_conv"):
                mixed, entry = ShortConv(cfg, self.dtype, name="mixer")(
                    h, **mixer_args)
        x = x + mixed
        b, s, d = x.shape
        h = RMSNorm(cfg.norm_eps, centred=False, name="ffn_norm")(x)
        if self.index < cfg.num_dense_layers:
            with jax.named_scope("dense_mlp"):
                out = DenseMLP(cfg, self.dtype, name="mlp")(h)
            return x + out, entry, zero_stats(cfg)
        out, stats = HeldExperts(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            intermediate=cfg.moe_intermediate_size,
            norm_topk_prob=cfg.norm_topk_prob, scoring="sigmoid",
            selection_bias=cfg.use_expert_bias, norm_eps=1e-6,
            scaling=cfg.routed_scaling_factor, dtype=self.dtype,
            name="moe")(h.reshape(b * s, d), real.reshape(b * s),
                        dense_experts)
        return x + out.reshape(b, s, d), entry, stats


def active_params(tree, cfg: Lfm2MoeConfig) -> float:
    """Parameters a token's forward multiplies by: as
    ``qwen3_next.active_params`` (the experts it is not routed to left
    out), with the embedding counted, since tied it is the head's
    matrix."""
    return (qwen3_next.active_params(tree, cfg)
            + float(cfg.vocab_size * cfg.hidden_size))


class Lfm2MoeLM(nn.Module):
    """Causal LM with the zoo serving contract."""

    cfg: Lfm2MoeConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        # rows are looked up in the stored type and widened after: asked
        # for float32, the look-up widens the whole table first, and with
        # the head reading the same table the compiler wrote those 0.5 GB
        # out in every decode step (PR 34: a step took 3.15 ms for 1.58)
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed")
        self.layers = [Lfm2MoeLayer(cfg, i, dtype, name=f"layer_{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.embedding_norm = RMSNorm(cfg.norm_eps, centred=False,
                                      name="embedding_norm")

    def _logits(self, hidden):
        # the tied head, float32 at full precision: greedy argmax over
        # near-ties; at decode it is bound by reading the table either way
        return jax.lax.dot_general(
            self.embedding_norm(hidden), self.embed.embedding.astype(F32),
            (((hidden.ndim - 1,), (1,)), ((), ())), precision=HI)

    def _prefill_layers(self, input_ids, prompt_len, real_rows):
        """(hidden (B, P, D), per-layer cache entries at width P, stats)."""
        p = input_ids.shape[1]
        positions = jnp.arange(p)
        token_valid = positions[None, :] < prompt_len[:, None]
        mask = (jnp.tril(jnp.ones((p, p), bool))[None]
                & token_valid[:, None, :])[:, None]
        real = token_valid & real_rows[:, None]
        x = self.embed(input_ids).astype(F32)
        entries, stats = [], zero_stats(self.cfg)
        for layer in self.layers:
            args = (dict(positions=positions, mask=mask)
                    if layer.full_attention else dict(prompt_len=prompt_len))
            x, entry, layer_stats = layer(x, real, True, **args)
            entries.append(entry)
            stats = jax.tree_util.tree_map(jnp.add, stats, layer_stats)
        return x, entries, stats

    def __call__(self, input_ids: jax.Array) -> jax.Array:
        """Plain forward: (B, S) -> (B, S, V), every position real."""
        b, s = input_ids.shape
        x, _, _ = self._prefill_layers(
            input_ids, jnp.full((b,), s, jnp.int32), jnp.ones((b,), bool))
        return self._logits(x)

    def prefill(self, input_ids: jax.Array, prompt_len: jax.Array,
                max_len: int, row_mask=None) -> Tuple[jax.Array, dict]:
        """Right-padded prompt forward seeding the decode cache: k/v padded
        to ``max_len`` for the attention layers, each row's window at its
        own ``prompt_len`` for the convolution layers. ``row_mask`` (B,)
        marks the rows that are requests (None: all); padding rows are
        left out of ``stats``."""
        b, p = input_ids.shape
        assert p <= max_len
        real_rows = (jnp.ones((b,), bool) if row_mask is None
                     else row_mask.astype(bool))
        x, entries, stats = self._prefill_layers(input_ids, prompt_len,
                                                 real_rows)
        pad = ((0, 0), (0, max_len - p), (0, 0), (0, 0))
        entries = tuple(
            (jnp.pad(e[0], pad), jnp.pad(e[1], pad))
            if layer.full_attention else e
            for layer, e in zip(self.layers, entries))
        last = jnp.take_along_axis(
            x, (prompt_len - 1)[:, None, None], axis=1).squeeze(1)
        return self._logits(last), {"layers": entries, "stats": stats,
                                    "real": real_rows}

    def decode_step(self, token: jax.Array, index: jax.Array, cache: dict,
                    valid: jax.Array, positions: jax.Array
                    ) -> Tuple[jax.Array, dict]:
        """One cached decode step: ``token`` (B,) is written to cache slot
        ``index`` of the attention layers and rotated by its row's
        ``positions`` (B, 1), which lies below the slot for a row decoded
        in a wider prompt bucket's program than its own; the convolution
        layers shift their window and know no position. Returns (logits
        (B, V), new cache)."""
        x = self.embed(token[:, None]).astype(F32)
        real = cache["real"][:, None]
        mask = valid[:, None, None, :]
        entries, stats = [], cache["stats"]
        for layer, entry in zip(self.layers, cache["layers"]):
            args = (dict(positions=positions, mask=mask, kv_cache=entry,
                         index=index)
                    if layer.full_attention else dict(window=entry))
            x, entry, layer_stats = layer(x, real, False, **args)
            entries.append(entry)
            stats = jax.tree_util.tree_map(jnp.add, stats, layer_stats)
        return self._logits(x[:, 0]), {"layers": tuple(entries),
                                       "stats": stats,
                                       "real": cache["real"]}
