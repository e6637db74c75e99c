"""Qwen3-Next-class causal LM: Gated DeltaNet linear attention, gated full
attention and a sparse expert block in every layer.

Layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
0`` and a Gated DeltaNet token mixer otherwise; every layer's MLP is
models/moe.py::HeldExperts (top-k over the published number of experts, of
which this chip holds a range, plus a sigmoid-gated shared expert). It keeps
the zoo LM contract (``prefill`` / ``decode_step``), so the jitted decode
scan (ops/decode.py) and the serving PromptGenerator drive it like GPT-2.

What differs from the other families is the cache. It is a tree with two
kinds of entry: k/v of ``max_len`` positions for a full-attention layer,
and for a linear layer the recurrent state ``S (B, H_v, d_k, d_v)``
float32 with the last ``conv_kernel - 1`` inputs of the causal
convolution. Attention survives a right-padded prompt bucket through the
``valid`` mask; a recurrent state does not, so ``prefill`` hands over each
row's state as it stood at that row's own ``prompt_len``: a pad position
is a no-op of the recurrence (beta = 0, g = 0) and the convolution's window
is taken at ``prompt_len``. The cache also carries what the expert layers
routed (``stats``) and which rows are real (``real``), so the counters of
a dispatch come back with its tokens and cost no host sync.

Precision: weights are stored in ``cfg.dtype`` and every matmul reads its
operands in it, accumulating in float32; the residual stream, the norms,
the router, the decay and the recurrent state stay float32 (products with
the state at ``Precision.HIGHEST``: the TPU's default would round the
state to bfloat16 every step).

Layout of the seeded weights (the checkpoint groups ``qkvz`` / ``ba`` per
key head; with seeded weights one layout is fixed here and the plain
reference reads the same): ``in_proj_qkvz`` gives ``q | k | v | z`` flat,
``in_proj_ba`` gives ``b | a``, the convolution runs over ``q | k | v``;
value head ``h`` uses key head ``h // (H_v / H_k)``. ``q_proj`` gives, per
head, ``q | gate``. Not served: the checkpoint's multi-token-prediction
module (one token a step).
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import Qwen3NextConfig
from cassmantle_tpu.models.moe import HeldExperts, stored_dot

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class Linear(nn.Module):
    """x (B, S, K) @ kernel without a bias: operands in the kernel's
    storage dtype, float32 out; one token a row is a decode step, whose
    rows keep float32's precision (models/moe.py ``stored_dot``)."""

    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), F32)
        return stored_dot(x, kernel.astype(self.dtype), x.shape[-2] == 1)


class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * (1 + w) in float32 (zero-centred
    weight), or * w where ``centred`` is off (the DeltaNet's output norm)."""

    epsilon: float
    centred: bool = True

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight",
            nn.initializers.normal(0.02) if self.centred
            else nn.initializers.ones, (x.shape[-1],), F32).astype(F32)
        x = x.astype(F32)
        x = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon)
        return x * (1.0 + weight if self.centred else weight)


def rotary(x, positions, rotary_dim: int, theta: float):
    """Rotate-half rotary embedding on the first ``rotary_dim`` of the head.
    x (B, S, H, D), positions (S,) or (B, S)."""
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[..., None] * freqs
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]],
        axis=-1)


def window_at(inputs, prompt_len, width: int):
    """(padded, window): ``inputs`` (B, S, C) of a causal convolution with
    ``width`` zeros before the sequence's start, and each row's last
    ``width`` inputs before its own ``prompt_len`` (B, width, C), which is
    what a decode step's window starts from: inputs prompt_len - width ..
    prompt_len - 1 sit at padded prompt_len .. prompt_len + width - 1, so
    whatever the pad positions hold changes nothing."""
    padded = jnp.pad(inputs, ((0, 0), (width, 0), (0, 0)))
    window = jnp.take_along_axis(
        padded, (prompt_len[:, None] + jnp.arange(width)[None, :])[..., None],
        axis=1)
    return padded, window


def delta_rule(q, k, v, g, beta, state):
    """The gated delta rule, a token a step. q, k, v (B, S, H, d), g, beta
    (B, S, H), state (B, H, d_k, d_v) float32 -> (o (B, S, H, d_v), state).
    Per head: S <- exp(g) S; r = v - S^T k; S <- S + k (beta r)^T;
    o = S^T q. A position with beta = 0 and g = 0 leaves S as it was."""

    def step(s, per):
        q_t, k_t, v_t, g_t, b_t = per
        s = s * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI)
        s = s + k_t[..., :, None] * (b_t[..., None] * r)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    if q.shape[1] == 1:  # a decode step: no loop around one token
        state, out = step(state, [a[:, 0] for a in (q, k, v, g, beta)])
        return out[:, None], state
    time_major = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    state, out = jax.lax.scan(step, state, time_major)
    return jnp.moveaxis(out, 0, 1), state


class GatedDeltaNet(nn.Module):
    cfg: Qwen3NextConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, token_valid=None, prompt_len=None, state=None):
        """x (B, S, D) -> (out (B, S, D), (S, window)). Prefill: ``state``
        None, ``token_valid`` (B, S) marks the real positions and the
        window is the convolution's inputs before ``prompt_len``; a step:
        ``state`` is the layer's cache entry and S = 1."""
        cfg = self.cfg
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        kern = cfg.linear_conv_kernel_dim
        b, s, _ = x.shape
        qk_w, v_w = hk * dk, hv * dv
        conv_w = 2 * qk_w + v_w

        qkvz = Linear(conv_w + v_w, self.dtype, name="in_proj_qkvz")(x)
        ba = Linear(2 * hv, self.dtype, name="in_proj_ba")(x)
        mixed, z = qkvz[..., :conv_w], qkvz[..., conv_w:]
        conv = self.param("conv", nn.initializers.lecun_normal(),
                          (kern, conv_w), F32).astype(F32)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, F32, 1e-3, 16.0)), (hv,)).astype(F32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             F32).astype(F32)

        if state is None:
            recurrent = jnp.zeros((b, hv, dk, dv), F32)
            padded, window = window_at(mixed, prompt_len, kern - 1)
        else:
            recurrent, window = state
            padded = jnp.concatenate([window, mixed], axis=1)
            window = padded[:, 1:]
        mixed = nn.silu(sum(conv[j] * padded[:, j:j + s]
                            for j in range(kern)))

        def l2(t):
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

        q = l2(mixed[..., :qk_w].reshape(b, s, hk, dk)) * dk ** -0.5
        k = l2(mixed[..., qk_w:2 * qk_w].reshape(b, s, hk, dk))
        q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
        v = mixed[..., 2 * qk_w:].reshape(b, s, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        if token_valid is not None:
            beta = jnp.where(token_valid[..., None], beta, 0.0)
            g = jnp.where(token_valid[..., None], g, 0.0)

        o, recurrent = delta_rule(q, k, v, g, beta, recurrent)
        o = RMSNorm(cfg.rms_norm_eps, centred=False, name="norm")(o)
        o = o * nn.silu(z.reshape(b, s, hv, dv))
        out = Linear(cfg.hidden_size, self.dtype, name="out_proj")(
            o.reshape(b, s, v_w))
        return out, (recurrent, window)


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig
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
        rot = int(d * cfg.partial_rotary_factor)
        qg = Linear(h * 2 * d, self.dtype, name="q_proj")(x).reshape(
            b, s, h, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = Linear(kvh * d, self.dtype, name="k_proj")(x).reshape(
            b, s, kvh, d)
        v = Linear(kvh * d, self.dtype, name="v_proj")(x).reshape(
            b, s, kvh, d)
        q = rotary(RMSNorm(cfg.rms_norm_eps, name="q_norm")(q), positions,
                   rot, cfg.rope_theta)
        k = rotary(RMSNorm(cfg.rms_norm_eps, name="k_norm")(k), positions,
                   rot, cfg.rope_theta)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        if kv_cache is not None:
            k = jax.lax.dynamic_update_slice_in_dim(kv_cache[0], k, index,
                                                    axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(kv_cache[1], v, index,
                                                    axis=1)
        # KV head j serves query heads j*(H/KVH) .. (j+1)*(H/KVH)-1
        q = q.astype(self.dtype).reshape(b, s, kvh, h // kvh, d)
        scores = jnp.einsum("bqjhd,bkjd->bjhqk", q, k,
                            preferred_element_type=F32) * d ** -0.5
        scores = jnp.where(mask[:, :, None], scores, jnp.finfo(F32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        attn = jnp.einsum("bjhqk,bkjd->bqjhd", probs, v,
                          preferred_element_type=F32).reshape(b, s, h, d)
        out = Linear(cfg.hidden_size, self.dtype, name="o_proj")(
            (attn * jax.nn.sigmoid(gate)).reshape(b, s, h * d))
        return out, (k, v)


class Qwen3NextLayer(nn.Module):
    cfg: Qwen3NextConfig
    full_attention: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, real, dense_experts: bool, **mixer_args):
        """x (B, S, D) float32, real (B, S) -> (x, cache entry, stats)."""
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="norm1")(x)
        if self.full_attention:
            with jax.named_scope("gated_attn"):
                mixed, entry = GatedAttention(cfg, self.dtype, name="mixer")(
                    h, **mixer_args)
        else:
            with jax.named_scope("gated_delta"):
                mixed, entry = GatedDeltaNet(cfg, self.dtype, name="mixer")(
                    h, **mixer_args)
        x = x + mixed
        b, s, d = x.shape
        h = RMSNorm(cfg.rms_norm_eps, name="norm2")(x)
        out, stats = HeldExperts(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            intermediate=cfg.moe_intermediate_size,
            shared_intermediate=cfg.shared_expert_intermediate_size,
            norm_topk_prob=cfg.norm_topk_prob, dtype=self.dtype,
            name="moe")(h.reshape(b * s, d), real.reshape(b * s),
                        dense_experts)
        return x + out.reshape(b, s, d), entry, stats


def zero_stats(cfg: Qwen3NextConfig) -> dict:
    zero = jnp.zeros((), jnp.int32)
    return {"assignments": zero, "assignments_held": zero,
            "experts_touched": zero, "walk_reads_saved": zero,
            "load": jnp.zeros((cfg.experts_held,), jnp.int32)}


def cache_stats(cache) -> dict:
    """What the expert layers routed since ``prefill`` began, real rows
    only: the decode scan hands this back with the tokens."""
    return cache["stats"]


def active_params(tree, cfg: Qwen3NextConfig) -> float:
    """Parameters a token's forward multiplies by: everything but the
    embedding table (a look-up) and the experts it is not routed to
    (``num_experts_per_tok`` of ``num_experts``, of which the held share
    lives here)."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        names = [str(getattr(p, "key", p)) for p in path]
        if "embed" in names:
            continue
        share = (cfg.num_experts_per_tok / cfg.num_experts
                 if names[-1] in ("gate_up", "down") else 1.0)
        total += share * leaf.size
    return total


class Qwen3NextLM(nn.Module):
    """Causal LM with the zoo serving contract."""

    cfg: Qwen3NextConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        # rows are looked up in the stored type and widened after: asked
        # for float32 rows, a step of two rows and more widened the whole
        # table first (311 MB written a step in qwen3next_game)
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed")
        self.layers = [
            Qwen3NextLayer(cfg, cfg.is_full_attention(i), dtype,
                           name=f"layer_{i}")
            for i in range(cfg.num_hidden_layers)]
        self.norm_f = RMSNorm(cfg.rms_norm_eps, name="norm_f")
        self.lm_head = self.param(
            "lm_head", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.vocab_size), F32)

    def _logits(self, hidden):
        # float32 at full precision: greedy argmax over near-ties; at
        # decode the head is bound by reading its weights either way
        return jnp.dot(self.norm_f(hidden), self.lm_head.astype(F32),
                       precision=HI)

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
                    if layer.full_attention
                    else dict(token_valid=token_valid, prompt_len=prompt_len))
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
        to ``max_len`` for the full-attention layers, each row's recurrent
        state and convolution window at its own ``prompt_len`` for the
        linear ones. ``row_mask`` (B,) marks the rows that are requests
        (None: all); padding rows are left out of ``stats``."""
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
        ``index`` of the full-attention layers and rotated by its row's
        ``positions`` (B, 1), which lies below the slot for a row decoded
        in a wider prompt bucket's program than its own; the linear layers
        step their state and know no position. Returns (logits (B, V),
        new cache)."""
        x = self.embed(token[:, None]).astype(F32)
        real = cache["real"][:, None]
        mask = valid[:, None, None, :]
        entries, stats = [], cache["stats"]
        for layer, entry in zip(self.layers, cache["layers"]):
            args = (dict(positions=positions, mask=mask, kv_cache=entry,
                         index=index)
                    if layer.full_attention else dict(state=entry))
            x, entry, layer_stats = layer(x, real, False, **args)
            entries.append(entry)
            stats = jax.tree_util.tree_map(jnp.add, stats, layer_stats)
        return self._logits(x[:, 0]), {"layers": tuple(entries),
                                       "stats": stats,
                                       "real": cache["real"]}
