"""Ouro-class looped causal LM: one stack of dense layers run
``total_ut_steps`` times a token over one set of weights.

A pass is the whole stack: layer ``l`` of pass ``p`` takes the output of
layer ``l - 1`` of the same pass, and the first layer of pass ``p`` the
output of pass ``p - 1`` after the model's final norm, so the normed
state is the next pass's input. A layer has sandwich norms, four RMSNorms
(weight not centred, eps ``rms_norm_eps``):

  a = N2(Attn(N1(h)));  h = h + a
  m = N4(W_down(silu(W_gate N3(h)) * W_up N3(h)));  h = h + m

``Attn`` is multi-head attention (as many key/value heads as query heads),
q/k/v/o without bias, rotate-half rotary over the whole head, causal. Its
keys and values are the same pass's, same layer's, over every earlier
position: the cache holds an entry for every pass and layer. The logits
are the untied head over the state after the last pass. Read from the
family's published modeling code: the norms sit on both sides of each
sublayer (``input_layernorm`` / ``input_layernorm_2``,
``post_attention_layernorm`` / ``post_attention_layernorm_2``); the final
norm runs after every pass and its output is what the next pass reads;
the exit gate ``sigmoid(w_g . h + b_g)`` after a pass gives the
probability of stopping there, and under ``early_exit_threshold`` 1 the
cumulative exit probability reaches the threshold only at the last pass,
so every token runs every pass and the gate decides nothing. The program
holds the gate's weights and computes it in the plain forward alone
(``intermediates``, ``exit_gate``); a served step does not.

The weights are held once: the layers' weights lie stacked on a leading
axis, the passes are one ``nn.scan`` over them (``variable_broadcast=
"params"``) around one over the layers, so a program holds the stack once
and loops over it, never ``total_ut_steps`` copies. The cache is k and v
of (layers, passes, B, max_len, H, D) in the stored type: the prefill
hands every pass's prompt k/v over in one write, and a step writes its
token's slot in place, each pass in its own entry, and attends that entry
alone. The tree also
carries the counters of a decode (``stats``: ``pass_steps``, steps times
the passes they ran; ``kv_slots``, valid slots attended by the real rows,
summed over steps, passes and layers) and which rows are real, so they
come back with the tokens (ops/decode.py ``cache_stats``) at no sync of
their own.

Precision as in models/qwen3_next.py: weights in ``cfg.dtype``, products
read their operands in it and accumulate in float32, the rows of a
decode step at float32's precision (models/moe.py ``stored_dot``); the
residual stream and the norms in float32; k/v in the stored type.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from cassmantle_tpu.config import OuroConfig
from cassmantle_tpu.models.moe import stored_dot
from cassmantle_tpu.models.qwen3_next import Linear, RMSNorm, rotary
from cassmantle_tpu.ops.attention import multi_head_attention

F32 = jnp.float32


class Attention(nn.Module):
    cfg: OuroConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, positions, mask, cache, at, index):
        """x (B, S, D), mask (B, 1, S, S_k) -> (out, k/v). A prompt
        (``cache`` None) attends its fresh k/v and hands them back. A step
        writes its k/v into the stack's ``cache`` (k, v of (layers, passes,
        B, max_len, H, D)) at ``at`` (its layer and pass), slot ``index``,
        attends that layer's and pass's whole entry and hands the cache
        back."""
        cfg = self.cfg
        h, d = cfg.num_attention_heads, cfg.head_dim
        b, s, _ = x.shape
        q, k, v = (Linear(h * d, self.dtype, name=f"{n}_proj")(x)
                   for n in "qkv")
        if cache is None:
            # a prompt's projections come out whole: left to fold the split
            # into heads into the product, the compiler copied the stacked
            # q, k and v weights to a heads-major layout (1.2 GB) at one row
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        q, k, v = (t.reshape(b, s, h, d) for t in (q, k, v))
        q = rotary(q, positions, d, cfg.rope_theta)
        k = rotary(k, positions, d, cfg.rope_theta).astype(self.dtype)
        v = v.astype(self.dtype)
        if cache is None:
            cache = (k, v)
        else:
            cache = tuple(jax.lax.dynamic_update_slice(
                entry, new[None, None], (*at, 0, index, 0, 0))
                for entry, new in zip(cache, (k, v)))
            k, v = (jax.lax.dynamic_slice(
                entry, (*at, 0, 0, 0, 0), (1, 1, *entry.shape[2:]))[0, 0]
                for entry in cache)
        # q stays float32, so the scores come out in it
        attn = multi_head_attention(q, k, v, mask=mask)
        out = Linear(cfg.hidden_size, self.dtype, name="o_proj")(
            attn.reshape(b, s, h * d))
        return out, cache


class MLP(nn.Module):
    cfg: OuroConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        width = self.cfg.intermediate_size
        gate = Linear(width, self.dtype, name="gate_proj")(x)
        up = Linear(width, self.dtype, name="up_proj")(x)
        return Linear(self.cfg.hidden_size, self.dtype, name="down_proj")(
            nn.silu(gate) * up)


class OuroLayer(nn.Module):
    """One layer; ``nn.scan`` runs it over the stack, its parameters
    stacked on a leading axis of ``num_hidden_layers``."""

    cfg: OuroConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, carry, layer, positions, mask, slot, index):
        """carry (x (B, S, D) float32, the stack's cache or None), layer ()
        -> (carry, this layer's fresh k/v for a prompt, or None)."""
        x, cache = carry
        eps = self.cfg.rms_norm_eps

        def norm(name):
            return RMSNorm(eps, centred=False, name=name)

        a, kv = Attention(self.cfg, self.dtype, name="self_attn")(
            norm("input_layernorm")(x), positions, mask, cache,
            (layer, slot), index)
        x = x + norm("input_layernorm_2")(a)
        m = MLP(self.cfg, self.dtype, name="mlp")(
            norm("post_attention_layernorm")(x))
        x = x + norm("post_attention_layernorm_2")(m)
        return ((x, kv), None) if cache is not None else ((x, None), kv)


def zero_stats() -> dict:
    zero = jnp.zeros((), jnp.int32)
    return {"pass_steps": zero, "kv_slots": zero}


def cache_stats(cache) -> dict:
    """What the decode steps since ``prefill`` did: the decode scan hands
    this back with the tokens."""
    return cache["stats"]


def active_params(tree, cfg: OuroConfig) -> float:
    """Parameters a token's forward multiplies by: the stack, its final
    norm and exit gate once a pass, the head once; the embedding table is
    a look-up."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        names = [str(getattr(p, "key", p)) for p in path]
        if "embed_tokens" in names:
            continue
        total += leaf.size * (1 if "lm_head" in names
                              else cfg.total_ut_steps)
    return total


class OuroLM(nn.Module):
    """Causal LM with the zoo serving contract."""

    cfg: OuroConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        # rows are looked up in the stored type and widened after: a
        # float32 look-up widens the whole table in every step
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     name="embed_tokens")
        # a loop in the program, each layer's weights read in place (the
        # slice fused into its product): unrolled, each of the six decode
        # programs compiled for 19-37 s and the init for 155 s for a v5e
        self.layers = nn.scan(
            OuroLayer, variable_axes={"params": 0},
            split_rngs={"params": True}, length=cfg.num_hidden_layers,
            in_axes=(0,) + (nn.broadcast,) * 4)(cfg, dtype, name="layers")
        self.norm = RMSNorm(cfg.rms_norm_eps, centred=False, name="norm")
        self.early_exit_gate = nn.Dense(1, name="early_exit_gate")
        self.lm_head = self.param("lm_head", nn.initializers.lecun_normal(),
                                  (cfg.hidden_size, cfg.vocab_size), F32)

    def _loop(self, x, cache, positions, mask, index=None, gates=False):
        """Every pass over the stack: one ``nn.scan`` over the passes with
        the weights broadcast, around one over the layers with the weights
        stacked, so a program holds each layer once. Pass ``p`` writes and
        reads cache entry ``p`` and hands on the final norm's output.
        Returns x after the last pass and its norm, and the cache a step
        wrote, or for a prompt (``cache`` None) every pass's and layer's
        fresh k/v, (passes, layers, B, S, H, D) each."""
        layers = jnp.arange(self.cfg.num_hidden_layers)

        def one_pass(mdl, carry, p):
            with jax.named_scope("ouro_pass"):
                carry, fresh = mdl.layers(carry, layers, positions, mask, p,
                                          index)
                x, cache = carry
                x = mdl.norm(x)
                if gates:
                    mdl.sow("intermediates", "exit_gate",
                            jax.nn.sigmoid(mdl.early_exit_gate(x)))
            return (x, cache), fresh

        loop = nn.scan(one_pass, variable_broadcast="params",
                       variable_axes={"intermediates": 0},
                       split_rngs={"params": False})
        (x, cache), fresh = loop(self, (x, cache),
                                 jnp.arange(self.cfg.total_ut_steps))
        return x, (fresh if cache is None else cache)

    def _logits(self, hidden):
        # the untied head over the normed state; a row reaches it at
        # float32's precision (greedy argmax over near-ties)
        with jax.named_scope("lm_head"):
            return stored_dot(hidden, self.lm_head.astype(self.cfg.dtype),
                              True)

    def _prompt(self, input_ids, prompt_len, max_len, gates=False):
        """(x (B, P, D) after the last pass, the cache at ``max_len``
        positions, or None for a plain forward: ``max_len`` None)."""
        p = input_ids.shape[1]
        positions = jnp.arange(p)
        token_valid = positions[None, :] < prompt_len[:, None]
        mask = (jnp.tril(jnp.ones((p, p), bool))[None]
                & token_valid[:, None, :])[:, None]
        x = self.embed_tokens(input_ids).astype(F32)
        x, fresh = self._loop(x, None, positions, mask, gates=gates)
        if max_len is None:
            return x, None
        # the cache as a decode step reads it: (layers, passes, B, max_len,
        # H, D), each pass's prompt k/v at its own entry, in one write
        pad = [(0, 0)] * 3 + [(0, max_len - p)] + [(0, 0)] * 2
        return x, tuple(jnp.pad(jnp.swapaxes(t, 0, 1), pad) for t in fresh)

    def __call__(self, input_ids: jax.Array) -> jax.Array:
        """Plain forward: (B, S) -> (B, S, V), every position real; the
        exit gate of every pass is sown as ``exit_gate`` (passes, B, S,
        1)."""
        b, s = input_ids.shape
        x, _ = self._prompt(input_ids, jnp.full((b,), s, jnp.int32), None,
                            gates=True)
        return self._logits(x)

    def prefill(self, input_ids: jax.Array, prompt_len: jax.Array,
                max_len: int, row_mask=None) -> Tuple[jax.Array, dict]:
        """Right-padded prompt forward seeding the decode cache: every
        pass's k/v at every layer, padded to ``max_len``. ``row_mask``
        (B,) marks the rows that are requests (None: all); padding rows
        are left out of ``stats``."""
        b, p = input_ids.shape
        assert p <= max_len
        real_rows = (jnp.ones((b,), bool) if row_mask is None
                     else row_mask.astype(bool))
        x, cache = self._prompt(input_ids, prompt_len, max_len)
        last = jnp.take_along_axis(
            x, (prompt_len - 1)[:, None, None], axis=1).squeeze(1)
        return self._logits(last), {"kv": cache, "stats": zero_stats(),
                                    "real": real_rows}

    def decode_step(self, token: jax.Array, index: jax.Array, cache: dict,
                    valid: jax.Array, positions: jax.Array
                    ) -> Tuple[jax.Array, dict]:
        """One cached decode step: ``token`` (B,) runs every pass, each
        writing cache slot ``index`` of its own entry at every layer and
        rotated by its row's ``positions`` (B, 1), which lies below the
        slot for a row decoded in a wider prompt bucket's program than its
        own. Returns (logits (B, V), new cache)."""
        x = self.embed_tokens(token[:, None]).astype(F32)
        x, kv = self._loop(x, cache["kv"], positions, valid[:, None, None, :],
                           index)
        passes = self.cfg.total_ut_steps
        attended = jnp.sum(valid & cache["real"][:, None]).astype(jnp.int32)
        stats = cache["stats"]
        stats = {"pass_steps": stats["pass_steps"] + passes,
                 "kv_slots": stats["kv_slots"]
                 + passes * self.cfg.num_hidden_layers * attended}
        return self._logits(x[:, 0]), {"kv": kv, "stats": stats,
                                       "real": cache["real"]}
