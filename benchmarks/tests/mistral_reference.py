"""Plain reference of the test configuration's prompt LM (named_fixture):
RMSNorm, rotary embedding at the given positions, grouped-query attention
under the causal band mask, SwiGLU. It keeps the contract of a prompt LM's
reference (harness/reference.py::gpt2_logits): float32, operands through
the reference's ``_operand`` and FLOPs through its ``_add``, which its
``dense`` and ``attention`` do."""

import jax
import jax.numpy as jnp

from benchmarks.harness import reference as ref


def rms_norm(p, x, eps: float):
    x = x.astype(ref.F32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * p["scale"].astype(ref.F32))


def rotary(x, positions, theta: float):
    """x (B, S, H, D), positions (B, S); split-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=ref.F32) / half)
    angles = positions.astype(ref.F32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mistral_logits(params, ids, positions, sz):
    """ids, positions (B, S) -> logits (B, S, V). A position attends to
    the earlier ones, and itself, that lie less than ``sliding_window``
    positions behind it."""
    p = params["params"]
    b, seq = ids.shape
    heads, kv_heads, d = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    behind = positions[:, :, None] - positions[:, None, :]
    mask = (jnp.tril(jnp.ones((seq, seq), bool))[None]
            & (behind < sz["sliding_window"]))[:, None]
    x = p["embed"]["embedding"].astype(ref.F32)[ids]
    for i in range(sz["num_layers"]):
        blk = p[f"block_{i}"]
        h = rms_norm(blk["ln1"], x, sz["rms_eps"])
        q = rotary(ref.dense(blk["attn"]["q"], h).reshape(b, seq, heads, d),
                   positions, sz["rope_theta"])
        k = rotary(ref.dense(blk["attn"]["k"], h).reshape(
            b, seq, kv_heads, d), positions, sz["rope_theta"])
        v = ref.dense(blk["attn"]["v"], h).reshape(b, seq, kv_heads, d)
        k, v = (jnp.repeat(t, heads // kv_heads, axis=2).reshape(
            b, seq, heads * d) for t in (k, v))
        x = x + ref.dense(blk["attn"]["out"], ref.attention(
            q.reshape(b, seq, heads * d), k, v, heads, mask))
        h = rms_norm(blk["ln2"], x, sz["rms_eps"])
        x = x + ref.dense(blk["mlp"]["down"], jax.nn.silu(
            ref.dense(blk["mlp"]["gate"], h)) * ref.dense(blk["mlp"]["up"], h))
    return ref.dense(p["lm_head"], rms_norm(p["ln_f"], x, sz["rms_eps"]))
