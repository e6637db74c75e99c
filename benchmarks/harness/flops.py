"""Model FLOPs of one round, counted from the plain reference's shapes.

The count is of the work the models' equations need (2*M*N*K of every
matmul, convolution and attention product the reference traces), not of
what the program happens to execute: padding rows, bucket padding of the
LM prompt and recomputation count for nothing, and a change of the
program's implementation cannot change the count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import reference as ref


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _count(fn, *args) -> float:
    with ref.count_flops() as tally:
        jax.eval_shape(fn, *args)
    return tally[0]


def image_flops(trees: dict, sizes: dict, names: dict) -> dict:
    """FLOPs of one image: text towers on (prompt, negative prompt), the
    UNet forwards the configuration's trajectory makes, each at the CFG
    batch it makes it at, one VAE decode."""
    s = sizes["sampler"]
    lat = ref.latent_hw(sizes)
    ids = jax.ShapeDtypeStruct((2, s["prompt_pad_len"]), jnp.int32)
    out = {"clip": _count(lambda p, i: ref.clip_text(
        p, i, sizes["clip_text"]), _shapes(trees["clip_text"]), ids)}
    if "clip_text_2" in sizes:
        out["clip"] += _count(lambda p, i: ref.clip_text(
            p, i, sizes["clip_text_2"]), _shapes(trees["clip_text_2"]), ids)
    u = sizes["unet"]
    forward: dict = {}  # CFG batch -> FLOPs of one UNet forward

    def unet_forward(batch: int) -> float:
        if batch not in forward:
            x = jax.ShapeDtypeStruct((batch, lat, lat, 4), jnp.float32)
            t = jax.ShapeDtypeStruct((batch,), jnp.int32)
            ctx = jax.ShapeDtypeStruct(
                (batch, s["prompt_pad_len"], u["context_dim"]), jnp.float32)
            add = (jax.ShapeDtypeStruct((batch, u["addition_embed_dim"]),
                                        jnp.float32)
                   if u.get("addition_embed_dim") else None)
            forward[batch] = _count(
                lambda p, x, t, c, a: ref.unet(p, x, t, c, u, addition=a),
                _shapes(trees["unet"]), x, t, ctx, add)
        return forward[batch]

    def guided(x, t):
        # the trajectory says how many forwards it makes, and at which
        # batch, by making them: each costs a forward at twice its rows
        ref._add(unet_forward(2 * x.shape[0]))
        return x

    out["unet_step"] = unet_forward(2)
    out["trajectory"] = _count(
        lambda x: names["trajectory"](guided, x, s),
        jax.ShapeDtypeStruct((1, lat, lat, 4), jnp.float32))
    out["vae"] = _count(
        lambda p, z: ref.vae_decode(p, z, sizes["vae"]),
        _shapes(trees["vae"]),
        jax.ShapeDtypeStruct((1, lat, lat, 4), jnp.float32))
    out["image"] = out["clip"] + out["trajectory"] + out["vae"]
    return out


def lm_flops(trees: dict, names: dict, prompt_tokens: int,
             new_tokens: int) -> float:
    """One full causal forward over prompt + generated tokens: what the
    prefill and the cached decode steps compute between them."""
    n = prompt_tokens + new_tokens
    ids = jax.ShapeDtypeStruct((1, n), jnp.int32)
    return _count(
        lambda p, i, q: names["lm_logits"](p, i, q, names["lm_sizes"]),
        _shapes(trees["lm"]), ids, ids)


def scorer_row_flops(trees: dict, sizes: dict) -> float:
    m = sizes["minilm"]
    ids = jax.ShapeDtypeStruct((1, m["seq_len"]), jnp.int32)
    return _count(lambda p, i, k: ref.minilm_embed(p, i, k, m),
                  _shapes(trees["minilm"]), ids, ids)
