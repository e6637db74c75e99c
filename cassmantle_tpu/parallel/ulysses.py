"""Ulysses-style sequence parallelism: all-to-all head sharding.

The second sequence/context-parallel flavor next to ring attention
(parallel/ring.py): instead of rotating K/V blocks around the ring, ONE
``all_to_all`` re-shards the activations from sequence-sharded to
head-sharded, every device computes FULL-sequence attention for its
subset of heads, and a second ``all_to_all`` shards back by sequence.

Trade-offs vs ring (both ride ICI):
- Ulysses: 2 collective hops total, local attention sees the whole
  sequence (exact softmax in one pass — no online-softmax merging), but
  needs ``num_heads % sp == 0`` and moves Q, K, and V once each.
- Ring: n-1 hops of K/V only with compute/comm overlap; works for any
  head count; memory per device stays O(S/n) even inside attention.

Per SURVEY.md §5.7 this is the head-sharded scale-up path for 1024²+
image-token attention and long text sequences.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from cassmantle_tpu.ops.attention import xla_attention


def _ulysses_local(q, k, v, axis_name: str, scale: float, causal: bool):
    """Per-shard body. q/k/v: (B, S_l, H, D) — sequence-sharded in."""

    def seq_to_heads(t):
        # (B, S_l, H, D) -> (B, S, H/n, D): gather sequence, scatter heads
        return jax.lax.all_to_all(
            t, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(t):
        return jax.lax.all_to_all(
            t, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    mask = None
    if causal:
        # after the all-to-all, each device sees the FULL sequence for
        # its heads, so causal is the plain triangular mask
        s = qh.shape[-3]
        mask = jnp.tril(jnp.ones((s, s), bool))
    out = xla_attention(qh, kh, vh, mask=mask, scale=scale)
    return heads_to_seq(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale=None,
    causal: bool = False,
) -> jax.Array:
    """Sequence-parallel attention via head sharding.

    Global shapes (B, S, H, D); S shards over ``axis_name``; requires
    ``H % mesh.shape[axis_name] == 0``. ``causal=True`` applies the LM
    triangular mask.
    """
    n = int(mesh.shape[axis_name])
    h = q.shape[-2]
    assert h % n == 0, f"{h} heads not divisible by {axis_name}={n}"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    body = functools.partial(
        _ulysses_local, axis_name=axis_name, scale=float(scale),
        causal=causal,
    )
    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
