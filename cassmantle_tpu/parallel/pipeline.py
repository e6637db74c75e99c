"""Pipeline parallelism: GPipe-style microbatched stage execution.

The reference has no parallelism at all (SURVEY.md §2 #15); this is the
``pp`` rung of the TPU build's mesh. Idiomatic TPU pipelining is NOT a
scheduler thread per stage (the GPU/NCCL pattern) — it is a single SPMD
program over the ``pp`` mesh axis:

- every device holds ONE stage's parameters (the stage-stacked param tree
  is sharded on its leading axis with ``P("pp")``);
- a ``lax.scan`` runs ``M + S - 1`` ticks; on each tick every device
  applies its stage to the activation it holds, then the activations
  rotate one hop around the ring with ``lax.ppermute`` (one ICI hop —
  exactly the collective the hardware is built for);
- stage 0 feeds a fresh microbatch into tick ``t < M``; stage ``S-1``
  banks its output for microbatch ``t - (S-1)``. The bubble is the
  classic ``(S-1) / (M + S - 1)`` fraction.

``pipeline_apply`` is generic over any per-stage function; ``stack_stage
_params`` builds the stage-stacked tree from per-layer trees (e.g. GPT-2
blocks, models/gpt2.py). Composes with ``dp`` (shard the microbatch dim)
and ``tp`` (shard the stage weights) on the same mesh.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: Sequence):
    """List of S identically-shaped param trees -> one tree with a leading
    stage axis, ready to shard with ``P("pp")``."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params
    )


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    mesh: Mesh,
    num_microbatches: int = 0,
    axis: str = "pp",
) -> jax.Array:
    """Run ``x`` through ``S`` pipeline stages on the mesh's ``pp`` axis.

    ``stage_fn(params_s, h) -> h`` applies one stage; ``stage_params`` has
    a leading stage axis of size ``S = mesh.shape[axis]``; ``x`` is
    ``(B, ...)`` with ``B`` divisible by ``num_microbatches`` (defaults to
    ``S``). Returns the same-shaped output of the full stage stack.
    """
    S = int(mesh.shape[axis])
    M = num_microbatches or S
    b = x.shape[0]
    assert b % M == 0, f"batch {b} not divisible by {M} microbatches"
    mb = b // M
    xs = x.reshape(M, mb, *x.shape[1:])
    perm = [(j, (j + 1) % S) for j in range(S)]

    def per_device(params, xs):
        # shard_map leaves the sharded leading axis as size 1: strip it.
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)

        def tick(carry, t):
            buf, ys = carry
            # stage 0 ingests microbatch t while t < M
            inp = xs[jnp.minimum(t, M - 1)]
            buf = jnp.where(jnp.logical_and(idx == 0, t < M), inp, buf)
            out = stage_fn(params, buf)
            # last stage banks microbatch m = t - (S-1) once it's real
            m = t - (S - 1)
            banked = jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.maximum(m, 0), 0
            )
            ys = jnp.where(jnp.logical_and(idx == S - 1, m >= 0), banked, ys)
            buf = jax.lax.ppermute(out, axis, perm)
            return (buf, ys), None

        init = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
        (_, ys), _ = jax.lax.scan(tick, init, jnp.arange(M + S - 1))
        return ys[None]  # (1, M, mb, ...): stacked over pp outside

    stacked = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P(*(None,) * xs.ndim)),
        out_specs=P(axis),
        check_vma=False,
    )(stage_params, xs)
    # stage S-1 holds the real outputs; earlier stages hold zeros/garbage
    return stacked[S - 1].reshape(b, *x.shape[1:])


def gpt2_stage_fn(block_apply: Callable, mask: jax.Array) -> Callable:
    """Adapt a GPT2Block apply to the pipeline's ``(params, h) -> h``.

    ``block_apply({"params": p}, h, mask=mask)`` returns ``(h, kv)``; the
    pipeline carries hidden states only.
    """

    def fn(params, h):
        out, _ = block_apply({"params": params}, h, mask=mask)
        return out

    return fn


def pipelined_lm_forward(
    model,
    params,
    input_ids: jax.Array,
    mesh: Mesh,
    num_microbatches: int = 0,
) -> jax.Array:
    """GPT-2 forward with the block stack pipelined over ``pp``.

    Embedding/LM-head run replicated (they are a tiny fraction of FLOPs);
    the ``num_layers`` blocks split into ``pp`` equal stages of stacked
    layers. Numerically identical to ``model.apply`` up to reduction
    order — tests/test_pipeline_parallel.py asserts parity.
    """
    from cassmantle_tpu.models.gpt2 import GPT2Block

    S = int(mesh.shape["pp"])
    cfg = model.cfg
    L = cfg.num_layers
    assert L % S == 0, f"{L} layers not divisible into {S} stages"
    per_stage = L // S

    p = params["params"]
    block_params = [p[f"block_{i}"] for i in range(L)]
    # leading axes: (S stages, per_stage layers within the stage)
    stage_trees = [
        stack_stage_params(block_params[s * per_stage:(s + 1) * per_stage])
        for s in range(S)
    ]
    stacked = stack_stage_params(stage_trees)

    b, s_len = input_ids.shape
    positions = jnp.arange(s_len)[None, :]
    dtype = jnp.dtype(cfg.dtype)
    wte = p["wte"]["embedding"]
    wpe = p["wpe"]["embedding"]
    x = wte[input_ids].astype(dtype) + wpe[positions].astype(dtype)
    mask = jnp.tril(jnp.ones((s_len, s_len), dtype=bool))[None, None]

    block = GPT2Block(cfg, dtype)

    def stage_fn(stage_params, h):
        # sequentially apply this stage's stacked layers via lax.scan
        def layer(h, lp):
            out, _ = block.apply({"params": lp}, h, mask=mask)
            return out, None

        h, _ = jax.lax.scan(layer, h, stage_params)
        return h

    x = pipeline_apply(stage_fn, stacked, x, mesh,
                       num_microbatches=num_microbatches)

    # final LN + tied LM head, replicated (fp32, as in GPT2LM._logits)
    ln = p["ln_f"]
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    xn = (x - mean) / jnp.sqrt(var + 1e-6)
    xn = xn * ln["scale"] + ln["bias"]
    return xn.astype(jnp.float32) @ wte.astype(jnp.float32).T
