"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context support (SURVEY.md §5.7): the UNet's image-token axis (16k+
tokens at SDXL-1024 and beyond) and any long text sequence shard over the
``sp`` mesh axis. Each device holds a sequence slice of Q/K/V; K/V blocks
rotate around the ring via ``ppermute`` (one ICI hop per step) while the
online-softmax running max/denominator merge partial results — the
shard_map/XLA-collective formulation of the same math the Pallas flash
kernel does within a chip. Memory per device stays O(S/n), and the K/V
transfer for step i+1 overlaps with the compute of step i (XLA schedules
the ppermute async on ICI).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_local(q, k, v, axis_name: str, scale: float,
                          causal: bool):
    """Per-shard body (runs under shard_map). q/k/v: (B, S_l, H, D).

    Causal mode (the LM long-context path): with the sequence sharded
    contiguously, at ring step ``i`` this device holds the K/V block that
    ORIGINATED on device ``(j - i) mod n``; masking compares global
    positions. Step 0 is the local (diagonal) block, where every query
    sees at least itself — so the running max is finite from the first
    step and fully-masked later blocks contribute exp(-1e30 - m) = 0,
    keeping the online softmax NaN-free with additive finite masking.

    Known trade-off: fully-masked blocks still compute their QK^T in
    SPMD lockstep (wall-time neutral — at every ring step some device
    computes a live block, so the critical path is one block either
    way — but ~2x the attention FLOPs/energy of the load-balanced
    zigzag layout, where each device holds two symmetric sequence
    slices). ``ring_attention(causal=True)`` therefore dispatches to
    the zigzag schedule whenever 2n divides S; this contiguous
    formulation remains for schedule="contiguous" (the fallback for
    S % 2n != 0 and the oracle the zigzag tests compare against)."""
    n = jax.lax.psum(1, axis_name)
    j = jax.lax.axis_index(axis_name)
    s_l = q.shape[1]
    q_pos = j * s_l + jnp.arange(s_l)                      # global q idx

    def step(carry, i):
        k_cur, v_cur, m, l, acc = carry
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_cur,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            origin = (j - i) % n                           # block owner
            k_pos = origin * s_l + jnp.arange(s_l)
            visible = q_pos[:, None] >= k_pos[None, :]     # (S_l, S_l)
            s = jnp.where(visible[None, None], s, _NEG_INF)
        m_new, l_new, acc_new = _merge((m, l, acc), s, v_cur)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l_new, acc_new), None

    b, s_l, h, d = q.shape
    # initial carries are constants -> mark them device-varying over the
    # ring axis so the scan carry type stays consistent
    from cassmantle_tpu.parallel.mesh import pcast_varying

    vary = lambda x: pcast_varying(x, axis_name)  # noqa: E731
    m0 = vary(jnp.full((b, h, s_l, 1), _NEG_INF, dtype=jnp.float32))
    l0 = vary(jnp.zeros((b, h, s_l, 1), dtype=jnp.float32))
    acc0 = vary(jnp.zeros((b, h, s_l, d), dtype=jnp.float32))
    (_, _, _, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n)
    )
    out = acc / l
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def zigzag_permute(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Reorder the sequence axis into the zigzag layout: split into 2n
    chunks c_0..c_{2n-1} and lay them out as [c_0, c_{2n-1}, c_1,
    c_{2n-2}, ...] so that a contiguous n-way shard gives device j the
    pair (c_j, c_{2n-1-j}). This balances causal-attention work: device
    j's low chunk is early (few keys visible) exactly when its high
    chunk is late (many keys visible)."""
    s = x.shape[axis]
    assert s % (2 * n) == 0, f"seq {s} not divisible by 2n={2 * n}"
    chunks = jnp.split(x, 2 * n, axis=axis)
    order = [c for j in range(n) for c in (chunks[j], chunks[2 * n - 1 - j])]
    return jnp.concatenate(order, axis=axis)


def zigzag_unpermute(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Inverse of :func:`zigzag_permute`."""
    chunks = jnp.split(x, 2 * n, axis=axis)
    out: list = [None] * (2 * n)
    for j in range(n):
        out[j] = chunks[2 * j]
        out[2 * n - 1 - j] = chunks[2 * j + 1]
    return jnp.concatenate(out, axis=axis)


def _merge(stats, logits, v_blk):
    """Online-softmax merge of one (BQ, BK) logits block into carried
    (m, l, acc); logits fp32 (B, H, S_q, S_k), v (B, S_k, H, D)."""
    m, l, acc = stats
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc * alpha + pv


def _zigzag_local(q, k, v, axis_name: str, scale: float, n: int):
    """Per-shard body: local sequence is the pair [c_j, c_{2n-1-j}],
    each of length S_c. Prologue handles the device's own (diagonal)
    blocks with triangular masks; every scanned ring step then computes
    exactly TWO fully-visible (S_c x S_c) blocks — no masking, no wasted
    QK^T — which is the zigzag schedule's whole point:

      at step i the received K/V pair originated on o = (j - i) mod n;
      for j > o both local q chunks fully see k_low = c_o (and never
      k_high = c_{2n-1-o}); for j < o only q_high = c_{2n-1-j} is live,
      and it fully sees BOTH received chunks. Either way: two full
      blocks, every device, every step.
    """
    j = jax.lax.axis_index(axis_name)
    s2 = q.shape[1]
    s_c = s2 // 2
    ql, qh = q[:, :s_c], q[:, s_c:]

    def logits(qb, kb):
        return jnp.einsum(
            "bqhd,bkhd->bhqk", qb, kb,
            preferred_element_type=jnp.float32,
        ) * scale

    # -- prologue: the device's own diagonal blocks --------------------
    tri = jnp.tril(jnp.ones((s_c, s_c), bool))[None, None]
    b, _, h, d = q.shape
    zeros = lambda: (  # noqa: E731
        jnp.full((b, h, s_c, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, s_c, 1), jnp.float32),
        jnp.zeros((b, h, s_c, d), jnp.float32),
    )
    kl, kh, vl, vh = k[:, :s_c], k[:, s_c:], v[:, :s_c], v[:, s_c:]
    low = _merge(zeros(), jnp.where(tri, logits(ql, kl), _NEG_INF), vl)
    high = _merge(zeros(), jnp.where(tri, logits(qh, kh), _NEG_INF), vh)
    high = _merge(high, logits(qh, kl), vl)   # c_{2n-1-j} fully sees c_j

    # (carries derive from q/k/v, so they are already device-varying —
    # no pcast needed, unlike _ring_attention_local's constant inits)

    # -- ring: two full blocks per step --------------------------------
    def step(carry, i):
        kv, low, high = carry
        k_cur, v_cur = kv
        o = (j - i) % n
        from_lower = j > o                     # scalar, device-varying
        k_lo, k_hi = k_cur[:, :s_c], k_cur[:, s_c:]
        v_lo, v_hi = v_cur[:, :s_c], v_cur[:, s_c:]

        # block A: q = (j>o ? q_low : q_high), k = received low chunk.
        # Select the DESTINATION stats first and merge once (one PV
        # einsum), then scatter back — not merge-into-both-and-select,
        # which would execute a third, discarded merge per step.
        aq = jnp.where(from_lower, ql, qh)
        sel = tuple(jnp.where(from_lower, lo, hi)
                    for lo, hi in zip(low, high))
        merged = _merge(sel, logits(aq, k_lo), v_lo)
        low = tuple(jnp.where(from_lower, m, lo)
                    for m, lo in zip(merged, low))
        high = tuple(jnp.where(from_lower, hi, m)
                     for m, hi in zip(merged, high))

        # block B: q = q_high, k = (j>o ? received low : received high)
        bk = jnp.where(from_lower, k_lo, k_hi)
        bv = jnp.where(from_lower, v_lo, v_hi)
        high = _merge(high, logits(qh, bk), bv)

        perm = [(r, (r + 1) % n) for r in range(n)]
        kv = (jax.lax.ppermute(k_cur, axis_name, perm),
              jax.lax.ppermute(v_cur, axis_name, perm))
        return (kv, low, high), None

    if n == 1:
        out_low, out_high = low, high
    else:
        perm = [(r, (r + 1) % n) for r in range(n)]
        kv0 = (jax.lax.ppermute(k, axis_name, perm),
               jax.lax.ppermute(v, axis_name, perm))
        (_, out_low, out_high), _ = jax.lax.scan(
            step, (kv0, low, high), jnp.arange(1, n)
        )

    def finish(stats):
        m, l, acc = stats
        return jnp.einsum("bhqd->bqhd", acc / l)

    out = jnp.concatenate([finish(out_low), finish(out_high)], axis=1)
    return out.astype(q.dtype)


def zigzag_sharded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Causal zigzag attention over ALREADY-zigzag-permuted sequences.

    The model-integration entry point: a long-context training step
    permutes its data once on input (parallel/lm_train.py) and keeps
    every layer's activations in zigzag order, so attention needs no
    per-layer permute collectives. ``batch_axis`` lets the batch dim
    ride an outer data-parallel axis (activations (B/dp, S/sp, H, D)
    per device)."""
    n = int(mesh.shape[axis_name])
    assert q.shape[1] % (2 * n) == 0, (q.shape, n)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    body = functools.partial(
        _zigzag_local, axis_name=axis_name, scale=float(scale), n=n
    )
    spec = P(batch_axis, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


def zigzag_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
) -> jax.Array:
    """Load-balanced CAUSAL ring attention (zigzag schedule).

    Takes/returns tensors in NATURAL sequence order, (B, S, H, D) with
    S % 2n == 0; the zigzag permutation is applied and undone inside.
    Halves critical-path attention compute vs contiguous causal ring:
    every ring step computes two fully-live (S/2n)^2 blocks on every
    device instead of one half-masked (S/n)^2 block on some of them.
    """
    n = int(mesh.shape[axis_name])
    qz = zigzag_permute(q, n)
    kz = zigzag_permute(k, n)
    vz = zigzag_permute(v, n)
    out = zigzag_sharded_attention(
        qz, kz, vz, mesh, axis_name=axis_name, scale=scale
    )
    return zigzag_unpermute(out, n)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    causal: bool = False,
    schedule: str = "auto",
) -> jax.Array:
    """Sequence-parallel attention. Global shapes (B, S, H, D); S shards
    over ``axis_name``; every other dim is replicated across that axis.
    ``causal=True`` applies the LM triangular mask on global positions.

    ``schedule`` (causal only): ``"auto"`` — the default — routes to the
    load-balanced zigzag ring whenever ``S % (2n) == 0``, which computes
    two fully-live blocks per device per step instead of half-masked
    ones (~2x fewer attention FLOPs on the critical path);
    ``"contiguous"`` forces the plain contiguous-shard schedule (the
    reference formulation kept as a fallback for sequences that divide
    n but not 2n, and as the independent oracle the zigzag tests check
    against)."""
    if schedule not in ("auto", "contiguous"):
        raise ValueError(f"schedule must be 'auto' or 'contiguous', "
                         f"got {schedule!r}")
    n = int(mesh.shape[axis_name])
    if causal and schedule == "auto" and q.shape[1] % (2 * n) == 0:
        return zigzag_ring_attention(
            q, k, v, mesh, axis_name=axis_name, scale=scale
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    body = functools.partial(
        _ring_attention_local, axis_name=axis_name, scale=float(scale),
        causal=causal,
    )
    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
