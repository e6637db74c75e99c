"""Mixture-of-Experts MLP with expert parallelism (the ``ep`` mesh axis).

The reference has no MoE (it has no local models at all, SURVEY.md §2);
this supplies the expert-parallel rung of the build's mesh so the
framework's parallelism surface covers dp/tp/sp/pp/ep. Design is the
TPU-canonical Switch/GShard formulation — everything is dense einsums over
static shapes, so XLA can lay the expert dim out across the mesh:

- **router**: top-1 token→expert assignment with a fixed capacity
  ``C = capacity_factor · T / E`` per expert. Overflowing tokens fall
  through the residual (standard Switch behavior) — no dynamic shapes.
- **dispatch/combine** are one-hot einsums producing ``(E, C, D)``
  buffers; with the expert axis sharded ``P("ep")`` GSPMD turns the
  einsums into the all-to-all shuffles that ride ICI.
- **expert FFN**: batched (E, ·, ·) matmuls — every expert's GEMM runs
  concurrently on its own shard of the ``ep`` axis.

``MoEMLP`` drops in anywhere a TransformerMLP fits; ``expert_specs`` gives
the ``P("ep", ...)`` param specs for mesh placement.

``HeldExperts`` is the serving-side expert layer (models/qwen3_next.py,
models/lfm2_moe.py): top-k routing over the published number of experts
under the family's routing rule, told which contiguous range of them
this chip holds (all of them, or a share), no capacity and no drop; it
computes its own experts' part of the result and says what it routed.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cassmantle_tpu.ops.moe_walk import moe_walk, moe_walk_fits
from cassmantle_tpu.ops.platform import on_tpu
from cassmantle_tpu.utils.logging import metrics


class MoEMLP(nn.Module):
    """Top-1 (Switch) routed MLP: x (B, S, D) -> (B, S, D)."""

    num_experts: int
    intermediate: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e = self.num_experts
        t = b * s
        cap = max(1, int(self.capacity_factor * t / e))

        tokens = x.reshape(t, d)
        # router in fp32: small, and argmax stability matters
        gate_w = self.param(
            "router", nn.initializers.lecun_normal(), (d, e), jnp.float32
        )
        logits = tokens.astype(jnp.float32) @ gate_w          # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                   # (T,)
        gate = jnp.take_along_axis(
            probs, expert[:, None], axis=-1
        )[:, 0]                                               # (T,)

        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)   # (T, E)
        pos = jnp.cumsum(onehot, axis=0) * onehot             # 1-based
        pos = jnp.sum(pos, axis=-1) - 1                       # (T,)
        keep = pos < cap                                      # overflow drops

        # dispatch tensor (T, E, C): one-hot routing incl. capacity slot
        disp = (
            jax.nn.one_hot(expert, e, dtype=self.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=self.dtype)[:, None, :cap]
        )
        buf = jnp.einsum("td,tec->ecd", tokens.astype(self.dtype), disp)

        # expert FFN: batched GEMMs over the (sharded) expert axis
        w1 = self.param(
            "w1", nn.initializers.lecun_normal(),
            (e, d, self.intermediate), jnp.float32,
        ).astype(self.dtype)
        w2 = self.param(
            "w2", nn.initializers.lecun_normal(),
            (e, self.intermediate, d), jnp.float32,
        ).astype(self.dtype)
        h = jnp.einsum("ecd,edf->ecf", buf, w1)
        h = nn.gelu(h)
        h = jnp.einsum("ecf,efd->ecd", h, w2)

        # combine: weight by the gate, scatter back to token order
        combine = disp * gate[:, None, None].astype(self.dtype)
        out = jnp.einsum("ecd,tec->td", h, combine)
        # aux load-balancing loss (Switch eq. 4), exposed as a sown value
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jax.nn.one_hot(expert, e, dtype=jnp.float32), axis=0
        )
        self.sow("aux_loss", "load_balance", e * jnp.sum(me * ce))
        return out.reshape(b, s, d).astype(x.dtype)


def stored_dot(x: jax.Array, kernel: jax.Array, step: bool) -> jax.Array:
    """x (..., K) @ kernel (K, N) in its stored type -> (..., N) float32.
    Prefill reads both operands in the stored type. The rows of a decode
    ``step`` reach the matrix at float32's precision at every row count,
    in whichever form the chip multiplies them fastest: ONE row is the
    float32 product over the widened matrix (a multiply-reduce at the
    memory's pace, which is what the compiler made of a one-row step
    before this was written down: it drops a rounding the source writes
    where it can keep more); two rows and more go through the MXU as two
    operands of the stored type, a row's rounding and what the rounding
    left (the row to 2**-17 under bfloat16), stacked so that the matrix
    is read once.

    Why: fed the rounded rows as written, a row in company read
    ``lm_logit_gap`` higher than alone on 138 of 144 prompts, up to
    ``lfm2_game``'s fp8 control (PERF.md section 2); with this its
    readings in company spread as they do alone. It does not make a row's
    tokens those of its solo decode: the prefill's products and every
    float32 sum are ordered by the batch's shape, and each later rounding
    to the stored type turns 1e-7 into 1e-3 (PERF.md section 6, PR 37;
    every row as its own float32 multiply-reduce was 11% slower at four
    rows and no closer). The rounding is ``reduce_precision`` because
    that one the compiler may not drop: ``x - x.astype(stored).astype(
    float32)`` it folds to 0."""
    rows = x.size // x.shape[-1]
    if not step or kernel.dtype.itemsize >= 4:
        return jnp.dot(x.astype(kernel.dtype), kernel,
                       preferred_element_type=jnp.float32)
    flat = x.astype(jnp.float32).reshape(rows, x.shape[-1])
    if rows == 1:
        out = jnp.dot(flat, kernel.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    else:
        kind = jnp.finfo(kernel.dtype)
        hi = jax.lax.reduce_precision(flat, kind.nexp, kind.nmant)
        out = jnp.dot(jnp.concatenate([hi, flat - hi]).astype(kernel.dtype),
                      kernel, preferred_element_type=jnp.float32)
        out = out[:rows] + out[rows:]
    return out.reshape(*x.shape[:-1], -1)


def walk_operands(local, weight, here, load):
    """What a step's routing hands the walk, from ``local`` (T, top_k)
    held ids, ``weight`` (T, top_k), ``here`` (T, top_k) (the assignment
    landed, from a real row) and ``load`` (held,) (landed assignments an
    expert): ``experts`` (held,), the ones some row chose first in
    ascending id, each read once for every row of the step, so a row adds
    its experts in that order whatever its company; and ``combine`` (T,
    held), each row's weight for each expert, 0 where it did not choose
    it. How many were chosen is ``sum(load > 0)``."""
    t, held = local.shape[0], load.shape[0]
    combine = jnp.zeros((t, held), jnp.float32).at[
        jnp.arange(t)[:, None], local].add(jnp.where(here, weight, 0.0))
    experts = jnp.argsort(load == 0, stable=True)
    return experts, combine


class HeldExperts(nn.Module):
    """Top-k routed SwiGLU experts of which ``experts_held`` live here,
    ids ``[first_expert, first_expert + experts_held)``, plus an optional
    sigmoid-gated shared expert: x (T, D) -> (out (T, D) float32, stats).

    The router scores all ``num_experts`` in float32 and keeps the
    ``top_k`` best, weights renormalised over the k when
    ``norm_topk_prob``; an assignment to an absent expert adds nothing
    (another chip's part). The routing rule is data of the module:
    ``scoring`` ("softmax" over the experts, or "sigmoid" of each),
    ``selection_bias`` (a buffer ``expert_bias`` (num_experts,) added to
    the scores for the choice alone: the weights stay the unbiased scores
    of the chosen), ``norm_eps`` (added to the sum the weights are
    divided by) and ``scaling`` (what the weights are multiplied by
    last). No assignment is dropped: ``dense=True``
    runs every held expert over every token and combines by the routing
    weights (prefill: with hundreds of tokens every expert is touched
    anyway); ``dense=False`` walks the held experts that some row chose,
    one expert's weights a step, each read once and multiplied against
    every row, which keeps its own weight for it or 0 (decode: a handful
    of rows touch a handful of the held experts, and the step is bound by
    the weights it reads; rows that chose the same expert share its
    read). ``real`` (T,) marks tokens that count: padding is neither
    computed in the walk nor counted in ``stats`` (``assignments``,
    ``assignments_held``, ``experts_touched``, ``load`` (experts_held,),
    and ``walk_reads_saved``: landed assignments less the experts the walk
    read, 0 in the dense form).

    Which form the walk takes is a rule on what the code can see, and
    nothing sets it: on the TPU, at widths the kernel tiles
    (``moe_walk_fits``), one Pallas call a layer that copies the chosen
    experts' matrices back to back while the one before multiplies
    (ops/moe_walk.py, ``walk_kernel``); anywhere else ``_walk``'s
    ``fori_loop`` of dependent products (``walk_xla``), which is also the
    form the kernel is tested against. Counted under
    ``moe.dispatch{path}`` once a site a trace, with ``dense``. Until the
    walk went by expert it read once an assignment; on one
    v5e a layer call at 1 / 2 / 4 rows, net of the scan around it, took
    36.6 / 81.6 / 163.3 us as the loop and 23.4 / 43.0 / 88.2 as the
    kernel against a read of 19.1 / 39.0 / 78.7, and a whole batch-1
    dispatch 146.5 ms and 138.5 (PR 32, experts of 6.3 MB); with experts
    of 18.9 MB, all held, 124.2 / 263.0 / 520.4 and 103.0 / 205.1 / 400.5
    against 92.2 / 184.4 / 368.7, and a dispatch 192.1 and 191.8 (PR 34;
    the forms, piece sizes and cost statements tried are in the kernel's
    module)."""

    num_experts: int
    experts_held: int
    first_expert: int
    top_k: int
    intermediate: int
    shared_intermediate: int = 0
    norm_topk_prob: bool = True
    scoring: str = "softmax"
    selection_bias: bool = False
    norm_eps: float = 0.0
    scaling: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, real: jax.Array, dense: bool):
        t, d = x.shape
        held_n, k, f = self.experts_held, self.top_k, self.intermediate
        hi = jax.lax.Precision.HIGHEST
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        x32 = x.astype(jnp.float32)
        xb = x.astype(self.dtype)

        with jax.named_scope("moe_router"):
            router = self.param("router", nn.initializers.lecun_normal(),
                                (d, self.num_experts), jnp.float32)
            logits = jnp.dot(x32, router.astype(jnp.float32), precision=hi)
            scores = {"softmax": lambda z: jax.nn.softmax(z, axis=-1),
                      "sigmoid": jax.nn.sigmoid}[self.scoring](logits)
            if self.selection_bias:
                bias = self.param(
                    "expert_bias", lambda key, shape: jax.random.uniform(
                        key, shape, jnp.float32, -0.1, 0.1),
                    (self.num_experts,)).astype(jnp.float32)
                _, top_i = jax.lax.top_k(scores + bias, k)      # (T, k)
                top_p = jnp.take_along_axis(scores, top_i, axis=-1)
            else:
                top_p, top_i = jax.lax.top_k(scores, k)         # (T, k)
            if self.norm_topk_prob:
                total = jnp.sum(top_p, axis=-1, keepdims=True)
                if self.norm_eps:
                    total = total + self.norm_eps
                top_p = top_p / total
            if self.scaling != 1.0:
                top_p = top_p * self.scaling
            local = top_i - self.first_expert
            here = (local >= 0) & (local < held_n) & real[:, None]
            local = jnp.clip(local, 0, held_n - 1)
            load = jnp.zeros((held_n,), jnp.int32).at[local.reshape(-1)].add(
                here.reshape(-1).astype(jnp.int32))
            stats = {
                "assignments": jnp.sum(real).astype(jnp.int32) * k,
                "assignments_held": jnp.sum(here).astype(jnp.int32),
                "experts_touched": jnp.sum(load > 0).astype(jnp.int32),
                "load": load,
            }

        with jax.named_scope("moe_experts"):
            gate_up = self.param("gate_up", init, (held_n, d, 2 * f),
                                 jnp.float32).astype(self.dtype)
            down = self.param("down", init, (held_n, f, d),
                              jnp.float32).astype(self.dtype)
            experts, combine = walk_operands(local, top_p, here, load)
            if dense:
                metrics.inc("moe.dispatch", labels={"path": "dense"})
                stats["walk_reads_saved"] = jnp.zeros((), jnp.int32)
                gu = jnp.einsum("td,edf->tef", xb, gate_up,
                                preferred_element_type=jnp.float32)
                h = nn.silu(gu[..., :f]) * gu[..., f:] * combine[..., None]
                out = jnp.einsum("tef,efd->td", h.astype(self.dtype), down,
                                 preferred_element_type=jnp.float32)
            else:
                touched = stats["experts_touched"]
                stats["walk_reads_saved"] = (stats["assignments_held"]
                                             - touched)
                kernel = on_tpu() and moe_walk_fits(
                    d, f, jnp.dtype(self.dtype).itemsize)
                metrics.inc("moe.dispatch", labels={
                    "path": "walk_kernel" if kernel else "walk_xla"})
                walk = (functools.partial(moe_walk, top_k=k) if kernel
                        else self._walk)
                out = walk(xb, gate_up, down, experts, combine, touched)

        if self.shared_intermediate:
            with jax.named_scope("moe_shared"):
                fs = self.shared_intermediate
                dense_init = nn.initializers.lecun_normal()
                s_gate_up = self.param("shared_gate_up", dense_init,
                                       (d, 2 * fs), jnp.float32)
                s_down = self.param("shared_down", dense_init, (fs, d),
                                    jnp.float32)
                s_gate = self.param("shared_gate", dense_init, (d, 1),
                                    jnp.float32)
                gu = stored_dot(x32, s_gate_up.astype(self.dtype), not dense)
                h = nn.silu(gu[:, :fs]) * gu[:, fs:]
                shared = stored_dot(h, s_down.astype(self.dtype), not dense)
                out = out + shared * jax.nn.sigmoid(jnp.dot(
                    x32, s_gate.astype(jnp.float32), precision=hi))
        return out, stats

    def _walk(self, xb, gate_up, down, experts, combine, count):
        """The walk as XLA runs it, and what ops/moe_walk.py is tested
        against: one expert a loop trip, ``experts[i]`` for ``i`` below
        ``count``, multiplied against every row and added into each row
        times its column of ``combine`` (T, held): 0 adds nothing."""
        f = self.intermediate

        def body(i, acc):
            e = experts[i]
            gu = jnp.dot(
                xb, jax.lax.dynamic_index_in_dim(gate_up, e, keepdims=False),
                preferred_element_type=jnp.float32)
            h = nn.silu(gu[:, :f]) * gu[:, f:]
            y = jnp.dot(h.astype(self.dtype),
                        jax.lax.dynamic_index_in_dim(down, e,
                                                     keepdims=False),
                        preferred_element_type=jnp.float32)
            w = jax.lax.dynamic_index_in_dim(combine, e, axis=1)
            return acc + jnp.where(w != 0.0, w * y, 0.0)

        return jax.lax.fori_loop(
            0, count, body,
            jnp.zeros((xb.shape[0], down.shape[-1]), jnp.float32))


def expert_specs(params) -> dict:
    """PartitionSpecs placing expert-stacked weights over ``ep``."""

    def spec_for(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name.endswith("w1") or name.endswith("w2"):
            return P("ep", None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_moe_params(params, mesh: Mesh):
    """Place MoE params: experts over ``ep``, router replicated."""
    ep = int(mesh.shape.get("ep", 1))

    def place(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        spec = P()
        if (name.endswith("w1") or name.endswith("w2")) and \
                leaf.shape[0] % ep == 0:
            spec = P("ep", None, None)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


@functools.lru_cache(maxsize=32)
def _moe_jitted(model: MoEMLP, mesh: Mesh):
    """One compiled executable per (model config, mesh) — MoEMLP is a
    frozen dataclass and Mesh hashes by devices+axes, so both key the
    cache; a fresh closure per call would retrace every time."""
    batch_spec = P("dp") if "dp" in mesh.axis_names else P()

    @jax.jit
    def fn(p, x):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec)
        )
        return model.apply(p, x)

    return fn


def moe_sharded_apply(model: MoEMLP, params, x: jax.Array, mesh: Mesh):
    """MoE forward with expert-sharded params and batch-sharded
    activations; GSPMD inserts the dispatch/combine all-to-alls."""
    return _moe_jitted(model, mesh)(params, x)
