"""Plain reference of the ``lfm2_game`` configuration's prompt LM
(LFM2-MoE: gated short convolutions among grouped-query attention layers,
a dense leading MLP, sigmoid-routed sparse experts, tied embeddings).

It keeps the contracts harness/reference.py states: float32 at
``Precision.HIGHEST``, every product's operands through ``_operand`` and
its FLOPs through ``_add``, no cache, no kernel. It imports nothing of the
program and reads the seeded weights in the layout the model declares
them in. A layer runs as a few blocks (its mixer, its router, its experts
eight at a time), so that only 0.3 GB of float32 expert matrices live
beside the 10.4 GB tree at a time.

The equations (h the residual stream; every projection without bias;
``rms_norm(x) = x * rsqrt(mean(x^2) + eps) * w``, weight not centred):

  layer i    h = h + op_i(rms_norm(h)); h = h + ffn_i(rms_norm(h));
             op_i by ``layer_types[i]``, ffn_i the dense MLP for
             i < num_dense_layers, the sparse block after
  conv       [B | C | x] = in_proj(.); u = B * x;
             c_t = sum_j w[j] * u[t - (L - 1) + j] (depthwise, causal,
             u = 0 before the start); out_proj(C * c); no activation
  attention  q, k per-head rms_norm, rotary over the whole head
             (rotate-half), causal softmax(q k^T / sqrt(d)) v, KV head j
             serving query heads j*(H/KVH) .. (j+1)*(H/KVH) - 1
  dense MLP  w2(silu(w1 x) * w3 x)
  sparse     s = sigmoid(x W_r); chosen = top-k(s + expert_bias);
             p = s[chosen] / (sum + 1e-6) (``norm_topk_prob``), times
             ``routed_scaling_factor``; sum_e p_e w2_e(silu(w1_e x) * w3_e x)
  head       rms_norm(h) E^T, E the input embedding

Departures from the published model, each the served path's own and in
the configuration's file: the embedding is tied (the catalog row dropped
the key; the family's default); ``expert_bias`` is drawn from the seed; an
expert's ``w1 | w3`` lie side by side in ``gate_up``; of the
``num_experts`` routed experts the chip holds ``experts_held`` from
``first_expert`` on, and what an absent expert would add is left out;
generated token ``i`` sits at position ``bucket + i`` for the rotary
embedding (the convolution knows no position, only order).

FLOPs of the experts are booked for the routed assignments a token makes
on average, ``num_experts_per_tok * experts_held / num_experts``, never
for the held experts this file evaluates for simplicity.

``without`` names parts of the mathematics to take away, for the tests
that show the comparison sees each: ``gate_b``, ``gate_c``, ``tap`` (the
convolution's oldest tap), ``qk_norm``, ``selection_bias``, ``norm_eps``.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from benchmarks.harness import reference as ref
from benchmarks.references.qwen3_next import (  # noqa: F401
    matmul,
    moe_floor_s,  # the same floor, at this configuration's expert size
    rms_norm as centred_rms_norm,
    rotary,
)

F32, HI = ref.F32, ref.HI
#: experts a block of the sparse layer evaluates at a time
EXPERT_GROUP = 8

Dims = collections.namedtuple("Dims", [
    "hidden_size", "num_attention_heads", "num_key_value_heads",
    "rope_theta", "conv_L_cache", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor", "norm_eps", "experts_held", "first_expert"])


def rms_norm(w, x, eps: float):
    return centred_rms_norm(w, x, eps, centred=False)


def short_conv(p, x, d: Dims, without=()):
    """x (B, S, D) -> (B, S, D)."""
    b, s, width = x.shape
    taps = d.conv_L_cache
    gate_b, gate_c, inner = jnp.split(ref.dense(p["in_proj"], x), 3, axis=-1)
    u = inner if "gate_b" in without else gate_b * inner
    w = ref._operand(p["conv"])
    padded = jnp.pad(ref._operand(u), ((0, 0), (taps - 1, 0), (0, 0)))
    ref._add(2.0 * b * s * width * taps)
    mixed = sum(w[j] * padded[:, j:j + s]
                for j in range(1 if "tap" in without else 0, taps))
    return ref.dense(p["out_proj"],
                     mixed if "gate_c" in without else gate_c * mixed)


def attention(p, x, positions, d: Dims, without=()):
    b, s, width = x.shape
    h, kvh = d.num_attention_heads, d.num_key_value_heads
    hd = width // h
    q = ref.dense(p["q_proj"], x).reshape(b, s, h, hd)
    k = ref.dense(p["k_proj"], x).reshape(b, s, kvh, hd)
    v = ref.dense(p["v_proj"], x).reshape(b, s, kvh, hd)
    if "qk_norm" not in without:
        q = rms_norm(p["q_norm"]["weight"], q, d.norm_eps)
        k = rms_norm(p["k_norm"]["weight"], k, d.norm_eps)
    q = rotary(q, positions, hd, d.rope_theta)
    k = rotary(k, positions, hd, d.rope_theta)
    k, v = (jnp.repeat(t, h // kvh, axis=2).reshape(b, s, h * hd)
            for t in (k, v))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    return ref.dense(p["out_proj"], ref.attention(
        q.reshape(b, s, h * hd), k, v, h, causal))


def dense_mlp(p, x):
    return ref.dense(p["w2"], jax.nn.silu(ref.dense(p["w1"], x))
                     * ref.dense(p["w3"], x))


def routing(p, x, d: Dims, without=()):
    """x (T, D) -> (T, num_experts): the weight each expert's output
    enters the sum with, 0 for an expert that was not chosen."""
    t = x.shape[0]
    scores = jax.nn.sigmoid(matmul(x, p["router"]))
    choice = scores
    if d.use_expert_bias and "selection_bias" not in without:
        choice = scores + p["expert_bias"].astype(F32)
    _, top_i = jax.lax.top_k(choice, d.num_experts_per_tok)
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    if d.norm_topk_prob:
        top_p = top_p / (jnp.sum(top_p, -1, keepdims=True)
                         + (0.0 if "norm_eps" in without else 1e-6))
    top_p = top_p * d.routed_scaling_factor
    return jnp.zeros((t, d.num_experts), F32).at[
        jnp.arange(t)[:, None], top_i].add(top_p)


def experts(gate_up, down, x, weight):
    """gate_up (G, D, 2F), down (G, F, D), x (T, D), weight (T, G) ->
    (T, D): every expert of the group on every token, weighted by what
    was routed to it."""
    f = down.shape[1]
    gu = jnp.einsum("td,edf->tef", ref._operand(x), ref._operand(gate_up),
                    precision=HI)
    hid = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    per = jnp.einsum("tef,efd->ted", ref._operand(hid), ref._operand(down),
                     precision=HI)
    return jnp.sum(per * weight[..., None], axis=1)


@ref.block("d", "full", "without")
def _mixer(p, x, positions, *, d: Dims, full: bool, without: tuple):
    h = rms_norm(p["operator_norm"]["weight"], x, d.norm_eps)
    return x + (attention(p["mixer"], h, positions, d, without) if full
                else short_conv(p["mixer"], h, d, without))


@ref.block("eps")
def _dense_ffn(norm_w, p, x, *, eps: float):
    return x + dense_mlp(p, rms_norm(norm_w, x, eps))


@ref.block("d", "without")
def _route(norm_w, router, x, *, d: Dims, without: tuple):
    """(the block's input (T, D), the held experts' weights (T, held))."""
    h = rms_norm(norm_w, x, d.norm_eps).reshape(-1, x.shape[-1])
    weight = routing(router, h, d, without)
    return h, weight[:, d.first_expert:d.first_expert + d.experts_held]


@ref.block()
def _expert_group(gate_up, down, x, weight):
    return experts(gate_up, down, x, weight)


@ref.block("eps")
def _head(norm_w, embedding, x, *, eps: float):
    h = rms_norm(norm_w, x, eps)
    ref._add(2.0 * h.size * embedding.shape[0])
    return jnp.matmul(ref._operand(h), ref._operand(embedding).T,
                      precision=HI)


def sparse_block(norm_w, p, x, d: Dims, without=()):
    """x (B, S, D) -> x + this chip's experts' part."""
    router = {k: v for k, v in p.items() if k in ("router", "expert_bias")}
    h, weight = _route(norm_w, router, x, d=d, without=tuple(without))
    f = d.moe_intermediate_size
    # booked: the routed assignments a token makes here on average
    share = d.num_experts_per_tok * d.experts_held / d.num_experts
    ref._add(share * h.shape[0] * 6.0 * d.hidden_size * f)
    out = 0.0
    for first in range(0, d.experts_held, EXPERT_GROUP):
        group = slice(first, first + EXPERT_GROUP)
        out = out + _expert_group(p["gate_up"][group], p["down"][group], h,
                                  weight[:, group])
    return x + out.reshape(x.shape)


#: what the equations here take for granted of the published config
ASSUMES = {"conv_bias": False, "tie_word_embeddings": True}


def dims(sz: dict) -> Dims:
    other = {k: sz[k] for k, v in ASSUMES.items() if sz.get(k, v) != v}
    if other:
        raise ValueError(f"this reference does not compute {other}")
    return Dims(**{k: sz[k] for k in Dims._fields})


def lfm2_logits(params, ids, positions, sz, without=()):
    """ids, positions (B, S) -> logits (B, S, V); causal, no padding."""
    p = params["params"]
    d = dims(sz)
    x = p["embed"]["embedding"][ids].astype(F32)
    for i, kind in enumerate(sz["layer_types"]):
        layer = p[f"layer_{i}"]
        x = _mixer({k: layer[k] for k in ("operator_norm", "mixer")}, x,
                   positions, d=d, full=kind == "full_attention",
                   without=tuple(without))
        norm_w = layer["ffn_norm"]["weight"]
        x = (_dense_ffn(norm_w, layer["mlp"], x, eps=d.norm_eps)
             if i < sz["num_dense_layers"]
             else sparse_block(norm_w, layer["moe"], x, d, without))
    return _head(p["embedding_norm"]["weight"], p["embed"]["embedding"], x,
                 eps=d.norm_eps)
