"""Plain reference of the ``ouro_game`` configuration's prompt LM (Ouro: one
stack of dense layers run ``total_ut_steps`` times a token over one set of
weights).

It keeps the contracts harness/reference.py states: float32 at
``Precision.HIGHEST``, every product's operands through ``_operand`` and
its FLOPs through ``_add`` (every pass's, so the count is four times the
stack's), no cache, no kernel. It imports nothing of the program and reads
the seeded weights in the layout the model declares them in. A layer runs
as a block of its own, so that one layer's weights are widened to float32
at a time (the whole model in float32 would be 10.7 GB).

The equations (h the residual stream; every projection without bias;
``rms_norm(x) = x * rsqrt(mean(x^2) + eps) * w``, weight not centred):

  embedding  h = E[ids], no scaling, untied from the head
  pass       for p in 1..total_ut_steps: for every layer l in order:
             a = rms_norm(Attn(rms_norm(h)));  h = h + a
             m = rms_norm(W_down(silu(W_gate x) * W_up x)), x = rms_norm(h);
             h = h + m
             then h = rms_norm_f(h), the final norm: the next pass's input
  attention  16 heads of 128 over as many key/value heads, rotate-half
             rotary over the whole head, causal softmax(q k^T / sqrt(d)) v;
             keys and values are the same pass's, same layer's
  exit gate  lambda_p = sigmoid(w_g . h + b_g) after each pass; the exit
             distribution is lambda_p prod_{j<p} (1 - lambda_j), the last
             pass taking what is left, and a token leaves at the first pass
             whose cumulative probability reaches ``early_exit_threshold``:
             at 1 that is the last pass, which this file asserts
  head       W_head h after the last pass

Departures from the published model, each the served path's own and in
the configuration's file: the byte tokenizer; generated token ``i`` sits
at position ``bucket + i`` for the rotary embedding.

``decode_floor_s`` is the least time the chip could take for a mean decode
step of those the program's counters describe (``ouro.pass_steps``,
``ouro.kv_slots``), read by readers/step_roofline.py.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference as ref
from benchmarks.references.qwen3_next import (
    ELEMENT_BYTES,
    matmul,
    rms_norm as centred_rms_norm,
    rotary,
)

F32 = ref.F32

Dims = collections.namedtuple("Dims", [
    "hidden_size", "intermediate_size", "num_attention_heads", "head_dim",
    "rope_theta", "rms_norm_eps", "total_ut_steps", "early_exit_threshold"])


def rms_norm(w, x, eps: float):
    return centred_rms_norm(w, x, eps, centred=False)


def attention(p, x, positions, d: Dims):
    b, s, _ = x.shape
    h, hd = d.num_attention_heads, d.head_dim
    q, k, v = (ref.dense(p[f"{n}_proj"], x).reshape(b, s, h, hd)
               for n in "qkv")
    q = rotary(q, positions, hd, d.rope_theta)
    k = rotary(k, positions, hd, d.rope_theta)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    out = ref.attention(*(t.reshape(b, s, h * hd) for t in (q, k, v)), h,
                        causal)
    return ref.dense(p["o_proj"], out)


def mlp(p, x):
    return ref.dense(p["down_proj"], jax.nn.silu(ref.dense(p["gate_proj"], x))
                     * ref.dense(p["up_proj"], x))


@ref.block("d")
def _layer(stack, i, x, positions, *, d: Dims):
    """Layer ``i`` of the stack, whose weights lie on a leading axis."""
    p = jax.tree_util.tree_map(lambda w: w[i], stack)
    eps = d.rms_norm_eps
    a = attention(p["self_attn"], rms_norm(p["input_layernorm"]["weight"], x,
                                           eps), positions, d)
    x = x + rms_norm(p["input_layernorm_2"]["weight"], a, eps)
    m = mlp(p["mlp"], rms_norm(p["post_attention_layernorm"]["weight"], x,
                               eps))
    return x + rms_norm(p["post_attention_layernorm_2"]["weight"], m, eps)


@ref.block("eps")
def _after_pass(norm_w, gate, x, *, eps: float):
    """(the next pass's input, the exit gate (B, S))."""
    x = rms_norm(norm_w, x, eps)
    return x, jax.nn.sigmoid(ref.dense(gate, x)[..., 0])


@ref.block()
def _head(w, x):
    return matmul(x, w)


def exit_pass(gates, threshold: float):
    """gates (passes, ...) -> the pass (from 0) each position leaves at:
    the first whose cumulative exit probability reaches ``threshold``, the
    last pass taking what the others left."""
    gates = np.asarray(gates, np.float64)
    stay = np.cumprod(1.0 - gates, axis=0)
    cdf = 1.0 - stay
    cdf[-1] = 1.0
    return np.argmax(cdf >= threshold, axis=0)


#: what the equations here take for granted of the published config
ASSUMES = {"tie_word_embeddings": False, "hidden_act": "silu",
           "use_sliding_window": False, "rope_scaling": None}


def dims(sz: dict) -> Dims:
    other = {k: sz[k] for k, v in ASSUMES.items() if sz.get(k, v) != v}
    if other or sz["num_key_value_heads"] != sz["num_attention_heads"]:
        raise ValueError(f"this reference does not compute {other or sz}")
    return Dims(**{k: sz[k] for k in Dims._fields})


def ouro_logits(params, ids, positions, sz):
    """ids, positions (B, S) -> logits (B, S, V); causal, no padding."""
    p = params["params"]
    d = dims(sz)
    x = p["embed_tokens"]["embedding"][ids].astype(F32)
    gates = []
    for _ in range(d.total_ut_steps):
        for i in range(sz["num_hidden_layers"]):
            x = _layer(p["layers"], i, x, positions, d=d)
        x, gate = _after_pass(p["norm"]["weight"], p["early_exit_gate"], x,
                              eps=d.rms_norm_eps)
        gates.append(gate)
    if not isinstance(x, jax.core.Tracer):
        left = exit_pass(jnp.stack(gates), d.early_exit_threshold)
        if (left != d.total_ut_steps - 1).any():
            raise ValueError(
                f"a position leaves before the last pass at threshold "
                f"{d.early_exit_threshold}: the served model runs them all")
    return _head(p["lm_head"], x)


def decode_floor_s(sz: dict, device_kind: str, pass_steps: float,
                   kv_slots: float) -> float:
    """The least seconds the chip could take for a mean decode step of
    those that ran ``pass_steps`` passes in all (every step runs every
    pass) and attended ``kv_slots`` valid cache slots (summed over rows,
    passes and layers): the larger of the bytes over the memory's peak and
    the FLOPs over the MXU's, over the steps.

    Bytes: every pass of a step reads the whole stack in the stored type
    (a pass needs all its layers' output before the next starts, and the
    stack stays on no chip), a step reads the head once, and a slot
    attended reads its k and v. FLOPs: 2 a parameter of the stack a pass
    and of the head a step, for one row (the counters do not say how many
    rows a step had, and the bytes bind by far), and 4 a slot and head
    width for the scores and the weighted sum."""
    from benchmarks.harness import peaks

    width = sz["num_attention_heads"] * sz["head_dim"]
    d, f = sz["hidden_size"], sz["intermediate_size"]
    layer = 4 * d * width + 3 * d * f + 4 * d
    stack = sz["num_hidden_layers"] * layer
    head = sz["vocab_size"] * d
    steps = pass_steps / sz["total_ut_steps"]
    element = ELEMENT_BYTES[sz["dtype"]]
    moved = element * (pass_steps * stack + steps * head
                       + kv_slots * 2 * width)
    ops = 2.0 * (pass_steps * stack + steps * head) + 4.0 * kv_slots * width
    return max(moved / peaks.peak(device_kind, "hbm_bytes_per_s"),
               ops / peaks.peak(device_kind, "bf16_flops_per_s")) / steps
