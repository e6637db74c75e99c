"""Plain reference of the ``qwen3next_game`` configuration: its prompt LM
(Qwen3-Next: Gated DeltaNet linear attention, gated full attention, sparse
experts with a shared expert) and its image trajectory (few-step
consistency sampling).

It keeps the contracts harness/reference.py states. The LM: float32 at
``Precision.HIGHEST``, every product's operands through ``_operand`` and
its FLOPs through ``_add``, no cache, no kernel; the linear layers'
recurrence runs a token at a time (a ``lax.scan`` over the tokens, so
that the block compiles in seconds; nothing is chunked or reordered). It
imports nothing of the program and reads the seeded weights in the layout
the model declares them in.

Departures from the published model, each the served path's own and in
the configuration's file: of the ``num_experts`` routed experts the chip
holds ``experts_held`` from ``first_expert`` on; the router scores all of
them and keeps its ``num_experts_per_tok`` best, weights normalised over
those, and what an absent expert would add is left out (another chip's
part); the vocabulary is the held slice; ``in_proj_qkvz`` gives ``q | k |
v | z`` flat and ``in_proj_ba`` ``b | a`` (the checkpoint groups them per
key head; with seeded weights one layout is fixed); the multi-token
prediction module is not served; generated token ``i`` sits at position
``bucket + i`` for the rotary embedding (the linear layers know no
position, only order).

FLOPs of the experts are booked for the routed assignments a token makes
on average, ``num_experts_per_tok * experts_held / num_experts``, never
for the held experts this file evaluates for simplicity.
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference as ref

F32, HI = ref.F32, ref.HI

Dims = collections.namedtuple("Dims", [
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "rms_norm_eps", "experts_held", "first_expert"])


def matmul(x, w):
    ref._add(2.0 * math.prod(x.shape) * w.shape[-1])
    return jnp.matmul(ref._operand(x), ref._operand(w), precision=HI)


def rms_norm(w, x, eps: float, centred: bool = True):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * ((1.0 + w.astype(F32)) if centred else w.astype(F32))


def rotary(x, positions, rot: int, theta: float):
    """x (B, S, H, D), positions (B, S): rotate-half on the first ``rot``."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def gated_delta(p, x, d: Dims, conv_window: bool = True, decay: bool = True):
    """x (B, S, D) -> (B, S, D). ``conv_window`` and ``decay`` exist for
    the tests that take a part of the mathematics away."""
    b, s, _ = x.shape
    hk, hv = d.linear_num_key_heads, d.linear_num_value_heads
    dk, dv, kern = (d.linear_key_head_dim, d.linear_value_head_dim,
                    d.linear_conv_kernel_dim)
    qk_w, v_w = hk * dk, hv * dv
    conv_w = 2 * qk_w + v_w
    qkvz = ref.dense(p["in_proj_qkvz"], x)
    ba = ref.dense(p["in_proj_ba"], x)
    mixed, z = qkvz[..., :conv_w], qkvz[..., conv_w:]
    # depthwise causal convolution: y[t] = sum_j w[j] * u[t - (kern-1) + j]
    w = ref._operand(p["conv"])
    padded = jnp.pad(ref._operand(mixed), ((0, 0), (kern - 1, 0), (0, 0)))
    taps = range(kern) if conv_window else [kern - 1]
    ref._add(2.0 * b * s * conv_w * kern)
    mixed = jax.nn.silu(sum(w[j] * padded[:, j:j + s] for j in taps))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                                 + 1e-6)

    q = l2(mixed[..., :qk_w].reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2(mixed[..., qk_w:2 * qk_w].reshape(b, s, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    v = mixed[..., 2 * qk_w:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = (-jnp.exp(p["A_log"].astype(F32))
         * jax.nn.softplus(ba[..., hv:] + p["dt_bias"].astype(F32)))
    if not decay:
        g = jnp.zeros_like(g)

    def token(state, per):
        q_t, k_t, v_t, g_t, b_t = per
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", ref._operand(state),
                             ref._operand(k_t), precision=HI)
        state = state + k_t[..., :, None] * (b_t[..., None] * r)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", ref._operand(state),
                                 ref._operand(q_t), precision=HI)

    # S^T k, k (beta r)^T and S^T q: 2 d_k d_v each, a token and a head
    ref._add(6.0 * b * s * hv * dk * dv)
    _, o = jax.lax.scan(
        token, jnp.zeros((b, hv, dk, dv), F32),
        [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)])
    o = rms_norm(p["norm"]["weight"], jnp.moveaxis(o, 0, 1), d.rms_norm_eps,
                 centred=False)
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return ref.dense(p["out_proj"], o.reshape(b, s, v_w))


def gated_attention(p, x, positions, d: Dims):
    b, s, _ = x.shape
    h, kvh, hd = d.num_attention_heads, d.num_key_value_heads, d.head_dim
    rot = int(hd * d.partial_rotary_factor)
    qg = ref.dense(p["q_proj"], x).reshape(b, s, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = ref.dense(p["k_proj"], x).reshape(b, s, kvh, hd)
    v = ref.dense(p["v_proj"], x).reshape(b, s, kvh, hd)
    q = rotary(rms_norm(p["q_norm"]["weight"], q, d.rms_norm_eps),
               positions, rot, d.rope_theta)
    k = rotary(rms_norm(p["k_norm"]["weight"], k, d.rms_norm_eps),
               positions, rot, d.rope_theta)
    k, v = (jnp.repeat(t, h // kvh, axis=2).reshape(b, s, h * hd)
            for t in (k, v))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    attn = ref.attention(q.reshape(b, s, h * hd), k, v, h, causal)
    return ref.dense(p["o_proj"], attn * jax.nn.sigmoid(
        gate.reshape(b, s, h * hd)))


def sparse_block(p, x, d: Dims):
    """x (T, D) -> (T, D): this chip's experts' part, and the shared
    expert's."""
    t, width = x.shape
    f, fs = d.moe_intermediate_size, d.shared_expert_intermediate_size
    probs = jax.nn.softmax(matmul(x, p["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, d.num_experts_per_tok)
    if d.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    weight = jnp.zeros((t, d.num_experts), F32).at[
        jnp.arange(t)[:, None], top_i].add(top_p)
    weight = weight[:, d.first_expert:d.first_expert + d.experts_held]
    # every held expert on every token, weighted by what was routed to it;
    # booked: the routed assignments a token makes here on average
    share = d.num_experts_per_tok * d.experts_held / d.num_experts
    ref._add(share * t * (2.0 * width * 2 * f + 2.0 * f * width))
    gu = jnp.einsum("td,edf->tef", ref._operand(x), ref._operand(p["gate_up"]),
                    precision=HI)
    hid = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    per = jnp.einsum("tef,efd->ted", ref._operand(hid),
                     ref._operand(p["down"]), precision=HI)
    out = jnp.sum(per * weight[..., None], axis=1)
    gu = matmul(x, p["shared_gate_up"])
    shared = matmul(jax.nn.silu(gu[:, :fs]) * gu[:, fs:], p["shared_down"])
    return out + shared * jax.nn.sigmoid(matmul(x, p["shared_gate"]))


def layer(p, x, positions, d: Dims, full: bool, **parts):
    h = rms_norm(p["norm1"]["weight"], x, d.rms_norm_eps)
    x = x + (gated_attention(p["mixer"], h, positions, d) if full
             else gated_delta(p["mixer"], h, d, **parts))
    b, s, width = x.shape
    h = rms_norm(p["norm2"]["weight"], x, d.rms_norm_eps)
    return x + sparse_block(p["moe"], h.reshape(b * s, width), d).reshape(
        b, s, width)


@ref.block("d")
def _linear_layer(p, x, positions, *, d: Dims):
    return layer(p, x, positions, d, False)


@ref.block("d")
def _full_layer(p, x, positions, *, d: Dims):
    return layer(p, x, positions, d, True)


@ref.block("eps")
def _head(norm_w, head, x, *, eps: float):
    return matmul(rms_norm(norm_w, x, eps), head)


#: what the equations here take for granted of the published config: a
#: sparse block in every layer, SiLU, an untied head, plain rotary, no window
ASSUMES = {"decoder_sparse_step": 1, "mlp_only_layers": [],
           "hidden_act": "silu", "tie_word_embeddings": False,
           "use_sliding_window": False, "rope_scaling": None}


def dims(sz: dict) -> Dims:
    other = {k: sz[k] for k, v in ASSUMES.items() if sz.get(k, v) != v}
    if other:
        raise ValueError(f"this reference does not compute {other}")
    return Dims(**{k: sz[k] for k in Dims._fields})


def qwen3next_logits(params, ids, positions, sz, linear_layer=_linear_layer):
    """ids, positions (B, S) -> logits (B, S, V) over the held vocabulary;
    causal, no padding."""
    p = params["params"]
    d = dims(sz)
    x = p["embed"]["embedding"].astype(F32)[ids]
    for i in range(sz["num_hidden_layers"]):
        full = (i + 1) % sz["full_attention_interval"] == 0
        x = (_full_layer if full else linear_layer)(
            p[f"layer_{i}"], x, positions, d=d)
    return _head(p["norm_f"]["weight"], p["lm_head"], x,
                 eps=sz["rms_norm_eps"])


# -- the image trajectory -----------------------------------------------------

#: seed of the re-noise ladder (ops/samplers.py::CONSISTENCY_NOISE_SEED)
RENOISE_SEED = 0x1C3
SIGMA_DATA = 0.5


def consistency_schedule(num_steps: int, teacher_steps: int):
    """(timesteps, alpha_bar, alpha_bar_next, c_skip, c_out): the teacher's
    "leading" grid of ``teacher_steps`` without its t = 0 point, strided
    from the noisiest point down; the boundary parameterization c_skip,
    c_out at sigma = sqrt((1 - a) / a), the identity at sigma_min."""
    betas = np.linspace(0.00085 ** 0.5, 0.012 ** 0.5, 1000,
                        dtype=np.float64) ** 2
    ab_full = np.cumprod(1.0 - betas)
    grid = ref.ddim_schedule(teacher_steps)[0][:-1]
    ts = grid[(len(grid) // num_steps) * np.arange(num_steps)]
    ab = ab_full[ts]
    ab_next = np.concatenate([ab[1:], [1.0]])
    sigma = np.sqrt((1.0 - ab) / ab)
    sigma_min = np.sqrt((1.0 - ab_full[0]) / ab_full[0])
    c_skip = SIGMA_DATA ** 2 / ((sigma - sigma_min) ** 2 + SIGMA_DATA ** 2)
    c_out = SIGMA_DATA * (sigma - sigma_min) / np.sqrt(
        sigma ** 2 + SIGMA_DATA ** 2)
    return (ts.astype(np.int32),) + tuple(
        a.astype(np.float32) for a in (ab, ab_next, c_skip, c_out))


def consistency_trajectory(guided, x, sampler):
    """x_T (B, h, w, 4) -> x_0: multistep consistency sampling, one guided
    forward a step. A step maps the state to an x_0 estimate through the
    boundary parameterization and re-noises it to the next timestep with a
    draw keyed on the timestep alone, one latent row for the whole batch
    (ops/samplers.py::consistency_sample). A sampler that is not a
    consistency sampler is refused: another trajectory serves it."""
    if not sampler.get("consistency"):
        raise SystemExit(
            "consistency_trajectory was named for a sampler without "
            "consistency: name the trajectory the program's sampler runs")
    for t, ab, ab_next, c_skip, c_out in zip(*consistency_schedule(
            sampler["num_steps"], sampler["consistency_teacher_steps"])):
        eps = guided(x, t)
        x0 = (x - jnp.sqrt(1.0 - ab) * eps) / jnp.sqrt(ab)
        f = c_skip * x + c_out * x0
        noise = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(RENOISE_SEED), t),
            x.shape[1:], x.dtype)
        x = jnp.sqrt(ab_next) * f + jnp.sqrt(1.0 - ab_next) * noise
    return x


# -- the expert layers' roofline ----------------------------------------------

ELEMENT_BYTES = {"bfloat16": 2, "float32": 4}


def moe_floor_s(sz: dict, device_kind: str, experts_touched: float,
                assignments_held: float) -> float:
    """The least seconds the chip could take for the routed experts' work:
    each expert touched in a call of an expert layer read once (its gate,
    up and down matrices in the stored type) over the memory's peak, or
    the routed assignments' FLOPs (2 a parameter of an expert) over the
    MXU's, whichever is larger."""
    from benchmarks.harness import peaks

    expert_params = 3.0 * sz["hidden_size"] * sz["moe_intermediate_size"]
    moved = experts_touched * expert_params * ELEMENT_BYTES[sz["dtype"]]
    ops = assignments_held * 2.0 * expert_params
    return max(moved / peaks.peak(device_kind, "hbm_bytes_per_s"),
               ops / peaks.peak(device_kind, "bf16_flops_per_s"))
