"""Plain references for every model a round or a guess runs.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no kernels, no
cache, no batching, no scan. It imports nothing of the program. It reads
the weights the benchmark made (harness/weights.py) in the tree layout the
models declare them in, and its sizes come from the configuration's file.

Departures from the published models, all taken from what the served path
states and noted in PERF.md: the byte tokenizer (no vocabulary files are in
the repo), generated token ``i`` of the LM sits at position ``bucket + i``
while the prompt sits at ``0..len-1``, CLIP's pad id is 258.

``MODE`` selects the precision every matmul, convolution and attention
product reads its operands in: ``"f32"`` (the reference) or ``"fp8"``
(the control: operands rounded through float8_e4m3 with one scale per
tensor, the nearest precision below the bfloat16 the configurations state).
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST

_MODE = "f32"
_TALLY = None  # a list [flops] while counting
_SITES = None  # a list of attention sites (B, H, S_q, S_k, D) while counting
_BLOCKS = None  # {(function, mode, static args): jitted} while block by block


@contextlib.contextmanager
def precision_mode(mode: str):
    """Trace-time switch: functions jitted inside see ``mode``."""
    global _MODE
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown precision mode {mode!r}")
    before, _MODE = _MODE, mode
    try:
        yield
    finally:
        _MODE = before


@contextlib.contextmanager
def count_flops():
    """Tally 2*M*N*K of every product traced inside (use under
    ``jax.eval_shape``: nothing runs)."""
    global _TALLY
    before, _TALLY = _TALLY, [0.0]
    try:
        yield _TALLY
    finally:
        _TALLY = before


@contextlib.contextmanager
def list_attention():
    """Collect (B, H, S_q, S_k, D) of every attention product traced
    inside, in order (use under ``jax.eval_shape``: nothing runs)."""
    global _SITES
    before, _SITES = _SITES, []
    try:
        yield _SITES
    finally:
        _SITES = before


@contextlib.contextmanager
def block_by_block(cache: dict):
    """Run the models eagerly with each repeated block (a residual block, a
    transformer block, an encoder layer) as a jitted program of its own,
    kept in ``cache``: blocks of one shape share one compiled program, so a
    70-block UNet compiles a handful, and only one block's float32 copies
    of its weights live at a time."""
    global _BLOCKS
    before, _BLOCKS = _BLOCKS, cache
    try:
        yield
    finally:
        _BLOCKS = before


def block(*static):
    """Mark a function as a repeated block; ``static`` names its
    keyword arguments that are sizes."""

    def wrap(fn):
        def call(*args, **kw):
            global _BLOCKS
            if _BLOCKS is None:
                return fn(*args, **kw)
            key = (fn.__name__, _MODE) + tuple(kw[k] for k in static)
            if key not in _BLOCKS:
                mode = _MODE

                def traced(*a, **k):
                    global _BLOCKS
                    held, _BLOCKS = _BLOCKS, None  # inner blocks inline
                    try:
                        with precision_mode(mode):
                            return fn(*a, **k)
                    finally:
                        _BLOCKS = held

                _BLOCKS[key] = jax.jit(traced, static_argnames=static)
            return _BLOCKS[key](*args, **kw)

        call.__name__ = fn.__name__
        return call

    return wrap


def _add(flops: float) -> None:
    if _TALLY is not None:
        _TALLY[0] += float(flops)


def round_e4m3(x):
    """float32 -> the nearest float8_e4m3 value, as float32, for |x| <= 448:
    three mantissa bits (round to nearest even) down to 2**-6, multiples
    of 2**-9 below. Integer arithmetic on the bits: the chip has no fp8
    unit and converts through it far more slowly."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & jnp.uint32(1))) \
        & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(bits, F32)
    subnormal = jnp.round(x * 512.0) / 512.0
    return jnp.where(jnp.abs(x) < 2.0 ** -6, subnormal, normal)


def _operand(x):
    x = x.astype(F32)
    if _MODE == "f32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return round_e4m3(x / scale) * scale


# -- primitives ---------------------------------------------------------------

def dense(p, x):
    k = p["kernel"]
    _add(2.0 * math.prod(x.shape) * k.shape[-1])
    y = jnp.matmul(_operand(x), _operand(k), precision=HI)
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    return y


def conv(p, x, stride: int = 1):
    k = p["kernel"]
    kh, kw, cin, cout = k.shape
    pad = (kh // 2, kh // 2)
    y = jax.lax.conv_general_dilated(
        _operand(x), _operand(k), (stride, stride), (pad, pad),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    _add(2.0 * math.prod(y.shape) * kh * kw * cin)
    return y + p["bias"].astype(F32)


def layer_norm(p, x, eps: float):
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def group_norm(p, x, eps: float, groups: int = 32):
    p = p["norm"]
    b, c = x.shape[0], x.shape[-1]
    g = x.astype(F32).reshape(b, -1, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return (g.reshape(x.shape) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def attention(q, k, v, heads: int, mask=None):
    """q (B, Sq, C), k/v (B, Sk, C); mask broadcasts to (B, H, Sq, Sk)."""
    b, sq, c = q.shape
    sk, d = k.shape[1], c // heads
    q = _operand(q).reshape(b, sq, heads, d)
    k = _operand(k).reshape(b, sk, heads, d)
    v = _operand(v).reshape(b, sk, heads, d)
    _add(4.0 * b * heads * sq * sk * d)
    if _SITES is not None:
        _SITES.append((b, heads, sq, sk, d))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * d ** -0.5
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(F32).min)
    w = _operand(jax.nn.softmax(logits, axis=-1))
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      precision=HI).reshape(b, sq, c)


def mha(p, x, heads: int, context=None, mask=None):
    if "qkv" in p:
        q, k, v = jnp.split(dense(p["qkv"], x), 3, axis=-1)
    elif "kv" in p:
        q = dense(p["q"], x)
        k, v = jnp.split(dense(p["kv"], context), 2, axis=-1)
    else:
        ctx = x if context is None else context
        q, k, v = dense(p["q"], x), dense(p["k"], ctx), dense(p["v"], ctx)
    return dense(p["out"], attention(q, k, v, heads, mask))


def quick_gelu(x):
    return x * jax.nn.sigmoid(1.702 * x)


def gelu_erf(x):
    return jax.nn.gelu(x, approximate=False)


def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


def mlp(p, x, act):
    return dense(p["fc2"], act(dense(p["fc1"], x)))


def upsample2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def timestep_embedding(t, dim: int):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    args = t.astype(F32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


ACTS = {"quick_gelu": quick_gelu, "gelu": gelu_erf, "gelu_tanh": gelu_tanh}


@block("heads", "act")
def _pre_ln_layer(b, x, mask, *, heads: int, act: str):
    """Pre-LN transformer layer (CLIP, GPT-2)."""
    x = x + mha(b["attn"], layer_norm(b["ln1"], x, 1e-5), heads, mask=mask)
    return x + mlp(b["mlp"], layer_norm(b["ln2"], x, 1e-5), ACTS[act])


# -- CLIP text encoder --------------------------------------------------------

def clip_text(params, ids, sz):
    """ids (B, S) -> dict(hidden, pooled, penultimate), float32."""
    p = params["params"]
    seq = ids.shape[1]
    x = (p["token_embedding"]["embedding"].astype(F32)[ids]
         + p["position_embedding"].astype(F32)[None, :seq])
    causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    penultimate = x
    for i in range(sz["num_layers"]):
        x = _pre_ln_layer(p[f"block_{i}"], x, causal,
                          heads=sz["num_heads"], act=sz["hidden_act"])
        if i == sz["num_layers"] - 2:
            penultimate = x
    hidden = layer_norm(p["ln_final"], x, 1e-5)
    eot = jnp.argmax(ids, axis=-1)
    pooled = jnp.take_along_axis(hidden, eot[:, None, None], axis=1)[:, 0]
    return {"hidden": hidden, "pooled": pooled, "penultimate": penultimate}


# -- UNet ---------------------------------------------------------------------

@block()
def _res_block(p, x, temb):
    h = conv(p["conv1"], jax.nn.silu(group_norm(p["norm1"], x, 1e-5)))
    h = h + dense(p["time_proj"], jax.nn.silu(temb))[:, None, None, :]
    h = conv(p["conv2"], jax.nn.silu(group_norm(p["norm2"], h, 1e-5)))
    if "skip" in p:
        x = conv(p["skip"], x)
    return x + h


@block("heads")
def _transformer_block(blk, x, context, *, heads: int):
    x = x + mha(blk["self_attn"], layer_norm(blk["ln1"], x, 1e-5), heads)
    x = x + mha(blk["cross_attn"], layer_norm(blk["ln2"], x, 1e-5),
                heads, context=context)
    hcat = dense(blk["ff"]["proj"], layer_norm(blk["ln3"], x, 1e-5))
    val, gate = jnp.split(hcat, 2, axis=-1)
    return x + dense(blk["ff"]["out"], val * gelu_tanh(gate))


@block()
def _tokens_in(p, x):
    b, h, w, c = x.shape
    return dense(p["proj_in"], group_norm(p["norm"], x, 1e-6)
                 ).reshape(b, h * w, c)


@block()
def _tokens_out(p, x, residual):
    return dense(p["proj_out"], x.reshape(residual.shape)) + residual


def _spatial_transformer(p, x, context, heads: int, depth: int):
    tokens = _tokens_in(p, x)
    for i in range(depth):
        tokens = _transformer_block(p[f"block_{i}"], tokens, context,
                                    heads=heads)
    return _tokens_out(p, tokens, x)


@block("base")
def _time_embedding(p, t, addition, *, base: int):
    temb = dense(p["time_fc2"], jax.nn.silu(
        dense(p["time_fc1"], timestep_embedding(t, base))))
    if addition is not None:
        temb = temb + dense(p["add_fc2"], jax.nn.silu(
            dense(p["add_fc1"], addition.astype(F32))))
    return temb


@block("stride")
def _conv(p, x, *, stride: int = 1):
    return conv(p, x.astype(F32), stride)


@block()
def _upsample_conv(p, x):
    return conv(p, upsample2(x))


@block("eps")
def _norm_silu_conv(p_norm, p_conv, x, *, eps: float):
    return conv(p_conv, jax.nn.silu(group_norm(p_norm, x, eps)))


def unet(params, latents, t, context, sz, addition=None):
    """latents (B, H, W, 4), t (B,), context (B, S, D) -> eps float32."""
    p = params["params"]
    base, mults = sz["base_channels"], sz["channel_mults"]
    levels = len(mults)

    def heads(ch):
        return sz["num_heads"] or max(1, ch // 64)

    def attn_at(lvl):
        return sz["attention_levels"][lvl] and sz["transformer_depth"][lvl]

    temb = _time_embedding(
        {k: p[k] for k in ("time_fc1", "time_fc2", "add_fc1", "add_fc2")
         if k in p}, t,
        addition if sz.get("addition_embed_dim") else None, base=base)
    x = _conv(p["conv_in"], latents, stride=1)
    skips = [x]
    for lvl in range(levels):
        ch = base * mults[lvl]
        for blk in range(sz["blocks_per_level"]):
            x = _res_block(p[f"down_{lvl}_res_{blk}"], x, temb)
            if attn_at(lvl):
                x = _spatial_transformer(
                    p[f"down_{lvl}_attn_{blk}"], x, context, heads(ch),
                    sz["transformer_depth"][lvl])
            skips.append(x)
        if lvl != levels - 1:
            x = _conv(p[f"down_{lvl}_downsample"], x, stride=2)
            skips.append(x)
    mid_ch = base * mults[-1]
    mid_depth = max([d for lvl, d in enumerate(sz["transformer_depth"])
                     if sz["attention_levels"][lvl]] or [1])
    x = _res_block(p["mid_res_0"], x, temb)
    x = _spatial_transformer(p["mid_attn"], x, context, heads(mid_ch),
                             mid_depth)
    x = _res_block(p["mid_res_1"], x, temb)
    for lvl in reversed(range(levels)):
        ch = base * mults[lvl]
        for blk in range(sz["blocks_per_level"] + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = _res_block(p[f"up_{lvl}_res_{blk}"], x, temb)
            if attn_at(lvl):
                x = _spatial_transformer(
                    p[f"up_{lvl}_attn_{blk}"], x, context, heads(ch),
                    sz["transformer_depth"][lvl])
        if lvl != 0:
            x = _upsample_conv(p[f"up_{lvl}_upsample"], x)
    assert not skips
    return _norm_silu_conv(p["norm_out"], p["conv_out"], x, eps=1e-5)


# -- VAE decoder --------------------------------------------------------------

@block()
def _vae_res(p, x):
    h = conv(p["conv1"], jax.nn.silu(group_norm(p["norm1"], x, 1e-6)))
    h = conv(p["conv2"], jax.nn.silu(group_norm(p["norm2"], h, 1e-6)))
    if "skip" in p:
        x = conv(p["skip"], x)
    return x + h


@block()
def _vae_attention(p, x):
    b, h, w, c = x.shape
    a = group_norm(p["norm"], x, 1e-6).reshape(b, h * w, c)
    return x + mha(p["attn"], a, 1).reshape(b, h, w, c)


def vae_decode(params, latents, sz):
    """scaled latents (B, h, w, 4) -> (B, 8h, 8w, 3) in [-1, 1]."""
    p = params["params"]
    mults = sz["channel_mults"]
    z = _conv(p["post_quant_conv"],
              latents.astype(F32) / sz["scaling_factor"], stride=1)
    x = _conv(p["conv_in"], z, stride=1)
    x = _vae_res(p["mid_res_0"], x)
    x = _vae_attention(p["mid_attn"], x)
    x = _vae_res(p["mid_res_1"], x)
    for lvl in reversed(range(len(mults))):
        for blk in range(sz["blocks_per_level"] + 1):
            x = _vae_res(p[f"up_{lvl}_res_{blk}"], x)
        if lvl != 0:
            x = _upsample_conv(p[f"up_{lvl}_upsample"], x)
    return _norm_silu_conv(p["norm_out"], p["conv_out"], x, eps=1e-6)


def latent_hw(sizes: dict) -> int:
    """Latent side: one 2x upsample per VAE level transition."""
    return sizes["sampler"]["image_size"] // 2 ** (
        len(sizes["vae"]["channel_mults"]) - 1)


def to_uint8(decoded):
    return jnp.round(jnp.clip(decoded * 0.5 + 0.5, 0.0, 1.0) * 255.0
                     ).astype(jnp.uint8)


# -- DDIM ---------------------------------------------------------------------

def ddim_schedule(num_steps: int, train_steps: int = 1000):
    """(timesteps, alpha_bar_t, alpha_bar_prev): SD's scaled-linear betas,
    "leading" spacing, descending."""
    betas = np.linspace(0.00085 ** 0.5, 0.012 ** 0.5, train_steps,
                        dtype=np.float64) ** 2
    ab = np.cumprod(1.0 - betas)
    ts = (np.arange(num_steps) * (train_steps // num_steps))[::-1]
    a_t = ab[ts].astype(np.float32)
    a_prev = np.concatenate([ab[ts[1:]], [1.0]]).astype(np.float32)
    return ts.astype(np.int32), a_t, a_prev


def ddim_step(x, eps, a_t, a_prev):
    x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
    return jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps


def ddim_trajectory(guided, x, sampler):
    """x_T (B, h, w, 4) -> x_0 under ``sizes.sampler``: deterministic DDIM,
    one guided forward a step. A configuration's file names a trajectory
    under ``image_trajectory`` by its sampler kind; ``guided(x, t)`` is the
    reference's UNet under classifier-free guidance, one forward at batch
    2B a call, and the calls a trajectory makes are what the FLOP count
    of an image multiplies a forward by (flops.image_flops)."""
    for t, a_t, a_prev in zip(*ddim_schedule(sampler["num_steps"])):
        x = ddim_step(x, guided(x, t), a_t, a_prev)
    return x


# -- GPT-2 --------------------------------------------------------------------

def gpt2_logits(params, ids, positions, sz):
    """ids, positions (B, S) -> logits (B, S, V); causal, no padding. The
    contract of a prompt LM's reference, which a configuration's file
    names under ``prompt_lm``: float32, every product's operands through
    ``_operand`` and its FLOPs through ``_add``, so that the fp8 control
    and the FLOP count come with it."""
    p = params["params"]
    seq = ids.shape[1]
    wte = p["wte"]["embedding"].astype(F32)
    x = wte[ids] + p["wpe"]["embedding"].astype(F32)[positions]
    causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
    for i in range(sz["num_layers"]):
        x = _pre_ln_layer(p[f"block_{i}"], x, causal,
                          heads=sz["num_heads"], act="gelu_tanh")
    h = layer_norm(p["ln_f"], x, 1e-5)
    _add(2.0 * math.prod(h.shape) * wte.shape[0])
    return jnp.matmul(_operand(h), _operand(wte).T, precision=HI)


# -- MiniLM -------------------------------------------------------------------

@block("heads")
def _post_ln_layer(b, x, attend, *, heads: int):
    x = layer_norm(b["ln1"], x + mha(b["attn"], x, heads, mask=attend),
                   1e-12)
    return layer_norm(b["ln2"], x + mlp(b["mlp"], x, gelu_erf), 1e-12)


def minilm_embed(params, ids, mask, sz):
    """ids, mask (B, S) -> unit-norm (B, D)."""
    p = params["params"]
    seq = ids.shape[1]
    x = (p["word_embeddings"]["embedding"].astype(F32)[ids]
         + p["position_embeddings"].astype(F32)[None, :seq])
    x = layer_norm(p["embed_ln"], x, 1e-12)
    attend = mask.astype(bool)[:, None, None, :]
    for i in range(sz["num_layers"]):
        x = _post_ln_layer(p[f"block_{i}"], x, attend,
                           heads=sz["num_heads"])
    w = mask.astype(F32)[..., None]
    pooled = (x * w).sum(axis=1) / (w.sum(axis=1) + 1e-9)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)


# -- tokenisation (the served path's byte fallback) ---------------------------

BYTE_EOS, BYTE_PAD = 257, 258


def byte_tokens(text: str):
    return list(text.encode("utf-8"))


def clip_ids(prompts, pad_len: int, vocab: int) -> np.ndarray:
    out = np.full((len(prompts), pad_len), BYTE_PAD, np.int32)
    for i, text in enumerate(prompts):
        toks = byte_tokens(text)[: pad_len - 1] + [BYTE_EOS]
        out[i, : len(toks)] = np.asarray(toks) % vocab
    return out


def minilm_ids(texts, seq_len: int, vocab: int):
    ids = np.full((len(texts), seq_len), BYTE_PAD, np.int32)
    mask = np.zeros((len(texts), seq_len), np.int32)
    for i, text in enumerate(texts):
        toks = byte_tokens(text)[:seq_len] or [BYTE_PAD]
        ids[i, : len(toks)] = np.asarray(toks, np.int32) % vocab
        mask[i, : len(toks)] = 1
    return ids, mask
