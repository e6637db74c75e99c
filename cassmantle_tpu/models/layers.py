"""Shared Flax building blocks for the model zoo.

TPU-first conventions used throughout:
- channels-last NHWC for all image tensors (XLA's native TPU conv layout);
- matmuls sized to MXU tiles (model dims are all multiples of 128 at
  production scale) and computed in the module dtype (bf16 on TPU) with
  fp32 softmax/normalization accumulations;
- attention goes through ops.attention so the Pallas flash kernel applies
  everywhere at once.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from cassmantle_tpu.ops import quant
from cassmantle_tpu.ops.attention import multi_head_attention
from cassmantle_tpu.ops.platform import on_tpu
from cassmantle_tpu.utils.logging import metrics


def nearest_upsample_2x(x: jax.Array) -> jax.Array:
    """2x nearest-neighbor upsample via broadcast+reshape (pure data
    movement XLA fuses well; jax.image.resize lowers to gathers, which
    the TPU executes much more slowly)."""
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, 2, w, 2, c))
    return x.reshape(b, h * 2, w * 2, c)


def timestep_embedding(
    timesteps: jax.Array, dim: int, max_period: float = 10000.0
) -> jax.Array:
    """Sinusoidal diffusion-timestep embedding, fp32. (B,) -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(
        -math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half
    )
    args = timesteps.astype(jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


def chunk_causal_mask(valid: jax.Array, index: jax.Array, length: int,
                      window: Optional[int] = None) -> jax.Array:
    """Causal mask for a multi-token decode chunk appended at ``index``.

    ``valid`` (B, max_len) is the caller's cache-validity mask (the same
    convention single-token ``decode_step`` takes, covering the prompt
    and every chunk position); query j of the chunk sits at cache
    position ``index + j`` and may additionally attend only positions
    ``<= index + j`` — the within-chunk causal triangle a single-step
    decode gets for free. With ``window`` the Mistral sliding band is
    enforced per query on top. Returns (B, 1, length, max_len), ready
    for the attention op's (B, H, Sq, Sk) broadcast.

    This is the one definition of the chunk-mask convention the
    speculative-decode verify forward (ops/decode.py) relies on: cache
    positions past the accepted prefix are *rolled back* simply by the
    next chunk's ``valid`` excluding them before the kv chunk-append
    overwrites them.
    """
    max_len = valid.shape[-1]
    cache_pos = jnp.arange(max_len)
    q_pos = index + jnp.arange(length)
    ok = cache_pos[None, :] <= q_pos[:, None]            # (length, max_len)
    if window is not None:
        ok = ok & (cache_pos[None, :] > q_pos[:, None] - window)
    return valid[:, None, None, :] & ok[None, None, :, :]


class QDense(nn.Module):
    """Param-twin of ``nn.Dense`` whose kernel leaf may be quantized.

    Declares kernel/bias with nn.Dense's exact names, shapes,
    initializers, and RNG fold path, so checkpoints, the init cache, and
    every converter see one tree. At apply time it branches on the leaf:

    - plain array → nn.Dense's exact computation (same promote_dtype +
      dot_general + bias reshape), bit-identical to the module it
      replaces — which is what lets the w8a8 kill switch revert
      bit-exactly by simply not quantizing at load;
    - :class:`~cassmantle_tpu.ops.quant.ActQTensor` (the W8A8 serving
      tree, ops/quant.py ``w8a8_tree_host``) → the int8 Pallas matmul
      with scales folded into the int32→fp epilogue
      (ops/quant_matmul.py ``w8a8_dense``), per-token activation scales
      when ``act_per_token`` (the LM decode path).

    Also the calibration tap: when a ``collect_act_stats`` pass is
    active (eager, parallel/calibrate.py) it records this site's input
    absmax under its flax path — zero traced ops otherwise.

    Used at every w8a8-capable site (attention projections, transformer
    MLPs, GEGLU); plain ``nn.Dense`` remains at quality-sensitive or
    tiny sites (time embeds, heads, proj_in/out), which the w8a8
    predicate whitelist (ops/quant.py) therefore must never select.
    """

    features: int
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    act_per_token: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,)) if self.use_bias else None
        if quant.act_stats_active():
            quant.note_act_stat("/".join(self.path), x)
        if isinstance(kernel, quant.ActQTensor):
            from cassmantle_tpu.ops.quant_matmul import w8a8_dense

            return w8a8_dense(x, kernel, bias,
                              out_dtype=self.dtype or x.dtype,
                              per_token=self.act_per_token)
        x, kernel, bias = promote_dtype(x, kernel, bias,
                                        dtype=self.dtype)
        y = jax.lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
        if bias is not None:
            y = y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
        return y


class MultiHeadAttention(nn.Module):
    """Projection + ops.attention + out-projection.

    Self-attention when ``context`` is None, cross-attention otherwise.
    """

    num_heads: int
    head_dim: Optional[int] = None
    out_dim: Optional[int] = None
    use_bias: bool = True
    # The published SD UNet (data/manifests/unet_*.json) is bias-free on
    # to_q/to_k/to_v but carries a bias on to_out.0 — the two knobs must
    # be independent or real weights can't load faithfully. None -> same
    # as use_bias.
    out_bias: Optional[bool] = None
    # Fuse q/k/v (self-attn) or k/v (cross-attn) into one projection
    # dot — full-forward sites only (UNet); incompatible with the
    # kv-cache decode path, which updates k/v separately.
    fused_qkv: bool = False
    # W8A8 activation-scale granularity for the projection QDenses:
    # per-token on the LM path (models/gpt2.py), per-tensor elsewhere.
    act_per_token: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context=None, mask=None, kv_cache=None,
                 return_kv: bool = False, causal: bool = False):
        """Attention with optional KV-cache decode.

        - Full mode: returns out, or (out, (k, v)) if ``return_kv`` (used by
          prefill to seed a decode cache).
        - Decode mode (``kv_cache=(cache_k, cache_v, index)``): writes this
          call's k/v into the cache at ``index`` along the sequence axis and
          attends over the whole cache; the caller supplies ``mask`` marking
          valid cache positions. Returns (out, (new_k, new_v)). The write
          is a chunk-append: ``x`` may carry S > 1 positions (speculative
          verify, ops/decode.py) and the S-wide k/v slab lands at
          ``index..index+S-1`` in one ``dynamic_update_slice`` — the caller
          then owes a per-query causal mask (``chunk_causal_mask``), since
          with S > 1 a plain validity mask would let early chunk positions
          see later ones.
        """
        features = x.shape[-1]
        head_dim = self.head_dim or features // self.num_heads
        inner = self.num_heads * head_dim
        out_dim = self.out_dim or features
        ctx = x if context is None else context

        dense = lambda name, mult=1: QDense(  # noqa: E731
            mult * inner, use_bias=self.use_bias, dtype=self.dtype,
            name=name, act_per_token=self.act_per_token
        )
        if self.fused_qkv:
            # One projection dot instead of three: the input activation
            # streams from HBM once (the q/k/v kernels read the same x),
            # and the MXU sees one (M, C)x(C, 3C) matmul whose wider N
            # pads the 128-lane tile boundary once, not three times —
            # the optimization the UNet cost table indicates
            # (docs/PERF_NOTES.md): projection dots are ~17% of UNet
            # FLOPs across 32 attention sites. Checkpoint layout is
            # unchanged — the converters concatenate the published
            # to_q/to_k/to_v tensors at load (weights.py dense_fused).
            assert kv_cache is None and not return_kv, (
                "fused_qkv is a full-forward optimization; decode "
                "caching uses the separate-projection layout")
            if context is None:
                q, k, v = jnp.split(dense("qkv", 3)(x), 3, axis=-1)
            else:
                q = dense("q")(x)
                k, v = jnp.split(dense("kv", 2)(ctx), 2, axis=-1)
        else:
            q = dense("q")(x)
            k = dense("k")(ctx)
            v = dense("v")(ctx)

        split = lambda t: t.reshape(  # noqa: E731
            t.shape[:-1] + (self.num_heads, head_dim)
        )
        q, k, v = split(q), split(k), split(v)

        kv_out = None
        if kv_cache is not None:
            cache_k, cache_v, index = kv_cache
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k.astype(cache_k.dtype), index, axis=-3
            )
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v.astype(cache_v.dtype), index, axis=-3
            )
            k, v = cache_k, cache_v
            kv_out = (cache_k, cache_v)
        elif return_kv:
            kv_out = (k, v)

        out = multi_head_attention(q, k, v, mask=mask, causal=causal)
        out = out.reshape(out.shape[:-2] + (inner,))
        out = QDense(
            out_dim,
            use_bias=(self.use_bias if self.out_bias is None
                      else self.out_bias),
            dtype=self.dtype, name="out",
            act_per_token=self.act_per_token,
        )(out)
        if kv_out is not None:
            return out, kv_out
        return out


class TransformerMLP(nn.Module):
    """Standard 2-layer MLP with configurable activation."""

    intermediate: int
    activation: Callable = nn.gelu
    act_per_token: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        h = QDense(self.intermediate, dtype=self.dtype, name="fc1",
                   act_per_token=self.act_per_token)(x)
        h = self.activation(h)
        return QDense(features, dtype=self.dtype, name="fc2",
                      act_per_token=self.act_per_token)(h)


class GEGLU(nn.Module):
    """Gated-GELU feed-forward used by SD's transformer blocks."""

    intermediate: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        h = QDense(self.intermediate * 2, dtype=self.dtype, name="proj")(x)
        h, gate = jnp.split(h, 2, axis=-1)
        h = h * nn.gelu(gate)
        return QDense(features, dtype=self.dtype, name="out")(h)


def quick_gelu(x):
    """OpenAI CLIP's activation: x * sigmoid(1.702 x)."""
    return x * jax.nn.sigmoid(1.702 * x)


def exact_gelu(x):
    """Erf-based GELU — the published BERT and OpenCLIP-bigG activation
    (jax.nn.gelu defaults to the tanh approximation, which is GPT-2's
    gelu_new but NOT what those checkpoints were trained with)."""
    return jax.nn.gelu(x, approximate=False)


class LayerNorm32(nn.Module):
    """LayerNorm with fp32 statistics applied in the activation dtype.

    ``nn.LayerNorm(dtype=fp32)`` on a bf16 tensor casts the whole tensor
    up and back, doubling elementwise HBM traffic per norm — with 3 norms
    per transformer block this is real money on the UNet's token tensors.
    Stats (mean/var) reduce in fp32; the affine applies as one FMA in the
    input dtype. Param layout matches nn.LayerNorm (scale/bias (C,)).
    """

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True) \
            - jnp.square(mean)
        inv = jax.lax.rsqrt(var + self.epsilon)
        scale32 = scale.astype(jnp.float32)
        a = (inv * scale32).astype(x.dtype)
        b = (bias.astype(jnp.float32) - (mean * inv) * scale32
             ).astype(x.dtype)
        return x * a + b


class _GroupNormCore(nn.Module):
    """GroupNorm with fp32 statistics and activation-dtype application.

    The straightforward ``cast-to-fp32 -> nn.GroupNorm -> cast-back``
    doubles elementwise HBM traffic on the UNet's biggest tensors and the
    cast boundaries block XLA fusion; at SD1.5-512 the UNet step is
    memory-bound (23 GB accessed/step), so this matters. Here only the
    mean/var *reductions* run in fp32; the normalize folds into one
    multiply-add applied in the input dtype:

        out = x * a + b,  a = inv*scale,  b = bias - mean*inv*scale

    with ``a``/``b`` computed in fp32 at (B, G|C) size — numerically the
    sensitive part — then cast once. Param layout matches nn.GroupNorm
    (scale/bias of shape (C,)) so checkpoints load unchanged.
    """

    num_groups: int
    epsilon: float

    @nn.compact
    def __call__(self, x, return_affine: bool = False):
        """Normalize ``x`` — or, with ``return_affine``, return the
        per-(batch, channel) fp32 affine ``(a, b)`` with
        ``out = x * a + b`` instead of applying it. The affine form
        feeds the fused GroupNorm+SiLU+conv3x3 Pallas path
        (ops/fused_conv.py): the sensitive fp32 statistics stay here,
        the cheap FMA moves into the kernel."""
        c = x.shape[-1]
        g = self.num_groups
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))

        spatial = x.shape[1:-1]
        # Reduce in the tensor's native channels-last layout: per-channel
        # sum and sum-of-squares over the spatial axis — the minor (lane)
        # dimension stays C (a few hundred, tiles well), not C/G (10-80,
        # which pads each 128-lane vector op mostly empty). The tiny
        # (B, C) moments then fold into (B, G) group stats exactly
        # (groups are equal-sized, so the group mean is the mean of its
        # channel means).
        x2 = x.reshape(x.shape[0], -1, c).astype(jnp.float32)
        n_spatial = x2.shape[1]
        sum_c = jnp.sum(x2, axis=1)                          # (B, C)
        sumsq_c = jnp.sum(jnp.square(x2), axis=1)            # (B, C)
        n_group = n_spatial * (c // g)
        mean = jnp.sum(sum_c.reshape(-1, g, c // g), axis=-1) / n_group
        ex2 = jnp.sum(sumsq_c.reshape(-1, g, c // g), axis=-1) / n_group
        var = ex2 - jnp.square(mean)                         # (B, G)
        inv = jax.lax.rsqrt(var + self.epsilon)              # (B, G)

        # per-(batch, channel) affine in fp32, one cast, one fused FMA
        inv_c = jnp.repeat(inv, c // g, axis=-1)             # (B, C)
        mean_c = jnp.repeat(mean, c // g, axis=-1)
        a = inv_c * scale.astype(jnp.float32)[None, :]
        b = bias.astype(jnp.float32)[None, :] - mean_c * a
        if return_affine:
            return a, b                                      # (B, C) fp32
        shape = (x.shape[0],) + (1,) * len(spatial) + (c,)
        a = a.reshape(shape).astype(x.dtype)
        b = b.reshape(shape).astype(x.dtype)
        return x * a + b


class GroupNorm32(nn.Module):
    """GroupNorm with fp32 statistics (diffusion UNets are numerically
    sensitive here) applied in the activation dtype — see _GroupNormCore.
    Nests the core under ``norm`` to keep the nn.GroupNorm param paths."""

    num_groups: int = 32
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x, return_affine: bool = False):
        return _GroupNormCore(
            num_groups=self.num_groups, epsilon=self.epsilon, name="norm"
        )(x, return_affine=return_affine)


def fused_gn_silu_conv3x3(x, out_channels: int, dtype,
                          norm_name: str, conv_name: str,
                          epsilon: float = 1e-5, pad_to: int = 0):
    """The fused-conv dispatch glue shared by the UNet and VAE
    ResBlocks: fp32 GroupNorm statistics here (``return_affine``),
    param declaration via :class:`Conv3x3Params` (nn.Conv's exact
    tree), then the one-pass GN-affine+SiLU+conv3x3 Pallas kernel
    (ops/fused_conv.py). Must be called inside the parent module's
    ``@nn.compact`` ``__call__`` — the explicit submodule names keep
    the param paths identical to the unfused ``GroupNorm32``/
    ``nn.Conv`` layout. ``epsilon`` is the GroupNorm epsilon (UNet
    resblocks 1e-5, VAE 1e-6); ``pad_to`` the MXU channel padding."""
    from cassmantle_tpu.ops.fused_conv import gn_silu_conv3x3

    a, b = GroupNorm32(epsilon=epsilon, name=norm_name)(
        x, return_affine=True)
    act_stat_of = None
    if quant.act_stats_active():
        # calibration probe: the conv's actual input is silu(x*a+b),
        # which only the kernel normally materializes — reproduce it
        # lazily here (eager calibration pass only; never traced)
        act_stat_of = lambda: jax.nn.silu(  # noqa: E731
            x * a[:, None, None, :].astype(x.dtype)
            + b[:, None, None, :].astype(x.dtype))
    kernel, bias = Conv3x3Params(out_channels, name=conv_name)(
        x.shape[-1], act_stat_of=act_stat_of)
    if isinstance(kernel, quant.ActQTensor):
        from cassmantle_tpu.ops.quant_matmul import gn_silu_conv3x3_w8a8

        return gn_silu_conv3x3_w8a8(x, a, b, kernel, bias,
                                    pad_to=pad_to)
    return gn_silu_conv3x3(x, a, b, kernel.astype(dtype),
                           bias.astype(dtype), pad_to=pad_to)


class Conv3x3Params(nn.Module):
    """Parameter twin of ``nn.Conv(features, (3, 3))`` that DECLARES the
    kernel/bias without running the convolution.

    The fused GroupNorm+SiLU+conv path (ops/fused_conv.py) computes the
    conv inside a Pallas kernel, but the param tree must stay identical
    to the unfused ``nn.Conv`` layout — same names ("kernel"/"bias"),
    same HWIO shape, same initializers, same RNG fold path — so
    checkpoints (models/weights.py Converter.conv), the init cache, and
    the fused/unfused A/B all share one tree. Returns the raw params;
    dtype casting happens at the use site like ``nn.Conv(dtype=...)``.
    """

    features: int

    @nn.compact
    def __call__(self, in_features: int, act_stat_of=None):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (3, 3, in_features, self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        if act_stat_of is not None and quant.act_stats_active():
            # w8a8 calibration tap (ops/quant.py): records this site's
            # conv-input absmax under the module path — the same key
            # the w8a8 tree transform looks up
            quant.note_act_stat("/".join(self.path), act_stat_of())
        return kernel, bias


#: Batch rows from which the TPU compiler runs a 3x3 convolution on
#: (B, H, W, C) as it stands, 8 rows to the sublanes. Under it, it
#: rewrites the convolution space-to-batch: W cut into 8 chunks an
#: image, each W/8 + 1 columns wide, the extra column computed and
#: masked off (x1.5 the products at 16x16, x1.25 at 32x32, x1.125 at
#: 64x64); at 8x8 it keeps B rows in the 8 sublanes. PERF.md section 7
#: item 6 has the recipe to read the compiled program.
DIRECT_FORM_BATCH = 8


def conv3x3_form(tpu: bool, batch: int, height: int, width: int) -> str:
    """Which operand shape a 3x3, stride-1, SAME convolution is handed
    to the compiler in: a function of the platform and the call's static
    shape, and of nothing else. ``"rows_folded"``: H in the batch
    (:func:`conv3x3_rows_folded`), so the compiler sees B·H rows and
    takes its direct form. ``"xla_2d"``: ``nn.Conv``'s convolution: off
    the TPU; from ``DIRECT_FORM_BATCH`` rows up, where the 2-D form is
    the direct one already; and from 64 columns up, where the padded
    column is a ninth of the work or less and costs less than three
    passes over that much activation (timed on the chip alone and
    between its neighbours: PERF.md section 5, PR 30)."""
    if not tpu or batch >= DIRECT_FORM_BATCH:
        return "xla_2d"
    folds = 8 <= width <= 32 and batch * height >= DIRECT_FORM_BATCH
    return "rows_folded" if folds else "xla_2d"


def conv3x3_rows_folded(x: jax.Array, kernel: jax.Array,
                        axis: int = 1) -> jax.Array:
    """3x3, stride-1, SAME convolution of ``x`` (B, H, W, C) with
    ``kernel`` (3, 3, C, F) as 1-D convolutions along the other spatial
    axis with ``axis`` (1: H, 2: W) folded into the batch: B·H (or B·W)
    rows, so the TPU compiler takes its direct form and multiplies
    exactly B·H·W output positions. One convolution a tap of the folded
    axis over that tap's rows of the padded input, summed in float32:
    the same products as the 2-D form in another order of summation,
    and no stacked copy of the input."""
    if axis == 2:
        x, kernel = jnp.swapaxes(x, 1, 2), jnp.swapaxes(kernel, 0, 1)
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0), (0, 0)))
    y = sum(jax.lax.conv_general_dilated(
        xp[:, dh:dh + h].reshape(b * h, w, c), kernel[dh], (1,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.float32) for dh in range(3))
    y = y.astype(x.dtype).reshape(b, h, w, kernel.shape[-1])
    return jnp.swapaxes(y, 1, 2) if axis == 2 else y


def conv3x3_same(x: jax.Array, features: int, dtype, name: str):
    """``nn.Conv(features, (3, 3), padding=1, dtype=dtype, name=name)``
    at a site whose form :func:`conv3x3_form` chooses; counted under
    ``conv.dispatch{form=...}`` once a site a trace, as
    ``attention.dispatch`` is. Must be called inside the parent module's
    ``@nn.compact`` ``__call__``; the param tree is ``nn.Conv``'s either
    way (:class:`Conv3x3Params`)."""
    form = conv3x3_form(on_tpu(), *x.shape[:3])
    metrics.inc("conv.dispatch", labels={"form": form})
    if form == "xla_2d":
        return nn.Conv(features, (3, 3), padding=1, dtype=dtype,
                       name=name)(x)
    kernel, bias = Conv3x3Params(features, name=name)(x.shape[-1])
    x, kernel, bias = promote_dtype(x, kernel, bias, dtype=dtype)
    with jax.named_scope(name):
        return conv3x3_rows_folded(x, kernel) + bias
