"""Typed configuration for the whole framework.

The reference scatters its tunables across constructor kwargs and hardcoded
constants (server.py:15-24, backend.py:20-26, 47-50, 319; SURVEY.md §5.6).
Here everything lives in one tree of frozen dataclasses so a single
``FrameworkConfig`` names the model zoo, samplers, parallelism mesh, serving
queue, and game constants, and can be overridden per-test or per-deployment.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from cassmantle_tpu.utils.logging import DEFAULT_BUCKETS_S as _DEFAULT_BUCKETS_S


@dataclasses.dataclass(frozen=True)
class ClipTextConfig:
    """SD1.5's text tower (OpenAI CLIP ViT-L/14 text model) dimensions."""

    vocab_size: int = 49408
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_positions: int = 77
    # ViT-L/14 trained with quick_gelu; OpenCLIP bigG with exact gelu —
    # the published hidden_act of each checkpoint.
    hidden_act: str = "quick_gelu"
    # SDXL adds a second, bigger text tower (OpenCLIP ViT-bigG); same module,
    # different dims.
    @staticmethod
    def sdxl_big() -> "ClipTextConfig":
        return ClipTextConfig(
            vocab_size=49408,
            hidden_size=1280,
            intermediate_size=5120,
            num_layers=32,
            num_heads=20,
            max_positions=77,
            hidden_act="gelu",
        )


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    """Diffusion UNet. Defaults = SD1.5; ``sdxl()`` = SDXL-base geometry."""

    sample_channels: int = 4
    base_channels: int = 320
    channel_mults: Tuple[int, ...] = (1, 2, 4, 4)
    # Per-level: whether the level's resnet blocks carry transformer
    # (self+cross attention) blocks.
    attention_levels: Tuple[bool, ...] = (True, True, True, False)
    # Transformer depth per level (SDXL uses 2/10 at its two attn levels).
    transformer_depth: Tuple[int, ...] = (1, 1, 1, 1)
    blocks_per_level: int = 2
    num_heads: int = 8
    context_dim: int = 768
    time_embed_dim: int = 1280
    # SDXL micro-conditioning (added time-embedding channels); 0 disables.
    addition_embed_dim: int = 0
    dtype: str = "bfloat16"
    # Fused GroupNorm+SiLU+conv3x3 Pallas path for the ResBlock hot loop
    # (ops/fused_conv.py): the normalized/activated tensor stays in VMEM
    # instead of round-tripping HBM before every 3x3 conv (~45% of UNet
    # FLOPs are these convs — docs/PERF_NOTES.md). Param tree, checkpoint
    # layout, and outputs are unchanged (parity-pinned,
    # tests/test_fused_conv.py); A/B measured by the `sd15_fusedconv`
    # bench entry. CASSMANTLE_NO_FUSED_CONV=1 is the runtime kill switch.
    fused_conv: bool = False
    # With fused_conv: round conv channel dims up to this multiple so
    # MXU tiles fill (SD1.5's 320/960 levels are 2.5/7.5 lanes-tiles
    # wide; 128 trades ~3.4% UNet FLOPs for full tile occupancy —
    # docs/PERF_NOTES.md). 0 disables padding.
    conv_pad_to: int = 0

    def arch(self) -> "UNetConfig":
        """This config with execution-strategy flags cleared — the
        ARCHITECTURE identity (param tree + numerics), used for param
        cache keys and ``share_params_with`` compatibility: fused_conv /
        conv_pad_to change how convs execute, never what the tree is."""
        return dataclasses.replace(self, fused_conv=False, conv_pad_to=0)

    @staticmethod
    def sdxl() -> "UNetConfig":
        return UNetConfig(
            base_channels=320,
            channel_mults=(1, 2, 4),
            attention_levels=(False, True, True),
            transformer_depth=(0, 2, 10),
            num_heads=None,  # SDXL uses fixed head_dim 64 -> heads = ch // 64
            context_dim=2048,
            time_embed_dim=1280,
            addition_embed_dim=2816,
        )


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    """SD autoencoder (decoder is the serving hot path)."""

    latent_channels: int = 4
    base_channels: int = 128
    channel_mults: Tuple[int, ...] = (1, 2, 4, 4)
    blocks_per_level: int = 2
    scaling_factor: float = 0.18215  # SD1.5; SDXL uses 0.13025
    # bf16 compute (fp32 GroupNorm statistics via GroupNorm32): the decode
    # is a one-shot memory-bound pass; bf16 halves its HBM traffic.
    dtype: str = "bfloat16"
    # Fused GroupNorm+SiLU+conv3x3 Pallas path for the VAE ResBlock
    # pairs (ops/fused_conv.py — the same kernel, return_affine +
    # Conv3x3Params trick, and CASSMANTLE_NO_FUSED_CONV kill switch the
    # UNet ResBlocks use): the cost table prices VAE decode at 10.47 TF
    # per SDXL image and, like the UNet's, each of its norm→act→conv
    # sequences otherwise round-trips the level activation through HBM.
    # Param tree/checkpoint layout unchanged (parity-pinned,
    # tests/test_fused_conv.py). VAE channels (128/256/512) are already
    # 128-lane aligned, so no conv_pad_to analogue is needed.
    fused_conv: bool = False

    def arch(self) -> "VAEConfig":
        """This config with execution-strategy flags cleared — the
        ARCHITECTURE identity (param tree + numerics), mirroring
        UNetConfig.arch(): ``fused_conv`` changes how the decode
        executes, never what the tree is. Used for param cache keys and
        ``share_params_with`` compatibility."""
        return dataclasses.replace(self, fused_conv=False)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    """GPT-2-small for prompt/hint generation (greedy decode)."""

    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_positions: int = 1024
    dtype: str = "bfloat16"


@dataclasses.dataclass(frozen=True)
class MistralConfig:
    """Mistral-7B-Instruct-class causal LM — the reference's actual prompt
    model (backend.py:25 calls the hosted Mistral-7B-Instruct-v0.1 endpoint).

    Architecture: RoPE positions, grouped-query attention (8 KV heads),
    sliding-window attention, RMSNorm, SwiGLU MLP. Defaults are the 7B
    geometry; ``tiny()`` is the CPU-test variant.
    """

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_positions: int = 4096
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    @staticmethod
    def tiny() -> "MistralConfig":
        return MistralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_positions=64, sliding_window=16, dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Qwen3-Next-class causal LM (models/qwen3_next.py): three Gated
    DeltaNet (linear attention) layers to one gated full-attention layer,
    a sparse expert block in every layer. Field names are the published
    ``config.json``'s; defaults are Qwen3-Next-80B-A3B-Instruct's widths.

    ``experts_held`` / ``first_expert`` say which of the ``num_experts``
    routed experts this chip holds (expert parallelism: the router keeps
    its published width and top-k, the layer computes its own experts'
    part). ``vocab_size`` is the rows of the vocabulary held here.
    ``tiny()`` is the CPU-test variant.
    """

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: int = 512
    first_expert: int = 0
    dtype: str = "bfloat16"

    @property
    def max_positions(self) -> int:
        """The name the serving layer knows the position limit by."""
        return self.max_position_embeddings

    def is_full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @staticmethod
    def tiny() -> "Qwen3NextConfig":
        """One period, 8 experts top-2, all held."""
        return Qwen3NextConfig(
            vocab_size=300, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
            shared_expert_intermediate_size=16,
            max_position_embeddings=128, experts_held=8, dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """LFM2-MoE-class causal LM (models/lfm2_moe.py): gated short
    convolutions among grouped-query attention layers (``layer_types``),
    a dense SwiGLU MLP in the first ``num_dense_layers`` layers and
    sigmoid-scored sparse experts after, tied embeddings. Field names are
    the published ``config.json``'s; defaults are LFM2-24B-A2B's widths
    (``rope_theta`` is its ``rope_parameters.rope_theta``).

    ``experts_held`` / ``first_expert`` as in ``Qwen3NextConfig``.
    ``tiny()`` is the CPU-test variant.
    """

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (
        "conv", "conv", "full_attention", "conv") * 10
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    experts_held: int = 64
    first_expert: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.layer_types) == self.num_hidden_layers, (
            self.layer_types, self.num_hidden_layers)
        assert not self.conv_bias, "a convolution bias is not served"

    @property
    def max_positions(self) -> int:
        """The name the serving layer knows the position limit by."""
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny() -> "Lfm2MoeConfig":
        """A dense leading layer and one period, 8 experts top-2, all
        held."""
        return Lfm2MoeConfig(
            vocab_size=300, hidden_size=32, num_hidden_layers=5,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            num_dense_layers=1, intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=16,
            max_position_embeddings=128, experts_held=8, dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Ouro-class looped causal LM (models/ouro.py): one stack of
    ``num_hidden_layers`` dense layers (multi-head attention, SwiGLU MLP,
    four RMSNorms a layer) run ``total_ut_steps`` times a token over one
    set of weights, the final norm between passes, an untied head. Field
    names are the published ``config.json``'s; defaults are Ouro-2.6B's.

    ``early_exit_threshold`` 1 runs every token through every pass: the
    exit gate is held but decides nothing, and no other value is served
    (rows that leave the loop at different passes need a scheduler that
    serves them). ``tiny()`` is the CPU-test variant, four passes kept.
    """

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    max_position_embeddings: int = 65536
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert self.num_key_value_heads == self.num_attention_heads, (
            "grouped key/value heads are not served for this family")
        assert self.early_exit_threshold == 1.0, (
            "early exit is not served: every token runs every pass")
        assert not self.tie_word_embeddings, "a tied head is not served"

    @property
    def max_positions(self) -> int:
        """The name the serving layer knows the position limit by."""
        return self.max_position_embeddings

    @staticmethod
    def tiny() -> "OuroConfig":
        """Two layers of 4 heads, four passes."""
        return OuroConfig(
            vocab_size=300, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, head_dim=8,
            max_position_embeddings=128, dtype="float32")


@dataclasses.dataclass(frozen=True)
class MiniLMConfig:
    """all-MiniLM-L6-v2-class sentence encoder for guess scoring."""

    vocab_size: int = 30522
    hidden_size: int = 384
    intermediate_size: int = 1536
    num_layers: int = 6
    num_heads: int = 12
    max_positions: int = 512
    dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class ModelZooConfig:
    clip_text: ClipTextConfig = dataclasses.field(default_factory=ClipTextConfig)
    # SDXL's second text tower (OpenCLIP bigG); None for SD1.5.
    clip_text_2: Optional[ClipTextConfig] = None
    unet: UNetConfig = dataclasses.field(default_factory=UNetConfig)
    vae: VAEConfig = dataclasses.field(default_factory=VAEConfig)
    gpt2: GPT2Config = dataclasses.field(default_factory=GPT2Config)
    # Optional Mistral-7B-class prompt LM; when set, the serving layer
    # generates story episodes with it instead of GPT-2 (the reference's
    # actual LLM family, backend.py:25).
    mistral: Optional[MistralConfig] = None
    # Optional Qwen3-Next-class prompt LM (linear + full attention layers,
    # sparse experts); when set it is the prompt LM. Weights-only int8,
    # W8A8 and speculative decode are refused for it (serving/pipeline.py).
    qwen3_next: Optional[Qwen3NextConfig] = None
    # Optional LFM2-MoE-class prompt LM (short-convolution + grouped-query
    # attention layers, a dense leading layer, sigmoid-routed experts);
    # when set it is the prompt LM, with the same refusals as qwen3_next.
    lfm2_moe: Optional[Lfm2MoeConfig] = None
    # Optional Ouro-class looped prompt LM (one dense stack run several
    # times a token); when set it is the prompt LM, with the same
    # refusals as qwen3_next.
    ouro: Optional[OuroConfig] = None
    minilm: MiniLMConfig = dataclasses.field(default_factory=MiniLMConfig)
    # Directory holding safetensors checkpoints; None -> deterministic
    # random-init (fixed PRNG) so the full pipeline runs without artifacts.
    weights_dir: Optional[str] = None
    # Storage dtype for UNet/text-model params ("bfloat16" halves HBM
    # weight traffic per denoise step — the TPU-standard serving layout;
    # norm layers still compute fp32 internally). "float32" to disable.
    param_dtype: str = "bfloat16"
    # Weights-only int8 for the prompt LM's matmul kernels (ops/quant.py):
    # halves weight HBM footprint and streaming bytes — what makes the
    # Mistral-7B-class prompt model (the reference's LLM family) fit and
    # decode fast on a single 16 GB chip. Embeddings/norms stay bf16.
    lm_int8: bool = False
    # Weights-only int8 for the diffusion UNet's large matmul/conv
    # kernels: halves denoise-loop weight streaming (the per-step HBM
    # read of ~1.7 GB bf16 UNet params). Dequantization happens inside
    # the jit (per-output-channel scales, ops/quant.py) so the MXU still
    # sees bf16 tiles. Quality must be re-gated via tools/clip_report.py
    # when enabled.
    unet_int8: bool = False
    # Full W8A8 for the diffusion UNet (ISSUE 20): selected kernel
    # leaves become ActQTensors (ops/quant.py w8a8_tree_host) and the
    # attention/MLP/fused-conv sites dispatch the int8 Pallas kernels
    # (ops/quant_matmul.py) — int8 weights AND activations, scales
    # folded into the int32→fp epilogue. Requires unet.fused_conv for
    # the conv sites; mutually exclusive with unet_int8. Static
    # activation scales load from the calibration artifact
    # (parallel/calibrate.py, data/act_scales.json) when its signature
    # matches, dynamic absmax otherwise. CASSMANTLE_NO_W8A8 kill switch
    # reverts bit-exactly at pipeline build (never quantizes).
    unet_w8a8: bool = False
    # Full W8A8 for the prompt LM with PER-TOKEN activation scales
    # (models/gpt2.py); mutually exclusive with lm_int8. Same artifact,
    # kill switch, and epilogue scheme as unet_w8a8.
    lm_w8a8: bool = False
    # Minimum weight-element count for a site to quantize under w8a8
    # (ops/quant.py w8a8_default_predicate): small kernels aren't worth
    # the quantize/dequantize round-trip. Tests drop it to 0 so reduced
    # test-geometry models still exercise the int8 kernel path.
    w8a8_min_size: int = 1 << 16


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """Image sampler + greedy text decode settings.

    ``kind``: "ddim" (default), "euler", or "dpmpp_2m" (ops/samplers.py;
    DPM++(2M) reaches DDIM-50 quality in ~20-25 steps — the fast-serving
    configuration).
    """

    kind: str = "ddim"
    num_steps: int = 50
    guidance_scale: float = 7.5
    eta: float = 0.0
    image_size: int = 512
    # CFG negative conditioning — the reference passes this to its
    # hosted diffusion call (backend.py:284); "" disables (plain
    # unconditional arm). Tokenized host-side per batch, so changing it
    # never recompiles.
    negative_prompt: str = "blurry, distorted, fake, abstract, negative"
    # Few-step consistency serving (ops/samplers.py::consistency_sample;
    # ISSUE 15): sample with a consistency/LCM-distilled student —
    # ``num_steps`` (1-8) direct x0 predictions through the boundary
    # c_skip/c_out parameterization instead of a long ODE solve. The
    # student shares the teacher's UNetConfig arch and checkpoint
    # layout (parallel/train.py::ConsistencyDistillTrainer), so it
    # loads through the unchanged utils/checkpoint.py / share_compatible
    # machinery. Composes with the staged continuous-batching path
    # (a consistency slot stepper) and the execution-level levers
    # (fused_conv, int8). CASSMANTLE_NO_CONSISTENCY=1 is the runtime
    # kill switch: it reverts serving bit-exactly to the TEACHER path —
    # the plain ``kind`` sampler at ``consistency_teacher_steps``.
    # Quality gates via eval/clip_parity.py::consistency_quality_report.
    consistency: bool = False
    # The deployed UNet checkpoint IS a consistency-distilled student,
    # even though serving defaults to the teacher schedule — the signal
    # that lets the brownout ladder's few-step tier step INTO
    # consistency sampling under SLO burn (serving/overload.py). Stock
    # (undistilled) checkpoints MUST leave this False: 4-step
    # boundary-parameterized sampling through an eps-net that was never
    # distilled produces near-noise, so without this flag the ladder
    # skips the few-step delta and falls through to the resolution tier
    # instead. ``consistency=True`` implies a student checkpoint and
    # does not need this flag.
    consistency_available: bool = False
    # The teacher schedule the kill switch reverts to — and the solver
    # discretization the distillation trainer integrates.
    consistency_teacher_steps: int = 50
    # Text decode (reference decodes 32-96 new tokens, backend.py:250-255;
    # its hosted call samples greedily — temperature 0 is reference
    # parity, >0 enables top-k Gumbel sampling for story variety).
    min_new_tokens: int = 32
    max_new_tokens: int = 96
    prompt_pad_len: int = 77
    text_temperature: float = 0.0
    text_top_k: int = 40


@dataclasses.dataclass(frozen=True)
class SpecDecodeConfig:
    """Speculative decoding for the prompt-LM serving decode
    (ops/decode.py::speculative_decode): a draft proposes ``gamma``
    tokens and the target scores all gamma+1 positions in one
    ``decode_chunk`` forward, amortizing one full weight read over the
    chunk — the step-count lever for the memory-bound greedy loop
    (docs/PERF_NOTES.md "LM decode accounting").

    Engages only when ``sampler.text_temperature == 0`` (greedy — the
    reference's decode mode), where acceptance is exact argmax match and
    output is bit-identical to the plain greedy scan
    (tests/test_spec_decode.py). ``CASSMANTLE_NO_SPEC_DECODE=1`` is the
    runtime kill switch (docs/DEPLOY.md §6)."""

    # "off" | "ngram" (self-drafting prompt lookup, zero extra HBM) |
    # "draft_model" (a smaller zoo LM with its own prefill/decode cache)
    mode: str = "off"
    # drafted tokens per verify chunk: each chunk commits 1..gamma+1
    # tokens for one target forward of width gamma+1
    gamma: int = 4
    # suffix length for the "ngram" prompt-lookup draft
    ngram: int = 3
    # the "draft_model" draft: a smaller GPT-2-family config sharing the
    # target's tokenizer/vocab (gpt2-small drafting for gpt2-large; its
    # checkpoint loads from <weights_dir>/gpt2_draft.safetensors). When
    # it EQUALS the target's gpt2 config the target's own params are
    # reused (the self-draft degenerate, useful in tests).
    draft_model: Optional[GPT2Config] = None


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axes follow the scaling-book convention:

    - ``dp``: data parallel (batch sharding) — rides ICI within a slice.
    - ``tp``: tensor parallel (attention heads / MLP columns).
    - ``sp``: sequence/context parallel (ring attention over image tokens).
    - ``pp``: pipeline parallel (layer stages; activations ppermute
      stage-to-stage, parallel/pipeline.py).
    - ``ep``: expert parallel (MoE experts sharded; token dispatch
      all-to-all inserted by GSPMD, models/moe.py).
    Sizes of -1 mean "fill with remaining devices".
    """

    dp: int = -1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    # Axis names, in mesh order.
    axis_names: Tuple[str, ...] = ("dp", "pp", "tp", "sp", "ep")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching queue bounds (fixed shapes; no recompile storms)."""

    image_batch_sizes: Tuple[int, ...] = (1, 4, 8)
    # 2048 covers guesses+answers of a full 1k-pair scoring in ONE device
    # dispatch (each dispatch pays the host<->device round trip).
    score_batch_sizes: Tuple[int, ...] = (8, 64, 256, 1024, 2048)
    max_queue_delay_ms: float = 25.0
    max_pending: int = 4096
    # -- supervision (serving/queue.py, serving/supervisor.py) ------------
    # Per-request deadline: a submitted item whose batch never resolves
    # (wedged XLA call) fails its future instead of hanging the caller.
    # None disables. Sized to survive a legitimate cold-cache first
    # compile (minutes) — it bounds hangs, it is NOT a latency SLO;
    # latency-sensitive callers pass a tighter submit(deadline_s=...).
    submit_deadline_s: Optional[float] = 300.0
    # Dispatch watchdog: a handler exceeding this has wedged the dispatch
    # thread — the batch fails, the thread is disowned + replaced, the
    # supervisor flips degraded. Generous: first-dispatch XLA compiles
    # legitimately take minutes on cold caches. None disables.
    dispatch_hang_s: Optional[float] = 300.0
    # Tightened admission bound while the supervisor reports degraded —
    # a sick device gets a short queue, not max_pending of doomed work.
    degraded_max_pending: int = 256
    # -- overload control plane (serving/overload.py; ISSUE 13) ------------
    # Adaptive (AIMD) admission per queue: the effective pending bound
    # tracks measured queue-wait + batch-service latency against this
    # target, between admission_min_pending and max_pending. Rejections
    # carry a COMPUTED Retry-After (predicted wait = depth × observed
    # per-item service time) and predicted-late submissions fail at
    # submit. CASSMANTLE_NO_ADAPTIVE_ADMISSION=1 reverts to the static
    # max_pending/degraded_max_pending pair.
    queue_latency_target_s: float = 1.0
    admission_min_pending: int = 8
    # Background work (round generation, reserve refill, bench) sheds
    # at this fraction of the adaptive limit — first under pressure.
    admission_background_fraction: float = 0.5
    # Starvation bound for the background tier: after this many
    # consecutive batches dispatched with background work pending, the
    # oldest background item heads the next batch (rounds keep rotating
    # under sustained interactive load).
    background_every_batches: int = 8
    # Event-loop saturation threshold: when the server.loop_lag_s
    # sleep-overshoot gauge (obs/process.py) exceeds this, background
    # submissions shed BEFORE queues back up (interactive sheds at 4x).
    loop_lag_shed_s: float = 0.25
    # -- SLO-driven brownout ladder (serving/overload.py) ------------------
    # Dwell before stepping UP a quality tier on sustained fast-window
    # burn, and — the hysteresis — before stepping DOWN after the slow
    # window recovers. CASSMANTLE_NO_BROWNOUT=1 pins tier 0.
    brownout_step_up_dwell_s: float = 10.0
    brownout_step_down_dwell_s: float = 30.0
    # SLO objectives the ladder watches (obs/slo.py default_objectives
    # names); replication lag is deliberately absent — quality tiers
    # cannot fix a store problem.
    brownout_objectives: Tuple[str, ...] = ("score_latency",
                                            "round_generation")
    # Drill/test stand-in for device scoring cost on the FAKE backend:
    # >0 routes fake similarity through a real BatchingQueue whose
    # handler holds the dispatch thread this long per batch — what lets
    # `bench.py overload_drill` exercise the real admission path on a
    # CPU-only host. 0 (the default) keeps the instant hash scorer.
    fake_score_batch_ms: float = 0.0
    # -- stage-disaggregated image serving (serving/stages.py) -------------
    # Split the image path into encode / denoise / decode stages, each
    # independently batched, with the denoise stage running step-level
    # continuous batching over a fixed-capacity slot tensor: a request
    # arriving mid-denoise of another joins at the next STEP boundary
    # instead of waiting a whole image's latency for the dispatch lock
    # (ROADMAP item 1; SwiftDiffusion / LegoDiffusion, PAPERS.md). Solo
    # output is bit-identical to the monolithic path
    # (tests/test_stages.py); CASSMANTLE_NO_STAGED_SERVING=1 is the
    # runtime kill switch (docs/DEPLOY.md §6). Configs the slot stepper
    # cannot replay exactly (eta>0, a dp/sp mesh)
    # fall back to the monolithic dispatch automatically.
    staged_serving: bool = False
    # Fixed denoise slot capacity. The slot tensor keeps this shape
    # forever; each step gathers live slots into the smallest
    # power-of-two width bucket ≥ occupancy, so the step function
    # compiles once per bucket (never per admission/retirement) and
    # per-step compute tracks load.
    denoise_slots: int = 4
    # Bucket ladders for the encode/decode stage queues (batch dims pad
    # to the next bucket, shapes stay static across calls).
    stage_encode_batch_sizes: Tuple[int, ...] = (1, 2, 4, 8)
    stage_decode_batch_sizes: Tuple[int, ...] = (1, 2, 4)
    # Coalescing window for the encode/decode stage queues. Short: the
    # denoise stage's step-boundary admission does the real batching,
    # so holding encode work to widen a batch only adds latency.
    stage_max_delay_ms: float = 3.0


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs (cassmantle_tpu/obs/, utils/logging.py).

    Applied to the process-global tracer / flight recorder / metrics
    registry by ``obs.configure_observability`` at server build."""

    # Healthy-baseline sampling FLOOR (ISSUE 18): fraction of root
    # spans retained unconditionally (head-certain). Every other trace
    # buffers in the pending ring and is tail-retained only when its
    # root completes slow/errored/marked; IDs always propagate
    # (X-Trace-Id stays useful for log correlation) either way.
    # CASSMANTLE_NO_TAIL_SAMPLING=1 reverts this to the pre-tail
    # head-sampling decision (docs/DEPLOY.md §6).
    trace_sample_rate: float = 1.0
    # Bounded per-trace span sink: how many traces stay queryable at
    # /debugz?trace=... (LRU eviction), and the per-trace span cap.
    trace_capacity: int = 256
    trace_max_spans: int = 512
    # -- tail retention (ISSUE 18) -----------------------------------------
    # Pending ring for traces awaiting their root's retention verdict:
    # occupancy cap, and the TTL sweep that reclaims traces whose root
    # never completes (client disconnect, watchdog kill) — counted
    # obs.traces_abandoned.
    trace_pending_capacity: int = 512
    trace_pending_ttl_s: float = 120.0
    # Per-route slow thresholds for tail retention: a completed root
    # span at least this slow is promoted. Keyed by root span name
    # ("http.post /compute_score"); ()-pairs because the dataclass is
    # frozen/hashable.
    tail_slow_default_s: float = 1.0
    tail_slow_routes: Tuple[Tuple[str, float], ...] = ()
    # Flight-recorder ring: how many structured events /debugz replays.
    recorder_capacity: int = 512
    # Default latency-histogram bucket bounds (seconds, cumulative) —
    # the single definition lives in utils/logging.py so series created
    # before configure_observability runs get the SAME ladder.
    latency_buckets_s: Tuple[float, ...] = _DEFAULT_BUCKETS_S
    # -- cluster observability (ISSUE 9) -----------------------------------
    # Per-peer timeout for cluster fan-outs (/metrics?scope=cluster,
    # /debugz?trace=&scope=cluster): a dark peer costs at most this per
    # scrape and is marked, never silently dropped.
    cluster_fanout_timeout_s: float = 2.0
    # Background cadence of the process self-metrics sampler (uptime,
    # rss, cpu, event-loop lag; obs/process.py).
    process_sample_interval_s: float = 5.0
    # -- SLO burn-rate engine (obs/slo.py) ---------------------------------
    # Evaluation cadence of the background loop; /sloz also evaluates
    # on scrape (rate-limited internally). CASSMANTLE_NO_SLO=1 disables
    # the background loop (docs/DEPLOY.md §6).
    slo_eval_interval_s: float = 10.0
    # Multi-window burn rates: trip on the fast window, recover on the
    # slow one (obs/slo.py module docstring).
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    # Default objective thresholds (obs/slo.py default_objectives):
    # p99 bound for /compute_score, round-generation success ratio,
    # replication-lag bound in log commands.
    slo_score_p99_s: float = 2.0
    slo_generation_ratio: float = 0.9
    slo_repl_lag_max: float = 512.0
    # -- synthetic canary prober (obs/prober.py, ISSUE 18) -----------------
    # Background cadence of the end-to-end probe loop (self + peers)
    # and the per-leg HTTP timeout. CASSMANTLE_NO_PROBER=1 disables the
    # loop; CASSMANTLE_PROBE_INTERVAL_S overrides the cadence
    # (docs/DEPLOY.md §6).
    probe_interval_s: float = 15.0
    probe_timeout_s: float = 5.0
    # Black-box SLO objectives fed by probe verdicts: minimum probe
    # success ratio, and the p99 bound on probe end-to-end time.
    probe_success_ratio: float = 0.95
    probe_p99_s: float = 3.0


@dataclasses.dataclass(frozen=True)
class GameConfig:
    """Round/game constants (reference values cited in SURVEY.md §2/§5.6)."""

    min_score: float = 0.01          # server.py:17
    time_per_prompt: float = 900.0   # main.py:23 (15 min)
    buffer_at_fraction: float = 0.7  # server.py:162
    num_masked: int = 2              # backend.py:49
    episodes_per_story: int = 20     # backend.py:50
    min_blur: float = 0.0            # backend.py:319
    max_blur: float = 15.0           # backend.py:319
    lock_timeout: float = 120.0      # backend.py:47
    acquire_timeout: float = 2.0     # backend.py:48
    max_retries: int = 5             # server.py:19
    rate_limit_default: float = 3.0  # req/s per IP, main.py:19
    rate_limit_api: float = 2.0      # main.py:48 etc.
    # Round-reserve ring (engine/reserve.py): archived rounds rotated in
    # while generation is dark, so degraded rounds stay FRESH puzzles
    # instead of replaying one. 0 disables (pure reference replay).
    reserve_capacity: int = 8


@dataclasses.dataclass(frozen=True)
class FabricConfig:
    """Room fabric: sharded multi-room game over the shared store
    (cassmantle_tpu/fabric/). One worker with one room (the defaults)
    is exactly the pre-fabric game — the default room lives at the
    legacy un-prefixed store keys, so old stores resume and old
    frontends keep working."""

    # Concurrent rooms, each with its own round clock, content, and
    # score state. Room ids are ``default_room`` plus room-1..room-N-1;
    # sessions consistent-hash onto them (fabric/directory.py).
    num_rooms: int = 1
    # The room legacy un-roomed requests map to (empty key prefix).
    default_room: str = "lobby"
    # Stable worker identity for room placement; "" derives host:pid
    # (CASSMANTLE_ROOM_WORKER_ID overrides at runtime).
    worker_id: str = ""
    # Address peers should redirect to for rooms this worker owns,
    # e.g. "http://10.0.0.3:8000" (CASSMANTLE_ROOM_ADVERTISE overrides);
    # "" means this worker cannot be redirected to (single-worker).
    advertise_addr: str = ""
    # Membership heartbeat cadence and staleness cutoff: a worker whose
    # last heartbeat is older than ``membership_ttl_s`` leaves the ring
    # and its rooms re-place onto the survivors.
    heartbeat_s: float = 2.0
    membership_ttl_s: float = 6.0
    # Virtual nodes per worker on the consistent-hash ring (higher =
    # smoother room distribution, slower ring rebuild).
    vnodes: int = 64
    # Replicated-store endpoints ("host:port", ...): when non-empty the
    # worker talks to the mantlestore cluster through ReplicatedStore
    # (leader writes, log-shipping pump, lease failover) instead of a
    # single node. CASSMANTLE_REPL_ENDPOINTS overrides.
    repl_endpoints: Tuple[str, ...] = ()
    # Pump poll cadence (replication lag floor) and leader lease TTL
    # (failover detection time); CASSMANTLE_REPL_POLL_MS /
    # CASSMANTLE_REPL_LEASE_MS override.
    repl_poll_s: float = 0.05
    repl_lease_s: float = 3.0
    # Graceful SIGTERM handoff bound (fabric/rooms.py RoomFabric.handoff):
    # after leaving membership and draining rooms, the worker waits up to
    # this long for every live peer to heartbeat PAST the departure — the
    # beat that rebuilds the peer's ring and adopts the rooms — so
    # adoption happens before process exit, not after the staleness TTL.
    handoff_grace_s: float = 5.0


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Deterministic fault injection (cassmantle_tpu/chaos/,
    docs/CHAOS.md). ``spec`` uses the same grammar as the
    ``CASSMANTLE_CHAOS`` env lever (which wins when both are set):
    ``seed=N;point=kind:k=v,...`` clauses against the fault-point
    registry. Empty spec (the default) = disarmed, and every fault
    point is a zero-overhead no-op."""

    spec: str = ""
    # Default plan seed when the spec carries no ``seed=`` clause —
    # the same seed replays the same fault schedule.
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class QualityGateConfig:
    """CLIP-parity thresholds a fast preset must clear before its
    throughput counts as a win (BASELINE.md quality gate). Enforced by
    tools/clip_report.py whenever the report is a real measurement
    (real_weights=true); advisory on random-init plumbing runs. Keyed
    by preset name; a preset absent here is reported but not gated.

    Ratios are preset clip_sim_mean / ddim50 anchor clip_sim_mean.
    DPM-Solver++(2M)@25 claims DDIM-50-class quality, so it gates at
    0.97; int8 is a weights-only quantization and must stay
    ~lossless."""

    parity_vs_ddim50: Tuple[Tuple[str, float], ...] = (
        ("dpmpp25", 0.97),
        ("int8", 0.98),
        # the 4-step consistency student trades the most quality for
        # the biggest step-count win (LCM-class results, PAPERS.md
        # Efficient Diffusion Models survey)
        ("lcm", 0.90),
        # full W8A8 (int8 weights AND activations, ISSUE 20) rounds
        # twice per matmul; with per-channel weight scales + calibrated
        # activation scales it must stay near-lossless, a hair below
        # the weights-only int8 bar. One row per image pipeline —
        # SDXL's depth-10 transformer level accumulates more
        # quantization noise than SD1.5's depth-1 blocks.
        ("w8a8", 0.98),
        ("sdxl_w8a8", 0.98),
    )
    # absolute floor for the anchor itself: catches a pipeline bug that
    # degrades every preset uniformly (ratios would all still pass)
    ddim50_min_sim: float = 0.18

    def threshold_for(self, preset: str):
        return dict(self.parity_vs_ddim50).get(preset)


@dataclasses.dataclass(frozen=True)
class FrameworkConfig:
    models: ModelZooConfig = dataclasses.field(default_factory=ModelZooConfig)
    sampler: SamplerConfig = dataclasses.field(default_factory=SamplerConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    game: GameConfig = dataclasses.field(default_factory=GameConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    fabric: FabricConfig = dataclasses.field(default_factory=FabricConfig)
    chaos: ChaosConfig = dataclasses.field(default_factory=ChaosConfig)
    spec_decode: SpecDecodeConfig = dataclasses.field(
        default_factory=SpecDecodeConfig)
    quality: QualityGateConfig = dataclasses.field(
        default_factory=QualityGateConfig)
    seed: int = 0

    def replace(self, **kw) -> "FrameworkConfig":
        return dataclasses.replace(self, **kw)


def sdxl_config() -> FrameworkConfig:
    """SDXL-base-1.0 at 1024×1024: dual text towers (CLIP-L + OpenCLIP
    bigG), micro-conditioned UNet, 0.13025 VAE scaling — the BASELINE.md
    "SDXL-base 1024 batched prompts, data-parallel" workload."""

    return FrameworkConfig(
        models=ModelZooConfig(
            clip_text=ClipTextConfig(),
            clip_text_2=ClipTextConfig.sdxl_big(),
            unet=UNetConfig.sdxl(),
            vae=VAEConfig(scaling_factor=0.13025),
        ),
        sampler=SamplerConfig(image_size=1024),
    )


def fast_serving_config() -> FrameworkConfig:
    """Low-latency game serving: DPM-Solver++(2M) at 25 steps reaches
    DDIM-50 visual quality in half the denoise time (ops/samplers.py).
    The benchmark keeps the 50-step DDIM north-star config; this preset
    is for round serving where latency budget matters
    (reference budget: 270 s per round, server.py:162)."""

    return FrameworkConfig(
        sampler=SamplerConfig(kind="dpmpp_2m", num_steps=25)
    )


def fusedconv_serving_config() -> FrameworkConfig:
    """The fixed DDIM-50 north-star config with the conv-side Pallas
    path on: fused GroupNorm+SiLU+conv3x3 in every UNet ResBlock plus
    128-lane channel padding at the non-aligned 320/960 levels
    (UNetConfig.fused_conv / conv_pad_to; ops/fused_conv.py). Same
    trajectory and param tree as the plain config — this is the ON arm
    of the `sd15_fusedconv` bench A/B, and it composes with the
    workload-level presets (dpmpp/int8) because it changes
    how ResBlock convs execute, not what they compute."""

    base = FrameworkConfig()
    return base.replace(models=dataclasses.replace(
        base.models, unet=dataclasses.replace(
            base.models.unet, fused_conv=True, conv_pad_to=128)))


def w8a8_serving_config() -> FrameworkConfig:
    """The fixed DDIM-50 config served fully W8A8 (ISSUE 20): int8
    weights AND activations at every attention/MLP/GEGLU projection and
    fused-conv ResBlock site in the UNet, plus the prompt LM with
    per-token activation scales — the quantization lever the Efficient
    Diffusion survey (PAPERS.md) ranks beside step reduction, composing
    multiplicatively with LCM/staged since it changes how
    matmuls execute, not what the schedule computes. Rides the fused
    GN+SiLU+conv path (fused_conv=True + 128-lane padding), so this is
    fusedconv_serving_config plus quantized trees. Static activation
    scales come from the committed calibration artifact
    (data/act_scales.json) when its signature matches this config;
    quality gates via the `w8a8` QualityGateConfig row; this is the ON
    arm of the `sd15_w8a8`/`gpt2_w8a8` bench A/Bs.
    CASSMANTLE_NO_W8A8=1 reverts bit-exactly at pipeline build."""

    base = FrameworkConfig()
    return base.replace(models=dataclasses.replace(
        base.models,
        unet=dataclasses.replace(
            base.models.unet, fused_conv=True, conv_pad_to=128),
        unet_w8a8=True, lm_w8a8=True))


def spec_decode_serving_config() -> FrameworkConfig:
    """The default serving config with speculative decoding on for the
    prompt LM, self-drafting n-gram mode (zero extra HBM, no draft
    checkpoint needed — works in every deployment). Same decode output
    as the plain config by construction (exact greedy acceptance); this
    is the ON arm of the `gpt2_spec` bench A/B. Swap ``mode`` to
    "draft_model" with a gpt2-small config to draft with a second zoo
    LM instead."""

    return FrameworkConfig(
        spec_decode=SpecDecodeConfig(mode="ngram", gamma=4, ngram=3))


def staged_serving_config() -> FrameworkConfig:
    """The fixed DDIM-50 config served through the stage graph
    (serving/stages.py): CLIP encode, denoise, and VAE decode run as
    independently batched stages, and the denoise loop admits/retires
    requests at STEP granularity over a fixed slot tensor — a request
    landing one step after another's dispatch starts denoising at the
    next step boundary instead of waiting a whole image's latency.
    Same trajectory per request as the monolithic path (solo output is
    bit-identical, tests/test_stages.py); this is the ON arm of the
    `sd15_staged` mixed-load bench A/B. CASSMANTLE_NO_STAGED_SERVING=1
    is the runtime kill switch."""

    return FrameworkConfig(serving=ServingConfig(staged_serving=True))


def lcm_serving_config() -> FrameworkConfig:
    """Few-step image serving (ROADMAP item 3a, ISSUE 15): a
    consistency/LCM-distilled student of the zoo UNet sampled at FOUR
    direct x0 predictions per image instead of the 50-step DDIM solve —
    the step-COUNT lever the Efficient Diffusion Models survey
    (PAPERS.md) names as the largest remaining family, ~9x fewer
    per-image FLOPs than the north star (docs/PERF_NOTES.md "Few-step
    accounting"). The student shares the teacher's param tree and
    checkpoint layout (distill with
    parallel/train.py::ConsistencyDistillTrainer, serve its checkpoint
    through the unchanged weights path); quality gates via
    eval/clip_parity.py::consistency_quality_report and the `lcm` row
    of QualityGateConfig. This is the ON arm of the `sd15_lcm` bench
    A/B; CASSMANTLE_NO_CONSISTENCY=1 reverts bit-exactly to the
    teacher's DDIM-50 path."""

    return FrameworkConfig(
        sampler=SamplerConfig(consistency=True, num_steps=4))


def qwen3next_game_config() -> FrameworkConfig:
    """The game with a Qwen3-Next-class story model and a distilled
    few-step image model: one chip's share of a deployment in which four
    chips share each layer of Qwen3-Next-80B-A3B-Instruct (experts and
    vocabulary divided over the four, everything else replicated; further
    layers on further hosts as pipeline stages). Held here: two periods of
    (linear, linear, linear, full) at the published widths, experts
    [0, 128) of 512, vocabulary rows [0, 37984) of 151936: 3.67 B
    parameters. The image side is ``lcm_serving_config``'s sampler, so
    that the prompt LM is most of a round's device time."""

    return FrameworkConfig(
        models=ModelZooConfig(qwen3_next=Qwen3NextConfig(
            num_hidden_layers=8, experts_held=128, first_expert=0,
            vocab_size=37984)),
        sampler=SamplerConfig(consistency=True, num_steps=4))


def lfm2_game_config() -> FrameworkConfig:
    """The game with an LFM2-24B-A2B-class story model and the few-step
    image model of ``qwen3next_game_config``: the first pipeline stage's
    chip of a deployment that divides no layer. Held here: published
    layers 0 and 2-9 at the published widths (one leading dense layer,
    counted once, and two whole periods of full, conv, conv, conv), all
    64 experts of each of the 8 expert layers, the whole tied embedding:
    5.18 B parameters."""

    return FrameworkConfig(
        models=ModelZooConfig(lfm2_moe=Lfm2MoeConfig(
            num_hidden_layers=9, num_dense_layers=1,
            layer_types=("conv",) + (
                "full_attention", "conv", "conv", "conv") * 2)),
        sampler=SamplerConfig(consistency=True, num_steps=4))


def ouro_game_config() -> FrameworkConfig:
    """The game with an Ouro-2.6B-class looped story model and the
    few-step image model of ``qwen3next_game_config``. Nothing is cut:
    all 48 layers, all 4 passes, the whole vocabulary and the untied head
    at the published widths, 2.67 B parameters held once on one chip."""

    return FrameworkConfig(
        models=ModelZooConfig(ouro=OuroConfig()),
        sampler=SamplerConfig(consistency=True, num_steps=4))


def test_config() -> FrameworkConfig:
    """A tiny config for CPU tests: small models, fast rounds, 64px images."""

    return FrameworkConfig(
        models=ModelZooConfig(
            clip_text=ClipTextConfig(
                vocab_size=1024, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, max_positions=16,
            ),
            unet=UNetConfig(
                base_channels=32, channel_mults=(1, 2), num_heads=4,
                attention_levels=(True, False), transformer_depth=(1, 0),
                blocks_per_level=1, context_dim=64, time_embed_dim=128,
                dtype="float32",
            ),
            vae=VAEConfig(base_channels=32, channel_mults=(1, 2),
                          blocks_per_level=1, dtype="float32"),
            gpt2=GPT2Config(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_positions=64, dtype="float32"),
            minilm=MiniLMConfig(vocab_size=512, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, max_positions=32),
            # fp32 storage on CPU tests: keeps golden/parity tolerances
            # tight and bit-stable
            param_dtype="float32",
        ),
        # negative_prompt neutral: with random-init weights the uncond
        # arm's content only adds noise to statistical test properties;
        # the wiring is covered explicitly (test_pipeline.py)
        sampler=SamplerConfig(num_steps=4, image_size=64, max_new_tokens=8,
                              min_new_tokens=2, prompt_pad_len=16,
                              negative_prompt=""),
        game=GameConfig(time_per_prompt=2.0, lock_timeout=5.0,
                        acquire_timeout=0.5),
    )


def test_qwen3next_config() -> FrameworkConfig:
    """``qwen3next_game_config`` at the CPU-test size."""

    base = test_config()
    return base.replace(
        models=dataclasses.replace(base.models,
                                   qwen3_next=Qwen3NextConfig.tiny()),
        sampler=dataclasses.replace(base.sampler, consistency=True))


def test_lfm2_config() -> FrameworkConfig:
    """``lfm2_game_config`` at the CPU-test size."""

    base = test_config()
    return base.replace(
        models=dataclasses.replace(base.models,
                                   lfm2_moe=Lfm2MoeConfig.tiny()),
        sampler=dataclasses.replace(base.sampler, consistency=True))


def test_ouro_config() -> FrameworkConfig:
    """``ouro_game_config`` at the CPU-test size."""

    base = test_config()
    return base.replace(
        models=dataclasses.replace(base.models, ouro=OuroConfig.tiny()),
        sampler=dataclasses.replace(base.sampler, consistency=True))


def test_sdxl_config() -> FrameworkConfig:
    """Tiny SDXL-shaped config for CPU tests: dual towers, micro-conds."""

    base = test_config()
    tower = base.models.clip_text
    tower2 = dataclasses.replace(tower, hidden_size=96, num_heads=4)
    return base.replace(
        models=dataclasses.replace(
            base.models,
            clip_text_2=tower2,
            unet=UNetConfig(
                base_channels=32, channel_mults=(1, 2), num_heads=4,
                attention_levels=(False, True), transformer_depth=(0, 2),
                blocks_per_level=1, context_dim=tower.hidden_size + 96,
                time_embed_dim=128,
                # pooled (96) + 6 sinusoidal time_ids × 32
                addition_embed_dim=96 + 6 * 32,
                dtype="float32",
            ),
            vae=dataclasses.replace(base.models.vae, scaling_factor=0.13025),
        ),
    )
