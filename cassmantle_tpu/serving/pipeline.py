"""TPU inference pipelines: text→image, prompt generation, content backend.

This is the local replacement for the reference's two Inference-API calls
(backend.py:240-295): CLIP encode → DDIM scan → VAE decode compile into one
XLA computation per (batch, resolution) bucket, and GPT-2 prefill+greedy
scan into one per prompt bucket. The game engine reaches all of it through
:class:`TPUContentBackend.generate` — the same seam the fake backend
implements for tests (engine/content.py).
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import os
import random
import threading
from functools import partial, wraps
from typing import (
    Callable,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from cassmantle_tpu.chaos import fault_point
from cassmantle_tpu.config import FrameworkConfig
from cassmantle_tpu.engine.rounds import ContentBackend, RoundContent
from cassmantle_tpu.models.clip_text import ClipTextEncoder
from cassmantle_tpu.models.gpt2 import GPT2LM
from cassmantle_tpu.models.unet import UNet
from cassmantle_tpu.models.vae import VAEDecoder, postprocess_images
from cassmantle_tpu.models.weights import (
    convert_clip_text,
    convert_gpt2,
    convert_unet,
    convert_vae_decoder,
    init_params_cached,
    maybe_load,
)
from cassmantle_tpu.utils.compile_cache import (
    enable_compile_cache,
    param_cache_path,
)
from cassmantle_tpu.ops.ddim import (
    initial_latents,
    make_cfg_denoiser,
)
from cassmantle_tpu.ops.samplers import make_sampler
from cassmantle_tpu.ops.decode import greedy_decode
from cassmantle_tpu.serving import integrity
from cassmantle_tpu.utils.locks import OrderedLock, Turns
from cassmantle_tpu.utils.logging import get_logger, metrics
from cassmantle_tpu.utils.profiling import (
    block_timer,
    host_span,
    named_jit,
)
from cassmantle_tpu.utils.tokenizers import load_tokenizer

log = get_logger("pipeline")


def dp_sharded_sampler(sample_impl, mesh, name: str):
    """Jit a ``(params, ids, uncond_ids, rng)`` sampler for the mesh,
    as the program ``name`` (``t2i_sample`` / ``sdxl_sample``: what a
    device trace's ``XLA Modules`` line and every op's scope path say).

    Returns ``(jitted_fn, dp)``: with a mesh, token ids arrive sharded
    over the required ``dp`` axis and params replicate (GSPMD inserts
    nothing in the forward — batch parallelism is collective-free);
    without one, a plain jit and dp=1. The flash kernels inside are
    traced per batch shard (ops/attention.py::batch_sharded_kernels):
    GSPMD cannot partition a Mosaic kernel. Shared by the SD1.5 and
    SDXL pipelines so the sharding/padding contract lives in one place.
    """
    if mesh is None:
        return named_jit(sample_impl, name), 1
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cassmantle_tpu.ops.attention import batch_sharded_kernels

    @wraps(sample_impl)
    def sharded_impl(*args):
        with batch_sharded_kernels(mesh, "dp"):
            return sample_impl(*args)

    batch = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    fn = named_jit(
        sharded_impl, name,
        in_shardings=(repl, batch, batch, repl),
        out_shardings=batch,
    )
    return fn, int(mesh.shape["dp"])


def replicate_on_mesh(params, mesh):
    """Place a param tree once, replicated over the serving mesh (the
    tree unchanged without one). Loaders put trees on the first device;
    left there, a meshed jit's replicated ``in_shardings`` would copy
    the whole tree to the other devices on every dispatch."""
    if mesh is None:
        return params
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(params, NamedSharding(mesh, P()))


def spatially_shard_latents(lat, mesh):
    """Latency scale-up for big latents (SURVEY §5.7's 1024²+ path,
    IN SERVING): constrain (B, H, W, C) latents to P("dp", "sp") so
    GSPMD spatially partitions the whole denoise over the mesh's sp
    axis — halo exchanges around every conv, resharding around the
    attention flattens, all compiler-inserted, riding ICI. A no-op
    without a mesh or with sp=1 (the batch-throughput layout). sp must
    divide the latent H."""
    if mesh is None or int(mesh.shape.get("sp", 1)) <= 1:
        return lat
    from jax.sharding import NamedSharding, PartitionSpec as P

    # lint: ignore[host-sync] — mesh.shape is static host metadata, not a device value
    assert lat.shape[1] % int(mesh.shape["sp"]) == 0, (
        f"latent H {lat.shape[1]} not divisible by sp={mesh.shape['sp']}")
    return jax.lax.with_sharding_constraint(
        lat, NamedSharding(mesh, P("dp", "sp")))


def share_compatible(models_a, models_b) -> bool:
    """True when two ModelZooConfigs can share Text2ImagePipeline param
    trees (same architectures + storage dtype; ``unet_int8`` MAY differ
    — the pipeline then derives/loads its own UNet). The single
    definition of the ``share_params_with`` contract: the pipeline's
    assert and callers picking anchors (tools/clip_report.py) both use
    this. UNet and VAE configs compare by ``arch()``: the fused-conv
    execution flags (fused_conv/conv_pad_to) change how convs run,
    never the param tree, so a fused A/B arm shares the donor's
    weights."""
    return (models_a.clip_text == models_b.clip_text
            and models_a.unet.arch() == models_b.unet.arch()
            and models_a.vae.arch() == models_b.vae.arch()
            and models_a.param_dtype == models_b.param_dtype)


def int8_unet_tools(models_cfg):
    """(loader transform, apply wrapper) for the weights-only int8 UNet
    option — the one place the int8 serving contract lives (shared by
    the SD1.5 and SDXL pipelines, like dp_sharded_sampler): quantize
    host-side before device placement, dequantize inside the jit."""
    if not models_cfg.unet_int8:
        return None, lambda apply: apply
    from cassmantle_tpu.ops.quant import quantize_tree_host, quantized_apply

    return (quantize_tree_host,
            lambda apply: quantized_apply(
                apply, jnp.dtype(models_cfg.param_dtype)))


def unet_w8a8_armed(models_cfg) -> bool:
    """True when the UNet actually serves through the int8 W8A8 kernels:
    the config knob AND the kill switch agree. The kill switch is read
    at pipeline BUILD time (never per dispatch): with it set the param
    tree is never quantized, so every module takes its plain fp branch
    and the revert is bit-exact against an unquantized build."""
    from cassmantle_tpu.ops.quant_matmul import w8a8_disabled

    return bool(models_cfg.unet_w8a8) and not w8a8_disabled()


def lm_w8a8_armed(models_cfg) -> bool:
    """LM twin of :func:`unet_w8a8_armed` (same build-time kill-switch
    contract; per-token activation scales, models/gpt2.py)."""
    from cassmantle_tpu.ops.quant_matmul import w8a8_disabled

    return bool(models_cfg.lm_w8a8) and not w8a8_disabled()


def w8a8_unet_tools(models_cfg):
    """Loader transform for the W8A8 UNet option, or None when off —
    the one place the image-side W8A8 serving contract lives (shared by
    the SD1.5 and SDXL pipelines, like int8_unet_tools): quantize
    weights host-side before device placement (per-output-channel int8
    scales), folding in static activation scales when the committed
    calibration artifact matches this model config's signature (else
    the kernels fall back to dynamic per-dispatch absmax). Unlike
    int8_unet_tools there is NO apply wrapper: the quantized leaves ride
    the tree into the unchanged ``unet.apply`` and each QDense /
    fused-conv site branches on its own leaf type."""
    if not unet_w8a8_armed(models_cfg):
        return None
    assert not models_cfg.unet_int8, (
        "unet_w8a8 and unet_int8 are mutually exclusive: both rewrite "
        "the same kernel leaves")
    assert models_cfg.unet.fused_conv, (
        "unet_w8a8 conv sites ride the fused GN+SiLU+conv path "
        "(ops/quant_matmul.py quantizes the fused activation); set "
        "models.unet.fused_conv=True")
    from cassmantle_tpu.ops.quant import (
        w8a8_default_predicate,
        w8a8_tree_host,
    )
    from cassmantle_tpu.parallel.calibrate import load_act_scales

    scales = load_act_scales(models_cfg)
    pred = partial(w8a8_default_predicate,
                   min_size=models_cfg.w8a8_min_size)
    return lambda params: w8a8_tree_host(
        params, act_scales=scales, predicate=pred)


def consistency_plan(sampler_cfg) -> int:
    """Validate a few-step consistency sampler config and return its
    step count (shared by the SD1.5 and SDXL pipelines, like
    dp_sharded_sampler). Consistency serving IS the few-step path —
    1-8 direct x0 predictions. eta>0 is rejected (the re-noise ladder
    is deterministic by construction — what lets few-step requests
    ride the staged slot stepper)."""
    s = sampler_cfg
    assert s.eta == 0.0, \
        "consistency sampling is deterministic (eta=0)"
    assert 1 <= s.num_steps <= 8, (
        f"consistency serving is the few-step path (1-8 steps), got "
        f"{s.num_steps}; the teacher schedule lives in "
        f"consistency_teacher_steps")
    assert s.consistency_teacher_steps > s.num_steps, (
        f"consistency_teacher_steps ({s.consistency_teacher_steps}) must "
        f"exceed num_steps ({s.num_steps}): the student only ever trains "
        f"on the teacher discretization's query points "
        f"(ops/samplers.py::ConsistencySchedule), and the kill switch "
        f"reverts to this schedule")
    return s.num_steps


def effective_sampler_cfg(sampler_cfg):
    """The sampler config the pipeline is ACTUALLY dispatching: with
    consistency configured but KILLED (CASSMANTLE_NO_CONSISTENCY=1)
    serving reverts to the teacher path — the configured kind at
    ``consistency_teacher_steps``. Cost-model signatures must digest
    THIS config, not the nominal one: the lcm preset under the kill
    switch runs ~9x the student's FLOPs, and resolving the committed
    student entry would under-report mxu_utilization exactly during
    the quality incident the switch exists for."""
    import dataclasses as _dc

    from cassmantle_tpu.ops.samplers import consistency_disabled

    if sampler_cfg.consistency and consistency_disabled():
        return _dc.replace(sampler_cfg, consistency=False,
                           num_steps=sampler_cfg.consistency_teacher_steps)
    return sampler_cfg


def effective_sampler_steps(sampler_cfg) -> int:
    """The step count the pipeline's plain ``make_sampler`` schedule
    should use (the revert is bit-exact — the pinned contract,
    tests/test_samplers.py). Shared by both pipelines and the staged
    slot stepper so every dispatch path reverts identically."""
    return effective_sampler_cfg(sampler_cfg).num_steps


def note_consistency_counter(sampler_cfg, n_images: int) -> None:
    """Diagnosis counter for few-step serving (host-side, derived from
    the static schedule — the step loop itself is one XLA computation,
    so per-step device counters would cost a host sync): how many
    consistency UNet forwards the dispatch performed —
    ``pipeline.consistency_steps`` / images = UNet forwards per image,
    the number the `sd15_lcm` bench A/B attaches. Silent when the knob
    or the kill switch has consistency off, so A/B counter deltas
    separate the arms."""
    from cassmantle_tpu.ops.samplers import consistency_disabled

    if sampler_cfg.consistency and not consistency_disabled():
        metrics.inc("pipeline.consistency_steps",
                    sampler_cfg.num_steps * n_images)


def note_moe_counters(routed) -> None:
    """Publish what a sparse prompt LM's expert layers routed in the
    dispatches just synced (``cache_stats`` trees, ops/decode.py; they
    came back with the tokens, so this transfer waits for nothing):
    ``moe.assignments`` (real rows x tokens x layers x top-k),
    ``moe.assignments_held`` (those that landed on an expert held here),
    ``moe.experts_touched`` (held experts with at least one real token,
    summed over the expert layers' calls), ``moe.walk_reads_saved``
    (landed assignments less the experts the decode walk read, summed
    over its calls: reads that rows choosing the same expert shared) and
    the gauge ``moe.load_max_over_mean`` over the held experts of a
    dispatch."""
    if not routed:
        return
    # ONE transfer for every dispatch of the batch, already computed
    trees = jax.device_get(routed)
    for name in ("assignments", "assignments_held", "experts_touched",
                 "walk_reads_saved"):
        metrics.inc("moe." + name, sum(t[name].item() for t in trees))
    load = trees[-1]["load"]
    if load.sum() > 0:
        metrics.gauge("moe.load_max_over_mean",
                      load.max().item() / load.mean().item())


def note_w8a8_counter(models_cfg, sampler_cfg, n_images: int) -> None:
    """Diagnosis counter for quantized serving (host-side, derived from
    the static schedule like note_consistency_counter): how many UNet
    forwards the dispatch ran through the int8 W8A8 kernel path —
    ``pipeline.w8a8_dispatches``. The `sd15_w8a8`/`sdxl_w8a8` bench A/B
    receipts attach this delta to prove the kernel path actually
    engaged (a CPU smoke that silently fell back to fp would otherwise
    look like a 1.0x win). Silent when the knob is off or the kill
    switch reverted the build, so A/B counter deltas separate the
    arms."""
    if unet_w8a8_armed(models_cfg):
        metrics.inc("pipeline.w8a8_dispatches",
                    effective_sampler_steps(sampler_cfg) * n_images)


def run_cfg_denoise(sampler_cfg, sample_latents, unet_apply,
                    params, ctx, uncond_ctx, lat,
                    addition_embeds=None, uncond_addition_embeds=None):
    """The denoise stage both image pipelines share: few-step
    consistency sampling (the distilled-student path) when configured,
    else plain CFG sampling."""
    from cassmantle_tpu.ops.samplers import consistency_disabled

    denoise = make_cfg_denoiser(
        unet_apply, params, ctx, uncond_ctx, sampler_cfg.guidance_scale,
        addition_embeds=addition_embeds,
        uncond_addition_embeds=uncond_addition_embeds,
    )
    if sampler_cfg.consistency and not consistency_disabled():
        from cassmantle_tpu.ops.samplers import make_consistency_sampler

        return make_consistency_sampler(
            sampler_cfg.num_steps,
            sampler_cfg.consistency_teacher_steps)(denoise, lat)
    return sample_latents(denoise, lat)


def degraded_dispatch_variant(cache: dict, sampler_cfg, mesh,
                              build_impl, log_, name: str):
    """Shared brownout-variant machinery for BOTH image pipelines
    (serving/overload.py, ISSUE 13): resolve the active tier into a
    degraded SamplerConfig, build that delta's sampler + schedules +
    jitted dispatch ONCE (cached by the (steps, size, consistency) key
    — a tier change never recompiles in steady state), and fall back
    to full quality on any build failure. ``build_impl(scfg, sampler)``
    returns the pipeline-specific sample impl, jitted under the
    pipeline's program ``name``; returns ``(sample_fn, scfg)`` or None
    (tier 0 / no-op delta / unusable delta)."""
    from cassmantle_tpu.serving import overload

    tier = overload.quality_overrides()
    if tier is None:
        return None
    try:
        scfg = overload.degraded_sampler_cfg(sampler_cfg, tier)
        if scfg == sampler_cfg:
            return None
        key = (scfg.num_steps, scfg.image_size, scfg.consistency)
        entry = cache.get(key)
        if entry is None:
            if scfg.consistency:
                consistency_plan(scfg)
            # consistency tiers dispatch their own sampler inside
            # run_cfg_denoise; a plain schedule here would be dead code
            sampler = (None if scfg.consistency
                       else make_sampler(scfg.kind, scfg.num_steps,
                                         eta=scfg.eta))
            fn, _ = dp_sharded_sampler(build_impl(scfg, sampler),
                                       mesh, name)
            entry = (fn, scfg)
            cache[key] = entry
        return entry
    except Exception:
        # counted: the ladder believes it engaged a cheaper tier, but
        # this config is quietly serving full quality — invisible in
        # the tier gauge, so the mismatch needs its own counter
        metrics.inc("pipeline.brownout_delta_unusable")
        log_.exception("brownout tier delta unusable for this config; "
                       "serving full quality")
        return None


# pipeline.image_batch_size bounds: rows callers asked for per image
# dispatch (1 a round today; cross-room batching, ROADMAP A5, moves it)
IMAGE_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)


def pad_prompts_to_dp(prompts: Sequence[str], dp: int):
    """Pad a prompt list to a multiple of the dp width (equal per-device
    shards); callers drop the pad rows from the output."""
    n = len(prompts)
    return list(prompts) + [""] * ((-n) % dp), n


class ImageHandOver:
    """An image pipeline's dispatch lock, passed to the next room once
    this room's program is in the device's queue and the program ahead
    of it has finished, so the device finds the next image waiting.

    A dispatch takes the lock in its turn (``in_turn``: rounds reach the
    device in the order their texts came back), enqueues its program,
    waits under the lock for the pipeline's previous program if that is
    still running (``pipeline.image_ahead_wait``), releases the lock and
    the room's ticket with it, and waits for its own result outside.
    At most two of one pipeline's programs are on the device at once:
    the one running and the next. ``pipeline.image_queued_behind_size``
    is 1 for a dispatch that found the program ahead unfinished, else
    0. The stage timer (``pipeline.t2i_s`` / ``pipeline.sdxl_s``) runs
    from the later of the enqueue and the moment this thread saw the
    program ahead finish, so it never counts the time queued behind
    another image."""

    def __init__(self, name: str, rank: int) -> None:
        # outermost hierarchy tier (docs/STATIC_ANALYSIS.md): held from
        # the enqueue until the image ahead is done, so nothing coarser
        # may nest inside
        self.lock = OrderedLock(name, rank=rank,
                                wait_span="pipeline.image_lock_wait",
                                in_turn=True)
        # the last program enqueued here: written under the lock, and
        # cleared without it by a device-loss rebuild, whose dispatch
        # may be the one holding the lock
        self._ahead = None

    def forget(self) -> None:
        """Device-loss rebuild: the program ahead ran on the dead runtime,
        so no dispatch waits for it."""
        self._ahead = None

    def dispatch(self, enqueue: Callable[[], object],
                 timer: Callable[[], contextlib.AbstractContextManager],
                 peer: str):
        """Run ``enqueue`` (the sampler's jitted call) in turn and return
        its result once ready, timed by ``timer()`` (the pipeline's
        ``block_timer``)."""
        with contextlib.ExitStack() as timed:
            with self.lock:
                ahead = self._ahead
                try:
                    queued = ahead is not None and not ahead.is_ready()
                except RuntimeError:
                    # failed, or its runtime is gone: finished for whoever
                    # is behind it; the dispatch that made it raises it
                    queued = False
                metrics.observe("pipeline.image_queued_behind_size",
                                int(queued), buckets=(0, 1))
                if not queued:
                    timed.enter_context(timer())
                with host_span("pipeline.image_enqueue"):
                    fault_point("device.lost", peer=peer)
                    result = enqueue()
                self._ahead = result
                if queued:
                    with host_span("pipeline.image_ahead_wait"):
                        try:
                            # the lock keeps a third program off the
                            # device; waiting under it is the point
                            # lint: ignore[lock-blocking-call] — intentional sync under dispatch lock
                            jax.block_until_ready(ahead)
                        except RuntimeError:
                            pass        # raised by the dispatch that made it
                    timed.enter_context(timer())
            return jax.block_until_ready(result)


def tokenize_clip_prompts(tokenizer, prompts: Sequence[str], pad_len: int,
                          vocab_size: int) -> np.ndarray:
    """Right-padded CLIP token ids: encode, trim, append EOS, pad.

    Shared by the SD1.5 and SDXL pipelines so both tokenize identically.
    """
    out = np.full((len(prompts), pad_len), tokenizer.pad_id, dtype=np.int32)
    for i, p in enumerate(prompts):
        toks = tokenizer.encode(p)[: pad_len - 1]
        toks = toks + [tokenizer.eos_id]
        # lint: ignore[host-sync] — toks is a host token list, not a device array
        out[i, : len(toks)] = np.asarray(toks) % vocab_size
    return out


class Text2ImagePipeline:
    """prompts -> uint8 images; whole sampler jitted per batch bucket.

    With ``mesh`` the batch shards over the ``dp`` axis (params
    replicated by GSPMD) — the v5e-8 batch-data-parallel serving layout;
    partial batches pad to the dp width and pad rows are dropped.
    """

    def __init__(self, cfg: FrameworkConfig,
                 weights_dir: Optional[str] = None,
                 mesh=None,
                 share_params_with: "Optional[Text2ImagePipeline]" = None,
                 ) -> None:
        """``share_params_with``: reuse another pipeline's already-loaded
        param trees (device buffers are shared, nothing is copied) when
        the model architectures match — presets that differ only in
        sampler (ddim50 vs dpmpp25) then skip re-reading
        and re-converting the multi-GB checkpoints per variant. A donor
        that differs ONLY in ``unet_int8`` still shares CLIP/VAE, and an
        int8 pipeline derives its quantized UNet from the donor's
        in-memory fp tree instead of re-reading the checkpoint."""
        enable_compile_cache()
        m = cfg.models
        self.cfg = cfg
        self.mesh = mesh
        self._weights_dir = weights_dir
        self.clip = ClipTextEncoder(m.clip_text)
        self.unet = UNet(m.unet)
        self.vae = VAEDecoder(m.vae)
        if share_params_with is not None:
            assert share_compatible(share_params_with.cfg.models, m), (
                "share_params_with needs matching model architectures"
            )
        self.tokenizer = load_tokenizer(
            weights_dir, "clip", m.clip_text.vocab_size
        )
        self.pad_len = min(cfg.sampler.prompt_pad_len,
                           m.clip_text.max_positions)
        # pixels per latent: one 2x upsample per VAE level transition
        self.vae_scale = 2 ** (len(m.vae.channel_mults) - 1)
        unet_transform, wrap_unet_apply = int8_unet_tools(m)
        w8a8_transform = w8a8_unet_tools(m)
        if w8a8_transform is not None:
            # mutually exclusive with unet_int8 (asserted in
            # w8a8_unet_tools), so int8_unet_tools returned (None,
            # identity) and the slot is free
            unet_transform = w8a8_transform

        def load_unet(transform):
            """maybe_load-or-init for the UNet tree, shared by the
            fresh-load and fp-joins-int8-donor paths."""
            lat_hw = cfg.sampler.image_size // self.vae_scale
            loaded = maybe_load(
                weights_dir, "unet.safetensors",
                lambda t: convert_unet(t, m.unet), "unet",
                cast_to=m.param_dtype, transform=transform)
            if loaded is not None:
                return loaded, True
            # cache key on arch(): the fused-conv flags don't change the
            # tree, so both A/B arms reuse one cached init
            return init_params_cached(
                self.unet, 2,
                jnp.zeros((1, lat_hw, lat_hw, 4), jnp.float32),
                jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, self.pad_len, m.unet.context_dim),
                          jnp.float32),
                cache_path=param_cache_path("unet", m.unet.arch()),
                cast_to=m.param_dtype, transform=transform), False

        def load_all_params() -> None:
            """Load/convert/share every stage tree and publish it on
            ``self``. Boot runs this once; a device-loss rebuild
            (serving/device_recovery.py, via :meth:`reload_params`)
            runs it again to re-upload the fingerprint-verified
            checkpoints onto the fresh runtime."""
            if share_params_with is not None:
                donor = share_params_with
                self.clip_params = donor.clip_params
                self.vae_params = donor.vae_params
                unet_was_loaded = True
                donor_m = donor.cfg.models
                donor_plain = (not donor_m.unet_int8
                               and not unet_w8a8_armed(donor_m))
                if (donor_m.unet_int8 == m.unet_int8
                        and unet_w8a8_armed(donor_m)
                        == unet_w8a8_armed(m)):
                    # same quantization mode (both fp, both int8, or
                    # both w8a8 with the same effective kill-switch
                    # state): share the device buffers outright
                    self.unet_params = donor.unet_params
                elif m.unet_int8 and donor_plain:
                    # int8 arm joining an fp donor: quantize the donor's
                    # in-memory tree (host-side) — no second checkpoint
                    # read
                    from cassmantle_tpu.ops.quant import (
                        quantize_tree_host,
                    )

                    self.unet_params = quantize_tree_host(
                        donor.unet_params)
                elif w8a8_transform is not None and donor_plain:
                    # w8a8 arm joining an fp donor: same derivation,
                    # through the w8a8 transform (static act scales and
                    # all)
                    self.unet_params = w8a8_transform(donor.unet_params)
                else:
                    # joining a donor quantized in a different mode:
                    # dequantization is lossy, so load this arm's own
                    # tree properly (through its own transform, if any)
                    self.unet_params, unet_was_loaded = load_unet(
                        unet_transform)
                # the donor's flag vouches only for tensors actually
                # taken from the donor; the fp-joins-int8-donor arm
                # re-loads its own UNet, and if the checkpoint vanished
                # between the two constructions that arm is random-init
                # and must say so
                self.loaded_real_weights = (
                    donor.loaded_real_weights and unet_was_loaded)
            else:
                ids = jnp.zeros((1, self.pad_len), dtype=jnp.int32)
                loaded_clip = maybe_load(
                    weights_dir, "clip_text.safetensors",
                    lambda t: convert_clip_text(
                        t, m.clip_text.num_layers),
                    "clip_text", cast_to=m.param_dtype)
                self.clip_params = (
                    loaded_clip if loaded_clip is not None
                    else init_params_cached(
                        self.clip, 1, ids,
                        cache_path=param_cache_path(
                            "clip_text", m.clip_text),
                        cast_to=m.param_dtype)
                )
                lat_hw = cfg.sampler.image_size // self.vae_scale
                lat = jnp.zeros((1, lat_hw, lat_hw, 4),
                                dtype=jnp.float32)
                self.unet_params, unet_was_loaded = load_unet(
                    unet_transform)
                loaded_vae = maybe_load(
                    weights_dir, "vae.safetensors",
                    lambda t: convert_vae_decoder(t, m.vae), "vae")
                self.vae_params = (
                    loaded_vae if loaded_vae is not None
                    else init_params_cached(
                        self.vae, 3, lat,
                        # cache key on arch(): fused_conv changes
                        # execution, not the tree (see UNet note above)
                        cache_path=param_cache_path(
                            f"vae{cfg.sampler.image_size}",
                            m.vae.arch()))
                )
                # True only when EVERY stage came from a checkpoint:
                # quality evals (tools/clip_report.py) refuse to call a
                # partially random-init pipeline a measurement
                self.loaded_real_weights = (
                    loaded_clip is not None
                    and unet_was_loaded
                    and loaded_vae is not None
                )

        self._param_loader = load_all_params
        load_all_params()
        self.unet_apply = wrap_unet_apply(self.unet.apply)
        from cassmantle_tpu.ops.fused_conv import describe as fc_describe

        if fc_describe(m.unet):
            log.info("%s", fc_describe(m.unet))
        if w8a8_transform is not None:
            from cassmantle_tpu.ops.quant import (
                w8a8_calibrated,
                w8a8_site_count,
            )
            from cassmantle_tpu.ops.quant_matmul import (
                describe as w8a8_describe,
            )

            log.info("%s", w8a8_describe(
                w8a8_calibrated(self.unet_params),
                w8a8_site_count(self.unet_params)))
        # fail fast on invalid few-step consistency configs; with the
        # kill switch set the plain schedule below IS the teacher path
        # (run_cfg_denoise falls through to it), so the revert is
        # bit-exact against a non-consistency teacher config. With
        # consistency ACTIVE there is no plain schedule at all —
        # run_cfg_denoise dispatches its own consistency sampler and
        # would silently ignore one built here
        if cfg.sampler.consistency:
            consistency_plan(cfg.sampler)
        self.sample_latents = (
            None if effective_sampler_cfg(cfg.sampler).consistency
            else make_sampler(
                cfg.sampler.kind, effective_sampler_steps(cfg.sampler),
                eta=cfg.sampler.eta))
        # Params enter the jit as ARGUMENTS (device buffers), never as
        # captured constants — capturing bakes ~4 GB of weights into the
        # HLO, blowing up compile payloads and compile-cache keys.
        self._publish_params()
        self._sample, self.dp = dp_sharded_sampler(
            self._sample_impl, mesh, "t2i_sample")
        # brownout actuation (serving/overload.py, ISSUE 13): degraded
        # sampler variants keyed by their (steps, size, consistency) delta —
        # each TIER compiles once on first engagement and is reused
        # (bucketed like every other serving variant), so steady-state
        # tier changes never recompile. Tier 0 uses self._sample
        # untouched: unloaded behavior is bit-for-bit the old path.
        self._tier_fns: dict = {}
        # roofline attribution (obs/costmodel.py, ISSUE 14): per-image
        # analytic FLOPs per dispatch variant, resolved lazily on first
        # dispatch (committed cost model for the production config,
        # trace-once otherwise; tier variants resolve on a background
        # thread — see _dispatch_flops)
        self._flops_cache: dict = {}
        self._flops_lock = threading.Lock()
        self._flops_pending: set = set()
        # Concurrent round buffering calls generate() from several
        # executor threads; they reach the device one program after
        # another, each enqueued while the one ahead still runs
        # (ImageHandOver). img2img holds the same lock for its whole
        # dispatch.
        self._hand_over = ImageHandOver("pipeline.t2i_dispatch", rank=10)
        # stage-disaggregated serving (serving/stages.py): built lazily
        # on the first staged generate; the supervisor is wired by
        # InferenceService so per-stage watchdog health fuses into
        # /readyz like every other dispatch path
        self.supervisor = None
        self._staged = None
        # guards ONLY the lazy _staged construction (generate() is
        # called from multiple executor threads; two racing builders
        # would mean two denoise threads and duplicate jit graphs) —
        # rank 13, docs/STATIC_ANALYSIS.md
        self._staged_init_lock = OrderedLock("pipeline.staged_init",
                                             rank=13)

    def _publish_params(self) -> None:
        """The one tree the jits take, placed where they run: replicated
        over the mesh when there is one. The per-stage attributes are
        re-pointed at the placed arrays so no first-device-only copy
        stays alive beside them."""
        self._params = replicate_on_mesh(
            {"clip": self.clip_params, "unet": self.unet_params,
             "vae": self.vae_params}, self.mesh)
        self.clip_params = self._params["clip"]
        self.unet_params = self._params["unet"]
        self.vae_params = self._params["vae"]

    def reload_params(self) -> None:
        """Device-loss rebuild (serving/device_recovery.py): re-run the
        boot load path — fingerprint-verified checkpoint reads
        (utils/checkpoint.py), donor sharing, int8 transform — and
        republish the tree onto the fresh runtime. Compiled executables
        take params as ARGUMENTS (see the __init__ note), so existing
        jitted fns stay valid; the recovery manager's warm pass
        verifies zero recompiles. The staged slot server held device
        state tied to the dead runtime: stop and drop it here — it
        rebuilds lazily on the next staged generate; so did the image
        dispatch's last program, which no dispatch waits for now."""
        staged = self._staged
        if staged is not None:
            self._staged = None
            try:
                staged.stop()
            # lint: ignore[swallowed-error] — the staged server is dropped and rebuilt regardless; recovery's warm-pass counters cover the reload outcome
            except Exception:
                log.exception("staged server stop during reload failed")
        self._hand_over.forget()
        self._param_loader()
        self._publish_params()
        if getattr(self, "vae_enc", None) is not None:
            # lazy img2img encoder state: drop it; _ensure_encoder
            # re-loads (fingerprint-verified) on the next img2img call
            self.vae_enc = None
            self.enc_params = None

    # -- stage-disaggregated serving (serving/stages.py) -------------------

    def _staged_enabled(self) -> bool:
        """Per-call routing decision: the ServingConfig knob, minus the
        runtime kill switch, minus configs the slot stepper cannot
        replay exactly — eta>0's per-step noise chain, non-stageable
        sampler kinds, and meshed (dp/sp) serving all keep the proven
        monolithic dispatch."""
        from cassmantle_tpu.serving.stages import (
            STAGEABLE_KINDS,
            staged_serving_disabled,
        )

        s = self.cfg.sampler
        return (self.cfg.serving.staged_serving
                and not staged_serving_disabled()
                and self.mesh is None
                and s.eta == 0.0
                and s.kind in STAGEABLE_KINDS)

    def _encode_stage(self, params, ids, uncond_ids):
        """Encode-stage computation: exactly the conditioning block of
        ``_sample_impl`` (rows are batch-independent, so a staged row
        matches its monolithic counterpart bit for bit)."""
        return {
            "ctx": self.clip.apply(params["clip"], ids)["hidden"],
            "uctx": self.clip.apply(params["clip"], uncond_ids)["hidden"],
        }

    def _decode_stage(self, params, lat):
        """Decode-stage computation: exactly the VAE + uint8 tail of
        ``_sample_impl`` (the staged server's retirement verdict runs
        as its own dispatch on the latents — folding it in here would
        change fusion and break bit-parity with the monolith)."""
        decoded = self.vae.apply(params["vae"], lat)
        return postprocess_images(decoded)

    def _staged_server(self):
        if self._staged is None:
            with self._staged_init_lock:
                if self._staged is None:
                    from cassmantle_tpu.serving.stages import (
                        StagedImageServer,
                    )

                    self._staged = StagedImageServer(
                        self.cfg, self._params,
                        encode_fn=self._encode_stage,
                        decode_fn=self._decode_stage,
                        unet_apply=self.unet_apply,
                        tokenize=self._tokenize,
                        vae_scale=self.vae_scale,
                        supervisor=self.supervisor,
                    )
        return self._staged

    def _sample_impl(self, params, ids, uncond_ids, rng):
        return self._build_tier_impl(
            self.cfg.sampler, self.sample_latents)(
                params, ids, uncond_ids, rng)

    def _tokenize(self, prompts: Sequence[str]) -> np.ndarray:
        return tokenize_clip_prompts(
            self.tokenizer, prompts, self.pad_len,
            self.cfg.models.clip_text.vocab_size,
        )

    # -- brownout actuation (serving/overload.py, ISSUE 13) ----------------

    def _build_tier_impl(self, scfg, sampler):
        """The SD1.5 sample impl bound to a sampler config: the
        pipeline's own (``_sample_impl``) or a degraded tier's, with
        (steps, size) swapped. The stage scopes are op
        metadata: a device trace puts every operation under
        ``clip_encode``, ``denoise_scan/denoise_step`` or
        ``vae_decode``, then the Flax module path."""

        def impl(params, ids, uncond_ids, rng):
            with jax.named_scope("clip_encode"):
                ctx = self.clip.apply(params["clip"], ids)["hidden"]
                uncond = self.clip.apply(params["clip"],
                                         uncond_ids)["hidden"]
            lat = initial_latents(rng, ids.shape[0], scfg.image_size,
                                  self.vae_scale)
            lat = spatially_shard_latents(lat, self.mesh)
            with jax.named_scope("denoise_scan"):
                final = run_cfg_denoise(
                    scfg, sampler, self.unet_apply,
                    params["unet"], ctx, uncond, lat,
                )
            with jax.named_scope("vae_decode"):
                decoded = self.vae.apply(params["vae"], final)
            return postprocess_images(decoded)

        return impl

    def _degraded_sampler(self):
        """(sample_fn, sampler_cfg) for the active
        brownout tier, or None at full quality (see
        :func:`degraded_dispatch_variant`)."""
        return degraded_dispatch_variant(
            self._tier_fns, self.cfg.sampler, self.mesh,
            self._build_tier_impl, log, "t2i_sample")

    def _dispatch_flops(self, sample_fn, scfg, kind: str = "t2i",
                        signature=None):
        """Per-image analytic FLOPs for this dispatch variant (None =
        no attribution yet): the committed data/cost_model.json entry
        when the runtime signature matches the artifact, else a
        trace-once of the actual jitted ``sample_fn`` — exact for any
        variant (brownout tiers) because the jaxpr is the truth. Shared
        by the SDXL pipeline (same dispatch shape).

        Resolution is locked (racing executor threads pay one trace,
        not one each) and tiered by urgency: the pipeline's OWN config
        resolves inline — its cold dispatch is compile-dominated, so a
        trace is noise there — but a BROWNOUT-TIER variant engages
        exactly when the system is shedding latency, so its trace runs
        on a daemon thread and the first degraded dispatches simply
        carry no attribution until it lands."""
        from cassmantle_tpu.obs import costmodel

        # attribution follows what is DISPATCHED: under the consistency
        # kill switch the effective config is the teacher schedule
        eff = effective_sampler_cfg(scfg)
        key = (eff.num_steps, eff.image_size, eff.consistency)
        if signature is None:
            signature = costmodel.t2i_signature(self.cfg, eff)

        def resolve():
            def trace() -> float:
                # minimal valid batch (the dp width with a mesh),
                # scaled back to per-image; tracing is abstract —
                # nothing runs on device
                ids = jax.ShapeDtypeStruct((self.dp, self.pad_len),
                                           jnp.int32)
                flops, _ = costmodel.trace_cost(
                    sample_fn, self._params, ids, ids,
                    jax.random.PRNGKey(0))
                return flops / self.dp

            return costmodel.flops_per_item(kind, signature,
                                            tracer=trace)

        with self._flops_lock:
            if key in self._flops_cache:
                return self._flops_cache[key]
            if scfg != self.cfg.sampler:
                if key not in self._flops_pending:
                    self._flops_pending.add(key)

                    def run_background():
                        value = resolve()
                        with self._flops_lock:
                            self._flops_cache[key] = value

                    threading.Thread(
                        target=run_background, daemon=True,
                        name="cassmantle-costtrace").start()
                return None
            per_image = resolve()
            self._flops_cache[key] = per_image
            return per_image

    def generate(self, prompts: Sequence[str], seed: int = 0,
                 deadline_s: Optional[float] = None) -> np.ndarray:
        """prompts -> (B, H, W, 3) uint8. One compiled graph per batch.

        With ``serving.staged_serving`` on (and the kill switch clear)
        the request rides the stage graph instead: encode/denoise/decode
        batch independently and the denoise loop admits at step
        granularity — same output bit for bit for a solo request.
        ``deadline_s`` is honored at step boundaries on the staged path
        (an expired request frees its denoise slot); the monolithic
        dispatch is all-or-nothing and ignores it."""
        # brownout tier first: a degraded delta routes to its own
        # monolithic variant (the staged slot stepper replays the FULL
        # schedule and cannot honor a tier's step/size delta)
        degraded = self._degraded_sampler()
        if degraded is None and self._staged_enabled():
            images = self._staged_server().generate(
                list(prompts), seed, deadline_s=deadline_s)
            metrics.inc("pipeline.images", len(prompts))
            note_consistency_counter(self.cfg.sampler, len(prompts))
            note_w8a8_counter(self.cfg.models, self.cfg.sampler,
                              len(prompts))
            return images
        # the host's part before the lock: CLIP tokens and their copies
        # to the device, the key, the dispatch's FLOPs
        with host_span("pipeline.image_prep"):
            sample_fn, scfg = (
                degraded if degraded is not None
                else (self._sample, self.cfg.sampler))
            padded, n = pad_prompts_to_dp(prompts, self.dp)
            ids = jnp.asarray(self._tokenize(padded))
            uncond = jnp.asarray(self._tokenize(
                [scfg.negative_prompt] * len(padded)))
            rng = jax.random.PRNGKey(seed)
            per_image = self._dispatch_flops(sample_fn, scfg)
            metrics.observe("pipeline.image_batch_size", n,
                            buckets=IMAGE_BATCH_BUCKETS)
        # the wait for the lock is its own span (the lock's wait_span);
        # block_timer = metric + device-synchronized trace span (the
        # whole CLIP->denoise->VAE jit is ONE XLA computation; its
        # stages are named scopes inside it, in a device trace's op
        # metadata) + roofline attribution: flops_est on the span, live
        # pipeline.mxu_utilization{pipeline="t2i"} vs the chip ceiling;
        # inside it, the jitted call's own dispatch
        images = self._hand_over.dispatch(
            lambda: sample_fn(self._params, ids, uncond, rng),
            lambda: block_timer(
                "pipeline.t2i_s",
                flops_est=(per_image * len(padded)) if per_image
                else None,
                pipeline="t2i", attrs={"padded_rows": len(padded)}),
            peer="t2i")
        # the round's host tail: device result ready -> generate returns
        with host_span("pipeline.image_host"):
            out = integrity.poison(np.asarray(images[:n]), peer="t2i")
            # host-side sentinel on the already-transferred uint8
            # batch: NaN/zeroed latents decode to constant frames, which
            # the degenerate-frame detector catches (the verdict stays
            # OUT of the sample jit to preserve staged-vs-monolithic
            # bit-parity)
            integrity.enforce(np.ones(n, dtype=bool), pipeline="t2i",
                              stage="sample", images=out, n=n)
            metrics.inc("pipeline.images", n)
            if degraded is not None:
                metrics.inc("pipeline.brownout_images", n)
            note_consistency_counter(scfg, n)
            note_w8a8_counter(self.cfg.models, scfg, n)
        return out

    # -- img2img ----------------------------------------------------------
    def _ensure_encoder(self) -> None:
        """Lazy VAE-encoder state: only img2img pays for it. The
        attribute checked by callers (``vae_enc``) is assigned LAST so a
        failed load leaves the pipeline retryable, not half-built."""
        if getattr(self, "vae_enc", None) is not None:
            return
        from cassmantle_tpu.models.vae import VAEEncoder
        from cassmantle_tpu.models.weights import convert_vae_encoder

        m = self.cfg.models
        encoder = VAEEncoder(m.vae)
        size = self.cfg.sampler.image_size
        img = jnp.zeros((1, size, size, 3), jnp.float32)
        self.enc_params = (
            maybe_load(self._weights_dir, "vae.safetensors",
                       lambda t: convert_vae_encoder(t, m.vae),
                       "vae_encoder")
            or init_params_cached(
                encoder, 4, img, jax.random.PRNGKey(0),
                cache_path=param_cache_path(f"vae_enc{size}", m.vae.arch()))
        )
        self._i2i_fns = {}
        self.vae_enc = encoder

    def _img2img_impl(self, k: int, params, ids, uncond_ids, images, rng):
        """Encode -> noise to the strength step -> run the schedule tail
        under the CONFIGURED sampler kind (same solver txt2img uses).
        ``k`` is static: one compiled graph per strength bucket."""
        from cassmantle_tpu.ops.samplers import make_img2img_sampler

        with jax.named_scope("clip_encode"):
            ctx = self.clip.apply(params["clip"], ids)["hidden"]
            uncond = self.clip.apply(params["clip"], uncond_ids)["hidden"]
        denoise = make_cfg_denoiser(
            self.unet_apply, params["unet"], ctx, uncond,
            self.cfg.sampler.guidance_scale,
        )
        rng_enc, rng_noise = jax.random.split(rng)
        # vae_enc is pure module structure (its params enter as the
        # ``params["vae_enc"]`` argument); reload_params nulls it only
        # so _ensure_encoder re-verifies the checkpoint and rebuilds an
        # architecturally identical module — the baked trace stays valid
        # lint: ignore[recompile-hazard] — structural capture, see above
        lat0 = self.vae_enc.apply(params["vae_enc"], images, rng_enc)
        s = self.cfg.sampler
        prepare, sample = make_img2img_sampler(
            s.kind, s.num_steps, s.num_steps - k, eta=s.eta
        )
        noise = jax.random.normal(rng_noise, lat0.shape, lat0.dtype)
        with jax.named_scope("denoise_scan"):
            final = sample(denoise, prepare(lat0, noise))
        with jax.named_scope("vae_decode"):
            decoded = self.vae.apply(params["vae"], final)
        return postprocess_images(decoded)

    def _i2i_fn(self, k: int):
        """The ``i2i_sample`` program of strength bucket ``k``."""
        if k not in self._i2i_fns:
            self._i2i_fns[k] = named_jit(
                partial(self._img2img_impl, k), "i2i_sample")
        return self._i2i_fns[k]

    def generate_img2img(
        self,
        images: np.ndarray,          # (B, H, W, 3) uint8
        prompts: Sequence[str],
        strength: float = 0.6,
        seed: int = 0,
    ) -> np.ndarray:
        """Image-conditioned generation (DDIM tail from a noised VAE
        encoding — e.g. episode-to-episode visual continuity, an ability
        the reference's remote txt2img call could not offer). ``strength``
        in (0, 1]: fraction of the schedule re-run; higher = less of the
        input survives. Single-chip path (no dp sharding)."""
        assert 0.0 < strength <= 1.0
        if self.cfg.sampler.consistency:
            raise NotImplementedError(
                "img2img does not support the few-step consistency "
                "sampler (the student is trained to map noise states on "
                "the schedule, not arbitrary strength tails); use a "
                "non-consistency config for image-conditioned generation"
            )
        self._ensure_encoder()
        steps = self.cfg.sampler.num_steps
        k = max(1, min(steps, int(round(strength * steps))))
        imgf = jnp.asarray(
            np.asarray(images, dtype=np.float32) / 127.5 - 1.0
        )
        ids = jnp.asarray(self._tokenize(list(prompts)))
        uncond = jnp.asarray(self._tokenize(
            [self.cfg.sampler.negative_prompt] * len(prompts)))
        params = dict(self._params, vae_enc=self.enc_params)
        with self._hand_over.lock, block_timer("pipeline.i2i_s"):
            out = self._i2i_fn(k)(
                params, ids, uncond, imgf, jax.random.PRNGKey(seed)
            )
            # lint: ignore[lock-blocking-call] — intentional sync under dispatch lock
            out = jax.block_until_ready(out)
        out = np.asarray(out)
        # host-side degenerate-frame sentinel (see generate())
        integrity.enforce(np.ones(out.shape[0], dtype=bool),
                          pipeline="t2i", stage="img2img", images=out)
        metrics.inc("pipeline.images", len(prompts))
        return out


def _dense_params(tree) -> int:
    from cassmantle_tpu.obs import costmodel

    return costmodel.params_count(tree)


class LMFamily(NamedTuple):
    """One prompt-LM family as ``PromptGenerator`` needs it: ``name`` (the
    attribute of ``cfg.models`` that holds its config, and what its
    tokenizer and checkpoint are called), how to build the model, how to
    convert a checkpoint (None: no converter, ``weights_dir`` is refused),
    which options it serves, the parameters a token's forward touches
    (``active_params(tree, mcfg)``: all of them for a dense LM), the
    counters its cache carries (``ops/decode.py`` ``cache_stats``) and
    whether a decode batch may mix prompt buckets (``mixed_buckets``:
    everything a decode step does with a position follows the row's own
    position id, not its cache slot)."""

    name: str
    model: Callable
    converter: Optional[Callable] = None
    quantized: bool = True       # lm_int8 / lm_w8a8
    speculative: bool = True     # needs decode_chunk and a cache that rolls back
    active_params: Callable = lambda tree, mcfg: _dense_params(tree)
    cache_stats: Optional[Callable] = None
    mixed_buckets: bool = True


def _lm_families() -> Tuple[LMFamily, ...]:
    """The families in the order they are looked for: the first whose
    config attribute is set on ``cfg.models`` is the prompt LM; GPT-2,
    always set, comes last."""
    from cassmantle_tpu.models import lfm2_moe, qwen3_next
    from cassmantle_tpu.models.mistral import MistralLM
    from cassmantle_tpu.models.weights import convert_mistral

    return (
        LMFamily("qwen3_next", qwen3_next.Qwen3NextLM,
                 quantized=False, speculative=False,
                 active_params=qwen3_next.active_params,
                 cache_stats=qwen3_next.cache_stats),
        LMFamily("lfm2_moe", lfm2_moe.Lfm2MoeLM,
                 quantized=False, speculative=False,
                 active_params=lfm2_moe.active_params,
                 cache_stats=lfm2_moe.cache_stats),
        LMFamily("mistral", MistralLM,
                 lambda m: lambda t: convert_mistral(t, m.num_layers),
                 mixed_buckets=False),  # its window counts cache slots
        LMFamily("gpt2", GPT2LM,
                 lambda m: lambda t: convert_gpt2(t, m.num_layers,
                                                  m.hidden_size)),
    )


class PromptGenerator:
    """Story-episode text generation: greedy decode, bucketed.

    The LM family is config-selected (``_lm_families``): GPT-2 by default,
    a Mistral-7B-class model (the reference's actual prompt model,
    backend.py:25) when ``cfg.models.mistral`` is set, a Qwen3-Next-class
    sparse model with linear-attention layers when ``cfg.models.qwen3_next``
    is, an LFM2-MoE-class one (short convolutions, sigmoid-routed experts)
    when ``cfg.models.lfm2_moe`` is. All expose the same prefill/decode_step
    contract, so the scan in ops/decode.py drives each."""

    PROMPT_BUCKETS = (32, 64, 128, 256)

    def __init__(self, cfg: FrameworkConfig,
                 weights_dir: Optional[str] = None) -> None:
        enable_compile_cache()
        self.cfg = cfg
        self._decode_calls = 0  # auto-advancing sampling key (decode_ids)
        # one in-flight decode per generator (see Text2ImagePipeline's
        # dispatch lock; the prompt queue usually serializes decodes, but
        # direct generate() callers can race it, so the wait is timed)
        self._dispatch_lock = OrderedLock(
            "pipeline.prompt_dispatch", rank=12,
            wait_span="pipeline.lm_lock_wait")
        assert not (cfg.models.lm_int8 and cfg.models.lm_w8a8), (
            "lm_w8a8 and lm_int8 are mutually exclusive: both rewrite "
            "the same kernel leaves")
        family = next(f for f in _lm_families()
                      if getattr(cfg.models, f.name) is not None)
        m = getattr(cfg.models, family.name)
        if not family.quantized and (cfg.models.lm_int8
                                     or cfg.models.lm_w8a8):
            raise ValueError(
                f"lm_int8 / lm_w8a8 are not served for {family.name}: its "
                f"expert weights do not go through the quantized kernels")
        if not family.speculative and cfg.spec_decode.mode != "off":
            raise ValueError(
                f"speculative decode is not served for {family.name}: a "
                f"rejected draft would need its recurrent state or "
                f"convolution window rolled back")
        if family.converter is None and weights_dir:
            raise ValueError(
                f"no checkpoint converter for {family.name}: it runs on "
                f"seeded weights only (weights_dir={weights_dir!r})")
        self.family = family
        self.model = family.model(m)
        self.tokenizer = load_tokenizer(weights_dir, family.name,
                                        m.vocab_size)
        loader = (f"{family.name}.safetensors",
                  family.converter(m) if family.converter else None,
                  family.name)
        self.mcfg = m
        self._weights_dir = weights_dir
        self._int8_path = (
            os.path.join(weights_dir, f"{loader[2]}.int8.safetensors")
            if weights_dir else None)

        def load_params() -> None:
            """Load the LM tree and publish it on ``self``. Boot runs
            this once; a device-loss rebuild (reload_params) runs it
            again onto the fresh runtime."""
            ids = jnp.zeros((1, 8), dtype=jnp.int32)
            self.params = (
                self._load_int8_checkpoint(loader[2], weights_dir)
                if cfg.models.lm_int8 else None)
            if self.params is not None:
                # Pre-quantized checkpoint straight from disk.
                # Provenance: tools/quantize_weights.py falls back to
                # random init when no fp checkpoint exists, so the int8
                # file only counts as real weights if its fp source
                # (file or shards) is present (the staleness check
                # already ensures int8 is the newer).
                import glob as _glob

                stem = loader[0].rsplit(".", 1)[0]
                self.loaded_real_weights = bool(
                    os.path.exists(os.path.join(weights_dir, loader[0]))
                    or _glob.glob(os.path.join(
                        weights_dir, f"{stem}-*.safetensors")))
            else:
                transform = None
                if cfg.models.lm_int8:
                    # Quantize on HOST, before device placement: peak
                    # HBM stays at the int8 footprint (quantizing after
                    # would briefly hold the fp and int8 trees resident
                    # together — fatal for a 7B-class model on a 16 GB
                    # chip).
                    from cassmantle_tpu.ops.quant import (
                        quantize_tree_host,
                    )

                    transform = quantize_tree_host
                elif lm_w8a8_armed(cfg.models):
                    # W8A8 LM: same host-side quantize-before-placement
                    # rationale. No static act scales — the LM path
                    # quantizes activations per token (row absmax in
                    # graph, models/gpt2.py), so a calibration artifact
                    # has nothing to add here.
                    from cassmantle_tpu.ops.quant import (
                        w8a8_default_predicate,
                        w8a8_tree_host,
                    )

                    pred = partial(w8a8_default_predicate,
                                   min_size=cfg.models.w8a8_min_size)
                    transform = partial(w8a8_tree_host, predicate=pred)
                loaded = maybe_load(
                    weights_dir, loader[0], loader[1], loader[2],
                    cast_to=cfg.models.param_dtype, transform=transform)
                # measurement tools (tools/lm_int8_ab.py) refuse to
                # label a random-init decode a real-weights number
                self.loaded_real_weights = loaded is not None
                self.params = (
                    loaded if loaded is not None
                    else init_params_cached(
                        self.model, 5, ids,
                        cache_path=param_cache_path(loader[2], m),
                        cast_to=cfg.models.param_dtype,
                        transform=transform)
                )

        self._param_loader = load_params
        load_params()
        # params flow through greedy_decode as traced args (no captured
        # constants — see Text2ImagePipeline note)
        from cassmantle_tpu.ops.decode import make_apply_fns

        self._prefill, self._step, self._chunk = make_apply_fns(self.model)
        if cfg.models.lm_int8:
            from cassmantle_tpu.ops.quant import (
                quantized_apply,
                tree_nbytes,
            )

            dq_dtype = jnp.dtype(cfg.models.param_dtype)
            self._prefill = quantized_apply(self._prefill, dq_dtype)
            self._step = quantized_apply(self._step, dq_dtype)
            self._chunk = quantized_apply(self._chunk, dq_dtype)
            log.info("lm_int8: serving %.2f GB quantized param tree",
                     tree_nbytes(self.params) / 1e9)
        if lm_w8a8_armed(cfg.models):
            from cassmantle_tpu.ops.quant import (
                tree_nbytes,
                w8a8_site_count,
            )

            log.info(
                "lm_w8a8: int8 W8A8 matmuls at %d sites (per-token "
                "activation scales), %.2f GB param tree",
                w8a8_site_count(self.params),
                tree_nbytes(self.params) / 1e9)
        self._init_spec_decode(cfg, weights_dir)
        # roofline attribution (obs/costmodel.py): dense decode costs
        # 2·N(params) FLOPs per token processed; resolved lazily (the
        # committed cost model for the production LM, the same formula
        # over this tree otherwise) and accumulated per dispatch.
        # THREAD-LOCAL: concurrent generate_batch callers (two rooms
        # buffering rounds from separate executor threads) must each
        # read their OWN dispatch's total, and a decode that raises
        # attributes nothing (reset at decode entry) instead of the
        # previous successful dispatch's figure
        self._flops_per_token: Optional[float] = None
        self._decode_flops_tls = threading.local()
        # per-thread invalid-row indices from the LAST decode_ids_batch
        # on this thread (same ownership rationale as the flops TLS):
        # generate_batch reads it to fail exactly the poisoned rows
        self._decode_invalid_tls = threading.local()

    def _token_flops(self) -> float:
        """Analytic FLOPs per token processed (prefill or decode)."""
        if self._flops_per_token is None:
            from cassmantle_tpu.obs import costmodel

            self._flops_per_token = costmodel.flops_per_item(
                "prompt",
                costmodel.lm_signature(
                    self.mcfg, w8a8=lm_w8a8_armed(self.cfg.models)),
                tracer=lambda: 2.0 * self.family.active_params(
                    self.params, self.mcfg),
            ) or 0.0
        return self._flops_per_token

    def _init_spec_decode(self, cfg: FrameworkConfig, weights_dir) -> None:
        """Build the draft source for speculative decoding
        (ops/decode.py). ``self._spec_draft`` is None when off; else a
        static NgramDraft/ModelDraft whose identity is stable for the
        life of the generator (it keys the jit cache). Stats of the
        most recent spec decode land in ``self.last_spec_stats``."""
        from cassmantle_tpu.ops.decode import ModelDraft, NgramDraft

        spec = cfg.spec_decode
        self._spec_draft = None
        self._spec_draft_params = None
        # re-runnable loader for a SEPARATE draft tree (reload_params);
        # the self-draft arm shares self.params and needs no loader
        self._spec_params_loader = None
        self.last_spec_stats = None
        if spec.mode == "off":
            return
        if spec.mode == "ngram":
            self._spec_draft = NgramDraft(ngram=spec.ngram)
            return
        assert spec.mode == "draft_model", \
            f"unknown spec_decode.mode {spec.mode!r}"
        d = spec.draft_model
        assert d is not None, "spec_decode.mode='draft_model' needs a " \
                              "draft_model config"
        assert d.vocab_size == self.mcfg.vocab_size, (
            "draft and target must share a tokenizer/vocab "
            f"({d.vocab_size} vs {self.mcfg.vocab_size}) — speculative "
            "acceptance compares token ids directly")
        if self.family.name == "gpt2" and d == cfg.models.gpt2:
            # self-draft degenerate: reuse the target's (possibly
            # quantized) apply fns and params — no second tree
            self._spec_draft = ModelDraft(self._prefill, self._step)
            self._spec_draft_params = self.params
            return
        from cassmantle_tpu.models.weights import convert_gpt2
        from cassmantle_tpu.ops.decode import make_apply_fns

        draft_model = GPT2LM(d)

        def load_draft_params() -> None:
            loaded = maybe_load(
                weights_dir, "gpt2_draft.safetensors",
                lambda t: convert_gpt2(t, d.num_layers, d.hidden_size),
                "gpt2_draft", cast_to=cfg.models.param_dtype)
            self._spec_draft_params = (
                loaded if loaded is not None
                else init_params_cached(
                    draft_model, 6, jnp.zeros((1, 8), dtype=jnp.int32),
                    cache_path=param_cache_path("gpt2_draft", d),
                    cast_to=cfg.models.param_dtype))

        self._spec_params_loader = load_draft_params
        load_draft_params()
        d_prefill, d_step, _ = make_apply_fns(draft_model)
        self._spec_draft = ModelDraft(d_prefill, d_step)

    def reload_params(self) -> None:
        """Device-loss rebuild (serving/device_recovery.py): re-run the
        boot load path (fingerprint-verified reads, int8 transform) and
        republish the tree. The draft source object keeps its identity
        (it keys the jit cache — replacing it would recompile the spec
        graphs); only its PARAMS refresh: the self-draft arm re-shares
        the target tree, a separate draft tree re-loads."""
        shared_draft = self._spec_draft_params is self.params
        self._param_loader()
        if shared_draft:
            self._spec_draft_params = self.params
        elif self._spec_params_loader is not None:
            self._spec_params_loader()

    def _spec_enabled(self, bucket: int, max_new: int) -> bool:
        """Host-side, per bucket group: the spec path engages only for
        greedy decodes (temperature 0 — where acceptance is exact and
        output provably identical), only when the chunk scratch tail
        still fits the model's position table (the last chunk appends up
        to gamma past the budget), and only with the kill switch clear."""
        if self._spec_draft is None:
            return False
        if self.cfg.sampler.text_temperature > 0.0:
            return False
        if os.environ.get("CASSMANTLE_NO_SPEC_DECODE", "").lower() \
                not in ("", "0", "false", "no", "off"):
            return False
        gamma = self.cfg.spec_decode.gamma
        return bucket + max_new + gamma + 1 <= self.mcfg.max_positions

    def _load_int8_checkpoint(self, name: str, weights_dir):
        """Pre-quantized checkpoint (tools/quantize_weights.py): int8
        straight from disk — no fp pass, half the read bytes. Returns
        None (-> normal fp path) when the file is absent, STALE (the fp
        checkpoint is newer — an operator re-fetched weights without
        re-quantizing), or structurally unloadable (e.g. the model
        config changed since quantization)."""
        if not (self._int8_path and os.path.exists(self._int8_path)):
            return None
        fp_path = os.path.join(weights_dir, f"{name}.safetensors")
        if os.path.exists(fp_path) and \
                os.path.getmtime(fp_path) > os.path.getmtime(self._int8_path):
            log.warning(
                "%s is older than %s; re-quantizing from the fp "
                "checkpoint (run quantize-weights to refresh)",
                self._int8_path, fp_path)
            return None
        from cassmantle_tpu.ops.quant import load_quantized

        log.info("%s: loading quantized %s", name, self._int8_path)
        try:
            return jax.tree_util.tree_map(
                jnp.asarray, load_quantized(self._int8_path))
        # lint: ignore[swallowed-error] — load-time degrade: the fp fallback is the documented recovery, logged with the re-quantize instruction; serving correctness is unaffected
        except Exception:
            log.exception(
                "quantized checkpoint %s failed to load (model config "
                "changed since quantization?); falling back to the fp "
                "path", self._int8_path)
            return None

    def save_quantized(self, path: Optional[str] = None) -> str:
        """Persist the (quantized) param tree so later boots load int8
        straight from disk. Requires lm_int8; default path is the
        weights-dir convention the constructor checks."""
        assert self.cfg.models.lm_int8, "construct with lm_int8=True first"
        from cassmantle_tpu.ops.quant import save_quantized

        path = path or self._int8_path
        assert path, "no weights_dir: pass an explicit path"
        save_quantized(self.params, path)
        return path

    # Batch-size buckets: concurrent prompt requests coalesce into one
    # decode whose batch dim pads to the next bucket, so the jitted
    # greedy_decode graph is reused across calls instead of recompiling
    # per batch size (the image pipeline's bucket discipline applied to
    # text; reference issues one hosted LLM call per prompt,
    # backend.py:240-268, and cannot batch at all).
    BATCH_BUCKETS = (1, 2, 4, 8)

    def _bucket_for(self, n_tokens: int, max_new: int, limit: int) -> int:
        m = self.mcfg
        return next(
            (b for b in self.PROMPT_BUCKETS
             if n_tokens <= b and b + max_new <= m.max_positions),
            limit,
        )

    def decode_ids_batch(self, seed_texts: Sequence[str],
                         max_new_tokens: Optional[int] = None,
                         seed: Optional[int] = None):
        """Batched continuation at the token level: N seed texts -> ONE
        bucketed prefill + cached decode scan; returns (tokens (N,
        max_new), gen_len (N,)).

        The batch runs in the program of its WIDEST row's prompt bucket.
        A row whose own bucket is narrower carries the difference as its
        position offset (``greedy_decode``): its generated token ``i``
        sits at position ``own_bucket + i`` while its cache slot is
        ``P + i``, so it decodes at the same positions and by the same
        mathematics as a decode of its own, whatever it was batched
        with; the masked slots between its prompt and its tokens differ
        (greedy; sampled rows draw per-row independent Gumbel noise). The
        batch dim pads to the next BATCH_BUCKETS size with 1-token dummy
        rows (decoded then dropped), keeping both shape axes static
        across calls.

        Where rows cannot share a program, they group by each prompt's
        OWN bucket, one dispatch a group: under speculative decode (its
        lockstep commit and draft context are laid out by cache slot)
        and for a family without ``mixed_buckets`` (Mistral: the sliding
        window counts slots). No benchmark cell runs either.

        Decode mode comes from the config (text_temperature=0 -> greedy,
        the reference behavior; >0 -> top-k sampling keyed on ``seed``,
        auto-advanced per call so sampled stories vary round to round)."""
        assert len(seed_texts) > 0, "decode_ids_batch needs >=1 prompt"
        m = self.mcfg
        max_new = max_new_tokens or self.cfg.sampler.max_new_tokens
        limit = m.max_positions - max_new - 1
        out_tokens = np.zeros((len(seed_texts), max_new), dtype=np.int32)
        out_len = np.zeros((len(seed_texts),), dtype=np.int32)
        spec_stats = []
        routed = []  # a sparse LM's routing counters, one tree a dispatch
        dispatched = []  # (row indices, real rows, tokens, gen_len)
        dispatch_flops = 0.0
        self._decode_flops_tls.value = 0.0  # failed decodes attr nothing
        self._decode_invalid_tls.value = ()
        bad_members: set = set()
        # the host's part before the device takes the batch: tokenizer,
        # buckets, the arrays and their copies to the device, the key and
        # the jitted call's own dispatch (pipeline.lm_prep); the wait for
        # the lock is its own span, before it
        with self._dispatch_lock, host_span("pipeline.lm_prep"):
            rows = []
            for text in seed_texts:
                toks = self.tokenizer.encode(text)
                rows.append(toks[-limit:] if len(toks) > limit else toks)
            if seed is None:
                seed = self._decode_calls
                self._decode_calls += 1
            own = [self._bucket_for(len(toks), max_new, limit)
                   for toks in rows]
            groups: dict = {}
            for i, bucket in enumerate(own):
                groups.setdefault(bucket, []).append(i)
            if self.family.mixed_buckets and not any(
                    self._spec_enabled(b, max_new) for b in groups):
                groups = {max(groups): list(range(len(rows)))}
            for bucket, idxs in groups.items():
                n = len(idxs)
                fault_point("device.lost", peer="prompt")
                n_pad = next((b for b in self.BATCH_BUCKETS if n <= b), n)
                # roofline attribution: the dispatched shapes are fixed —
                # n_pad rows prefill `bucket` tokens then run max_new
                # decode steps regardless of eos (masked, not skipped), so
                # the device work is exactly these tokens (spec decode
                # bounds the same budget; greedy-equivalent estimate)
                dispatch_flops += self._token_flops() * n_pad * (
                    bucket + max_new)
                # pad id normalized into the MODEL's vocab: the
                # byte-fallback tokenizer's pad (258) can exceed a small
                # model vocab, and an out-of-range id NaN-fills flax
                # Embed's take — the NaN then leaks through prefill into
                # every decoded token
                ids = np.full((n_pad, bucket),
                              self.tokenizer.pad_id % m.vocab_size,
                              dtype=np.int32)
                lens = np.ones((n_pad,), dtype=np.int32)  # dummies: 1 pad
                offsets = np.zeros((n_pad,), dtype=np.int32)
                for row, src in enumerate(idxs):
                    toks = rows[src]
                    # lint: ignore[host-sync] — toks is a host token list
                    ids[row, : len(toks)] = np.asarray(toks) % m.vocab_size
                    lens[row] = max(1, len(toks))
                    offsets[row] = own[src] - bucket
                # an out-of-vocab eos (byte-fallback tokenizer vs a smaller
                # model vocab) can never be emitted: pass vocab_size as an
                # unreachable sentinel so early-stop is cleanly disabled —
                # a modulo here would ALIAS a real token as a phantom
                # terminator and silently truncate generations
                eos = (self.tokenizer.eos_id
                       if self.tokenizer.eos_id < m.vocab_size
                       else m.vocab_size)
                if self._spec_enabled(bucket, max_new):
                    from cassmantle_tpu.ops.decode import speculative_decode

                    with block_timer("decode.verify_s") as sink:
                        # draft + verify fuse into one device computation;
                        # the spec_draft/spec_verify named scopes split
                        # the two in a device trace
                        tokens, gen_len, stats = speculative_decode(
                            (self._prefill, self._step, self._chunk),
                            self.params,
                            jnp.asarray(ids),
                            jnp.asarray(lens),
                            max_new,
                            eos,
                            self.cfg.spec_decode.gamma,
                            self._spec_draft,
                            self._spec_draft_params,
                            # dummy pad rows must not throttle the lockstep
                            # accept-min; their rows are dropped below
                            jnp.asarray(np.arange(n_pad) < n),
                        )
                        sink.append(tokens)  # device-synchronized span
                    spec_stats.append(stats)
                else:
                    stats_fn = self.family.cache_stats
                    tokens, gen_len, *stats = greedy_decode(
                        (self._prefill, self._step),
                        self.params,
                        jnp.asarray(ids),
                        jnp.asarray(lens),
                        jax.random.PRNGKey(seed),
                        max_new,
                        eos,
                        self.cfg.sampler.text_temperature,
                        self.cfg.sampler.text_top_k,
                        position_offset=jnp.asarray(offsets),
                        **(dict(row_mask=jnp.asarray(np.arange(n_pad) < n),
                                cache_stats=stats_fn) if stats_fn else {}),
                    )
                    routed += stats
                dispatched.append((idxs, n, tokens, gen_len))
        # one sync per DISPATCH (not per row): its result must land
        # before its rows scatter into the output
        landed = [integrity.poison(
            # lint: ignore[host-sync] — per-dispatch sync, not per-item
            np.asarray(tokens[:n]), peer="prompt")
            for _idxs, n, tokens, _len in dispatched]
        # the host's tail, tokens on the host -> return (pipeline.lm_tail;
        # generate_batch's own follows)
        with host_span("pipeline.lm_tail"):
            for (idxs, n, _tokens, gen_len), toks_host in zip(dispatched,
                                                              landed):
                if not integrity.integrity_disabled():
                    # token-range validity on the just-transferred array —
                    # no extra sync. Tokens are ints, so finiteness can't
                    # carry the verdict here; range IS the sentinel: a dead
                    # runtime hands back garbage buffers, and the chaos
                    # poison fills -1 — both land outside [0, vocab).
                    ok = ((toks_host >= 0)
                          & (toks_host < m.vocab_size)).all(axis=1)
                    bad_members.update(
                        idxs[row] for row in np.nonzero(~ok)[0])
                out_tokens[idxs] = toks_host
                # lint: ignore[host-sync] — per-dispatch sync, not per-item
                out_len[idxs] = np.asarray(gen_len[:n])
                if lm_w8a8_armed(self.cfg.models):
                    # one int8-kernel decode dispatch (the gpt2_w8a8 bench
                    # A/B's proof the path engaged)
                    metrics.inc("pipeline.w8a8_dispatches")
            self._record_spec_stats(spec_stats)
            note_moe_counters(routed)
            self._decode_flops_tls.value = dispatch_flops
            self._decode_invalid_tls.value = tuple(sorted(bad_members))
            return jnp.asarray(out_tokens), jnp.asarray(out_len)

    def _record_spec_stats(self, spec_stats) -> None:
        """ONE host transfer for the whole decode batch's spec counters
        (after the per-group dispatch loop — never per chunk):
        ``decode.spec_chunks`` counts verify forwards and
        ``decode.spec_accept_rate`` gauges accepted/drafted, the number
        that says whether the draft source is paying for itself."""
        if not spec_stats:
            return
        # stack the per-group device stats, then ONE transfer + sum
        chunks, drafted, accepted = np.asarray(
            jnp.stack(list(spec_stats))).sum(axis=0).tolist()
        self.last_spec_stats = {
            "chunks": chunks, "drafted": drafted, "accepted": accepted,
            "accept_rate": (accepted / drafted) if drafted else 0.0,
        }
        metrics.inc("decode.spec_chunks", chunks)
        if drafted:
            metrics.gauge("decode.spec_accept_rate", accepted / drafted)

    def decode_ids(self, seed_text: str,
                   max_new_tokens: Optional[int] = None,
                   seed: Optional[int] = None):
        """Single-prompt continuation: the B=1 case of
        :meth:`decode_ids_batch` (one code path, so the benchmark and
        the batched serving queue measure the same computation).
        Returns (tokens (1, max_new), gen_len (1,))."""
        return self.decode_ids_batch([seed_text], max_new_tokens, seed)

    def generate_batch(self, seed_texts: Sequence[str],
                       max_new_tokens: Optional[int] = None) -> List:
        """Batched greedy continuation: one device dispatch for N texts,
        each trimmed to its first two sentences (reference
        backend.py:253-265).

        Rows the integrity sentinel rejected come back as
        :class:`~cassmantle_tpu.serving.integrity.OutputInvalid`
        INSTANCES in their slots (not raised): the prompt queue's
        per-member distribution fails exactly those requests while the
        healthy rows of the same dispatch still serve."""
        # flops_est is a callable: the bucket grouping (and so the
        # dispatched token count) is only known after decode_ids_batch
        # runs; block_timer evaluates it at exit, on THIS thread (the
        # thread-local is written by the decode_ids_batch call below)
        with block_timer("pipeline.prompt_s",
                         flops_est=lambda: getattr(
                             self._decode_flops_tls, "value", 0.0),
                         pipeline="prompt") as sink:
            out_tokens, gen_len = self.decode_ids_batch(
                seed_texts, max_new_tokens)
            sink.append(out_tokens)
        # the rest of the host's tail (decode_ids_batch observed its own
        # part): ONE device->host transfer for the whole batch — the
        # per-row int(gen_len[i]) / np.asarray(out_tokens[i]) this loop
        # used to do was a sync per text (the host-sync lint's
        # serialization hazard, tools/check_concurrency.py) — detokenizing
        with host_span("pipeline.lm_tail"):
            out_tokens = np.asarray(out_tokens)
            lengths = np.asarray(gen_len).tolist()
            bad = frozenset(
                getattr(self._decode_invalid_tls, "value", ()) or ())
            if bad:
                integrity.note_invalid("prompt", "decode", sorted(bad))
            texts = []
            for i in range(len(seed_texts)):
                if i in bad:
                    # never decode a rejected row — garbage/poisoned ids
                    # must not reach the tokenizer, let alone a player
                    texts.append(integrity.OutputInvalid(
                        "prompt", "decode", [i]))
                    continue
                texts.append(two_sentences(self.tokenizer.decode(
                    out_tokens[i, : lengths[i]].tolist())))
            return texts

    def generate(self, seed_text: str, max_new_tokens: Optional[int] = None
                 ) -> str:
        """Greedy continuation of ``seed_text`` (the reference decodes
        32-96 tokens then keeps the first two sentences,
        backend.py:253-265). Raises
        :class:`~cassmantle_tpu.serving.integrity.OutputInvalid` when
        the integrity sentinel rejects the row (retriable)."""
        out = self.generate_batch([seed_text], max_new_tokens)[0]
        if isinstance(out, Exception):
            raise out
        return out


def sanitize_text(text: str) -> str:
    """Strip non-printable characters from generated text."""
    return "".join(c for c in text if c.isprintable() or c == " ").strip()


def two_sentences(text: str) -> str:
    """Trim generated text to its first two sentences (reference
    backend.py:265 keeps ``'.'.join(parts[:2]) + '.'``)."""
    parts = [p.strip() for p in text.split(".")]
    keep = [p for p in parts[:2] if p]
    if not keep:
        return text.strip() or "An empty page waited."
    return ". ".join(keep) + "."


class TPUContentBackend(ContentBackend):
    """Production ContentBackend: GPT-2 episode text + diffusion image.

    Heavy device calls run in a thread-pool executor so the asyncio game
    loop (clock ticks, WS pushes) stays responsive while the DDIM scan is
    on device — the async-over-sync bridge (SURVEY.md §7 hard part (d)).
    """

    def __init__(
        self,
        cfg: FrameworkConfig,
        weights_dir: Optional[str] = None,
        styles: Optional[List[str]] = None,
        rng: Optional[random.Random] = None,
        mesh=None,
        t2i=None,
    ) -> None:
        from cassmantle_tpu.server.assets import load_styles

        self.cfg = cfg
        if t2i is not None:
            # caller-owned pipeline (e.g. one already compiled for this
            # mesh); skips a duplicate param init + jit compile
            self.t2i = t2i
        elif cfg.models.clip_text_2 is not None:
            # SDXL config (both text towers): serve rounds at SDXL-1024,
            # the reference's actual image model (backend.py:24).
            from cassmantle_tpu.serving.sdxl import SDXLPipeline

            self.t2i = SDXLPipeline(cfg, weights_dir, mesh=mesh)
        else:
            self.t2i = Text2ImagePipeline(cfg, weights_dir, mesh=mesh)
        self.prompt_gen = PromptGenerator(cfg, weights_dir)
        self.styles = styles or load_styles()
        self.rng = rng or random.Random(cfg.seed)
        self._round = 0
        # rounds render in the order generate() was called (the order
        # their texts came back), not the order their executor threads
        # won the interpreter: the order of the rooms is then the same
        # in every orbit of a loop that hands over several texts at once
        self._turns = Turns()

    def _style_prompt(self, prompt: str) -> str:
        style = self.rng.choice(self.styles)
        return f"A {style.lower()} style piece depicting: {prompt}"

    def generate_sync(self, seed: str, is_seed: bool,
                      text: Optional[str] = None) -> RoundContent:
        """``text`` lets a caller inject an already-decoded continuation
        (the InferenceService prompt queue batches decodes across
        concurrent round generations); None decodes here, single."""
        from cassmantle_tpu.engine.content import template_text
        from cassmantle_tpu.utils.text import is_wordlike, tokenize_words

        if text is None:
            text = self.prompt_gen.generate(seed)
        text = sanitize_text(text)
        wordy = sum(is_wordlike(t) for t in tokenize_words(text))
        if wordy < self.cfg.game.num_masked + 1:
            # degenerate LM output (e.g. random weights): keep the round
            # playable with deterministic template text.
            log.warning("degenerate generated text; using template fallback")
            metrics.inc("pipeline.text_fallbacks")
            text = template_text(seed)
        self._round += 1
        images = self.t2i.generate(
            [self._style_prompt(text)], seed=self._round
        )
        return RoundContent(prompt_text=text, image=images[0])

    async def generate(self, seed: str, is_seed: bool,
                       text: Optional[str] = None) -> RoundContent:
        loop = asyncio.get_running_loop()
        # run_in_executor does not carry contextvars: copy the context
        # so the round-generation trace follows onto the worker thread
        # (the pipeline's block_timer stage spans land in it)
        ctx = contextvars.copy_context()
        ticket = self._turns.take()

        def in_turn():
            with self._turns.holding(ticket):
                return self.generate_sync(seed, is_seed, text)

        try:
            return await loop.run_in_executor(None, ctx.run, in_turn)
        finally:
            # cancelled before its thread started: nobody waits for it
            self._turns.leave(ticket)
