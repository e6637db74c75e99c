"""SDXL-base text→image pipeline: dual text towers + micro-conditioning,
data-parallel over the device mesh.

The reference's image generation IS a remote SDXL-base-1.0 call
(reference backend.py:24, 270-295); this is its local TPU replacement at
full 1024×1024 scale — the "SDXL-base 1024, batched prompts, data-parallel"
rung of the BASELINE.md workload ladder. SD1.5 serving lives in
serving/pipeline.py; this pipeline adds the SDXL-specific conditioning:

- TWO text towers (CLIP ViT-L + OpenCLIP bigG), each contributing its
  second-to-last hidden state, concatenated to the 2048-dim UNet context;
- pooled bigG embedding + sinusoidal size/crop "time ids" fed through the
  UNet's addition-embedding MLP (micro-conditioning);
- VAE with the 0.13025 SDXL scaling factor.

Parallelism is batch data-parallel over the mesh's ``dp`` axis via
``jax.jit`` in/out shardings: token ids arrive batch-sharded, params are
replicated by GSPMD, and each device denoises its shard of the batch —
collective-free in the forward pass, so throughput scales linearly over
ICI. The whole CLIP→DDIM→VAE trajectory is still ONE XLA computation.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from cassmantle_tpu.config import FrameworkConfig
from cassmantle_tpu.models.clip_text import ClipTextEncoder
from cassmantle_tpu.models.layers import timestep_embedding
from cassmantle_tpu.models.unet import UNet
from cassmantle_tpu.models.vae import VAEDecoder, postprocess_images
from cassmantle_tpu.models.weights import (
    convert_clip_text,
    convert_clip_text_projection,
    convert_tensors,
    convert_unet,
    convert_vae_decoder,
    init_params_cached,
    load_checkpoint_tensors,
    maybe_load,
)
from cassmantle_tpu.ops.ddim import initial_latents
from cassmantle_tpu.ops.samplers import make_sampler
from cassmantle_tpu.serving import integrity
from cassmantle_tpu.utils.compile_cache import (
    enable_compile_cache,
    param_cache_path,
)
from cassmantle_tpu.utils.logging import get_logger, metrics
from cassmantle_tpu.utils.profiling import block_timer, host_span
from cassmantle_tpu.utils.tokenizers import load_tokenizer

log = get_logger("sdxl")


class SDXLPipeline:
    """prompts -> (B, 1024, 1024, 3) uint8; batch-DP over ``mesh``'s dp axis.

    Build ``cfg`` with :func:`cassmantle_tpu.config.sdxl_config` (or the
    tiny :func:`test_sdxl_config` on CPU). With ``mesh=None`` it runs
    single-device, same as the SD1.5 pipeline.
    """

    def __init__(
        self,
        cfg: FrameworkConfig,
        weights_dir: Optional[str] = None,
        mesh: Optional[Mesh] = None,
        share_params_with: "Optional[SDXLPipeline]" = None,
    ) -> None:
        """``share_params_with``: reuse another SDXL pipeline's loaded
        param trees (device buffers shared, nothing copied) when the
        architectures match — pipelines that differ only in sampler
        then hold ONE set of the multi-GB SDXL weights in HBM instead
        of two. Stricter than the SD1.5 donor contract: both text towers
        and the int8 flag must match exactly (SDXL has no
        int8-asymmetry re-load path)."""
        enable_compile_cache()
        m = cfg.models
        assert m.clip_text_2 is not None, (
            "SDXL needs both text towers; use config.sdxl_config()"
        )
        assert m.unet.addition_embed_dim > 0, "SDXL UNet needs micro-conds"
        self.cfg = cfg
        self.mesh = mesh
        self.clip = ClipTextEncoder(m.clip_text)
        self.clip2 = ClipTextEncoder(m.clip_text_2)
        self.unet = UNet(m.unet)
        self.vae = VAEDecoder(m.vae)
        # Both towers share the CLIP BPE vocabulary.
        self.tokenizer = load_tokenizer(
            weights_dir, "clip", m.clip_text.vocab_size
        )
        self.pad_len = min(cfg.sampler.prompt_pad_len,
                           m.clip_text.max_positions,
                           m.clip_text_2.max_positions)
        self.vae_scale = 2 ** (len(m.vae.channel_mults) - 1)
        # addition vector = pooled bigG ++ 6 sinusoidal time-id embeddings
        self.time_id_dim = (
            m.unet.addition_embed_dim - m.clip_text_2.hidden_size
        ) // 6
        assert self.time_id_dim > 0, (
            "addition_embed_dim must exceed the bigG pooled width"
        )

        lat_hw = cfg.sampler.image_size // self.vae_scale
        lat = jnp.zeros((1, lat_hw, lat_hw, 4), dtype=jnp.float32)
        t0 = jnp.zeros((1,), dtype=jnp.int32)
        ctx = jnp.zeros((1, self.pad_len, m.unet.context_dim),
                        dtype=jnp.float32)
        add = jnp.zeros((1, m.unet.addition_embed_dim), dtype=jnp.float32)
        from cassmantle_tpu.serving.pipeline import (
            int8_unet_tools,
            w8a8_unet_tools,
        )

        unet_transform, wrap_unet_apply = int8_unet_tools(m)
        w8a8_transform = w8a8_unet_tools(m)
        if w8a8_transform is not None:
            # mutually exclusive with unet_int8 (asserted inside), so
            # the int8 slot is free (see Text2ImagePipeline)
            unet_transform = w8a8_transform

        def load_all_params() -> None:
            """Load/convert/share every stage tree and publish it on
            ``self``. Boot runs this once; a device-loss rebuild
            (serving/device_recovery.py, via :meth:`reload_params`)
            runs it again onto the fresh runtime."""
            if share_params_with is not None:
                from cassmantle_tpu.serving.pipeline import (
                    share_compatible,
                    unet_w8a8_armed,
                )

                donor = share_params_with
                dm = donor.cfg.models
                assert share_compatible(dm, m) \
                    and dm.clip_text_2 == m.clip_text_2 \
                    and dm.unet_int8 == m.unet_int8 \
                    and unet_w8a8_armed(dm) == unet_w8a8_armed(m), (
                        "share_params_with needs matching SDXL "
                        "architectures (incl. quantization mode)"
                    )
                self.clip_params = donor.clip_params
                self.clip2_params = donor.clip2_params
                self.clip2_proj = donor.clip2_proj
                self.unet_params = donor.unet_params
                self.vae_params = donor.vae_params
                return
            ids = jnp.zeros((1, self.pad_len), dtype=jnp.int32)
            self.clip_params = (
                maybe_load(weights_dir, "clip_text.safetensors",
                           lambda t: convert_clip_text(
                               t, m.clip_text.num_layers),
                           "clip_text", cast_to=m.param_dtype)
                or init_params_cached(
                    self.clip, 1, ids,
                    cache_path=param_cache_path("clip_text",
                                                m.clip_text),
                    cast_to=m.param_dtype)
            )
            # read once: the same file carries the tower AND its
            # text_projection (data/manifests/clip_bigg.json)
            t2 = load_checkpoint_tensors(
                weights_dir, "clip_text_2.safetensors", "clip_text_2")
            converted2 = convert_tensors(
                t2, lambda t: convert_clip_text(
                    t, m.clip_text_2.num_layers),
                "clip_text_2", cast_to=m.param_dtype)
            self.clip2_params = (
                converted2
                if converted2 is not None
                else init_params_cached(
                    self.clip2, 11, ids,
                    cache_path=param_cache_path("clip_text_2",
                                                m.clip_text_2),
                    cast_to=m.param_dtype)
            )
            # Real SDXL conditions on text_projection(pooled) — the
            # CLIPTextModelWithProjection text_embeds — not the raw
            # pooled state; skipping the (square, 1280x1280) projection
            # would silently divert from the published model the moment
            # real weights load. Random init keeps identity behavior.
            self.clip2_proj = None
            if converted2 is not None and t2 is not None \
                    and "text_projection.weight" in t2:
                self.clip2_proj = jnp.asarray(
                    convert_clip_text_projection(t2),
                    dtype=jnp.dtype(m.param_dtype))
            # cache key on arch(): the fused-conv execution flags
            # (UNetConfig.fused_conv / conv_pad_to) don't change the
            # tree, so A/B arms share one cached init (see
            # serving/pipeline.py)
            self.unet_params = (
                maybe_load(weights_dir, "unet_xl.safetensors",
                           lambda t: convert_unet(t, m.unet), "unet_xl",
                           cast_to=m.param_dtype,
                           transform=unet_transform)
                or init_params_cached(
                    self.unet, 2, lat, t0, ctx, add,
                    cache_path=param_cache_path("unet_xl",
                                                m.unet.arch()),
                    cast_to=m.param_dtype, transform=unet_transform)
            )
            self.vae_params = (
                maybe_load(weights_dir, "vae_xl.safetensors",
                           lambda t: convert_vae_decoder(t, m.vae),
                           "vae_xl")
                or init_params_cached(
                    self.vae, 3, lat,
                    cache_path=param_cache_path(
                        f"vae_xl{cfg.sampler.image_size}",
                        m.vae.arch()))
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
        from cassmantle_tpu.serving.pipeline import (
            consistency_plan,
            effective_sampler_cfg,
            effective_sampler_steps,
        )

        # few-step consistency serving (see Text2ImagePipeline): fail
        # fast on invalid configs; the plain schedule below is the
        # teacher path the kill switch reverts to bit-exactly, and with
        # consistency ACTIVE run_cfg_denoise dispatches its own sampler
        # (no plain schedule to build)
        if cfg.sampler.consistency:
            consistency_plan(cfg.sampler)
        self.sample_latents = (
            None if effective_sampler_cfg(cfg.sampler).consistency
            else make_sampler(
                cfg.sampler.kind, effective_sampler_steps(cfg.sampler),
                eta=cfg.sampler.eta))
        # Params are jit ARGUMENTS (device buffers), not captured constants
        # (see Text2ImagePipeline note on compile payloads).
        self._publish_params()

        from cassmantle_tpu.serving.pipeline import dp_sharded_sampler

        self._sample, self.dp = dp_sharded_sampler(
            self._sample_impl, mesh, "sdxl_sample")
        # rooms' images reach the device back to back, in turn (see
        # Text2ImagePipeline and serving/pipeline.py::ImageHandOver)
        from cassmantle_tpu.serving.pipeline import ImageHandOver
        from cassmantle_tpu.utils.locks import OrderedLock

        self._hand_over = ImageHandOver("pipeline.sdxl_dispatch", rank=11)
        # stage-disaggregated serving (serving/stages.py); supervisor is
        # wired by InferenceService, same as the SD1.5 pipeline
        self.supervisor = None
        self._staged = None
        self._staged_init_lock = OrderedLock("pipeline.staged_init",
                                             rank=13)
        # brownout tier variants (see Text2ImagePipeline._tier_fns)
        self._tier_fns: dict = {}
        # roofline attribution (see Text2ImagePipeline._flops_cache)
        self._flops_cache: dict = {}
        self._flops_lock = threading.Lock()
        self._flops_pending: set = set()

    def _publish_params(self) -> None:
        """See Text2ImagePipeline._publish_params: one tree for the
        jits, replicated over the mesh when there is one."""
        from cassmantle_tpu.serving.pipeline import replicate_on_mesh

        self._params = replicate_on_mesh({
            "clip": self.clip_params, "clip2": self.clip2_params,
            "clip2_proj": self.clip2_proj,  # None -> empty pytree leaf
            "unet": self.unet_params, "vae": self.vae_params,
        }, self.mesh)
        self.clip_params = self._params["clip"]
        self.clip2_params = self._params["clip2"]
        self.clip2_proj = self._params["clip2_proj"]
        self.unet_params = self._params["unet"]
        self.vae_params = self._params["vae"]

    def reload_params(self) -> None:
        """Device-loss rebuild (serving/device_recovery.py): re-run the
        boot load path and republish the tree (see
        Text2ImagePipeline.reload_params — same contract: params are
        jit ARGUMENTS, so nothing recompiles; the staged slot server is
        dropped and rebuilds lazily, and the image dispatch forgets its
        last program)."""
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

    # -- conditioning ------------------------------------------------------

    def _encode(self, params, ids: jax.Array) -> tuple:
        """ids -> (context (B,S,2048), pooled bigG (B,1280))."""
        out1 = self.clip.apply(params["clip"], ids)
        out2 = self.clip2.apply(params["clip2"], ids)
        context = jnp.concatenate(
            [out1["penultimate"], out2["penultimate"]], axis=-1
        )
        pooled = out2["pooled"]
        if params["clip2_proj"] is not None:  # None leaf: static at trace
            pooled = pooled @ params["clip2_proj"]
        return context, pooled

    def _time_ids(self, batch: int,
                  image_size: Optional[int] = None) -> jax.Array:
        """SDXL size/crop conditioning: (orig_h, orig_w, crop_t, crop_l,
        target_h, target_w), each sinusoidally embedded. ``image_size``
        overrides the configured resolution (brownout downshift)."""
        s = float(image_size if image_size is not None
                  else self.cfg.sampler.image_size)
        ids = jnp.asarray([s, s, 0.0, 0.0, s, s], dtype=jnp.float32)
        emb = timestep_embedding(ids, self.time_id_dim)  # (6, time_id_dim)
        flat = emb.reshape(-1)
        return jnp.broadcast_to(flat, (batch, flat.shape[0]))

    # -- sampling ----------------------------------------------------------

    def _sample_impl(self, params, ids, uncond_ids, rng):
        return self._build_tier_impl(
            self.cfg.sampler, self.sample_latents)(
                params, ids, uncond_ids, rng)

    def _tokenize(self, prompts: Sequence[str]) -> np.ndarray:
        from cassmantle_tpu.serving.pipeline import tokenize_clip_prompts

        return tokenize_clip_prompts(
            self.tokenizer, prompts, self.pad_len,
            self.cfg.models.clip_text.vocab_size,
        )

    # -- stage-disaggregated serving (serving/stages.py) -------------------

    def _staged_enabled(self) -> bool:
        """Same routing decision as Text2ImagePipeline._staged_enabled
        (one seam, two pipelines)."""
        from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

        return Text2ImagePipeline._staged_enabled(self)

    def _encode_stage(self, params, ids, uncond_ids):
        """Encode-stage computation: exactly the dual-tower +
        micro-conditioning block of ``_sample_impl`` (rows are
        batch-independent, so staged rows match monolithic bit for
        bit)."""
        ctx, pooled = self._encode(params, ids)
        uctx, uncond_pooled = self._encode(params, uncond_ids)
        time_ids = self._time_ids(ids.shape[0])
        return {
            "ctx": ctx,
            "uctx": uctx,
            "add": jnp.concatenate([pooled, time_ids], axis=-1),
            "uadd": jnp.concatenate([uncond_pooled, time_ids], axis=-1),
        }

    def _decode_stage(self, params, lat):
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

    def _build_tier_impl(self, scfg, sampler):
        """The SDXL sample impl bound to a sampler config: the
        pipeline's own (``_sample_impl``) or a degraded tier's, with
        (steps, size) swapped and the micro-conditioning
        time_ids tracking the size. Stage scopes as in the SD1.5
        pipeline, under the same names."""
        from cassmantle_tpu.serving.pipeline import (
            run_cfg_denoise,
            spatially_shard_latents,
        )

        def impl(params, ids, uncond_ids, rng):
            with jax.named_scope("clip_encode"):
                ctx, pooled = self._encode(params, ids)
                uctx, upooled = self._encode(params, uncond_ids)
            b = ids.shape[0]
            time_ids = self._time_ids(b, scfg.image_size)
            add = jnp.concatenate([pooled, time_ids], axis=-1)
            uadd = jnp.concatenate([upooled, time_ids], axis=-1)
            lat = initial_latents(rng, b, scfg.image_size,
                                  self.vae_scale)
            lat = spatially_shard_latents(lat, self.mesh)
            with jax.named_scope("denoise_scan"):
                final = run_cfg_denoise(
                    scfg, sampler, self.unet_apply,
                    params["unet"], ctx, uctx, lat,
                    addition_embeds=add,
                    uncond_addition_embeds=uadd,
                )
            with jax.named_scope("vae_decode"):
                decoded = self.vae.apply(params["vae"], final)
            return postprocess_images(decoded)

        return impl

    def _degraded_sampler(self):
        """Brownout actuation: the shared variant cache
        (`serving/pipeline.py::degraded_dispatch_variant`) with the
        SDXL impl builder."""
        from cassmantle_tpu.serving.pipeline import (
            degraded_dispatch_variant,
        )

        return degraded_dispatch_variant(
            self._tier_fns, self.cfg.sampler, self.mesh,
            self._build_tier_impl, log, "sdxl_sample")

    def _dispatch_flops(self, sample_fn, scfg):
        """Per-image analytic FLOPs (obs/costmodel.py): the shared
        Text2ImagePipeline resolver with the SDXL artifact key and
        signature (dispatch call shape is identical)."""
        from cassmantle_tpu.obs import costmodel
        from cassmantle_tpu.serving.pipeline import (
            Text2ImagePipeline,
            effective_sampler_cfg,
        )

        # sign what is DISPATCHED: under the consistency kill switch
        # the effective config is the teacher schedule (same contract
        # as the shared resolver's t2i signature path)
        return Text2ImagePipeline._dispatch_flops(
            self, sample_fn, scfg, kind="sdxl",
            signature=costmodel.sdxl_signature(
                self.cfg, effective_sampler_cfg(scfg)))

    def generate(self, prompts: Sequence[str], seed: int = 0,
                 deadline_s: Optional[float] = None) -> np.ndarray:
        """prompts -> (B, H, W, 3) uint8. Batch is padded to a multiple of
        the dp axis so every device holds an equal shard; pad rows are
        dropped before returning. With ``serving.staged_serving`` on the
        request rides the stage graph (see Text2ImagePipeline.generate);
        meshed serving stays monolithic."""
        from cassmantle_tpu.serving.pipeline import (
            IMAGE_BATCH_BUCKETS,
            note_consistency_counter,
            note_w8a8_counter,
        )

        degraded = self._degraded_sampler()
        if degraded is None and self._staged_enabled():
            images = self._staged_server().generate(
                list(prompts), seed, deadline_s=deadline_s)
            metrics.inc("pipeline.sdxl_images", len(prompts))
            note_consistency_counter(self.cfg.sampler, len(prompts))
            note_w8a8_counter(self.cfg.models, self.cfg.sampler,
                              len(prompts))
            return images
        from cassmantle_tpu.serving.pipeline import pad_prompts_to_dp

        # host preparation, lock wait, the hand-over's enqueue and
        # device-synchronized dispatch, host tail: the same spans as
        # Text2ImagePipeline.generate
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
        images = self._hand_over.dispatch(
            lambda: sample_fn(self._params, ids, uncond, rng),
            lambda: block_timer(
                "pipeline.sdxl_s",
                flops_est=(per_image * len(padded)) if per_image
                else None,
                pipeline="sdxl", attrs={"padded_rows": len(padded)}),
            peer="sdxl")
        with host_span("pipeline.image_host"):
            out = integrity.poison(np.asarray(images[:n]), peer="sdxl")
            # host-side degenerate-frame sentinel on the transferred
            # uint8 batch (the verdict stays OUT of the sample jit to
            # preserve staged-vs-monolithic bit-parity — see
            # Text2ImagePipeline)
            integrity.enforce(np.ones(n, dtype=bool), pipeline="sdxl",
                              stage="sample", images=out, n=n)
            metrics.inc("pipeline.sdxl_images", n)
            if degraded is not None:
                metrics.inc("pipeline.brownout_images", n)
            note_consistency_counter(scfg, n)
            note_w8a8_counter(self.cfg.models, scfg, n)
        return out
