"""Profile the SD1.5 UNet denoise step on the attached TPU.

Prints per-config step time, achieved TFLOP/s (from XLA's cost analysis),
and a flash-vs-XLA attention A/B at each spatial resolution, to target
optimization work.

Usage: python tools/profile_unet.py [batch] [--dump-hlo]

--dump-hlo additionally writes the backend-optimized HLO module (what
the TPU actually runs) to UNET_HLO.txt at the repo root.
"""

from __future__ import annotations

import functools
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import jax
import jax.numpy as jnp

from cassmantle_tpu.config import FrameworkConfig
from cassmantle_tpu.models.unet import UNet
from cassmantle_tpu.ops import attention as attn_mod
from cassmantle_tpu.utils.compile_cache import enable_compile_cache


def timeit(fn, *args, reps=10):
    """Thin adapter over tools/bench_parts.timeit (one timing
    methodology for all profilers), silencing its per-line print."""
    import contextlib
    import io

    try:
        from tools.bench_parts import timeit as _timeit
    except ImportError:  # run as `python tools/profile_unet.py`
        from bench_parts import timeit as _timeit

    with contextlib.redirect_stdout(io.StringIO()):
        return _timeit("", fn, *args, reps=reps)


def cost_table(fn, *args, top: int = 10):
    """Analytic per-op cost table from the jaxpr: FLOPs for every
    dot/conv (shape-derived — backend-independent, so it is valid even
    when compiled on CPU), grouped by (primitive, operand shapes),
    sorted by total FLOPs. The HARDWARE complement is the optimized-HLO
    dump (--dump-hlo) plus PROFILE_UNET.txt timings: this table says
    where the FLOPs are; the dump says what XLA fused around them.

    The per-eqn FLOP math is shared with the runtime cost model
    (cassmantle_tpu/obs/costmodel.py::eqn_flops), so this table, the
    committed cost-model artifact, and the live `pipeline.mxu_*`
    attribution can never disagree on what an op costs."""
    import collections

    from cassmantle_tpu.obs.costmodel import eqn_flops

    jaxpr = jax.make_jaxpr(fn)(*args)
    groups = collections.defaultdict(lambda: [0, 0.0])  # count, flops

    def visit(jx, mult: float = 1.0):
        for eqn in jx.eqns:
            # a scan body executes `length` times: its ops cost
            # length x (the full 50-step denoise loop would otherwise
            # count as one step)
            inner = mult
            if eqn.primitive.name == "scan":
                inner = mult * float(eqn.params.get("length", 1))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    visit(sub.jaxpr, inner)
                elif isinstance(sub, (list, tuple)):
                    for s in sub:
                        if hasattr(s, "jaxpr"):
                            visit(s.jaxpr, inner)
            name = eqn.primitive.name
            if name not in ("dot_general", "conv_general_dilated"):
                continue
            shapes = tuple(tuple(getattr(v.aval, "shape", ()))
                           for v in eqn.invars)
            flops = eqn_flops(eqn)
            key = (name, shapes)
            groups[key][0] += mult
            groups[key][1] += flops * mult

    visit(jaxpr.jaxpr)
    rows = sorted(groups.items(), key=lambda kv: -kv[1][1])
    total = sum(v[1] for v in groups.values())
    out_rows = []
    for (name, shapes), (count, flops) in rows[:top]:
        out_rows.append({
            "op": name,
            "shapes": "x".join(str(list(s)) for s in shapes[:2]),
            "count": int(count),
            "gflops": round(flops / 1e9, 2),
            "pct": round(100 * flops / total, 1) if total else 0.0,
        })
    return out_rows, total


# The analytic ceilings this tool prints, and the committed cost model's
# ``chip_tflops``, are for one v5e chip: its row of the peak table.
ANALYTIC_DEVICE_KIND = "TPU v5 lite"


def _image_cost_entry(kind: str, cfg) -> dict:
    """Per-stage analytic cost of one image pipeline (``t2i``/``sdxl``)
    at batch 1: eval_shape'd params (no init — the SDXL entry covers a
    2.6B tree in seconds on CPU), stage FLOPs/HBM-bytes from the same
    jaxpr walk the runtime uses (obs/costmodel.py::trace_cost). CFG
    factors are baked in per image: conditioning encodes cond+uncond
    (×2), the denoise stage runs 2·num_steps UNet forwards."""
    from cassmantle_tpu.models.clip_text import ClipTextEncoder
    from cassmantle_tpu.models.vae import VAEDecoder
    from cassmantle_tpu.obs import costmodel

    m = cfg.models
    s = cfg.sampler
    dtype = jnp.dtype(m.param_dtype)
    pad_len = min(s.prompt_pad_len, m.clip_text.max_positions)
    if kind == "sdxl":
        pad_len = min(pad_len, m.clip_text_2.max_positions)
    vae_scale = 2 ** (len(m.vae.channel_mults) - 1)
    lat_hw = s.image_size // vae_scale
    rng = jax.random.PRNGKey(0)
    ids = jax.ShapeDtypeStruct((1, pad_len), jnp.int32)
    lat = jax.ShapeDtypeStruct((1, lat_hw, lat_hw, 4), dtype)
    ts = jax.ShapeDtypeStruct((1,), jnp.int32)
    ctx = jax.ShapeDtypeStruct((1, pad_len, m.unet.context_dim), dtype)

    clip = ClipTextEncoder(m.clip_text)
    clip_params = jax.eval_shape(clip.init, rng, ids)
    enc_f, enc_b = costmodel.trace_cost(
        lambda p, i: clip.apply(p, i), clip_params, ids)
    unet = UNet(m.unet)
    if kind == "sdxl":
        clip2 = ClipTextEncoder(m.clip_text_2)
        clip2_params = jax.eval_shape(clip2.init, rng, ids)
        f2, b2 = costmodel.trace_cost(
            lambda p, i: clip2.apply(p, i), clip2_params, ids)
        enc_f, enc_b = enc_f + f2, enc_b + b2
        add = jax.ShapeDtypeStruct((1, m.unet.addition_embed_dim), dtype)
        unet_params = jax.eval_shape(unet.init, rng, lat, ts, ctx, add)
        unet_f, unet_b = costmodel.trace_cost(
            lambda p, l, t, c, a: unet.apply(p, l, t, c, a),
            unet_params, lat, ts, ctx, add)
        signature = costmodel.sdxl_signature(cfg)
    else:
        unet_params = jax.eval_shape(unet.init, rng, lat, ts, ctx)
        unet_f, unet_b = costmodel.trace_cost(
            lambda p, l, t, c: unet.apply(p, l, t, c),
            unet_params, lat, ts, ctx)
        signature = costmodel.t2i_signature(cfg)
    vae = VAEDecoder(m.vae)
    vae_params = jax.eval_shape(vae.init, rng, lat)
    vae_f, vae_b = costmodel.trace_cost(
        lambda p, z: vae.apply(p, z), vae_params, lat)

    # W8A8 serving (ISSUE 20): the fp trace above is still the FLOPs
    # proxy (the int8 kernels run the same dot/conv math on the MXU's
    # doubled int8 rate — a throughput factor, not an op-count change),
    # but weight-side HBM traffic halves at every quantized site: the
    # param read streams int8 instead of param_dtype per forward.
    w8a8 = _image_w8a8_armed(m)
    w8a8_elems = _w8a8_site_elements(unet_params, m.w8a8_min_size) \
        if w8a8 else 0
    unet_saved = w8a8_elems * (jnp.dtype(m.param_dtype).itemsize - 1)
    stages = {
        # cond + uncond conditioning per image
        "clip_encode": {"flops": int(2 * enc_f),
                        "hbm_bytes": int(2 * enc_b)},
        # CFG doubles every denoise forward
        "denoise": {"flops": int(2 * s.num_steps * unet_f),
                    "hbm_bytes": int(2 * s.num_steps
                                     * (unet_b - unet_saved))},
        "vae_decode": {"flops": int(vae_f), "hbm_bytes": int(vae_b)},
    }
    total_f = sum(st["flops"] for st in stages.values())
    total_b = sum(st["hbm_bytes"] for st in stages.values())
    buckets = (1, 2, 4, 8)
    return {
        "signature": signature,
        "image_size": s.image_size,
        "num_steps": s.num_steps,
        "sampler": s.kind,
        # few-step consistency preset (ISSUE 15): num_steps direct
        # forwards of the same UNet — the denoise math above already
        # covers it (2·num_steps CFG forwards)
        "consistency": bool(s.consistency),
        "w8a8": w8a8,
        "stages": stages,
        "flops_per_item": total_f,
        "hbm_bytes_per_item": total_b,
        # batch-linear (dot/conv flops scale with B): per-bucket totals
        "buckets": {str(b): total_f * b for b in buckets},
    }


def _image_w8a8_armed(models_cfg) -> bool:
    from cassmantle_tpu.serving.pipeline import unet_w8a8_armed

    return unet_w8a8_armed(models_cfg)


def _w8a8_site_elements(params, min_size: int) -> int:
    """Total weight-element count of w8a8-quantizable kernel sites in
    an eval_shape'd tree — the elements that stream int8 (1 byte)
    instead of param_dtype under W8A8 serving."""
    import math

    from cassmantle_tpu.ops.quant import w8a8_default_predicate

    total = 0

    def walk(tree, path=()):
        nonlocal total
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (k,))
        elif hasattr(tree, "shape") and w8a8_default_predicate(
                path, tree, min_size=min_size):
            total += math.prod(tree.shape)

    walk(params)
    return total


def _lm_cost_entry(cfg) -> dict:
    """Prompt-LM analytic cost: dense decode reads every weight per
    token — 2·N FLOPs and N·itemsize HBM bytes per token processed
    (PERF_NOTES "LM decode accounting"); N from an eval_shape init."""
    from cassmantle_tpu.models.gpt2 import GPT2LM
    from cassmantle_tpu.obs import costmodel
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    m = cfg.models.gpt2
    model = GPT2LM(m)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32))
    n = costmodel.params_count(params)
    per_token = 2 * n
    itemsize = jnp.dtype(cfg.models.param_dtype).itemsize
    # W8A8 (ISSUE 20): quantized matmul sites stream int8 weights —
    # same 2·N FLOPs per token, fewer weight-read bytes
    from cassmantle_tpu.serving.pipeline import lm_w8a8_armed

    w8a8 = lm_w8a8_armed(cfg.models)
    saved = _w8a8_site_elements(
        params, cfg.models.w8a8_min_size) * (itemsize - 1) if w8a8 else 0
    return {
        "signature": costmodel.lm_signature(m, w8a8=w8a8),
        "model": "gpt2",
        "params": n,
        "w8a8": w8a8,
        "flops_per_item": per_token,           # per token processed
        "hbm_bytes_per_item": n * itemsize - saved,
        "prompt_buckets": list(PromptGenerator.PROMPT_BUCKETS),
        "batch_buckets": list(PromptGenerator.BATCH_BUCKETS),
        "buckets": {str(b): per_token * b
                    for b in PromptGenerator.PROMPT_BUCKETS},
    }


def _scorer_cost_entry(cfg, seq_len: int = 16) -> dict:
    """MiniLM scorer analytic cost per encoded row (seq_len tokens)."""
    from cassmantle_tpu.models.minilm import MiniLMEncoder
    from cassmantle_tpu.obs import costmodel

    m = cfg.models.minilm
    model = MiniLMEncoder(m)
    seq_len = min(seq_len, m.max_positions)
    ids = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    mask = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, mask)
    n = costmodel.params_count(params)
    per_row = 2 * n * seq_len
    return {
        "signature": costmodel.scorer_signature(m, seq_len),
        "model": "minilm",
        "params": n,
        "seq_len": seq_len,
        "flops_per_item": per_row,             # per encoded row
        "hbm_bytes_per_item": n * 4,           # fp32 weight read
        "buckets": {str(b): per_row * b
                    for b in cfg.serving.score_batch_sizes},
    }


def _cost_model_configs() -> dict:
    """{entry name: (entry builder, config)} for the committed cost
    model: the production configs and their preset variants."""
    import dataclasses

    from cassmantle_tpu.config import (
        FrameworkConfig,
        lcm_serving_config,
        sdxl_config,
        w8a8_serving_config,
    )

    # the SDXL W8A8 arm: production SDXL geometry with the quantized
    # UNet path armed (same knobs w8a8_serving_config sets for SD1.5)
    sdxl_base = sdxl_config()
    sdxl_w8a8 = dataclasses.replace(
        sdxl_base, models=dataclasses.replace(
            sdxl_base.models,
            unet=dataclasses.replace(sdxl_base.models.unet,
                                     fused_conv=True, conv_pad_to=128),
            unet_w8a8=True))
    t2i = functools.partial(_image_cost_entry, "t2i")
    sdxl = functools.partial(_image_cost_entry, "sdxl")
    return {
        "t2i": (t2i, FrameworkConfig()),
        # the few-step consistency preset: same pipeline kind, the
        # committed 4-step geometry (resolved by signature scan —
        # obs/costmodel.py::committed_entry)
        "t2i_lcm": (t2i, lcm_serving_config()),
        "sdxl": (sdxl, sdxl_base),
        "prompt": (_lm_cost_entry, FrameworkConfig()),
        "scorer": (_scorer_cost_entry, FrameworkConfig()),
        # W8A8 serving variants (ISSUE 20): same analytic FLOPs,
        # weight-side HBM bytes halved at quantized sites — their
        # signatures differ (the armed w8a8 state digests in), so
        # quantized pipelines resolve these entries by scan
        "t2i_w8a8": (t2i, w8a8_serving_config()),
        "sdxl_w8a8": (sdxl, sdxl_w8a8),
        "prompt_w8a8": (_lm_cost_entry, w8a8_serving_config()),
    }


def _cost_model_entry(name: str) -> dict:
    builder, cfg = _cost_model_configs()[name]
    return builder(cfg)


def emit_cost_model(path: str) -> dict:
    """``--emit-cost-model``: write the machine-readable analytic cost
    model (FLOPs + HBM-bytes proxy per pipeline/stage/bucket for the
    PRODUCTION configs) the serving pipelines load at dispatch time
    (obs/costmodel.py). Everything is shape-derived under eval_shape —
    deterministic integers, no weights, runs on any backend —
    so the committed ``data/cost_model.json`` doubles as a drift gate
    (tests/test_obs_device.py regenerates and compares).

    The entries are traced in worker processes, side by side: tracing
    five UNet pipelines is ~50 s of pure Python in one process, the
    longest single test of a tier-1 window that is tight. Workers are
    spawned onto the CPU backend (an accelerator belongs to the parent,
    and shapes need none)."""
    import json
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from cassmantle_tpu.obs import costmodel

    names = list(_cost_model_configs())
    parent_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"   # inherited at spawn
    try:
        with ProcessPoolExecutor(
                max_workers=min(len(names), os.cpu_count() or 1),
                mp_context=multiprocessing.get_context("spawn")) as pool:
            pipelines = dict(zip(names, pool.map(_cost_model_entry, names)))
    finally:
        if parent_platforms is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = parent_platforms
    model = {
        "version": 1,
        "generated_by": "python tools/profile_unet.py --emit-cost-model",
        "chip_tflops": costmodel.peak_flops_for_kind(
            ANALYTIC_DEVICE_KIND) / 1e12,
        "note": ("analytic dot/conv FLOPs (obs/costmodel.py trace_cost; "
                 "same math as --cost-table); hbm_bytes is a roofline "
                 "proxy (operand+result buffer bytes, fusion ignored — "
                 "an upper bound on true traffic)"),
        "pipelines": pipelines,
    }
    with open(path, "w") as f:
        json.dump(model, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"cost model -> {path}")
    return model


def main():
    import argparse

    ap = argparse.ArgumentParser(
        description="Profile the SD1.5 UNet denoise step on the TPU")
    ap.add_argument("batch", nargs="?", type=int, default=8)
    ap.add_argument("--dump-hlo", action="store_true",
                    help="write the backend-optimized HLO to UNET_HLO.txt")
    ap.add_argument("--cost-table", action="store_true",
                    help="print the top-op analytic FLOP table "
                         "(shape-derived; valid on any backend) and exit")
    ap.add_argument("--full-pipeline", action="store_true",
                    help="with --cost-table: trace the WHOLE north-star "
                         "graph (CLIP encode + N-step CFG denoise scan, "
                         "scan body costs multiplied by its trip count, "
                         "+ VAE decode) instead of one UNet forward")
    ap.add_argument("--platform", default="auto", choices=("auto", "cpu"))
    ap.add_argument("--emit-cost-model", metavar="PATH",
                    help="write the machine-readable analytic cost model "
                         "(FLOPs + HBM bytes per pipeline/stage/bucket, "
                         "production configs, eval_shape only) the "
                         "serving pipelines load for live roofline "
                         "attribution, then exit; the committed copy is "
                         "data/cost_model.json")
    ap.add_argument("--sdxl", action="store_true",
                    help="with --cost-table: analyze the SDXL-base "
                         "geometry at 1024 instead of SD1.5-512 — the "
                         "SDXL ceiling accounting (VERDICT r5 weak #7). "
                         "Shape-only (jax.eval_shape params), so it "
                         "runs on any backend without the 2.6B init")
    opts = ap.parse_args()  # rejects unknown/typo'd flags
    if opts.platform == "cpu":
        from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

        pin_cpu_platform(virtual_devices=False)
    enable_compile_cache()
    if opts.emit_cost_model:
        emit_cost_model(opts.emit_cost_model)
        return
    batch = opts.batch
    if opts.sdxl:
        # Analytic-only path: abstract params via eval_shape (make_jaxpr
        # traces abstractly, so ShapeDtypeStructs suffice) — no init of
        # the 2.6B-param tree, runs in seconds on CPU.
        assert opts.cost_table, "--sdxl is a --cost-table mode"
        from cassmantle_tpu.config import sdxl_config

        xcfg = sdxl_config()
        ucfg = xcfg.models.unet
        model = UNet(ucfg)
        lat_hw = xcfg.sampler.image_size // 8  # 128 at 1024
        lat = jax.ShapeDtypeStruct((batch, lat_hw, lat_hw, 4),
                                   jnp.bfloat16)
        ts = jax.ShapeDtypeStruct((batch,), jnp.int32)
        ctx = jax.ShapeDtypeStruct((batch, 77, ucfg.context_dim),
                                   jnp.bfloat16)
        add = jax.ShapeDtypeStruct((batch, ucfg.addition_embed_dim),
                                   jnp.bfloat16)
        params = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((1, lat_hw, lat_hw, 4), jnp.bfloat16),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1, 77, ucfg.context_dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, ucfg.addition_embed_dim),
                                 jnp.bfloat16))
        rows, total = cost_table(
            lambda p, l, t, c, a: model.apply(p, l, t, c, a),
            params, lat, ts, ctx, add)
        steps = xcfg.sampler.num_steps
        per_img = total / batch * 2 * steps  # CFG doubles the forwards
        print(f"SDXL-base UNet forward, batch={batch}, "
              f"{xcfg.sampler.image_size}px: {total / 1e12 / batch:.3f} "
              f"analytic TFLOPs/forward (dot/conv)  -> "
              f"{per_img / 1e12:.1f} TF/image at {steps}-step CFG")
        print(f"{'op':22s} {'operand shapes':46s} "
              f"{'count':>5s} {'GFLOP':>9s} {'%':>5s}")
        for r in rows:
            print(f"{r['op']:22s} {r['shapes']:46s} "
                  f"{r['count']:5d} {r['gflops']:9.1f} {r['pct']:5.1f}")
        return
    cfg = FrameworkConfig()
    ucfg = cfg.models.unet
    model = UNet(ucfg)

    rng = jax.random.PRNGKey(0)
    lat = jax.random.normal(rng, (batch, 64, 64, 4), jnp.bfloat16)
    ts = jnp.full((batch,), 500, jnp.int32)
    ctx = jax.random.normal(rng, (batch, 77, ucfg.context_dim), jnp.bfloat16)

    from cassmantle_tpu.models.weights import init_params_cached
    from cassmantle_tpu.utils.compile_cache import param_cache_path

    params = init_params_cached(
        model, 2, lat[:1], ts[:1], ctx[:1],
        cache_path=param_cache_path("unet", ucfg),
        cast_to="bfloat16")

    step = jax.jit(lambda p, l, t, c: model.apply(p, l, t, c))

    if opts.cost_table:
        if opts.full_pipeline:
            from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

            pipe = Text2ImagePipeline(cfg)
            ids = jnp.zeros((batch, pipe.pad_len), jnp.int32)
            rows, total = cost_table(
                pipe._sample_impl, pipe._params, ids, ids,
                jax.random.PRNGKey(0))
            label = (f"full pipeline (CLIP + "
                     f"{cfg.sampler.num_steps}-step CFG scan + VAE), "
                     f"batch={batch}")
            per_img = total / batch
            extra = (f"  = {per_img / 1e12:.2f} TF/image "
                     f"(UNet-only ceiling math assumed "
                     f"{0.78 * 2 * cfg.sampler.num_steps:.1f})")
        else:
            rows, total = cost_table(
                lambda p, l, t, c: model.apply(p, l, t, c),
                params, lat, ts, ctx)
            label = f"UNet forward, batch={batch}"
            extra = ""
        print(f"{label}: {total / 1e12:.3f} analytic TFLOPs "
              f"(dot/conv){extra}")
        print(f"{'op':22s} {'operand shapes':46s} "
              f"{'count':>5s} {'GFLOP':>9s} {'%':>5s}")
        for r in rows:
            print(f"{r['op']:22s} {r['shapes']:46s} "
                  f"{r['count']:5d} {r['gflops']:9.1f} {r['pct']:5.1f}")
        return

    lowered = step.lower(params, lat, ts, ctx)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", 0.0)
    bytes_ = ca.get("bytes accessed", 0.0)

    if opts.dump_hlo:
        # the backend-optimized module: what the TPU actually runs —
        # fusion boundaries, layouts, pad/transpose insertions. Big
        # (tens of MB for the full UNet), hence opt-in.
        path = os.path.join(REPO_ROOT, "UNET_HLO.txt")
        with open(path, "w") as f:
            f.write(compiled.as_text())
        print(f"optimized HLO -> {path}")

    dt = timeit(step, params, lat, ts, ctx)
    print(f"batch={batch} step={dt*1e3:.2f} ms  "
          f"flops={flops/1e12:.3f} TF  -> {flops/dt/1e12:.1f} TFLOP/s  "
          f"bytes={bytes_/1e9:.2f} GB -> {bytes_/dt/1e9:.0f} GB/s")

    # flash vs XLA attention A/B per UNet resolution — self-attn AND the
    # S_k=77 cross-attn site (ragged K/V padded into the same kernel:
    # ops/flash_attention.py::flash_plan). Rows whose shape the kernel
    # won't take fall back to the XLA path inside the dispatcher — label
    # them by the plan's kind so the A/B can't lie.
    from cassmantle_tpu.ops.flash_attention import flash_plan

    def label(q, k):
        plan = flash_plan(q, k)
        return "xla-fallback" if plan is None else plan.kind

    for (s, heads, d) in [(4096, 8, 40), (1024, 8, 80), (256, 8, 160),
                          (64, 8, 160)]:
        q = jax.random.normal(rng, (batch, s, heads, d), jnp.bfloat16)
        fa = jax.jit(lambda q, k, v: attn_mod.multi_head_attention(
            q, k, v, use_flash=True))
        xa = jax.jit(lambda q, k, v: attn_mod.multi_head_attention(
            q, k, v, use_flash=False))
        flabel = label(q, q)
        tf_ = timeit(fa, q, q, q)
        tx = timeit(xa, q, q, q)
        # cross-attn: kv len 77 (flash_cross vs XLA)
        k77 = jax.random.normal(rng, (batch, 77, heads, d), jnp.bfloat16)
        clabel = label(q, k77)
        tfc = timeit(fa, q, k77, k77)
        txc = timeit(xa, q, k77, k77)
        print(f"S={s:5d} D={d:3d}: {flabel}={tf_*1e6:8.1f} us  "
              f"xla={tx*1e6:8.1f} us  cross77({clabel})={tfc*1e6:8.1f} us"
              f"  cross77(xla)={txc*1e6:8.1f} us")


if __name__ == "__main__":
    main()
