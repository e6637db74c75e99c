"""Benchmark: SD1.5-geometry 512x512 txt2img, 50-step DDIM, images/sec/chip.

The BASELINE.md north-star config: full serving pipeline (CLIP encode →
50-step CFG DDIM scan → VAE decode → uint8) on one chip. Weights are
deterministic random unless checkpoints exist under ``weights/`` —
throughput is weight-independent.

Default run prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline"} for the north-star metric. ``--suite`` additionally runs
the full BASELINE.md workload ladder (MiniLM scorer, GPT-2 greedy decode,
SD1.5-512, SDXL-1024 data-parallel, end-to-end round with 1k concurrent
guesses) and writes all results to BENCH_SUITE.json; the north-star line
is still the last stdout line.

Every suite entry snapshots the metrics registry before/after and
attaches the nonzero **counter deltas** of the diagnosis counters
(jit (re)compiles — the sentinel is armed per entry — cache
hits/misses, staged-serving preemptions, dispatch hangs/deadlines/
rejections) to its record, so a BENCH_SUITE.json trajectory explains
its own regressions without a rerun.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time

import os

BASELINE_IMAGES_PER_SEC = 4.0
BATCH = int(os.environ.get("BENCH_BATCH", "4"))
TIMED_ROUNDS = int(os.environ.get("BENCH_ROUNDS", "3"))


PROMPTS = [
    "A watercolor style piece depicting: a lighthouse over a stormy sea",
    "An art deco style piece depicting: a caravan crossing silver dunes",
    "A stained glass style piece depicting: an orchard under two moons",
    "A vaporwave style piece depicting: a night train between cities",
]


# Set by --platform-cpu: a smoke of the harness itself, whose numbers are
# not measurements. Without it a device-bound entry runs on a TPU or
# fails.
CPU_SMOKE = False


def _setup_jax():
    """Every device-bound entry starts here, in the process that will
    use the chip (the suite parent stays off jax): the chip is there or
    the entry fails — no waiting for one, no carrying on on the CPU."""
    import jax

    from cassmantle_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu" and not CPU_SMOKE:
        sys.exit(f"bench: jax found no TPU (default platform "
                 f"{platform!r}); a device entry measures the chip or "
                 f"fails. --platform-cpu smokes the harness instead.")
    # Persistent compile cache: first bench run pays the XLA compile, every
    # later run (and the driver's) reuses it.
    enable_compile_cache()
    return jax


def _bench_txt2img(config_factory, metric: str, weights_dir: str,
                   batch: int = None) -> dict:
    """Shared txt2img harness (one timing methodology for every image
    preset): build pipeline, warmup compile, TIMED_ROUNDS batches,
    report images/sec/chip."""
    jax = _setup_jax()
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    batch = BATCH if batch is None else batch
    pipe = Text2ImagePipeline(config_factory(), weights_dir=weights_dir)
    prompts = (PROMPTS * ((batch + len(PROMPTS) - 1) // len(PROMPTS)))[:batch]
    pipe.generate(prompts, seed=0)  # warmup / compile

    n_images = 0
    t0 = time.perf_counter()
    for i in range(TIMED_ROUNDS):
        images = pipe.generate(prompts, seed=i + 1)
        n_images += images.shape[0]
    elapsed = time.perf_counter() - t0

    ips_per_chip = n_images / elapsed / max(1, jax.local_device_count())
    return {
        "metric": metric,
        "value": round(ips_per_chip, 4),
        "unit": "images/sec/chip",
        "vs_baseline": round(ips_per_chip / BASELINE_IMAGES_PER_SEC, 4),
        "batch": batch,
        "timed_rounds": TIMED_ROUNDS,
    }


# Fixed-config physical ceiling for the SD1.5 DDIM-50 config: the
# FULL-PIPELINE analytic cost (82.87 TF/image — CLIP + 100 CFG UNet
# forwards + VAE decode, docs/PERF_NOTES.md "Full-pipeline accounting")
# on a ~197 TFLOP/s bf16 v5e chip = ~2.38 img/s at MFU 1.0. Earlier
# rounds used the UNet-only 2.51, which overstated headroom by ~6%
# (PERF_NOTES calls this out); BENCH_CEILING_IPS still overrides.
SD15_CEILING_IPS_DEFAULT = 2.38


def _sd15_ceiling_context(res: dict) -> dict:
    """Attach the fixed-config ceiling fraction to an SD1.5 DDIM-50
    entry (shared by the `sd15` north star and its `sd15_fusedconv`
    A/B arm so both report against the SAME ceiling)."""
    ceiling = float(os.environ.get("BENCH_CEILING_IPS",
                                   str(SD15_CEILING_IPS_DEFAULT)))
    if ceiling > 0 and "value" in res:
        res["fraction_of_fixed_config_ceiling"] = round(
            res["value"] / ceiling, 4)
    return res


def bench_sd15(weights_dir: str) -> dict:
    """North-star: SD1.5 512², 50-step CFG DDIM, images/sec/chip.
    Within the fixed DDIM-50 config, optimization is measured as
    fraction of the analytic full-pipeline ceiling
    (SD15_CEILING_IPS_DEFAULT), not of the workload-level 4.0."""
    from cassmantle_tpu.config import FrameworkConfig

    return _sd15_ceiling_context(_bench_txt2img(
        FrameworkConfig, "sd15_512px_ddim50_images_per_sec_per_chip",
        weights_dir))


def bench_sd15_b8(weights_dir: str) -> dict:
    """Batch-size A/B vs the `sd15` entry: same fixed DDIM-50 config at
    DOUBLE the batch (2x BENCH_BATCH, so the comparison survives an env
    override) — the cheapest MXU-utilization lever; if img/s/chip rises
    here, the serving batch should too. Both entries record ``batch``."""
    from cassmantle_tpu.config import FrameworkConfig

    return _bench_txt2img(
        FrameworkConfig, "sd15_512px_ddim50_2xbatch_images_per_sec_per_chip",
        weights_dir, batch=2 * BATCH)


def bench_sd15_fast(weights_dir: str) -> dict:
    """Fast-serving preset: DPM-Solver++(2M) @ 25 steps (the quality-
    equivalent low-latency sampler — BASELINE.md's workload-level path
    past the bf16 FLOP ceiling of the fixed 50-step DDIM config)."""
    from cassmantle_tpu.config import fast_serving_config

    return _bench_txt2img(
        fast_serving_config, "sd15_512px_dpmpp25_images_per_sec_per_chip",
        weights_dir)


def bench_sd15_fusedconv(weights_dir: str) -> dict:
    """A/B arm for the fused GroupNorm+SiLU+conv3x3 Pallas path on the
    fixed DDIM-50 config (config.fusedconv_serving_config): identical
    trajectory and param tree as the `sd15` entry — UNet ResBlock convs
    run through ops/fused_conv.py with 128-lane channel padding instead
    of the XLA norm->act->conv sequence. Compare directly against the
    `sd15` entry; the analytic case (one HBM round trip of the level
    activation saved per conv, full MXU tile fill at the 320/960
    levels, +3.4% padding FLOPs) is in docs/PERF_NOTES.md. Parity is
    pinned by tests/test_fused_conv.py; CASSMANTLE_NO_FUSED_CONV=1 is
    the kill switch if a TPU generation rejects the kernel."""
    from cassmantle_tpu.config import fusedconv_serving_config

    return _sd15_ceiling_context(_bench_txt2img(
        fusedconv_serving_config,
        "sd15_512px_ddim50_fusedconv_images_per_sec_per_chip",
        weights_dir))


def _poisson_mixed_schedule(n: int, rate_rps: float, seed: int = 0):
    """Deterministic Poisson arrival offsets + mixed request sizes for
    the staged-serving A/B: both arms replay the SAME schedule, so the
    comparison isolates the serving discipline, not the load draw.
    Sizes mix 2:1 single-image and two-image requests (the game's
    round-generation shape vs. a player-pair burst)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sizes = rng.choice([1, 1, 2], size=n)
    return arrivals, sizes


def _mixed_load_arm(pipe, arrivals, sizes):
    """Replay one arm of the mixed-load A/B: request i enters at
    ``arrivals[i]`` (open-loop — late completions do NOT delay later
    arrivals, exactly how real traffic behaves) and its latency is
    submit → uint8 batch. Returns (elapsed_s, latencies_s, images)."""
    from concurrent.futures import ThreadPoolExecutor

    n = len(arrivals)
    lats = [0.0] * n
    images = [0] * n
    start = time.perf_counter()

    def one(i: int) -> None:
        delay = start + float(arrivals[i]) - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        prompts = (PROMPTS * 2)[i % len(PROMPTS):][: int(sizes[i])]
        t0 = time.perf_counter()
        out = pipe.generate(prompts, seed=100 + i)
        lats[i] = time.perf_counter() - t0
        images[i] = out.shape[0]

    with ThreadPoolExecutor(max_workers=n) as ex:
        futs = [ex.submit(one, i) for i in range(n)]
        for f in futs:
            f.result()
    return time.perf_counter() - start, lats, sum(images)


def bench_sd15_staged(weights_dir: str) -> dict:
    """Mixed-load A/B for stage-disaggregated serving
    (serving/stages.py, config.staged_serving_config): Poisson arrivals
    of mixed-size requests through ONE pipeline, staged vs monolithic.
    The monolithic arm runs the SAME pipeline object with the
    CASSMANTLE_NO_STAGED_SERVING kill switch set, so params, tokenizer,
    and compiled monolithic jits are held constant — the A/B isolates
    the serving discipline (step-boundary admission vs whole-image
    dispatch-lock FIFO). Reports per-arm throughput and p50/p99
    REQUEST latency plus the staged arm's mean denoise-slot occupancy
    (slot_steps / steps x capacity). Solo outputs are bit-identical
    between arms (tests/test_stages.py), so quality needs no re-gate.

    Env: BENCH_STAGED_REQUESTS (default 12), BENCH_STAGED_RATE
    (arrivals/sec; default 0.6 ≈ 0.85 img/s offered at the 1.4
    images/request mix — ~70% of the measured v5e sd15 capacity, the
    regime where queueing exists but neither arm saturates; raise it
    toward capacity during the hardware window to map the knee),
    BENCH_STAGED_SLOTS (smoke-geometry slot count), and
    BENCH_STAGED_SMOKE_GEOMETRY=1 swaps in the 64px/4-step test
    geometry so the CPU harness smoke finishes — those numbers exercise
    the scheduler, not the MXU, and are NOT hardware evidence (the
    BENCH_SUITE.json annotation records this)."""
    import numpy as np

    _setup_jax()
    from cassmantle_tpu.config import staged_serving_config
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    n = int(os.environ.get("BENCH_STAGED_REQUESTS", "12"))
    rate = float(os.environ.get("BENCH_STAGED_RATE", "0.6"))
    if os.environ.get("BENCH_STAGED_SMOKE_GEOMETRY", "").lower() in (
            "1", "true", "yes", "on"):
        import dataclasses as _dc

        from cassmantle_tpu.config import test_config

        slots = int(os.environ.get("BENCH_STAGED_SLOTS", "4"))

        def config_factory():
            base = test_config()
            return base.replace(serving=_dc.replace(
                base.serving, staged_serving=True, denoise_slots=slots))
    else:
        config_factory = staged_serving_config

    pipe = Text2ImagePipeline(config_factory(), weights_dir=weights_dir)
    arrivals, sizes = _poisson_mixed_schedule(n, rate)

    base_stats = {}

    def run_arm(monolithic: bool):
        key = "CASSMANTLE_NO_STAGED_SERVING"
        prev = os.environ.pop(key, None)
        if monolithic:
            os.environ[key] = "1"
        try:
            # warmup compiles for both request sizes before timing
            pipe.generate(PROMPTS[:1], seed=0)
            pipe.generate(PROMPTS[:2], seed=0)
            if not monolithic:
                # snapshot AFTER warmup so the occupancy derivation
                # covers only the loaded phase, not two solo warmups
                base_stats.update(pipe._staged_server().stats)
            return _mixed_load_arm(pipe, arrivals, sizes)
        finally:
            os.environ.pop(key, None)
            if prev is not None:
                os.environ[key] = prev

    def arm_stats(elapsed, lats, images):
        s = np.sort(np.asarray(lats))
        return {
            "images_per_sec": round(images / elapsed, 4),
            "request_p50_s": round(float(s[len(s) // 2]), 3),
            "request_p99_s": round(float(s[int(len(s) * 0.99)]), 3),
        }

    mono = arm_stats(*run_arm(monolithic=True))
    staged = arm_stats(*run_arm(monolithic=False))
    srv = pipe._staged_server()
    d_steps = srv.stats["steps"] - base_stats["steps"]
    d_slot_steps = srv.stats["slot_steps"] - base_stats["slot_steps"]
    if d_steps > 0:
        staged["mean_slot_occupancy"] = round(
            d_slot_steps / (d_steps * srv.capacity), 4)
    srv.stop()
    return {
        "metric": "sd15_512px_ddim50_staged_mixedload_images_per_sec",
        "value": staged["images_per_sec"],
        "unit": "images/sec",
        "vs_baseline": None,
        "ab_versus": "monolithic (same pipeline, kill-switch arm)",
        "requests": n,
        "arrival_rate_rps": rate,
        "mixed_sizes": {str(k): int(v) for k, v in
                        zip(*np.unique(sizes, return_counts=True))},
        "staged": staged,
        "monolithic": mono,
    }


def bench_sd15_int8(weights_dir: str) -> dict:
    """A/B arm for weights-only int8 UNet on the fixed DDIM-50 config:
    same trajectory as `sd15`, int8 weight streaming (halved per-step
    HBM weight reads, dequant fused in-jit — ops/quant.py). Compare
    directly against the `sd15` entry; quality re-gated via
    tools/clip_report.py when enabled in serving."""
    import dataclasses as _dc

    from cassmantle_tpu.config import FrameworkConfig

    def cfg():
        base = FrameworkConfig()
        return base.replace(models=_dc.replace(base.models, unet_int8=True))

    return _bench_txt2img(
        cfg, "sd15_512px_ddim50_int8unet_images_per_sec_per_chip",
        weights_dir)


def _smoke_clip_harness(weights_dir: str, smoke: bool):
    """The quality-report harness the A/B entries share: real CLIP
    weights off-smoke, the tiny fixed test geometry on the CPU smoke
    (one definition so the lcm and w8a8 entries can never gate with
    different harnesses)."""
    from cassmantle_tpu.eval.clip_parity import ClipSimilarityHarness

    if not smoke:
        return ClipSimilarityHarness(weights_dir=weights_dir)

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.models.clip_vision import ClipVisionConfig

    return ClipSimilarityHarness(
        text_cfg=test_config().models.clip_text,
        vision_cfg=ClipVisionConfig(
            image_size=32, patch_size=8, hidden_size=64,
            intermediate_size=128, num_layers=2, num_heads=4,
            projection_dim=64),
        pad_len=16)


def _lcm_smoke_geometry() -> bool:
    return os.environ.get("BENCH_LCM_SMOKE_GEOMETRY", "").lower() in (
        "1", "true", "yes", "on")


def bench_sd15_lcm(weights_dir: str) -> dict:
    """Same-seed A/B for few-step consistency serving (the `sd15_lcm`
    entry, ISSUE 15): teacher arm = the fixed DDIM-50 SD1.5 config,
    student arm = config.lcm_serving_config() — FOUR direct x0
    predictions per image through the boundary-parameterized
    consistency sampler (ops/samplers.py). Both arms run the SAME
    prompts and seeds; the record carries img/s per arm, the
    UNet-forwards-per-image delta (teacher's schedule length vs the
    `pipeline.consistency_steps` counter, verified in-entry), and the
    eval/clip_parity.py consistency quality report between the arms'
    same-seed outputs. On hardware the student arm should load a
    DISTILLED checkpoint (parallel/train.py::ConsistencyDistillTrainer
    — same tree layout as the teacher's, so it drops into weights_dir
    as unet.safetensors of its own deployment); here the arms share
    one param tree, so the quality report measures the plumbing, and
    only counts as a gate once real distilled weights are in play.

    Env: BENCH_LCM_SMOKE_GEOMETRY=1 swaps in the 64px test geometry
    (teacher at 20 steps — the few-step accounting anchor in
    docs/PERF_NOTES.md — student at 4) so the CPU smoke exercises the
    real sampler structure; those numbers exercise the scan and the
    counter plumbing, not the MXU, and are NOT hardware evidence.
    BENCH_LCM_REPS overrides the timed rep count. ``noise_tolerance``
    is carried on the record so tools/bench_diff.py treats the smoke's
    run-to-run variance honestly."""
    import dataclasses as _dc

    jax = _setup_jax()
    from cassmantle_tpu.eval.clip_parity import (
        consistency_quality_report,
    )
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline
    from cassmantle_tpu.utils.logging import metrics

    smoke = _lcm_smoke_geometry()
    if smoke:
        from cassmantle_tpu.config import test_config

        base = test_config()
        base = base.replace(sampler=_dc.replace(base.sampler,
                                                num_steps=20))
        lcm_cfg = base.replace(sampler=_dc.replace(
            base.sampler, consistency=True, num_steps=4,
            consistency_teacher_steps=20))
    else:
        from cassmantle_tpu.config import (
            FrameworkConfig,
            lcm_serving_config,
        )

        base = FrameworkConfig()
        lcm_cfg = lcm_serving_config()

    full_pipe = Text2ImagePipeline(base, weights_dir=weights_dir)
    lcm_pipe = Text2ImagePipeline(lcm_cfg, weights_dir=weights_dir,
                                  share_params_with=full_pipe)

    batch = 1 if smoke else BATCH
    reps = int(os.environ.get("BENCH_LCM_REPS", "3"))
    prompts = (PROMPTS * ((batch + len(PROMPTS) - 1) // len(PROMPTS))
               )[:batch]

    def run_arm(pipe):
        steps_before = metrics.counter_total("pipeline.consistency_steps")
        imgs = pipe.generate(prompts, seed=0)     # warmup compile
        t0 = time.perf_counter()
        for _ in range(reps):
            imgs = pipe.generate(prompts, seed=1)  # same seed both arms
        elapsed = time.perf_counter() - t0
        ips = reps * len(prompts) / elapsed / max(
            1, jax.local_device_count())
        images = (reps + 1) * len(prompts)
        forwards = (metrics.counter_total("pipeline.consistency_steps")
                    - steps_before) / images
        return ips, imgs, forwards

    full_ips, full_imgs, full_counted = run_arm(full_pipe)
    lcm_ips, lcm_imgs, lcm_counted = run_arm(lcm_pipe)
    assert full_counted == 0.0, "teacher arm must not tick the counter"
    assert lcm_counted == lcm_cfg.sampler.num_steps, (
        f"counter says {lcm_counted} consistency forwards/image, "
        f"config says {lcm_cfg.sampler.num_steps}")

    harness = _smoke_clip_harness(weights_dir, smoke)
    quality = consistency_quality_report(harness, lcm_imgs, full_imgs,
                                         prompts)

    return {
        "metric": "sd15_512px_lcm4_images_per_sec_per_chip",
        "value": round(lcm_ips, 4),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "ab_versus": "teacher arm (same prompts/seed, shared params)",
        "full_images_per_sec": round(full_ips, 4),
        "speedup_vs_full": (round(lcm_ips / full_ips, 4)
                            if full_ips else None),
        "batch": batch,
        "timed_rounds": reps,
        # the CPU smoke measures scheduler wall clock on a shared
        # 2-core host at toy geometry — noisier than the MXU entries
        "noise_tolerance": 0.35,
        "unet_forwards_per_image": {
            "teacher": base.sampler.num_steps,
            "student": int(lcm_counted),
            "counter": "pipeline.consistency_steps",
        },
        "consistency": {
            "num_steps": lcm_cfg.sampler.num_steps,
            "teacher_steps": lcm_cfg.sampler.consistency_teacher_steps,
        },
        "quality": quality,
    }


def _w8a8_smoke_geometry() -> bool:
    return os.environ.get("BENCH_W8A8_SMOKE_GEOMETRY", "").lower() in (
        "1", "true", "yes", "on")


def _bench_w8a8_image_ab(metric: str, weights_dir: str,
                         sdxl: bool) -> dict:
    """Same-seed A/B for W8A8 quantized image serving (the `sd15_w8a8`
    / `sdxl_w8a8` entries, ISSUE 20): fp arm = the fixed DDIM-50
    schedule on the fused-conv tree, w8a8 arm = the SAME schedule with
    int8 weights AND activations at every attention/MLP projection and
    fused-conv ResBlock site (ops/quant.py W8A8 leaves through the
    ops/quant_matmul.py int8 kernels). Both arms run the SAME prompts
    and seeds; the record carries img/s per arm, the
    `pipeline.w8a8_dispatches` counter delta verified in-entry (fp arm
    silent; w8a8 arm = schedule steps per image — the proof the int8
    kernel path actually dispatched), and the eval/clip_parity.py
    w8a8 quality report between the arms' same-seed outputs.

    SD1.5 shares one param tree (Text2ImagePipeline quantizes the fp
    donor's tree at build); SDXL builds two pipelines because
    SDXLPipeline's donor contract requires matching quantization mode.

    Env: BENCH_W8A8_SMOKE_GEOMETRY=1 swaps in the 64px test geometry
    with w8a8_min_size=0 so the tiny matmuls quantize — on SD1.5 that
    config matches the committed calibration artifact's signature
    (data/act_scales.json), so the smoke also exercises the
    static-activation-scale path. Off-TPU the int8 kernels run in
    Pallas interpret mode: the smoke proves kernel-path engagement and
    epilogue numerics, not MXU throughput, and is NOT hardware
    evidence (the BENCH_SUITE.json annotation records this).
    BENCH_W8A8_REPS overrides the timed rep count."""
    import dataclasses as _dc

    jax = _setup_jax()
    from cassmantle_tpu.eval.clip_parity import w8a8_quality_report
    from cassmantle_tpu.ops import quant
    from cassmantle_tpu.utils.logging import metrics

    smoke = _w8a8_smoke_geometry()
    if smoke:
        from cassmantle_tpu.config import test_config, test_sdxl_config

        seed_cfg = test_sdxl_config() if sdxl else test_config()
        q_cfg = seed_cfg.replace(models=_dc.replace(
            seed_cfg.models,
            unet=_dc.replace(seed_cfg.models.unet, fused_conv=True),
            unet_w8a8=True, w8a8_min_size=0))
    elif sdxl:
        from cassmantle_tpu.config import sdxl_config

        seed_cfg = sdxl_config()
        q_cfg = seed_cfg.replace(models=_dc.replace(
            seed_cfg.models,
            unet=_dc.replace(seed_cfg.models.unet, fused_conv=True,
                             conv_pad_to=128),
            unet_w8a8=True))
    else:
        from cassmantle_tpu.config import w8a8_serving_config

        q_cfg = w8a8_serving_config()
    # fp arm = the w8a8 config with ONLY the quantization flags off:
    # same fused-conv tree layout, same schedule — the A/B isolates
    # quantization, and on SD1.5 lets the arms share one param tree
    base = q_cfg.replace(models=_dc.replace(
        q_cfg.models, unet_w8a8=False, lm_w8a8=False))

    if sdxl:
        from cassmantle_tpu.serving.sdxl import SDXLPipeline

        fp_pipe = SDXLPipeline(base, weights_dir=weights_dir)
        # the SDXL donor contract requires MATCHING quantization mode
        # (no lossy cross-mode join), so the w8a8 arm builds its own
        # pipeline — the loader's param cache keeps the second build
        # cheap
        q_pipe = SDXLPipeline(q_cfg, weights_dir=weights_dir)
    else:
        from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

        fp_pipe = Text2ImagePipeline(base, weights_dir=weights_dir)
        q_pipe = Text2ImagePipeline(q_cfg, weights_dir=weights_dir,
                                    share_params_with=fp_pipe)

    batch = 1 if (sdxl or smoke) else BATCH
    reps = int(os.environ.get("BENCH_W8A8_REPS", "2" if sdxl else "3"))
    prompts = (PROMPTS * ((batch + len(PROMPTS) - 1) // len(PROMPTS))
               )[:batch]

    def run_arm(pipe):
        before = metrics.counter_total("pipeline.w8a8_dispatches")
        imgs = pipe.generate(prompts, seed=0)     # warmup compile
        t0 = time.perf_counter()
        for _ in range(reps):
            imgs = pipe.generate(prompts, seed=1)  # same seed both arms
        elapsed = time.perf_counter() - t0
        ips = reps * len(prompts) / elapsed / max(
            1, jax.local_device_count())
        images = (reps + 1) * len(prompts)
        dispatched = (metrics.counter_total("pipeline.w8a8_dispatches")
                      - before) / images
        return ips, imgs, dispatched

    fp_ips, fp_imgs, fp_counted = run_arm(fp_pipe)
    q_ips, q_imgs, q_counted = run_arm(q_pipe)
    steps = q_cfg.sampler.num_steps
    assert fp_counted == 0.0, "fp arm must not tick the w8a8 counter"
    assert q_counted == steps, (
        f"counter says {q_counted} w8a8 UNet dispatches/image, "
        f"schedule says {steps}")

    harness = _smoke_clip_harness(weights_dir, smoke)
    quality = w8a8_quality_report(harness, q_imgs, fp_imgs, prompts)

    return {
        "metric": metric,
        "value": round(q_ips, 4),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "ab_versus": ("fp arm (same prompts/seed, separate param tree "
                      "— SDXL donor contract forbids cross-mode share)"
                      if sdxl else
                      "fp arm (same prompts/seed, w8a8 tree quantized "
                      "from the shared donor)"),
        "full_images_per_sec": round(fp_ips, 4),
        "speedup_vs_full": round(q_ips / fp_ips, 4) if fp_ips else None,
        "batch": batch,
        "timed_rounds": reps,
        # the CPU smoke runs the int8 kernels in interpret mode on a
        # shared host — noisier than the MXU entries
        "noise_tolerance": 0.35,
        "w8a8": {
            "sites": quant.w8a8_site_count(q_pipe.unet_params),
            "static_act_scales": quant.w8a8_calibrated(
                q_pipe.unet_params),
            "dispatches_per_image": int(q_counted),
            "counter": "pipeline.w8a8_dispatches",
        },
        "quality": quality,
    }


def bench_sd15_w8a8(weights_dir: str) -> dict:
    """A/B arm for full W8A8 serving on the fixed DDIM-50 SD1.5 config
    (config.w8a8_serving_config): int8 weights and activations at
    every projection and fused-conv ResBlock site, static calibrated
    activation scales from data/act_scales.json when the signature
    matches, halved weight-side HBM streaming (the `t2i_w8a8`
    cost-model entry carries the analytic bytes). Quality rides the
    record via eval/clip_parity.py::w8a8_quality_report (0.98 floor —
    the `w8a8` QualityGateConfig row). CASSMANTLE_NO_W8A8=1 reverts
    bit-exactly at pipeline build."""
    return _bench_w8a8_image_ab(
        "sd15_512px_ddim50_w8a8_images_per_sec_per_chip",
        weights_dir, sdxl=False)


def bench_sdxl_w8a8(weights_dir: str) -> dict:
    """SDXL twin of `sd15_w8a8`: the 1024² DDIM-50 config served W8A8
    (sdxl_config + fused_conv/128-lane padding + unet_w8a8 — the
    `sdxl_w8a8` cost-model entry). The arms are two pipelines because
    the SDXL donor contract requires matching quantization mode;
    quality gates via the `sdxl_w8a8` QualityGateConfig row."""
    return _bench_w8a8_image_ab(
        "sdxl_1024px_ddim50_w8a8_images_per_sec_per_chip",
        weights_dir, sdxl=True)


def bench_scorer(weights_dir: str) -> dict:
    """BASELINE ladder #1: MiniLM guess scorer, 1k pairs coalesced.

    Guesses are UNIQUE per rep (fresh misses — the device encode is
    what's being measured) while the 6 answer words repeat, matching
    real round traffic: the answer side rides the embed LRU
    (scorer.embed_cache_hits), so the device batch is ~half the text
    count. Reusing guess words here would let the cache absorb the
    whole workload and turn the entry into a dict-lookup benchmark."""
    _setup_jax()
    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.ops.scorer import EmbeddingScorer

    cfg = FrameworkConfig()
    scorer = EmbeddingScorer(cfg.models.minilm, weights_dir=weights_dir,
                             batch_buckets=cfg.serving.score_batch_sizes)
    words = ["stormy", "silver", "ancient", "quiet", "glass", "velvet"]

    def make_pairs(rep: int):
        return [(f"guess{rep}_{i}", words[i % 6]) for i in range(1000)]

    scorer.similarity(make_pairs(-1))  # warmup

    # best-of-reps = steady-state throughput (robust to one-off host
    # stalls; every rep is a full coalesced batch)
    best = float("inf")
    for rep in range(5):
        pairs = make_pairs(rep)
        t0 = time.perf_counter()
        scorer.similarity(pairs)
        best = min(best, time.perf_counter() - t0)
    gps = len(pairs) / best
    return {
        "metric": "minilm_guess_scorings_per_sec",
        "value": round(gps, 1),
        "unit": "pairs/sec",
        "vs_baseline": None,
        # bench_diff regression gate (tools/bench_diff.py): best-of-5
        # coalesced batches still swing with host contention
        "noise_tolerance": 0.25,
    }


def _bench_gpt2_with(seeds, metric: str, weights_dir: str,
                     config_factory=None) -> dict:
    """Shared GPT-2 decode harness (one timing methodology for the
    single-prompt, batched, and speculative entries): warmup compile, 5
    best-of reps through decode_ids_batch (decode_ids is its B=1 case),
    aggregate tokens ACTUALLY generated per second (gen_len stops at
    EOS). A config with spec_decode on annotates the measured accept
    rate — the number that says whether the draft paid for itself."""
    jax = _setup_jax()
    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    cfg = (config_factory or FrameworkConfig)()
    gen = PromptGenerator(cfg, weights_dir=weights_dir)
    gen.decode_ids_batch(seeds, max_new_tokens=96)  # warmup

    tps = 0.0
    for _ in range(5):
        t0 = time.perf_counter()
        _, gen_len = gen.decode_ids_batch(seeds, max_new_tokens=96)
        n = int(jax.block_until_ready(gen_len).sum())
        tps = max(tps, n / (time.perf_counter() - t0))
    res = {
        "metric": metric,
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
    }
    if len(seeds) > 1:
        res["batch"] = len(seeds)
    if gen.last_spec_stats is not None:
        res["spec_accept_rate"] = round(
            gen.last_spec_stats["accept_rate"], 4)
        res["spec_chunks"] = gen.last_spec_stats["chunks"]
    return res


def bench_gpt2(weights_dir: str) -> dict:
    """BASELINE ladder #2: GPT-2-small greedy decode, tokens/sec."""
    return _bench_gpt2_with(
        ["The lighthouse keeper walked down the winding stair"],
        "gpt2_greedy_tokens_per_sec", weights_dir)


def bench_gpt2_b4(weights_dir: str) -> dict:
    """Batched-decode A/B vs the `gpt2` entry: 4 prompts through ONE
    decode_ids_batch dispatch (the prompt-queue serving path,
    serving/pipeline.py BATCH_BUCKETS; all four seeds share the
    32-token prompt bucket) — aggregate tokens/sec should scale well
    past the single-prompt number because the per-step matmuls go from
    M=1 to M=4 on the same weights stream."""
    return _bench_gpt2_with(
        ["The lighthouse keeper walked down the winding stair",
         "A caravan crossed the silver dunes at dawn",
         "The night train rattled between sleeping cities",
         "An orchard bloomed under two pale moons"],
        "gpt2_greedy_batch4_tokens_per_sec", weights_dir)


def bench_gpt2_spec(weights_dir: str) -> dict:
    """A/B arm for speculative decoding vs the `gpt2` entry: same
    prompt, same greedy output BY CONSTRUCTION (exact argmax acceptance,
    tests/test_spec_decode.py pins bit-parity), decoded through
    ops/decode.py::speculative_decode with the self-drafting n-gram
    draft (config.spec_decode_serving_config — zero extra HBM, no draft
    checkpoint). The entry annotates ``spec_accept_rate``: tokens/sec
    rises over `gpt2` roughly by accept_rate x gamma per verify forward
    (docs/PERF_NOTES.md "LM decode accounting"), so a low accept rate on
    the real checkpoint is the signal to switch ``spec_decode.mode`` to
    "draft_model". CASSMANTLE_NO_SPEC_DECODE=1 is the kill switch."""
    from cassmantle_tpu.config import spec_decode_serving_config

    return _bench_gpt2_with(
        ["The lighthouse keeper walked down the winding stair"],
        "gpt2_spec_ngram_tokens_per_sec", weights_dir,
        config_factory=spec_decode_serving_config)


def bench_gpt2_w8a8(weights_dir: str) -> dict:
    """Same-seed A/B for the W8A8 prompt LM vs the fp `gpt2` path
    (ISSUE 20): both arms decode the SAME seed through
    decode_ids_batch with the same methodology as `_bench_gpt2_with`
    (warmup compile, 5 best-of reps, tokens actually generated per
    second). The w8a8 arm quantizes every GPT-2 block projection
    (qkv/out/fc1/fc2) to int8 with PER-TOKEN activation row scales
    computed in-graph (no calibration artifact — models/gpt2.py
    hardcodes act_per_token), so decode numerics track each token's
    own dynamic range. The record carries tokens/sec per arm, the
    `pipeline.w8a8_dispatches` counter delta verified in-entry (one
    tick per bucket-group decode dispatch: fp arm silent, w8a8 arm =
    warmup + timed reps — the proof the int8 kernel path served the
    tokens), and greedy token agreement between the arms as the
    quality report (advisory on random-init weights; on the real
    checkpoint a low agreement is the signal to re-examine per-token
    scale clipping).

    Env: BENCH_W8A8_SMOKE_GEOMETRY=1 swaps in the tiny test GPT-2 with
    w8a8_min_size=0 — off-TPU the int8 kernels run in Pallas interpret
    mode, far too slow for the full GPT-2-small decode on a CPU
    smoke."""
    import dataclasses as _dc

    import numpy as np

    jax = _setup_jax()
    from cassmantle_tpu.serving.pipeline import PromptGenerator
    from cassmantle_tpu.utils.logging import metrics

    smoke = _w8a8_smoke_geometry()
    if smoke:
        from cassmantle_tpu.config import test_config

        base = test_config()
        max_new = 16
        reps = 3
    else:
        from cassmantle_tpu.config import FrameworkConfig

        base = FrameworkConfig()
        max_new = 96
        reps = 5
    q_cfg = base.replace(models=_dc.replace(
        base.models, lm_w8a8=True,
        w8a8_min_size=0 if smoke else base.models.w8a8_min_size))
    seeds = ["The lighthouse keeper walked down the winding stair"]

    def run_arm(cfg):
        from cassmantle_tpu.ops import quant

        gen = PromptGenerator(cfg, weights_dir=weights_dir)
        before = metrics.counter_total("pipeline.w8a8_dispatches")
        gen.decode_ids_batch(seeds, max_new_tokens=max_new)  # warmup
        tps, ids, gen_len = 0.0, None, None
        for _ in range(reps):
            t0 = time.perf_counter()
            ids, gen_len = gen.decode_ids_batch(
                seeds, max_new_tokens=max_new)
            n = int(jax.block_until_ready(gen_len).sum())
            tps = max(tps, n / (time.perf_counter() - t0))
        dispatches = int(metrics.counter_total(
            "pipeline.w8a8_dispatches") - before)
        sites = quant.w8a8_site_count(gen.params)
        return tps, np.asarray(ids)[0], int(np.asarray(gen_len)[0]), \
            dispatches, sites, bool(gen.loaded_real_weights)

    fp_tps, fp_ids, fp_len, fp_disp, _, _ = run_arm(base)
    q_tps, q_ids, q_len, q_disp, q_sites, real = run_arm(q_cfg)
    assert fp_disp == 0, "fp arm must not tick the w8a8 counter"
    assert q_disp == reps + 1, (
        f"counter says {q_disp} w8a8 decode dispatches, "
        f"arm ran {reps + 1} (warmup + {reps} timed)")
    assert q_sites > 0, "w8a8 arm quantized zero LM sites"

    # greedy token agreement over the shorter arm's generated tokens:
    # the quality report for an LM A/B (images have CLIP; decode has
    # exact token identity)
    n_cmp = min(fp_len, q_len)
    agree = float(np.mean(fp_ids[:n_cmp] == q_ids[:n_cmp])) \
        if n_cmp else 0.0

    return {
        "metric": "gpt2_w8a8_tokens_per_sec",
        "value": round(q_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "ab_versus": "fp arm (same seed text, greedy, same bucket)",
        "full_tokens_per_sec": round(fp_tps, 1),
        "speedup_vs_full": round(q_tps / fp_tps, 4) if fp_tps else None,
        "max_new_tokens": max_new,
        "noise_tolerance": 0.35,
        "w8a8": {
            "sites": q_sites,
            "act_scales": "per-token (dynamic, in-graph)",
            "decode_dispatches": q_disp,
            "counter": "pipeline.w8a8_dispatches",
        },
        "quality": {
            "greedy_token_agreement": round(agree, 4),
            "compared_tokens": int(n_cmp),
            "gen_len": {"fp": fp_len, "w8a8": q_len},
            "real_weights": real,
        },
    }


def _bench_sdxl_with(config_factory, metric: str,
                     weights_dir: str) -> dict:
    """Shared SDXL harness (one timing methodology for both SDXL
    entries): dp mesh over the local devices, one prompt per device,
    images/sec/chip."""
    jax = _setup_jax()
    from cassmantle_tpu.config import MeshConfig
    from cassmantle_tpu.parallel.mesh import make_mesh
    from cassmantle_tpu.serving.sdxl import SDXLPipeline

    n = jax.local_device_count()
    mesh = make_mesh(MeshConfig(dp=-1, tp=1, sp=1)) if n > 1 else None
    pipe = SDXLPipeline(config_factory(), weights_dir=weights_dir,
                        mesh=mesh)
    prompts = (PROMPTS * ((n + len(PROMPTS) - 1) // len(PROMPTS)))[: max(n, 1)]
    pipe.generate(prompts, seed=0)  # warmup

    t0 = time.perf_counter()
    reps = 2
    for i in range(reps):
        pipe.generate(prompts, seed=i + 1)
    elapsed = time.perf_counter() - t0
    ips_chip = reps * len(prompts) / elapsed / max(1, n)
    return {
        "metric": metric,
        "value": round(ips_chip, 4),
        "unit": "images/sec/chip",
        "vs_baseline": None,
    }


# SDXL-base 1024² analytic full-pipeline cost (tools/profile_unet.py
# --cost-table --sdxl, backend-independent): 6.761 TF/UNet-forward x 100
# CFG forwards + 10.47 TF VAE decode + 0.22 TF dual text towers (cond +
# uncond) = ~686.8 TF/image. On a ~197 TFLOP/s bf16 v5e chip the fixed
# DDIM-50 in-config ceiling is therefore ~0.287 img/s/chip — the SDXL
# analogue of sd15's 2.51 (BASELINE.md has no workload-level SDXL img/s
# target, so the ceiling IS the baseline the fraction reports against).
SDXL_ANALYTIC_TF_PER_IMAGE = 686.8
SDXL_CEILING_IPS_DEFAULT = 0.287

def _sdxl_ceiling_context(res: dict) -> dict:
    """Attach the analytic ceiling context to an SDXL suite entry (the
    sd15 entries have carried this since round 4; VERDICT r5 weak #7
    flagged the asymmetry)."""
    ceiling = float(os.environ.get("BENCH_SDXL_CEILING_IPS",
                                   str(SDXL_CEILING_IPS_DEFAULT)))
    if ceiling > 0 and "value" in res:
        res["analytic_tf_per_image"] = SDXL_ANALYTIC_TF_PER_IMAGE
        res["ceiling_ips"] = ceiling
        res["fraction_of_fixed_config_ceiling"] = round(
            res["value"] / ceiling, 4)
        res["vs_baseline"] = res["fraction_of_fixed_config_ceiling"]
    return res


def bench_sdxl(weights_dir: str) -> dict:
    """BASELINE ladder #4: SDXL-base 1024², batched, data-parallel.
    ``vs_baseline`` reports fraction of the analytic in-config bf16
    ceiling (~0.287 img/s/chip — see SDXL_ANALYTIC_TF_PER_IMAGE)."""
    from cassmantle_tpu.config import sdxl_config

    return _sdxl_ceiling_context(_bench_sdxl_with(
        sdxl_config, "sdxl_1024px_ddim50_images_per_sec_per_chip",
        weights_dir))


def bench_e2e_round(weights_dir: str) -> dict:
    """BASELINE ladder #5: full round (prompt gen + image + 1k concurrent
    guess scorings through the continuous-batching queue)."""
    import asyncio

    _setup_jax()
    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.serving.service import InferenceService

    svc = InferenceService(FrameworkConfig(), weights_dir=weights_dir)

    async def run() -> float:
        svc.score_queue.start()
        # warmup both paths; OOV tokens so the embed table's rung 0
        # can't serve the pair — the point is compiling the DEVICE path
        await svc.content_backend.generate("An old ship left the harbor", True)
        await svc.similarity([("qzwarmupx", "qzwarmupy")] * 64)
        t0 = time.perf_counter()
        content_task = asyncio.ensure_future(
            svc.content_backend.generate("The market opened at dawn", False)
        )
        # 1k guesses land while the round is generating (the serving
        # pressure point: queue coalescing + device contention)
        guesses = [
            svc.similarity([(f"word{i}", "stormy")]) for i in range(1000)
        ]
        await asyncio.gather(*guesses)
        await content_task
        elapsed = time.perf_counter() - t0
        await svc.stop()
        return elapsed

    elapsed = asyncio.run(run())
    return {
        "metric": "e2e_round_with_1k_guesses_seconds",
        "value": round(elapsed, 3),
        "unit": "seconds",
        "vs_baseline": None,
    }


async def soak_run(svc, rounds: int, workers: int = 32):
    """N rounds of content generation while `workers` guess loops keep
    constant pressure on the score queue; -> (elapsed_s, latencies_s,
    error_count). Shared by bench_soak and its CPU smoke test
    (tests/test_queue.py)."""
    import asyncio

    svc.score_queue.start()
    await svc.content_backend.generate("An old ship left the harbor", True)
    # OOV warmup pair: must compile the device scorer, not hit the table
    await svc.similarity([("qzwarmupx", "qzwarmupy")] * 64)

    latencies: list = []
    stop = asyncio.Event()

    errors = [0]

    async def guess_pressure(worker: int) -> None:
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                await svc.similarity([(f"w{worker}_{i}", "stormy")])
            except Exception:
                # a worker must never die mid-soak: a transient scoring
                # error (rollover backpressure) would silently unload the
                # bench and overstate "sustained" throughput
                errors[0] += 1
                await asyncio.sleep(0.05)
                continue
            latencies.append(time.perf_counter() - t0)
            i += 1

    pressure = [asyncio.ensure_future(guess_pressure(w))
                for w in range(workers)]
    t0 = time.perf_counter()
    for r in range(rounds):
        await svc.content_backend.generate(f"Round {r} under load", False)
    elapsed = time.perf_counter() - t0
    stop.set()
    await asyncio.gather(*pressure, return_exceptions=True)
    await svc.stop()
    return elapsed, latencies, errors[0]


def bench_soak(weights_dir: str) -> dict:
    """BASELINE ladder rung 5 is *sustained* serving, not a burst: N
    consecutive rounds of content generation under CONTINUOUS guess
    load, reporting images/sec plus p50/p99 guess latency. The guess
    pressure never pauses between rounds — exactly the round-rollover
    contention the 1 Hz clock produces in production."""
    import asyncio

    _setup_jax()
    import numpy as np

    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.serving.service import InferenceService

    rounds = int(os.environ.get("BENCH_SOAK_ROUNDS", "5"))
    svc = InferenceService(FrameworkConfig(), weights_dir=weights_dir)
    elapsed, lats, errors = asyncio.run(soak_run(svc, rounds))
    if not lats:
        raise RuntimeError(
            f"soak produced no successful guess scorings ({errors} errors)"
        )
    ms = np.sort(np.asarray(lats)) * 1000.0
    return {
        "metric": f"soak_{rounds}rounds_images_per_sec_sustained",
        "value": round(rounds / elapsed, 4),
        "unit": "images/sec",
        "vs_baseline": None,
        "rounds": rounds,
        "guesses": len(lats),
        "guess_errors": errors,
        "guesses_per_sec": round(len(lats) / elapsed, 1),
        "guess_p50_ms": round(float(ms[len(ms) // 2]), 1),
        "guess_p99_ms": round(float(ms[int(len(ms) * 0.99)]), 1),
    }


def _rooms_worker_main(port: int, store_addr: str, num_rooms: int,
                       worker_id: str, advertise: str,
                       round_seconds: float,
                       score_batch_ms: float = 0.0) -> None:
    """Child process for the rooms_load harness: one fabric worker
    (fake content backend — the harness measures the GAME fabric, not
    the diffusion path) over the shared native (or replicated) store.
    ``score_batch_ms`` > 0 puts the fake scorer behind a real batching
    queue with that simulated per-batch device cost (the embed-table
    A/B arms need a device cost for the table rung to beat); the
    table arms themselves are selected via CASSMANTLE_FAKE_EMBED_TABLE
    / CASSMANTLE_NO_EMBED_TABLE in the spawn environment."""
    import dataclasses

    from aiohttp import web

    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.server.app import build_fabric, create_app

    cfg = FrameworkConfig()
    cfg = cfg.replace(
        # rate limits effectively off: the harness IS the flood
        game=dataclasses.replace(
            cfg.game, time_per_prompt=round_seconds, lock_timeout=10.0,
            acquire_timeout=0.5, rate_limit_default=1e6,
            rate_limit_api=1e6),
        fabric=dataclasses.replace(
            cfg.fabric, num_rooms=num_rooms, heartbeat_s=0.5,
            membership_ttl_s=2.5),
    )
    if score_batch_ms > 0:
        cfg = cfg.replace(serving=dataclasses.replace(
            cfg.serving, fake_score_batch_ms=score_batch_ms))
    fabric = build_fabric(cfg, fake=True, store_addr=store_addr,
                          worker_id=worker_id, advertise_addr=advertise)
    web.run_app(create_app(fabric, cfg), host="127.0.0.1", port=port,
                print=None)


async def _rooms_load_drive(base_urls, sessions: int, seconds: float,
                            ws_conns: int, guess_words=None) -> dict:
    """The synthetic load: N sessions in a sustained guess loop + M WS
    /clock subscriptions, spread across every worker (cross-worker 307s
    followed transparently); returns raw counters + latencies.
    ``guess_words`` replaces the default out-of-vocabulary ``guessN``
    stream with a fixed word cycle (the embed-table A/B arms drive
    in-vocabulary guesses through the same deterministic sequence)."""
    import asyncio

    import aiohttp

    timeout = aiohttp.ClientTimeout(total=15.0)
    latencies: list = []
    errors = [0]
    ws_ticks = [0]
    guesses = [0]
    async with aiohttp.ClientSession(timeout=timeout) as http:
        # the cluster map: room placement + advertised worker addresses
        # straight from the fabric block of /readyz
        async with http.get(base_urls[0] + "/readyz") as res:
            fabric_block = (await res.json()).get("fabric", {})
        placement = fabric_block.get("rooms", {})
        workers = fabric_block.get("workers", {})

        def owner_url(room: str) -> str:
            info = workers.get(placement.get(room) or "", {})
            return (info.get("addr") or base_urls[0]).rstrip("/")

        deadline = time.monotonic() + seconds

        async def player(i: int) -> None:
            sid = f"load-{i}"
            base = base_urls[i % len(base_urls)]
            q = f"?session={sid}"
            try:
                async with http.get(base + "/init" + q) as res:
                    await res.json()
                async with http.get(base + "/fetch/contents" + q) as res:
                    prompt = (await res.json())["prompt"]
                masks = prompt["masks"] or [0]
            except Exception:
                errors[0] += 1
                return
            g = 0
            while time.monotonic() < deadline:
                t0 = time.perf_counter()
                guess = (guess_words[g % len(guess_words)]
                         if guess_words else f"guess{g}")
                try:
                    async with http.post(
                        base + "/compute_score" + q,
                        json={"inputs": {str(masks[0]): guess}},
                    ) as res:
                        if res.status == 200:
                            await res.json()
                            latencies.append(time.perf_counter() - t0)
                            guesses[0] += 1
                        else:
                            errors[0] += 1
                except Exception:
                    errors[0] += 1
                    await asyncio.sleep(0.05)
                g += 1

        async def clock_watcher(i: int) -> None:
            rooms = sorted(placement) or [""]
            room = rooms[i % len(rooms)]
            url = owner_url(room) + f"/clock?session=ws-{i}&room={room}"
            try:
                async with http.ws_connect(url) as ws:
                    while time.monotonic() < deadline:
                        msg = await asyncio.wait_for(
                            ws.receive(), timeout=max(2.0, seconds))
                        if msg.type != aiohttp.WSMsgType.TEXT:
                            break
                        ws_ticks[0] += 1
            except Exception:
                errors[0] += 1

        tasks = [asyncio.ensure_future(player(i)) for i in range(sessions)]
        tasks += [asyncio.ensure_future(clock_watcher(i))
                  for i in range(ws_conns)]
        t0 = time.perf_counter()
        await asyncio.gather(*tasks, return_exceptions=True)
        elapsed = time.perf_counter() - t0
        # post-load attribution scrape: workers start at zero, so their
        # /metrics counter totals ARE this run's deltas (the embed-table
        # arms read scorer.table_hits / score.items here)
        worker_counters: dict = {}
        for url in base_urls:
            try:
                async with http.get(url + "/metrics") as res:
                    counters = (await res.json()).get("counters", {})
            except Exception:
                continue
            for name, value in counters.items():
                worker_counters[name] = \
                    worker_counters.get(name, 0) + value
    return {
        "elapsed": elapsed,
        "latencies": latencies,
        "guesses": guesses[0],
        "ws_ticks": ws_ticks[0],
        "errors": errors[0],
        "worker_counters": worker_counters,
    }


def rooms_load_spawn_workers(workers: int, rooms: int, base_port: int,
                             store_addr: str,
                             round_seconds: float = 8.0,
                             score_batch_ms: float = 0.0) -> tuple:
    """(procs, base_urls): N fabric worker processes over one shared
    store address, each advertised for cross-worker redirects, all
    confirmed /healthz-ready."""
    import multiprocessing
    import urllib.request

    procs = []
    base_urls = []
    # spawn, not fork: the driver (pytest, bench suite) has jax loaded
    # and multithreaded — forking that risks a child deadlock. Spawned
    # workers import only the fake-backend server path (no jax at all),
    # so the clean interpreter costs ~a second and buys determinism.
    ctx = multiprocessing.get_context("spawn")
    for w in range(workers):
        port = base_port + w
        url = f"http://127.0.0.1:{port}"
        base_urls.append(url)
        p = ctx.Process(
            target=_rooms_worker_main,
            args=(port, store_addr, rooms, f"bench-w{w}", url,
                  round_seconds, score_batch_ms),
            daemon=True)
        p.start()
        procs.append(p)
    for url in base_urls:
        deadline = time.monotonic() + 60.0
        while True:
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2.0) as res:
                    if res.status == 200:
                        break
            except Exception:
                pass
            if time.monotonic() >= deadline:
                for p in procs:
                    p.terminate()
                raise RuntimeError(f"worker {url} never became healthy")
            time.sleep(0.1)
    return procs, base_urls


def rooms_load_run(workers: int = 2, rooms: int = 4, sessions: int = 8,
                   seconds: float = 6.0, ws_conns: int = 4,
                   base_port: int = 8461, store_port: int = 7461,
                   round_seconds: float = 8.0,
                   store_addr: str = None,
                   score_batch_ms: float = 0.0,
                   guess_words=None) -> dict:
    """Spawn one shared mantlestore + N fabric worker processes, drive
    sustained guess + WS clock load across M rooms, return raw stats.
    ``store_addr`` overrides the store (e.g. ``repl:...`` against an
    externally spawned replicated cluster — the failover drill in
    tests/test_fabric_cluster.py). Shared by ``bench.py rooms_load``
    and the CPU smoke tests (tests/test_fabric.py)."""
    import asyncio

    from cassmantle_tpu.native.client import ensure_built, spawn_server

    if ensure_built() is None:
        raise RuntimeError("mantlestore toolchain unavailable")
    store_proc = None
    if store_addr is None:
        store_proc = spawn_server(store_port)
        store_addr = f"native:{store_port}"
    procs = []
    try:
        procs, base_urls = rooms_load_spawn_workers(
            workers, rooms, base_port, store_addr, round_seconds,
            score_batch_ms=score_batch_ms)
        raw = asyncio.run(
            _rooms_load_drive(base_urls, sessions, seconds, ws_conns,
                              guess_words=guess_words))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=5.0)
        if store_proc is not None:
            store_proc.kill()
            store_proc.wait()
    raw.update(workers=workers, rooms=rooms, sessions=sessions,
               ws_conns=ws_conns)
    return raw


def bench_rooms_load(weights_dir: str) -> dict:
    """ROADMAP item 2's deliverable: the game-fabric load rung made
    measurable. N worker processes × M rooms over one shared store,
    sustained guesses/sec + WS clock fan-out, request p50/p99 against a
    p99 SLO. Knobs: BENCH_ROOMS_WORKERS / BENCH_ROOMS_COUNT /
    BENCH_ROOMS_SESSIONS / BENCH_ROOMS_SECONDS / BENCH_ROOMS_WS /
    BENCH_ROOMS_P99_SLO_MS (env)."""
    import numpy as np

    env = os.environ.get
    raw = rooms_load_run(
        workers=int(env("BENCH_ROOMS_WORKERS", "2")),
        rooms=int(env("BENCH_ROOMS_COUNT", "4")),
        sessions=int(env("BENCH_ROOMS_SESSIONS", "8")),
        seconds=float(env("BENCH_ROOMS_SECONDS", "6")),
        ws_conns=int(env("BENCH_ROOMS_WS", "4")),
        base_port=int(env("BENCH_ROOMS_BASE_PORT", "8461")),
        store_port=int(env("BENCH_ROOMS_STORE_PORT", "7461")),
    )
    if not raw["latencies"]:
        raise RuntimeError(
            f"rooms_load produced no successful guesses "
            f"({raw['errors']} errors)")
    ms = np.sort(np.asarray(raw["latencies"])) * 1000.0
    slo_ms = float(env("BENCH_ROOMS_P99_SLO_MS", "2000"))
    p99 = float(ms[int(len(ms) * 0.99)])
    return {
        "metric": "rooms_load_guesses_per_sec_sustained",
        "value": round(raw["guesses"] / raw["elapsed"], 1),
        "unit": "guesses/sec",
        "vs_baseline": None,
        "workers": raw["workers"],
        "rooms": raw["rooms"],
        "sessions": raw["sessions"],
        "duration_s": round(raw["elapsed"], 2),
        "ws_conns": raw["ws_conns"],
        "ws_ticks": raw["ws_ticks"],
        "request_errors": raw["errors"],
        "request_p50_ms": round(float(ms[len(ms) // 2]), 1),
        "request_p99_ms": round(p99, 1),
        "p99_slo_ms": slo_ms,
        "slo_ok": bool(p99 <= slo_ms),
        # bench_diff regression gate: multi-process closed-loop load on
        # a shared host swings hard with core count and contention
        "noise_tolerance": 0.35,
    }


# -- chaos drill (ISSUE 12): seeded fault schedule vs the real fabric -----

def _phase_stats(raw: dict, extra: dict = None) -> dict:
    """One drill phase's record: p50/p99, error budget spent, plus the
    per-worker chaos.injections total scraped after the load."""
    import numpy as np

    lats = raw.get("latencies") or []
    total = raw.get("guesses", 0) + raw.get("errors", 0)
    stats = {
        "guesses": raw.get("guesses", 0),
        "errors": raw.get("errors", 0),
        "error_budget_spent": round(raw.get("errors", 0) / total, 4)
        if total else None,
    }
    if lats:
        ms = np.sort(np.asarray(lats)) * 1000.0
        stats["p50_ms"] = round(float(ms[len(ms) // 2]), 1)
        stats["p99_ms"] = round(float(ms[int(len(ms) * 0.99)]), 1)
    if extra:
        stats.update(extra)
    return stats


async def _scrape_chaos_injections(base_urls) -> int:
    """Sum of ``chaos.injections`` across the workers' /metrics — the
    drill's proof that the armed plan actually fired."""
    import aiohttp

    total = 0
    timeout = aiohttp.ClientTimeout(total=5.0)
    async with aiohttp.ClientSession(timeout=timeout) as http:
        for url in base_urls:
            try:
                async with http.get(url + "/metrics") as res:
                    counters = (await res.json()).get("counters", {})
            except Exception:
                continue
            total += int(counters.get("chaos.injections", 0))
    return total


async def _first_success_after(base_url: str, deadline_s: float) -> float:
    """Seconds until the worker answers a scoring request again —
    the drill's recovery clock (bounded; None-equivalent = deadline)."""
    import asyncio as _asyncio

    import aiohttp

    t0 = time.monotonic()
    timeout = aiohttp.ClientTimeout(total=3.0)
    async with aiohttp.ClientSession(timeout=timeout) as http:
        while time.monotonic() - t0 < deadline_s:
            try:
                async with http.post(
                    base_url + "/compute_score?session=recovery-probe",
                    json={"inputs": {"0": "probe"}},
                ) as res:
                    if res.status == 200:
                        return round(time.monotonic() - t0, 3)
            except Exception:
                pass
            await _asyncio.sleep(0.1)
    return round(deadline_s, 3)


def _drill_cluster_phase(name: str, spec: str, seed: int, *,
                         base_port: int, store_port: int, rooms: int,
                         sessions: int, seconds: float,
                         round_seconds: float = 8.0,
                         kill_leader: bool = False) -> dict:
    """One multi-process drill phase: fresh store(s) + 2 fabric workers
    booted with the phase's CASSMANTLE_CHAOS plan, sustained guess load,
    per-fault latency/error stats. ``kill_leader`` runs a replicated
    store pair and kills the leader mid-phase, measuring recovery."""
    import asyncio

    from cassmantle_tpu.native.client import spawn_server

    store_procs = []
    if kill_leader:
        store_procs.append(spawn_server(store_port, repl=True,
                                        repl_id="drill-A", lease_ms=600))
        store_procs.append(spawn_server(store_port + 1, follower=True,
                                        repl_id="drill-B", lease_ms=600))
        store_addr = (f"repl:127.0.0.1:{store_port},"
                      f"127.0.0.1:{store_port + 1}")
    else:
        store_procs.append(spawn_server(store_port))
        store_addr = f"native:{store_port}"
    prev = os.environ.pop("CASSMANTLE_CHAOS", None)
    if spec:
        os.environ["CASSMANTLE_CHAOS"] = f"seed={seed};{spec}"
    procs = []
    try:
        procs, base_urls = rooms_load_spawn_workers(
            2, rooms, base_port, store_addr,
            round_seconds=round_seconds)
        extra = {}
        if kill_leader:
            phase1 = asyncio.run(_rooms_load_drive(
                base_urls, sessions, seconds / 2.0, ws_conns=0))
            store_procs[0].kill()
            store_procs[0].wait()
            extra["recovery_s"] = asyncio.run(
                _first_success_after(base_urls[0], deadline_s=20.0))
            raw = asyncio.run(_rooms_load_drive(
                base_urls, sessions, seconds / 2.0, ws_conns=0))
            raw["guesses"] += phase1["guesses"]
            raw["errors"] += phase1["errors"]
            raw["latencies"] = phase1["latencies"] + raw["latencies"]
        else:
            raw = asyncio.run(_rooms_load_drive(
                base_urls, sessions, seconds, ws_conns=0))
        if spec:
            extra["injections"] = asyncio.run(
                _scrape_chaos_injections(base_urls))
        return _phase_stats(raw, extra)
    finally:
        if spec:
            os.environ.pop("CASSMANTLE_CHAOS", None)
        if prev is not None:
            os.environ["CASSMANTLE_CHAOS"] = prev
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=10.0)
        for sp in store_procs:
            try:
                sp.kill()
                sp.wait()
            except Exception:
                pass


def _drill_wedged_dispatch_phase(seed: int) -> dict:
    """In-process wedged-dispatch drill: a chaos ``wedge`` holds the
    REAL dispatch thread, submits fail at their deadline, the watchdog
    replaces the thread, and recovery is measured from the release to
    the next successful dispatch."""
    import asyncio

    from cassmantle_tpu import chaos
    from cassmantle_tpu.serving.queue import (
        BatchingQueue,
        DeadlineExceeded,
        DispatchTimeout,
        _DispatchWorker,
    )
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    chaos.configure(
        f"seed={seed};queue.dispatch=wedge:times=1,wedge_s=30")
    sup = ServingSupervisor(degraded_cooldown_s=0.2)
    q = BatchingQueue(
        lambda items: [0.0 for _ in items], max_batch=4,
        max_delay_ms=1, default_deadline_s=0.3, hang_timeout_s=0.6,
        supervisor=sup, name="drillscore",
        dispatcher=_DispatchWorker(name="drill.dispatch_worker"))
    stats = {"deadline_failures": 0}

    async def run() -> None:
        try:
            await q.submit("wedge-me")
        except (DeadlineExceeded, DispatchTimeout):
            stats["deadline_failures"] += 1
        # let the watchdog declare the wedge and replace the thread:
        # the hang clock arms when the handler is OBSERVED running,
        # one wait-window after dispatch, so the fire lands at up to
        # ~2x hang_timeout_s
        await asyncio.sleep(1.5)
        t0 = time.monotonic()
        chaos.release("queue.dispatch")
        assert await q.submit("after") == 0.0
        stats["recovery_s"] = round(time.monotonic() - t0, 3)
        # the overrun COUNT, not the live degraded flag: the short
        # drill cooldown has usually lapsed by this read
        stats["watchdog_fired"] = (
            sup.status()["watchdog"]["overruns"] >= 1)
        await q.stop()

    try:
        asyncio.run(run())
    finally:
        chaos.disarm()
    stats["injections"] = 1
    return stats


def _drill_sigterm_handoff_phase(*, base_port: int, store_port: int,
                                 rooms: int) -> dict:
    """The graceful-handoff drill: SIGTERM one of two workers and pin
    that (a) its rooms are adopted by the survivor BEFORE the process
    exits, and (b) a score accepted on the victim before the signal is
    still visible through the survivor after (no lost accepted
    scores — the ISSUE 12 acceptance)."""
    import asyncio
    import signal as _signal

    import aiohttp

    from cassmantle_tpu.native.client import spawn_server

    store_proc = spawn_server(store_port)
    procs = []
    try:
        procs, base_urls = rooms_load_spawn_workers(
            2, rooms, base_port, f"native:{store_port}",
            round_seconds=30.0)

        async def run() -> dict:
            timeout = aiohttp.ClientTimeout(total=5.0)
            async with aiohttp.ClientSession(timeout=timeout) as http:
                async with http.get(base_urls[1] + "/readyz") as res:
                    fab = (await res.json())["fabric"]
                victim_id = fab["worker"]
                victim_rooms = [r for r, w in fab["rooms"].items()
                                if w == victim_id]
                if not victim_rooms:
                    return {"error": "victim owns no rooms"}
                room = victim_rooms[0]
                sid = "handoff-s"
                q = f"?session={sid}&room={room}"
                async with http.get(base_urls[1] + "/init" + q) as res:
                    assert res.status == 200
                async with http.get(
                        base_urls[1] + "/fetch/contents" + q) as res:
                    prompt = (await res.json())["prompt"]
                mask = (prompt["masks"] or [0])[0]
                async with http.post(
                    base_urls[1] + "/compute_score" + q,
                    json={"inputs": {str(mask): "drill-guess"}},
                ) as res:
                    scores_before = await res.json()
                t_term = time.monotonic()
                os.kill(procs[1].pid, _signal.SIGTERM)
                adopted_at = None
                adopted_while_alive = False
                deadline = t_term + 15.0
                while time.monotonic() < deadline:
                    alive = procs[1].is_alive()
                    try:
                        async with http.get(
                                base_urls[0] + "/readyz") as res:
                            placement = (await res.json())[
                                "fabric"]["rooms"]
                    except Exception:
                        placement = {}
                    if adopted_at is None and all(
                            placement.get(r) not in (victim_id, None)
                            for r in victim_rooms):
                        adopted_at = time.monotonic()
                        adopted_while_alive = alive
                    if adopted_at is not None and not alive:
                        break
                    await asyncio.sleep(0.03)
                procs[1].join(timeout=10.0)
                exited_at = time.monotonic()
                # the survivor now owns the room: the victim's accepted
                # score must still be there (shared store, no loss)
                async with http.get(
                        base_urls[0] + "/fetch/contents" + q) as res:
                    prompt_after = (await res.json())["prompt"]
                key = str(mask)
                before = scores_before.get(key)
                after = prompt_after.get("scores", {}).get(key)
                # handoff() exits only after observing the peer beat
                # that rebuilt the ring, so adoption-before-exit holds
                # by construction; the 30ms external poll can still
                # miss the window, so the hard pins are adoption WELL
                # below the staleness TTL (the handoff moved the rooms,
                # not the TTL) + the draining verdict + score survival
                return {
                    "adopted_before_exit_observed": bool(
                        adopted_at is not None
                        and adopted_while_alive),
                    "adoption_s": round(adopted_at - t_term, 3)
                    if adopted_at else None,
                    "membership_ttl_s": 2.5,
                    "handoff_exit_s": round(exited_at - t_term, 3),
                    "score_preserved": (
                        before is not None and after is not None
                        and float(after) == float(before)),
                }

        return asyncio.run(run())
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=10.0)
        try:
            store_proc.kill()
            store_proc.wait()
        except Exception:
            pass


DRILL_PHASES = ("baseline", "slow_store", "flaky_generation",
                "heartbeat_flap", "leader_kill", "wedged_dispatch",
                "sigterm_handoff")


def chaos_drill_run(seed: int = 42, rooms: int = 3, sessions: int = 4,
                    seconds: float = 3.0, base_port: int = 8531,
                    store_port: int = 7531,
                    phases=DRILL_PHASES) -> dict:
    """The seeded chaos drill (docs/CHAOS.md runbook): a fresh
    two-worker fabric per phase, each phase arming one fault family
    via CASSMANTLE_CHAOS (same seed => same schedule), plus the
    in-process wedged-dispatch and process-level SIGTERM-handoff
    phases. Shared by ``bench.py chaos_drill`` and the slow-tier smoke
    (tests/test_chaos_drill.py)."""
    from cassmantle_tpu.native.client import ensure_built

    if ensure_built() is None:
        raise RuntimeError("mantlestore toolchain unavailable")
    specs = {
        "baseline": "",
        "slow_store": "store.client.op=latency:delay_s=0.02,p=0.3",
        "flaky_generation": "round.generate=flake:p=0.5",
        "heartbeat_flap": "fabric.heartbeat=flake:p=0.5",
        "leader_kill": "",
    }
    out = {"seed": seed, "phases": {}}
    port = base_port
    sport = store_port
    for phase in phases:
        if phase == "wedged_dispatch":
            out["phases"][phase] = _drill_wedged_dispatch_phase(seed)
            continue
        if phase == "sigterm_handoff":
            out["phases"][phase] = _drill_sigterm_handoff_phase(
                base_port=port, store_port=sport, rooms=rooms)
            port += 4
            sport += 4
            continue
        out["phases"][phase] = _drill_cluster_phase(
            phase, specs[phase], seed, base_port=port,
            store_port=sport, rooms=rooms, sessions=sessions,
            seconds=seconds,
            round_seconds=1.5 if phase == "flaky_generation" else 8.0,
            kill_leader=(phase == "leader_kill"))
        port += 4
        sport += 4
    return out


def bench_chaos_drill(weights_dir: str) -> dict:
    """ISSUE 12's deliverable: the fleet driven through a seeded fault
    schedule — slow store, flaky generation, membership flap, store
    leader kill, wedged dispatch, SIGTERM handoff — reporting per-fault
    p99, error budget spent, and recovery seconds. Knobs:
    BENCH_CHAOS_SEED / BENCH_CHAOS_SECONDS / BENCH_CHAOS_ROOMS /
    BENCH_CHAOS_SESSIONS / BENCH_CHAOS_BASE_PORT /
    BENCH_CHAOS_STORE_PORT (env)."""
    env = os.environ.get
    raw = chaos_drill_run(
        seed=int(env("BENCH_CHAOS_SEED", "42")),
        rooms=int(env("BENCH_CHAOS_ROOMS", "3")),
        sessions=int(env("BENCH_CHAOS_SESSIONS", "4")),
        seconds=float(env("BENCH_CHAOS_SECONDS", "4")),
        base_port=int(env("BENCH_CHAOS_BASE_PORT", "8531")),
        store_port=int(env("BENCH_CHAOS_STORE_PORT", "7531")),
    )
    phases = raw["phases"]
    recovery = phases.get("leader_kill", {}).get("recovery_s")
    return {
        "metric": "chaos_drill_leader_kill_recovery_s",
        "value": recovery,
        "unit": "seconds",
        "vs_baseline": None,
        "seed": raw["seed"],
        "phases": phases,
    }


# -- overload drill (ISSUE 13): ramp load past capacity, watch the -------
# -- control plane plateau instead of collapse ---------------------------

def _overload_worker_main(port: int, batch_ms: float, bucket: int,
                          round_seconds: float) -> None:
    """Child process for the overload drill: ONE fabric worker, fake
    content backend, the fake scorer behind a REAL BatchingQueue whose
    handler holds the dispatch thread ``batch_ms`` per batch (known
    capacity = bucket / batch_s items/sec), with drill-tight latency
    targets, deadlines, and SLO windows so adaptive admission and the
    brownout ladder act within a ~10 s drill instead of a ~10 min
    incident. No jax import (same contract as the rooms_load worker)."""
    import dataclasses

    from aiohttp import web

    from cassmantle_tpu.config import FrameworkConfig
    from cassmantle_tpu.server.app import build_fabric, create_app

    cfg = FrameworkConfig()
    cfg = cfg.replace(
        game=dataclasses.replace(
            cfg.game, time_per_prompt=round_seconds, lock_timeout=10.0,
            acquire_timeout=0.5, rate_limit_default=1e6,
            rate_limit_api=1e6),
        serving=dataclasses.replace(
            cfg.serving,
            fake_score_batch_ms=batch_ms,
            score_batch_sizes=(bucket,),
            max_queue_delay_ms=5.0,
            submit_deadline_s=1.5,
            queue_latency_target_s=0.5,
            admission_min_pending=4,
            # the drill saturates the host CPU by design; the loop-lag
            # leg is covered by units (tests/test_overload.py), so keep
            # it from double-firing here
            loop_lag_shed_s=2.0,
            brownout_step_up_dwell_s=0.5,
            brownout_step_down_dwell_s=0.5,
        ),
        obs=dataclasses.replace(
            cfg.obs,
            slo_eval_interval_s=0.25,
            slo_fast_window_s=1.5,
            slo_slow_window_s=3.0,
            slo_score_p99_s=0.2),
    )
    fabric = build_fabric(cfg, fake=True)
    web.run_app(create_app(fabric, cfg), host="127.0.0.1", port=port,
                print=None)


async def _overload_drive(base_url: str, phases, sessions: int,
                          guess_words=None) -> dict:
    """Open-loop synthetic load: each phase fires /compute_score POSTs
    at a fixed arrival rate WITHOUT waiting for completions (a closed
    loop would self-throttle and never overload anything). Tracks per
    phase: accepted latencies, rejection latencies + their Retry-After
    values, and the brownout tier (sampled from /metrics).
    ``guess_words`` replaces the all-OOV ``guessN`` stream with a fixed
    cycle (the embed-table drill mixes in-vocabulary words with OOV
    tokens so the table rung and the admission-controlled queue carry
    their designed shares of the same flood)."""
    import asyncio

    import aiohttp

    timeout = aiohttp.ClientTimeout(total=10.0)
    out = {"phases": {}}
    async with aiohttp.ClientSession(timeout=timeout) as http:
        sids = [f"ovl-{i}" for i in range(sessions)]
        masks = [0]
        for sid in sids:
            q = f"?session={sid}"
            async with http.get(base_url + "/init" + q) as res:
                await res.json()
        async with http.get(base_url + "/fetch/contents"
                            + f"?session={sids[0]}") as res:
            masks = (await res.json())["prompt"]["masks"] or [0]

        tier_seen = [0.0]

        async def tier_sampler(stop: asyncio.Event) -> None:
            while not stop.is_set():
                try:
                    async with http.get(base_url + "/metrics") as res:
                        gauges = (await res.json())["gauges"]
                    tier_seen[0] = max(
                        tier_seen[0],
                        float(gauges.get("overload.brownout_tier", 0.0)))
                except Exception:
                    pass
                try:
                    await asyncio.wait_for(stop.wait(), 0.25)
                except asyncio.TimeoutError:
                    pass

        async def one_request(i: int, rec: dict) -> None:
            sid = sids[i % len(sids)]
            guess = (guess_words[i % len(guess_words)]
                     if guess_words else f"guess{i}")
            t0 = time.perf_counter()
            try:
                async with http.post(
                    base_url + f"/compute_score?session={sid}",
                    json={"inputs": {str(masks[0]): guess}},
                ) as res:
                    ms = (time.perf_counter() - t0) * 1000.0
                    if res.status == 200:
                        await res.json()
                        rec["accepted_ms"].append(ms)
                    elif res.status in (429, 503):
                        rec["rejected_ms"].append(ms)
                        ra = res.headers.get("Retry-After")
                        if ra is not None:
                            rec["retry_after_s"].append(float(ra))
                    else:
                        rec["errors"] += 1
            except Exception:
                rec["errors"] += 1

        for name, rate, seconds in phases:
            rec = {"accepted_ms": [], "rejected_ms": [],
                   "retry_after_s": [], "errors": 0,
                   "rate": rate, "seconds": seconds}
            stop = asyncio.Event()
            sampler = asyncio.ensure_future(tier_sampler(stop))
            tier_seen[0] = 0.0
            tasks = []
            interval = 1.0 / rate
            t_start = time.monotonic()
            i = 0
            while True:
                due = t_start + i * interval
                now = time.monotonic()
                if due - now > 0:
                    await asyncio.sleep(due - now)
                if time.monotonic() - t_start >= seconds:
                    break
                tasks.append(asyncio.ensure_future(one_request(i, rec)))
                i += 1
            await asyncio.gather(*tasks, return_exceptions=True)
            stop.set()
            await sampler
            rec["elapsed_s"] = time.monotonic() - t_start
            rec["max_tier"] = tier_seen[0]
            rec["goodput_per_s"] = (len(rec["accepted_ms"])
                                    / rec["elapsed_s"])
            out["phases"][name] = rec
        # the post-drill verdict: the /readyz overload block + final tier
        async with http.get(base_url + "/readyz") as res:
            body = await res.json()
        out["overload_block"] = body.get("overload", {})
        async with http.get(base_url + "/metrics") as res:
            body = await res.json()
        gauges = body["gauges"]
        out["final_tier"] = float(gauges.get("overload.brownout_tier",
                                             0.0))
        # the worker started at zero, so its counter totals ARE this
        # drill's deltas (table_served / score.batches attribution)
        out["worker_counters"] = dict(body.get("counters", {}))
    return out


def overload_drill_run(batch_ms: float = 100.0, bucket: int = 4,
                       base_port: int = 8571, sessions: int = 6,
                       baseline_s: float = 3.0, overload_s: float = 5.0,
                       recovery_s: float = 5.0,
                       round_seconds: float = 30.0,
                       guess_words=None) -> dict:
    """Spawn the drill worker and ramp: ~0.4x capacity (baseline), 2x
    (overload), ~0.2x (recovery). Capacity = bucket / batch_s. Shared
    by ``bench.py overload_drill`` and the tier-1 goodput smoke
    (tests/test_overload.py)."""
    import asyncio
    import multiprocessing
    import urllib.request

    capacity = bucket / (batch_ms / 1000.0)
    phases = [
        ("baseline", 0.4 * capacity, baseline_s),
        ("overload", 2.0 * capacity, overload_s),
        ("recovery", 0.2 * capacity, recovery_s),
    ]
    ctx = multiprocessing.get_context("spawn")
    url = f"http://127.0.0.1:{base_port}"
    p = ctx.Process(target=_overload_worker_main,
                    args=(base_port, batch_ms, bucket, round_seconds),
                    daemon=True)
    p.start()
    try:
        deadline = time.monotonic() + 60.0
        while True:
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2.0) as res:
                    if res.status == 200:
                        break
            except Exception:
                pass
            if time.monotonic() >= deadline:
                raise RuntimeError("overload worker never became healthy")
            time.sleep(0.1)
        raw = asyncio.run(_overload_drive(url, phases, sessions,
                                          guess_words=guess_words))
    finally:
        p.terminate()
        p.join(timeout=5.0)
    raw.update(capacity_per_s=capacity, batch_ms=batch_ms,
               bucket=bucket)
    return raw


def _pctl(values, q: float) -> float:
    if not values:
        return 0.0
    vs = sorted(values)
    return float(vs[min(len(vs) - 1, int(len(vs) * q))])


def bench_overload_drill(weights_dir: str) -> dict:
    """ISSUE 13's proof: goodput under 2x sustained load plateaus at
    capacity instead of collapsing, accepted p99 stays inside the
    deadline budget, rejections fail fast with a computed Retry-After,
    and the brownout ladder engages under burn and recovers with
    hysteresis. Knobs: BENCH_OVERLOAD_BATCH_MS / BENCH_OVERLOAD_BUCKET
    / BENCH_OVERLOAD_SECONDS / BENCH_OVERLOAD_BASE_PORT (env)."""
    env = os.environ.get
    seconds = float(env("BENCH_OVERLOAD_SECONDS", "5"))
    raw = overload_drill_run(
        batch_ms=float(env("BENCH_OVERLOAD_BATCH_MS", "100")),
        bucket=int(env("BENCH_OVERLOAD_BUCKET", "4")),
        base_port=int(env("BENCH_OVERLOAD_BASE_PORT", "8571")),
        baseline_s=max(3.0, seconds * 0.6),
        overload_s=seconds,
        recovery_s=seconds,
    )
    phases = {}
    for name, rec in raw["phases"].items():
        phases[name] = {
            "offered_per_s": round(rec["rate"], 1),
            "goodput_per_s": round(rec["goodput_per_s"], 1),
            "accepted": len(rec["accepted_ms"]),
            "rejected": len(rec["rejected_ms"]),
            "errors": rec["errors"],
            "accepted_p50_ms": round(_pctl(rec["accepted_ms"], 0.5), 1),
            "accepted_p99_ms": round(_pctl(rec["accepted_ms"], 0.99), 1),
            "reject_p50_ms": round(_pctl(rec["rejected_ms"], 0.5), 1),
            "retry_after_min_s": (min(rec["retry_after_s"])
                                  if rec["retry_after_s"] else None),
            "max_brownout_tier": rec["max_tier"],
        }
    over = phases["overload"]
    base = phases["baseline"]
    return {
        "metric": "overload_drill_goodput_at_2x_per_s",
        "value": over["goodput_per_s"],
        "unit": "accepted req/s",
        "vs_baseline": None,
        "capacity_per_s": raw["capacity_per_s"],
        "goodput_vs_baseline": (
            round(over["goodput_per_s"] / base["goodput_per_s"], 2)
            if base["goodput_per_s"] else None),
        "final_brownout_tier": raw["final_tier"],
        "phases": phases,
    }


# -- embed-table A/B arms (ISSUE 16): the zero-device guess path vs ------
# -- the queued device path under identical load -------------------------

@contextlib.contextmanager
def _arm_env(extra: dict):
    """Temporarily set the arm-selection env flags. The rooms/overload
    workers are spawn children, so flags set here are inherited at
    Process.start() — no per-worker plumbing needed."""
    saved = {k: os.environ.get(k) for k in extra}
    os.environ.update(extra)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# both arms build + consult the SAME hash-embed table code path; the
# kill switch (the production bit-exact revert) is the only difference,
# so the delta is purely "rung 0 serves" vs "everything queues"
_TABLE_ARM_ENV = {"CASSMANTLE_FAKE_EMBED_TABLE": "1",
                  "CASSMANTLE_NO_EMBED_TABLE": "0"}
_DEVICE_ARM_ENV = {"CASSMANTLE_FAKE_EMBED_TABLE": "1",
                   "CASSMANTLE_NO_EMBED_TABLE": "1"}


def _invocab_guesses(n: int = 512, oov_every: int = 0):
    """Deterministic guess cycle drawn from the real wordlist (the
    embed-table arms need in-vocabulary traffic; the default guessN
    stream is 100% OOV by construction). ``oov_every`` > 0 interleaves
    a synthetic OOV token every k-th slot."""
    from cassmantle_tpu.server.assets import load_wordlist

    words = list(load_wordlist())
    out = []
    for j in range(n):
        if oov_every and j % oov_every == oov_every - 1:
            out.append(f"qzoov{j}")
        else:
            out.append(words[(j * 97) % len(words)])
    return out


def bench_rooms_load_table(weights_dir: str) -> dict:
    """ISSUE 16's tentpole proof: the rooms_load rung re-run as an A/B
    pair under identical geometry and an identical in-vocabulary guess
    stream, with the fake scorer behind a REAL batching queue that
    holds the dispatch thread BENCH_ROOMS_TABLE_BATCH_MS per batch (the
    simulated device cost). Table arm: hash-embed table armed
    (CASSMANTLE_FAKE_EMBED_TABLE=1) — every guess completes as a host
    int8 dot, zero queue submits. Device arm: same table built, kill
    switch on (CASSMANTLE_NO_EMBED_TABLE=1) — every guess rides the
    queue. value = table-arm guesses/s; the acceptance bar is
    speedup_vs_device_arm >= 2.0, and each arm's counter_deltas carry
    the attribution (scorer.table_hits up / score.items ~0 in the
    table arm, the reverse in the device arm)."""
    import numpy as np

    env = os.environ.get
    batch_ms = float(env("BENCH_ROOMS_TABLE_BATCH_MS", "200"))
    knobs = dict(
        workers=int(env("BENCH_ROOMS_WORKERS", "2")),
        rooms=int(env("BENCH_ROOMS_COUNT", "4")),
        sessions=int(env("BENCH_ROOMS_SESSIONS", "8")),
        seconds=float(env("BENCH_ROOMS_SECONDS", "6")),
        ws_conns=int(env("BENCH_ROOMS_WS", "4")),
        score_batch_ms=batch_ms,
        guess_words=_invocab_guesses(),
    )
    arms = {}
    for arm, extra, bport, sport in (
            ("table", _TABLE_ARM_ENV, 8481, 7481),
            ("device", _DEVICE_ARM_ENV, 8491, 7491),
    ):
        with _arm_env(extra):
            raw = rooms_load_run(base_port=bport, store_port=sport,
                                 **knobs)
        if not raw["latencies"]:
            raise RuntimeError(
                f"rooms_load_table {arm} arm produced no guesses "
                f"({raw['errors']} errors)")
        ms = np.sort(np.asarray(raw["latencies"])) * 1000.0
        arms[arm] = {
            "guesses_per_s": round(raw["guesses"] / raw["elapsed"], 1),
            "guesses": raw["guesses"],
            "request_errors": raw["errors"],
            "request_p50_ms": round(float(ms[len(ms) // 2]), 1),
            "request_p99_ms": round(float(ms[int(len(ms) * 0.99)]), 1),
            "counter_deltas": _counter_deltas(
                {}, raw.get("worker_counters", {})),
        }
    table, device = arms["table"], arms["device"]
    speedup = (round(table["guesses_per_s"] / device["guesses_per_s"], 2)
               if device["guesses_per_s"] else None)
    return {
        "metric": "rooms_load_table_arm_guesses_per_sec",
        "value": table["guesses_per_s"],
        "unit": "guesses/sec",
        "vs_baseline": None,
        "speedup_vs_device_arm": speedup,
        "speedup_floor": 2.0,
        "speedup_ok": bool(speedup is not None and speedup >= 2.0),
        "score_batch_ms": batch_ms,
        "workers": knobs["workers"],
        "sessions": knobs["sessions"],
        "arms": arms,
        # the table arm's attribution doubles as the entry-level record
        "counter_deltas": dict(table["counter_deltas"]),
        "noise_tolerance": 0.35,
    }


def bench_overload_drill_table(weights_dir: str) -> dict:
    """The overload drill re-run with the embed table armed and a
    half-in-vocabulary flood: the in-vocab share completes at rung 0
    (bypassing admission entirely — overload.table_served counts it)
    while the OOV share still saturates the queue and exercises the
    limiter. value = table-arm goodput at 2x offered; the device arm
    (kill switch) plateaus at queue capacity, so goodput_vs_device > 1
    is table-served headroom the limiter never had to police."""
    env = os.environ.get
    seconds = float(env("BENCH_OVERLOAD_SECONDS", "5"))
    knobs = dict(
        batch_ms=float(env("BENCH_OVERLOAD_BATCH_MS", "100")),
        bucket=int(env("BENCH_OVERLOAD_BUCKET", "4")),
        baseline_s=max(3.0, seconds * 0.6),
        overload_s=seconds,
        recovery_s=seconds,
        guess_words=_invocab_guesses(oov_every=2),
    )
    arms = {}
    for arm, extra, bport in (("table", _TABLE_ARM_ENV, 8581),
                              ("device", _DEVICE_ARM_ENV, 8591)):
        with _arm_env(extra):
            raw = overload_drill_run(base_port=bport, **knobs)
        over = raw["phases"]["overload"]
        arms[arm] = {
            "goodput_at_2x_per_s": round(over["goodput_per_s"], 1),
            "accepted": len(over["accepted_ms"]),
            "rejected": len(over["rejected_ms"]),
            "accepted_p99_ms": round(_pctl(over["accepted_ms"], 0.99), 1),
            "max_brownout_tier": over["max_tier"],
            "counter_deltas": _counter_deltas(
                {}, raw.get("worker_counters", {})),
        }
    table, device = arms["table"], arms["device"]
    ratio = (round(table["goodput_at_2x_per_s"]
                   / device["goodput_at_2x_per_s"], 2)
             if device["goodput_at_2x_per_s"] else None)
    capacity = knobs["bucket"] / (knobs["batch_ms"] / 1000.0)
    return {
        "metric": "overload_drill_table_goodput_at_2x_per_s",
        "value": table["goodput_at_2x_per_s"],
        "unit": "accepted req/s",
        "vs_baseline": None,
        "capacity_per_s": capacity,
        "goodput_vs_device_arm": ratio,
        "invocab_share": 0.5,
        "arms": arms,
        "counter_deltas": dict(table["counter_deltas"]),
        "noise_tolerance": 0.35,
    }


# -- device-loss drill (ISSUE 17): poison, then kill, the (fake) device --
# -- and prove zero invalid outputs served + bounded recovery ------------

def device_loss_drill_run(seed: int = 42, rate: float = 50.0,
                          baseline_s: float = 1.5, poison_s: float = 2.0,
                          kill_s: float = 5.0, recovered_s: float = 2.0,
                          rebuild_s: float = 0.25) -> dict:
    """The integrity/recovery stack driven end to end IN PROCESS: a
    real BatchingQueue (own dispatch worker), a real ServingSupervisor,
    a real DeviceRecoveryManager — only the device itself is fake (a
    handler whose 'runtime' the ``device.lost`` chaos rule kills and
    whose outputs the ``device.poison`` rule corrupts). Four phases:

    - **baseline**: closed-loop submits, everything serves.
    - **poison**: ``device.poison`` flake armed; corrupted batch members
      must fail their OWN future with OutputInvalid — zero non-finite
      values may ever resolve as results (``invalid_served`` == 0).
    - **kill**: ``device.lost`` fires once; the dispatch error
      classifies, the supervisor flips ``device_lost`` (submits fail
      fast), the manager rebuilds (``rebuild_s`` fake re-upload) and
      recovery_s is the lost->serving wall clock.
    - **recovered**: chaos disarmed; goodput must be back >= 90%.

    Every submit carries a deadline, so ALL futures resolve by
    construction — the drill asserts the accounting matches."""
    import asyncio
    import math

    import numpy as np

    from cassmantle_tpu.chaos import ChaosInjected, configure, disarm, \
        fault_point
    from cassmantle_tpu.serving import integrity
    from cassmantle_tpu.serving.device_recovery import (
        DeviceRecoveryManager,
    )
    from cassmantle_tpu.serving.integrity import OutputInvalid
    from cassmantle_tpu.serving.queue import (
        BatchingQueue,
        DeadlineExceeded,
        QueueFull,
        _DispatchWorker,
    )
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    dev = {"alive": True, "generation": 0}

    def handle(items):
        try:
            fault_point("device.lost", peer="drill")
        except ChaosInjected:
            dev["alive"] = False  # the runtime is gone until rebuilt
            raise
        if not dev["alive"]:
            raise RuntimeError("fake TPU: device is lost")
        out = np.asarray([float(len(str(s))) for s in items],
                         dtype=np.float32)
        out = integrity.poison(out, peer="drill")
        bad = set(integrity.invalid_members(np.isfinite(out)).tolist())
        if bad:
            integrity.note_invalid("drill", "score", sorted(bad))
        return [OutputInvalid("drill", "score", [i]) if i in bad
                else float(out[i]) for i in range(len(items))]

    def rebuild() -> None:
        time.sleep(rebuild_s)  # stands in for the checkpoint re-upload
        dev["generation"] += 1
        dev["alive"] = True

    def warm() -> None:
        if not dev["alive"]:
            raise RuntimeError("fake TPU: still lost after rebuild")

    sup = ServingSupervisor()
    rec = DeviceRecoveryManager(supervisor=sup, rebuild=rebuild,
                                warm=warm, backoff_s=0.1)

    async def drive() -> dict:
        q = BatchingQueue(
            handle, max_batch=8, max_delay_ms=5.0, name="drill",
            default_deadline_s=2.0, hang_timeout_s=5.0,
            supervisor=sup,
            dispatcher=_DispatchWorker("drill.dispatch", rank=20),
            on_dispatch_error=rec.note_dispatch_exception,
        )
        loop = asyncio.get_running_loop()
        invalid_served = [0]
        lost_at = [None]
        recovered_at = [None]

        async def phase(name: str, seconds: float) -> dict:
            stats = {"submitted": 0, "ok": 0, "invalid": 0,
                     "rejected": 0, "dispatch_failed": 0,
                     "deadline": 0}
            end = loop.time() + seconds
            i = 0
            while loop.time() < end:
                lost = sup.device_lost
                if lost is not None and lost_at[0] is None:
                    lost_at[0] = loop.time()
                if lost is None and lost_at[0] is not None \
                        and recovered_at[0] is None:
                    recovered_at[0] = loop.time()
                stats["submitted"] += 1
                try:
                    res = await q.submit(f"{name}-{i}", deadline_s=2.0)
                    if isinstance(res, float) and not math.isfinite(res):
                        invalid_served[0] += 1  # the one forbidden path
                    stats["ok"] += 1
                except OutputInvalid:
                    stats["invalid"] += 1
                except DeadlineExceeded:
                    stats["deadline"] += 1
                except QueueFull:
                    stats["rejected"] += 1
                except Exception:
                    stats["dispatch_failed"] += 1
                i += 1
                await asyncio.sleep(1.0 / rate)
            resolved = sum(stats[k] for k in
                           ("ok", "invalid", "rejected",
                            "dispatch_failed", "deadline"))
            stats["all_resolved"] = resolved == stats["submitted"]
            stats["goodput"] = (stats["ok"] / stats["submitted"]
                                if stats["submitted"] else 0.0)
            return stats

        phases = {"baseline": await phase("baseline", baseline_s)}
        configure(f"seed={seed};device.poison=flake:p=0.35,peer=drill")
        phases["poison"] = await phase("poison", poison_s)
        configure(f"seed={seed};device.lost=raise:times=1,peer=drill")
        phases["kill"] = await phase("kill", kill_s)
        disarm()
        rec.join(timeout=10.0)
        phases["recovered"] = await phase("recovered", recovered_s)
        await q.stop()
        return {
            "phases": phases,
            "invalid_served": invalid_served[0],
            "recovery_s": (
                round(recovered_at[0] - lost_at[0], 3)
                if lost_at[0] is not None and recovered_at[0] is not None
                else None),
            "device_generation": dev["generation"],
        }

    return asyncio.run(drive())


def bench_device_loss_drill(weights_dir: str) -> dict:
    """ISSUE 17's deliverable: zero invalid outputs served under device
    poison, bounded lost->serving recovery after a device kill, every
    submitted future resolved, and >= 90% goodput once recovered.
    Knobs: BENCH_DEVLOSS_SEED / BENCH_DEVLOSS_RATE /
    BENCH_DEVLOSS_KILL_S / BENCH_DEVLOSS_REBUILD_S (env)."""
    env = os.environ.get
    raw = device_loss_drill_run(
        seed=int(env("BENCH_DEVLOSS_SEED", "42")),
        rate=float(env("BENCH_DEVLOSS_RATE", "50")),
        kill_s=float(env("BENCH_DEVLOSS_KILL_S", "5")),
        rebuild_s=float(env("BENCH_DEVLOSS_REBUILD_S", "0.25")),
    )
    phases = raw["phases"]
    poison, recovered = phases["poison"], phases["recovered"]
    return {
        "metric": "device_loss_drill_recovery_s",
        "value": raw["recovery_s"],
        "unit": "seconds",
        "vs_baseline": None,
        "invalid_served": raw["invalid_served"],
        "zero_invalid_ok": raw["invalid_served"] == 0,
        "poison_invalid_failed": poison["invalid"],
        "all_resolved": all(p["all_resolved"] for p in phases.values()),
        "recovered_goodput": round(recovered["goodput"], 3),
        "recovered_goodput_ok": recovered["goodput"] >= 0.9,
        "device_generation": raw["device_generation"],
        "phases": phases,
        # recovery wall clock = rebuild sleep + classification/thread
        # latency; timing-noisy by nature on shared CI hosts
        "noise_tolerance": 0.5,
    }


# -- canary drill (ISSUE 18): does the synthetic prober actually catch ----
# -- the faults it exists to catch? ---------------------------------------

def canary_drill_run(seed: int = 42, store_port: int = 7661) -> dict:
    """The canary prober's proof-of-detection drill: one in-process
    fabric worker on a REAL socket over a REAL mantlestore (the
    ``store.client.op`` fault point lives in the native client), probed
    by the real :class:`CanaryProber` over real HTTP. Three fault
    classes are armed in turn — slow store, device output poison, a
    wedged dispatch thread — and each probe is driven explicitly, so
    "detected within one probe period" is literal: the single probe
    fired while the fault was armed must fail. Between faults the probe
    must recover (chaos disarmed => ok again), the FAILED probe's trace
    must be retrievable through the ``probe.e2e_s`` bucket exemplar,
    and the whole drill must leave player surfaces untouched:
    ``game.guesses`` flat, the score admission limiter's estimate
    unmoved (probe submits bypass it by design)."""
    import asyncio
    import dataclasses

    from aiohttp.test_utils import TestServer

    from cassmantle_tpu import chaos
    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.engine.content import FakeContentBackend
    from cassmantle_tpu.engine.game import Game
    from cassmantle_tpu.fabric.rooms import RoomFabric
    from cassmantle_tpu.native.client import (
        MantleStore,
        ensure_built,
        spawn_server,
    )
    from cassmantle_tpu.obs.prober import CanaryProber
    from cassmantle_tpu.obs.trace import tracer
    from cassmantle_tpu.serving.service import InferenceService
    from cassmantle_tpu.serving.supervisor import ServingSupervisor
    from cassmantle_tpu.server.app import create_app
    from cassmantle_tpu.utils.logging import metrics

    if ensure_built() is None:
        raise RuntimeError("mantlestore toolchain unavailable")

    base = test_config()
    cfg = base.replace(
        game=dataclasses.replace(
            base.game, rate_limit_default=1e6, rate_limit_api=1e6,
            time_per_prompt=30.0),
        fabric=dataclasses.replace(
            base.fabric, num_rooms=1, heartbeat_s=30.0),
        serving=dataclasses.replace(
            base.serving, submit_deadline_s=2.0, dispatch_hang_s=1.0),
        obs=dataclasses.replace(
            base.obs, probe_timeout_s=2.0, probe_interval_s=3600.0,
            slo_eval_interval_s=300.0, process_sample_interval_s=60.0),
    )

    store_proc = spawn_server(store_port)

    async def drive() -> dict:
        store = MantleStore(port=store_port)
        await store.connect()
        sup = ServingSupervisor()
        service = InferenceService(
            cfg, backend=FakeContentBackend(image_size=64),
            supervisor=sup)

        def factory(room, room_store):
            return Game(cfg, room_store, service.content_backend,
                        embed=service.embed,
                        similarity=service.similarity,
                        supervisor=sup, room=room)

        fabric = RoomFabric(cfg, store, factory, worker_id="canary-w",
                            start_timers=False, heartbeat=False,
                            supervisor=sup)
        server = TestServer(create_app(fabric, cfg, start_timer=False,
                                       device_health=False))
        await server.start_server()
        url = f"http://127.0.0.1:{server.port}"
        fabric.membership.addr = url
        prober = CanaryProber(fabric, cfg, self_addr=url)

        limiter = service.score_queue.admission
        limit_before = limiter._limit if limiter is not None else None
        counters_before = dict(metrics.snapshot()["counters"])

        def guesses_total(counters: dict) -> float:
            return sum(v for k, v in counters.items()
                       if k.split("{", 1)[0] == "game.guesses")

        def clear_embed_cache() -> None:
            # the probe's near-guess/answer rows land in the scorer LRU
            # on the first probe; a poison drill must force them back
            # onto the device path or the fault never executes
            with service.scorer._embed_cache_lock:
                service.scorer._embed_cache.clear()

        async def recover(deadline_s: float = 10.0) -> dict:
            t0 = time.monotonic()
            while True:
                v = await prober.probe_once()
                if v["ok"] or time.monotonic() - t0 > deadline_s:
                    return {"ok": bool(v["ok"]),
                            "recovery_s":
                                round(time.monotonic() - t0, 3)}
                await asyncio.sleep(0.25)

        def slim(v: dict) -> dict:
            return {"ok": bool(v["ok"]), "leg": v["leg"],
                    "error": v["error"], "e2e_s": v["e2e_s"],
                    "trace": v["trace"]}

        phases: dict = {}
        try:
            phases["baseline"] = slim(await prober.probe_once())

            chaos.configure(
                f"seed={seed};store.client.op=latency:delay_s=3.0")
            phases["slow_store"] = slim(await prober.probe_once())
            chaos.disarm()
            phases["slow_store"]["recovered"] = await recover()

            clear_embed_cache()
            chaos.configure(
                f"seed={seed};device.poison=raise:peer=scorer")
            phases["device_poison"] = slim(await prober.probe_once())
            chaos.disarm()
            clear_embed_cache()
            phases["device_poison"]["recovered"] = await recover()

            chaos.configure(f"seed={seed};queue.dispatch="
                            f"wedge:times=1,wedge_s=30,peer=score")
            phases["wedged_dispatch"] = slim(await prober.probe_once())
            chaos.release("queue.dispatch")
            chaos.disarm()
            # let the deadline fail the wedged batch and the watchdog
            # replace the dispatch thread (dispatch_hang_s=1.0)
            await asyncio.sleep(2.5)
            phases["wedged_dispatch"]["recovered"] = await recover()

            # the last FAILED probe's trace: retrievable directly from
            # the tracer AND linked from a probe.e2e_s bucket exemplar
            failed_trace = phases["wedged_dispatch"]["trace"]
            spans = tracer.get_trace(failed_trace)
            snap = metrics.snapshot(exemplars=True)
            ex = snap.get("exemplars", {}).get("probe.e2e_s", {})
            linked = {e["trace_id"] for e in ex.values()}
            counters_after = dict(snap["counters"])
            return {
                "phases": phases,
                "trace_retrievable": bool(spans),
                "exemplar_linked": failed_trace in linked,
                "probe_ok_total":
                    counters_after.get("probe.ok", 0.0)
                    - counters_before.get("probe.ok", 0.0),
                "probe_failures_total":
                    counters_after.get("probe.failures", 0.0)
                    - counters_before.get("probe.failures", 0.0),
                "game_guesses_delta":
                    guesses_total(counters_after)
                    - guesses_total(counters_before),
                "admit_limit_moved":
                    (limiter is not None
                     and limiter._limit != limit_before),
            }
        finally:
            chaos.disarm()
            await prober.close()
            await service.score_queue.stop()
            await service.prompt_queue.stop()
            await server.close()
            await store.close()

    try:
        return asyncio.run(drive())
    finally:
        store_proc.kill()
        store_proc.wait()


def bench_canary_drill(weights_dir: str) -> dict:
    """ISSUE 18's deliverable: every armed fault class (slow store,
    device poison, wedged dispatch) caught by the very next probe —
    within one probe period by construction — with the failed probe's
    trace retrievable via its histogram exemplar, recovery observed
    once chaos disarms, and zero probe bleed into player surfaces
    (``game.guesses`` and the admission limiter stay flat). Knobs:
    BENCH_CANARY_SEED / BENCH_CANARY_STORE_PORT (env)."""
    env = os.environ.get
    raw = canary_drill_run(
        seed=int(env("BENCH_CANARY_SEED", "42")),
        store_port=int(env("BENCH_CANARY_STORE_PORT", "7661")),
    )
    phases = raw["phases"]
    faults = ("slow_store", "device_poison", "wedged_dispatch")
    detected = sum(1 for f in faults if not phases[f]["ok"])
    return {
        "metric": "canary_drill_faults_detected",
        "value": detected,
        "unit": "faults",
        "vs_baseline": None,
        "baseline_ok": phases["baseline"]["ok"],
        "all_detected_within_one_probe": detected == len(faults),
        "detected_legs": {f: phases[f]["leg"] for f in faults},
        "all_recovered": all(phases[f]["recovered"]["ok"]
                             for f in faults),
        "trace_retrievable": raw["trace_retrievable"],
        "exemplar_linked": raw["exemplar_linked"],
        "probe_invisible_to_players":
            raw["game_guesses_delta"] == 0
            and not raw["admit_limit_moved"],
        "game_guesses_delta": raw["game_guesses_delta"],
        "admit_limit_moved": raw["admit_limit_moved"],
        "phases": phases,
        # a detection count, not a timing: exact by construction
        "noise_tolerance": 0.0,
    }


# Counters whose per-entry deltas carry diagnostic weight: recompiles,
# cache effectiveness, staged-serving churn, and every supervision
# counter (suffix match). Attached to each BENCH_SUITE.json record so
# the bench trajectory carries its own diagnosis — a throughput drop
# that arrives with a jit.recompiles delta or a dispatch_hangs count
# explains itself without a rerun.
_DELTA_COUNTERS = {
    "jit.compiles", "jit.recompiles",
    # cumulative XLA compile WALL seconds (utils/jit_sentinel.py): a
    # 100 s recompile is visible in the trajectory, not just countable
    "jit.compile_seconds",
    "scorer.embed_cache_hits", "scorer.embed_cache_misses",
    "game.image_cache_hits", "game.image_cache_misses",
    "stage.denoise.admissions", "stage.denoise.preemptions",
    "stage.denoise.steps", "dispatch.thread_replacements",
    # overload control plane (ISSUE 13): brownout churn + shed totals
    "overload.brownout_trips", "overload.brownout_recoveries",
    "overload.score_shed", "overload.loop_lag_sheds",
    "pipeline.brownout_images",
    # embed-table scoring ladder (ISSUE 16): rung-0 serves vs queued
    # device dispatch — the A/B arms' attribution lives in these plus
    # the score queue totals (flat score.items IS the zero-device proof)
    "scorer.table_hits", "scorer.table_oov", "scorer.table_pins",
    "overload.table_served", "score.batches", "score.items",
    # output integrity + device recovery (ISSUE 17): invalid members
    # caught per pipeline/stage, staged-slot quarantines, and the
    # recovery loop's outcomes — a perf delta arriving with recoveries
    # or quarantines names its own cause
    "pipeline.output_invalid", "stage.denoise.quarantines",
    "rounds.generate_invalid", "device.recoveries",
    "device.recovery_permanent", "retry.budget_exhausted",
    "checkpoint.fingerprint_mismatch",
    # canary prober + tail sampling (ISSUE 18): probe verdict totals
    # (probe.failures rides the .failures suffix) and the tail
    # retention/abandonment accounting — a perf delta that arrives with
    # probe failures or abandoned traces names its own cause
    "probe.ok", "obs.tail_retained", "obs.traces_abandoned",
    # W8A8 serving (ISSUE 20): UNet forwards / LM bucket-group decode
    # dispatches that went through the int8 kernel path — zero in the
    # fp arms and under CASSMANTLE_NO_W8A8, so the A/B deltas are the
    # kernel-engagement receipts
    "pipeline.w8a8_dispatches",
}
_DELTA_SUFFIXES = (".dispatch_hangs", ".deadline_expired", ".rejected",
                   ".rejected_degraded", ".failures", ".loop_errors",
                   # overload control plane (ISSUE 13)
                   ".rejected_overload", ".rejected_predicted_late",
                   ".rejected_background",
                   # device-lost fail-fast rejections (ISSUE 17)
                   ".rejected_device_lost")


def _counter_snapshot() -> dict:
    from cassmantle_tpu.utils.logging import metrics

    return dict(metrics.snapshot()["counters"])


def _counter_deltas(before: dict, after: dict) -> dict:
    """Nonzero deltas of the diagnosis counters between two /metrics
    counter snapshots (labeled series keep their label suffix)."""
    out = {}
    for name, value in sorted(after.items()):
        base = name.split("{", 1)[0]
        if base not in _DELTA_COUNTERS and \
                not base.endswith(_DELTA_SUFFIXES):
            continue
        delta = value - before.get(name, 0.0)
        if delta:
            out[name] = int(delta) if float(delta).is_integer() \
                else delta
    return out


# The north-star config and its fastest challenger run FIRST, so a suite
# cut short still lands the two numbers the perf case turns on. Cheap
# CPU-light entries (scorer, gpt2) and the long e2e/soak runs come last.
SUITE = {
    "sd15": bench_sd15,
    "sd15_fast": bench_sd15_fast,
    "sd15_fusedconv": bench_sd15_fusedconv,
    "sd15_int8": bench_sd15_int8,
    "sd15_w8a8": bench_sd15_w8a8,
    "sd15_staged": bench_sd15_staged,
    "sd15_lcm": bench_sd15_lcm,
    "sd15_b8": bench_sd15_b8,
    "sdxl": bench_sdxl,
    "sdxl_w8a8": bench_sdxl_w8a8,
    "scorer": bench_scorer,
    "gpt2": bench_gpt2,
    "gpt2_spec": bench_gpt2_spec,
    "gpt2_w8a8": bench_gpt2_w8a8,
    "gpt2_b4": bench_gpt2_b4,
    "e2e": bench_e2e_round,
    "soak": bench_soak,
    "rooms_load": bench_rooms_load,
    "chaos_drill": bench_chaos_drill,
    "overload_drill": bench_overload_drill,
    "rooms_load_table": bench_rooms_load_table,
    "overload_drill_table": bench_overload_drill_table,
    "device_loss_drill": bench_device_loss_drill,
    "canary_drill": bench_canary_drill,
}

# ``--north-star-only`` measures exactly these, with BENCH_ROUNDS=1
# unless the caller already pinned a rep count: the smallest run that
# yields a hardware number for the target metric and its fastest
# challenger.
NORTH_STAR_ENTRIES = ("sd15", "sd15_fast")


def _run_entry_isolated(name: str, weights_dir: str,
                        timeout_s: float, cpu: bool = False) -> dict:
    """Run one suite entry as ``bench.py --entry NAME`` in a child
    process with a wall-clock timeout. Isolation matters for the two
    non-exception failure modes that can't be caught in-process: a
    device call that hangs (it never raises) and an OOM poisoning the
    shared process for every later entry. The persistent compile cache
    keeps per-child recompiles cheap.

    A failing or hung child is reported with its own error and nothing
    else happens: no retry on another code path, so a number in the
    record was always measured on the path its entry names."""
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__),
           "--entry", name, weights_dir]
    if cpu:
        cmd.insert(2, "--platform-cpu")
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired as exc:
        # keep whatever the child said before the kill: the only
        # diagnostics for how far the entry got
        tail = (exc.stderr or b"")
        if isinstance(tail, bytes):
            tail = tail.decode("utf-8", "ignore")
        return {"metric": name,
                "error": f"timeout after {timeout_s:.0f}s "
                         f"(device hang mid-suite?)",
                "stderr_tail": tail[-500:]}
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        return {"metric": name,
                "error": f"exit {proc.returncode}: {proc.stderr[-500:]}"}
    try:
        return json.loads(proc.stdout.splitlines()[-1])
    except Exception:
        return {"metric": name,
                "error": f"unparseable output: {proc.stdout[-300:]}"}


def main() -> None:
    args = list(sys.argv[1:])
    suite = "--suite" in args
    # --north-star-only: suite machinery (isolation, persistence, merge)
    # restricted to NORTH_STAR_ENTRIES at 1 timed round. An explicit
    # BENCH_ROUNDS still wins.
    north_only = "--north-star-only" in args
    if north_only:
        suite = True
        os.environ.setdefault("BENCH_ROUNDS", "1")
    # --platform-cpu: CPU smoke of the bench harness itself (numbers
    # are NOT measurements). Must pin before any jax import.
    global CPU_SMOKE
    cpu = CPU_SMOKE = "--platform-cpu" in args
    if cpu:
        from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

        pin_cpu_platform(virtual_devices=False)
    entry = None
    if "--entry" in args:
        i = args.index("--entry")
        if i + 1 >= len(args):
            sys.exit("--entry needs a suite entry name")
        entry = args[i + 1]
        del args[i:i + 2]
        if entry not in SUITE:
            sys.exit(f"unknown suite entry {entry!r}")
    flags = [a for a in args if a.startswith("--")]
    unknown = [f for f in flags
               if f not in ("--suite", "--platform-cpu",
                            "--north-star-only")]
    if unknown:
        sys.exit(f"unknown flag(s): {' '.join(unknown)} "
                 f"(--suite, --entry, --platform-cpu, "
                 f"--north-star-only)")
    args = [a for a in args if not a.startswith("--")]
    # defaults resolve against the repo, not the cwd (module-CLI runs
    # from anywhere); an explicit positional path keeps shell meaning
    repo = os.path.dirname(os.path.abspath(__file__))
    weights_dir = args[0] if args else os.path.join(repo, "weights")

    if entry:  # child mode: one entry, one JSON line, no probe
        # arm the jit compile sentinel (log-only) so the entry's delta
        # record can say how many (re)compiles its wall clock hides
        from cassmantle_tpu.utils import jit_sentinel

        jit_sentinel.enable_sentinel()
        before = _counter_snapshot()
        t0 = time.perf_counter()
        res = SUITE[entry](weights_dir)
        res["bench_wall_s"] = round(time.perf_counter() - t0, 1)
        deltas = _counter_deltas(before, _counter_snapshot())
        if deltas:
            res["counter_deltas"] = deltas
        print(json.dumps(res))
        return

    if not suite:
        print(json.dumps(bench_sd15(weights_dir)))
        return

    entry_timeout = float(os.environ.get("BENCH_ENTRY_TIMEOUT", "2400"))
    wanted = os.environ.get("BENCH_SUITE_ENTRIES")
    if north_only:
        if wanted:
            sys.stderr.write(
                "[suite] --north-star-only overrides "
                f"BENCH_SUITE_ENTRIES={wanted!r}\n")
        names = list(NORTH_STAR_ENTRIES)
    elif wanted:
        names = [n.strip() for n in wanted.split(",") if n.strip()]
        bad = sorted(set(names) - set(SUITE))
        if bad or not names:
            # a typo must not buy a successful empty overnight run
            sys.exit(f"BENCH_SUITE_ENTRIES has unknown entries {bad}; "
                     f"valid: {sorted(SUITE)}")
    else:
        names = list(SUITE)
    # Per-entry persistence: the suite file is rewritten atomically the
    # moment each entry completes, so a suite that dies midway still
    # lands every number measured before it did. Merge semantics: the
    # run starts from the existing record; a fresh success always
    # overwrites, but a fresh ERROR never clobbers a previously-measured
    # success — a failed run must not erase evidence. Partial runs
    # (BENCH_SUITE_ENTRIES) merge into the same file for the same
    # reason; there is no side ".partial" file any more.
    # BENCH_SUITE_PATH redirects the artifact (tests must not rewrite
    # the repo's real evidence file). CPU smoke runs are NOT
    # measurements — they get their own default file so a debug
    # invocation can never overwrite hardware evidence.
    default_name = ("BENCH_SUITE.cpu-smoke.json" if cpu
                    else "BENCH_SUITE.json")
    suite_path = os.environ.get(
        "BENCH_SUITE_PATH", os.path.join(repo, default_name))
    def load_disk() -> dict:
        if not os.path.exists(suite_path):
            return {}
        try:
            with open(suite_path) as f:
                data = json.load(f)
        except Exception as exc:
            sys.stderr.write(
                f"[suite] existing {suite_path} unreadable ({exc}); "
                f"starting fresh\n")
            return {}
        if not isinstance(data, dict):
            sys.stderr.write(
                f"[suite] existing {suite_path} is not an object; "
                f"starting fresh\n")
            return {}
        return data

    def persist_entry(name: str, res: dict) -> None:
        """Write ONE entry's outcome under an exclusive lock.

        Each entry is persisted exactly once, the moment it completes —
        never re-merged at later persists — so a concurrent suite run's
        fresher same-name measurement can't be clobbered by our older
        one at suite end. The read-resolve-write runs under the lock
        (per-pid tmp name) so two processes' writes can't interleave,
        and the keep-prior decision sees the LIVE file, not a snapshot.
        Merge rule: a fresh success overwrites; a fresh ERROR keeps a
        previously-measured success (a failed run must not erase
        evidence), annotated last_error/last_error_at so the
        file records that this run could not reproduce it."""
        import fcntl

        with open(suite_path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            merged = load_disk()
            prev = merged.get(name)
            if ("error" in res and isinstance(prev, dict)
                    and "error" not in prev):
                sys.stderr.write(
                    f"[suite] {name} failed this run; keeping prior "
                    f"measurement from {prev.get('measured_at', '?')} "
                    f"(new error: {res['error'][:200]})\n")
                kept = dict(prev)
                kept["last_error"] = res["error"][:300]
                kept["last_error_at"] = res["measured_at"]
                merged[name] = kept
            else:
                merged[name] = res
            tmp = f"{suite_path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=2)
            os.replace(tmp, suite_path)

    # regression sentinel (tools/bench_diff.py): snapshot the PRE-run
    # suite state so the end-of-run diff compares this run's fresh
    # numbers against what the file held before we merged into it
    baseline_before = load_disk()
    fresh_results: dict = {}
    north_star = None
    for name in names:
        res = _run_entry_isolated(name, weights_dir, entry_timeout,
                                  cpu=cpu)
        res["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())
        if name == "sd15":
            # the north-star guard below must see THIS run's outcome:
            # a fresh failure exits non-zero even when the file keeps a
            # prior measurement, so callers keying on the exit code
            # never mistake a stale number for a fresh green run
            north_star = res
        # the per-entry JSON stream always reports THIS run's outcome,
        # errors included; keep-prior only affects what's persisted
        print(json.dumps(res), file=sys.stderr)
        fresh_results[name] = res
        persist_entry(name, res)
    # print the regression-sentinel diff table (ISSUE 14): fresh run vs
    # the pre-run baseline, noise-aware per-entry tolerances. Advisory
    # here — the suite's exit semantics stay the north-star guard's;
    # gate CI on a separate `tools/bench_diff.py` invocation.
    try:
        from tools.bench_diff import diff_suites, format_table

        rows = diff_suites(baseline_before, fresh_results,
                           entries=list(fresh_results))
        sys.stderr.write("\n[suite] bench_diff vs pre-run baseline "
                         "(tools/bench_diff.py):\n"
                         + format_table(rows) + "\n")
    except Exception as exc:  # the diff must never fail the suite
        sys.stderr.write(f"[suite] bench_diff table unavailable: "
                         f"{exc}\n")
    if "sd15" in names and (north_star is None or "error" in north_star):
        # never emit a malformed north-star line with a zero exit
        sys.exit(f"north-star bench failed: {north_star}")
    if north_star is not None:
        print(json.dumps(north_star))


if __name__ == "__main__":
    main()
