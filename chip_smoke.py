#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the default serve path once, in ONE process that holds the chip,
through the entry points a user calls, at the full width of the models
the game server runs by default (MiniLM scorer, GPT-2 prompt LM, SD1.5 at
512x512 with 50-step CFG DDIM), with random weights made from fixed
seeds (models/weights.py::init_params_cached):

    scorer  EmbeddingScorer through InferenceService.score_queue
    lm      PromptGenerator through InferenceService.prompt_queue
    image   Text2ImagePipeline(FrameworkConfig()).generate, and the
            lowered/compiled sampler must contain the Pallas flash
            kernels (``tpu_custom_call``)
    server  the real app the way server/app.py::_run_worker builds it
            (build_fabric + create_app, device_health on, short rounds):
            /init, /client/status, /fetch/contents, /compute_score,
            /readyz over loopback HTTP, then one round rotation

``--chips 4`` runs ONLY the data-parallel image path: SD1.5 generate on
the ``dp=4`` mesh against the same global batch on one device.

Output contract. Every line on stdout is one JSON object written by
this script: one per phase, then LAST the result

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with exactly those keys (timings and counters live on the phase lines).
Anything else that would reach stdout (aiohttp, a child, a teardown
logger) is sent to stderr: fd 1 is re-pointed there before any import of
the program. The script exits non-zero and prints no result when jax
finds no TPU; a failed phase prints ``"ok": false`` and exits 1.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

#: what one serving dispatch of the game carries: TPUContentBackend
#: generates one image per round (serving/pipeline.py::generate_sync)
SERVING_BATCH = 1
ROUND_SECONDS = 20.0
#: tests only: long enough for a tiny CPU generation to land in the
#: buffer window (the last 70% of a round)
REHEARSAL_ROUND_SECONDS = 6.0
SHARDED_CHIPS = 4

PROMPTS = (
    "A watercolor style piece depicting: a lighthouse over a stormy sea",
    "An art deco style piece depicting: a caravan crossing silver dunes",
    "A stained glass style piece depicting: an orchard under two moons",
    "A vaporwave style piece depicting: a night train between cities",
)
#: phrases, not words: the committed int8 wordlist table serves single
#: in-vocabulary words as host dot products with no device work at all
SCORE_PAIRS = (
    ("a storm over the harbor", "a storm over the harbor"),
    ("the lantern flickered twice", "the lantern flickered twice"),
    ("a storm over the harbor", "a tempest above the port"),
    ("a storm over the harbor", "seven bicycles in a row"),
)


class PhaseFailed(Exception):
    """A phase's check did not hold; its message goes on the phase line."""


def claim_stdout():
    """Keep the real stdout for this script's own lines and point fd 1
    (and ``sys.stdout``) at stderr for everything else in the process."""
    own = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return own


def check(cond: bool, message: str) -> None:
    if not cond:
        raise PhaseFailed(message)


class Reporter:
    """Phase lines: name, seconds, and the compile/cache/HBM counters the
    program already keeps (utils/jit_sentinel.py, utils/compile_cache.py,
    the device's memory_stats)."""

    def __init__(self, out) -> None:
        self.out = out

    def emit(self, obj: dict) -> None:
        self.out.write(json.dumps(obj) + "\n")
        self.out.flush()

    @staticmethod
    def _counters() -> dict:
        import jax

        from cassmantle_tpu.utils.compile_cache import cache_event_counts
        from cassmantle_tpu.utils.logging import metrics

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        cache = cache_event_counts()
        return {
            "compile_seconds": metrics.counter_total("jit.compile_seconds"),
            "jit_compiles": metrics.counter_total("jit.compiles"),
            "jit_cache_hits": cache["hits"],
            "jit_cache_misses": cache["misses"],
            "peak_bytes_in_use": (max(p for p in peaks if p is not None)
                                  if any(p is not None for p in peaks)
                                  else None),
        }

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase and print its line; the body fills ``extras``.
        A raising body is reported on the line and re-raised as
        PhaseFailed, so the run stops at the first broken phase."""
        extras: dict = {}
        before = self._counters()
        t0 = time.perf_counter()
        error = None
        try:
            yield extras
        except Exception as exc:  # the boundary: report, then fail the run
            traceback.print_exc(file=sys.stderr)
            error = f"{type(exc).__name__}: {exc}"
        after = self._counters()
        line = {"phase": name, "ok": error is None,
                "seconds": round(time.perf_counter() - t0, 3)}
        line["compile_seconds"] = round(
            after["compile_seconds"] - before["compile_seconds"], 3)
        for key in ("jit_compiles", "jit_cache_hits", "jit_cache_misses"):
            line[key] = int(after[key] - before[key])
        line["peak_bytes_in_use"] = after["peak_bytes_in_use"]
        line.update(extras)
        if error is not None:
            line["error"] = error[:2000]
        self.emit(line)
        if error is not None:
            raise PhaseFailed(f"{name}: {error}")


# -- phases -----------------------------------------------------------------

async def phase_scorer(service, extras: dict) -> None:
    import numpy as np

    from cassmantle_tpu.utils.logging import metrics

    rows_before = metrics.counter_total("scorer.embed_cache_misses")
    sims = np.asarray(await asyncio.gather(
        *(service.score_queue.submit(p) for p in SCORE_PAIRS)),
        dtype=np.float64)
    device_rows = int(metrics.counter_total("scorer.embed_cache_misses")
                      - rows_before)
    extras.update(similarities=[round(float(s), 5) for s in sims],
                  device_rows=device_rows)
    check(device_rows == len({t for pair in SCORE_PAIRS for t in pair}),
          f"{device_rows} rows were encoded on the device")
    check(bool(np.isfinite(sims).all()), f"non-finite similarity: {sims}")
    check(bool((np.abs(sims) <= 1.0 + 1e-3).all()),
          f"similarity outside [-1, 1]: {sims}")
    same = [s for (a, b), s in zip(SCORE_PAIRS, sims) if a == b]
    check(all(abs(s - 1.0) < 1e-2 for s in same),
          f"identical words must score ~1, got {same}")


async def phase_lm(service, cfg, extras: dict) -> None:
    import numpy as np

    from cassmantle_tpu.serving.overload import PRIORITY_BACKGROUND

    seed = "The keeper climbed the lighthouse stairs as the storm rose"
    text = await service.prompt_queue.submit(
        seed, priority=PRIORITY_BACKGROUND)
    # the same greedy decode at the token level (one more dispatch of
    # the program compiled above): the queue returns text only
    gen = service.backend.prompt_gen
    tokens, lengths = await asyncio.to_thread(gen.decode_ids_batch, [seed])
    tokens, n_decoded = np.asarray(tokens), int(np.asarray(lengths)[0])
    extras.update(decoded_tokens=n_decoded,
                  max_new_tokens=cfg.sampler.max_new_tokens,
                  text_chars=len(text))
    check(isinstance(text, str), f"prompt queue returned {type(text)}")
    check(tokens.shape == (1, cfg.sampler.max_new_tokens),
          f"decode shape {tokens.shape}")
    check(n_decoded > 0, "the decode produced no token")
    check(bool(((tokens >= 0) & (tokens < gen.mcfg.vocab_size)).all()),
          "decoded token outside the vocabulary")


def sampler_kernels(pipe, n_prompts: int):
    """(kernel counts, compiled text) of the pipeline's sampler: how many
    Pallas kernels (``tpu_custom_call``) it holds as lowered for this
    backend and in the compiled program. Zero means attention gave way
    to ``xla_attention``."""
    import jax
    import jax.numpy as jnp

    ids = jnp.zeros((n_prompts, pipe.pad_len), jnp.int32)
    lowered = pipe._sample.lower(pipe._params, ids, ids,
                                 jax.random.PRNGKey(0))
    compiled_text = lowered.compile().as_text()
    return {"lowered": lowered.as_text().count("tpu_custom_call"),
            "compiled": compiled_text.count("tpu_custom_call")
            }, compiled_text


async def phase_image(pipe, cfg, on_chip: bool, extras: dict) -> None:
    import numpy as np

    prompts = list(PROMPTS[:SERVING_BATCH])
    images = await asyncio.to_thread(pipe.generate, prompts, 1)
    size = cfg.sampler.image_size
    extras.update(shape=list(images.shape), pixel_std=round(
        float(images.std()), 3), steps=cfg.sampler.num_steps)
    check(images.shape == (len(prompts), size, size, 3)
          and images.dtype == np.uint8, f"image batch {images.shape} "
          f"{images.dtype}")
    check(all(float(img.std()) > 0.0 for img in images),
          "a generated image is constant")
    kernels, _ = await asyncio.to_thread(sampler_kernels, pipe,
                                         len(prompts))
    extras["tpu_custom_calls"] = kernels
    # off the chip the dispatch takes the XLA path: nothing to check
    if on_chip:
        check(kernels["lowered"] > 0 and kernels["compiled"] > 0,
              "no tpu_custom_call in the sampler: the flash kernels are "
              "not in the program")


#: any of these means the server answered without the chip doing the
#: work: a replayed or reserve round, a failed or invalid generation, a
#: wedged dispatch, a shed request
MUST_STAY_ZERO = (
    "rounds.replays", "rounds.reserve_promotions", "reserve.picks",
    "rounds.buffer_failures", "rounds.promote_failures",
    "rounds.generate_invalid", "pipeline.output_invalid",
    "supervisor.dispatch_overruns", "overload.score_shed",
    "overload.loop_lag_sheds")


def server_state(supervisor) -> dict:
    from cassmantle_tpu.utils.logging import metrics

    names = MUST_STAY_ZERO + (
        "rounds.promoted", "rounds.generated", "rounds.buffered",
        "pipeline.text_fallbacks", "pipeline.images")
    return {
        "counters": {n: metrics.counter_total(n) for n in names},
        "loop_lag_s": max(metrics.gauge_values("server.loop_lag_s"),
                          default=None),
        "breakers": {b.name: b.state for b in (
            supervisor.content_breaker, supervisor.score_breaker)},
    }


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def phase_server(cfg, round_seconds: float, extras: dict) -> None:
    """The worker exactly as ``server/app.py::_run_worker`` assembles it
    (AppRunner/TCPSite are what ``web.run_app`` itself drives, minus its
    stdout banner and signal handlers), played over real loopback HTTP.
    HTTP 200s alone prove nothing here — this server is built to keep
    answering with the chip dark — so the pass condition reads the
    supervisor, the breakers and the round counters."""
    import base64
    import io

    import aiohttp
    import numpy as np
    from aiohttp import web
    from PIL import Image

    from cassmantle_tpu.server.app import (
        apply_fabric_env,
        build_fabric,
        create_app,
    )
    from cassmantle_tpu.utils.logging import metrics

    cfg = apply_fabric_env(cfg.replace(game=dataclasses.replace(
        cfg.game, time_per_prompt=round_seconds)))
    promoted_before = metrics.counter_total("rounds.promoted")
    fabric = await asyncio.to_thread(
        build_fabric, cfg, fake=False, weights_dir=None, store_addr=None)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    runner = web.AppRunner(create_app(
        fabric, cfg, device_health=True, self_addr=base))
    try:
        # on_startup generates the first round (LM decode + one image)
        await runner.setup()
        await web.TCPSite(runner, "127.0.0.1", port).start()
        # unsafe=True: the default jar drops cookies from IP-address hosts
        jar = aiohttp.CookieJar(unsafe=True)
        async with aiohttp.ClientSession(cookie_jar=jar) as http:

            async def ok_json(res, what: str):
                # a 503 here is the server shedding (overload plane,
                # breakers): say which, it is the finding
                check(res.status == 200, f"{what} -> {res.status} "
                      f"{dict(res.headers)}: {(await res.text())[:300]}")
                return await res.json()

            async def get(path: str):
                async with http.get(base + path) as res:
                    return await ok_json(res, f"GET {path}"), res.headers

            init, _ = await get("/init")
            check("session_id" in init, f"/init body {init}")
            status, _ = await get("/client/status")
            check(status.get("needInitialization") is False,
                  f"/client/status {status}")
            contents, _ = await get("/fetch/contents")
            first = np.asarray(Image.open(io.BytesIO(
                base64.b64decode(contents["image"]))))
            size = cfg.sampler.image_size
            check(first.shape == (size, size, 3),
                  f"served image {first.shape}")
            masks = contents["prompt"]["masks"]
            check(len(masks) == cfg.game.num_masked,
                  f"masks {masks}")
            # an out-of-wordlist guess: the int8 table cannot serve it,
            # so the pair rides the score queue to the device
            async with http.post(base + "/compute_score", json={
                    "inputs": {str(masks[0]): "qzxvolith"}}) as res:
                extras["scores"] = await ok_json(res, "POST /compute_score")
                check("X-Score-Degraded" not in res.headers,
                      "scores were floor scores")
            ready, _ = await get("/readyz")
            check(ready.get("ready") is True and ready.get("state") == "ok"
                  and ready.get("device") is True, f"/readyz {ready}")

            # one rotation: buffered at 30% of the round, promoted at
            # its end; the clock started when startup finished
            deadline = time.monotonic() + 3 * round_seconds + 120
            while metrics.counter_total("rounds.promoted") \
                    <= promoted_before:
                check(time.monotonic() < deadline,
                      "no round rotation before the deadline")
                await asyncio.sleep(0.5)
            contents2, _ = await get("/fetch/contents")
            second = np.asarray(Image.open(io.BytesIO(
                base64.b64decode(contents2["image"]))))
            check(first.shape != second.shape
                  or bool((first != second).any()),
                  "the rotated round serves the same image")
            ready, _ = await get("/readyz")

        check(ready.get("ready") is True and ready.get("device") is True,
              f"/readyz after rotation {ready}")
    finally:
        # what the server counted goes on the phase line, pass or fail
        extras.update(server_state(fabric.supervisor))
        # stops the listener, the room clocks, the serving queues and
        # their dispatch threads (RoomFabric.shutdown)
        await runner.cleanup()
    counts, sup = extras["counters"], fabric.supervisor
    check(counts["rounds.promoted"] - promoted_before >= 1,
          "rounds.promoted did not advance")
    check(sup.device_lost is None, f"device_lost: {sup.device_lost}")
    check(all(state == "closed" for state in extras["breakers"].values()),
          f"a breaker is not closed: {extras['breakers']}")
    for name in MUST_STAY_ZERO:
        check(counts[name] == 0, f"{name} = {counts[name]}")


# -- the two runs -----------------------------------------------------------

async def run_default(cfg, report: Reporter, on_chip: bool,
                      round_seconds: float) -> None:
    from cassmantle_tpu.serving.service import InferenceService

    with report.phase("build") as extras:
        # one device here, or the dp mesh default_serving_mesh builds
        # on a multi-chip host: whatever a user's server would get
        service = await asyncio.to_thread(InferenceService, cfg)
        extras["mesh"] = (None if service.backend.t2i.mesh is None
                          else dict(service.backend.t2i.mesh.shape))
    try:
        with report.phase("scorer") as extras:
            await phase_scorer(service, extras)
        with report.phase("lm") as extras:
            await phase_lm(service, cfg, extras)
        with report.phase("image") as extras:
            await phase_image(service.backend.t2i, cfg, on_chip, extras)
    finally:
        await service.stop()
    # the server phase builds its own serving stack, as a worker does:
    # release this one's device buffers first
    del service
    gc.collect()
    with report.phase("server") as extras:
        await phase_server(cfg, round_seconds, extras)


def run_sharded(cfg, report: Reporter, on_chip: bool) -> None:
    """dp=4: the batch sharded over four chips against the same global
    batch (same prompts, same seed, so the same initial latents) on one
    chip. Attention and convolutions never mix batch rows, so the two
    must agree up to the bf16 rounding of differently tiled programs."""
    import jax
    import numpy as np

    from cassmantle_tpu.config import MeshConfig
    from cassmantle_tpu.parallel.mesh import make_mesh
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    devices = jax.devices()[:SHARDED_CHIPS]
    prompts = [PROMPTS[i % len(PROMPTS)]
               for i in range(SHARDED_CHIPS * SERVING_BATCH)]
    with report.phase("image_dp4") as extras:
        mesh = make_mesh(MeshConfig(dp=-1), devices=devices)
        pipe = Text2ImagePipeline(cfg, mesh=mesh)
        sharded = pipe.generate(prompts, seed=7)
        leaves = jax.tree_util.tree_leaves(pipe._params)
        on_devices = sorted({d.id for leaf in leaves
                             for d in leaf.sharding.device_set})
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        param_bytes = sum(leaf.nbytes for leaf in leaves)
        kernels, compiled_text = sampler_kernels(pipe, len(prompts))
        partitions = f"num_partitions={SHARDED_CHIPS}"
        extras.update(
            shape=list(sharded.shape), mesh=dict(mesh.shape),
            param_devices=on_devices, param_bytes=param_bytes,
            bytes_in_use=in_use, tpu_custom_calls=kernels,
            spmd_partitions=partitions in compiled_text)
        check(on_devices == sorted(d.id for d in devices),
              f"params live on devices {on_devices}")
        check(extras["spmd_partitions"],
              f"compiled sampler is not {partitions}")
        if on_chip:
            check(kernels["lowered"] > 0 and kernels["compiled"] > 0,
                  "no tpu_custom_call in the sharded sampler")
            check(all(b is not None and b >= param_bytes for b in in_use),
                  f"a device holds less than the param tree: {in_use}")
    with report.phase("image_one_device") as extras:
        single = Text2ImagePipeline(cfg).generate(prompts, seed=7)
        extras["shape"] = list(single.shape)
    with report.phase("compare") as extras:
        check(sharded.shape == single.shape, "shapes differ")
        diff = np.abs(sharded.astype(np.int16) - single.astype(np.int16))
        extras.update(
            max_abs_diff=int(diff.max()),
            mean_abs_diff=round(float(diff.mean()), 4),
            mean_abs_diff_per_image=[round(float(d.mean()), 4)
                                     for d in diff],
            frac_within_2=round(float((diff <= 2).mean()), 5),
            frac_within_8=round(float((diff <= 8).mean()), 5),
            pixel_std=round(float(single.std()), 2))
        # uint8 levels. The math per row is the same, so only the
        # rounding of differently tiled bf16 programs, carried through
        # 50 steps, may separate the two; rows that were mixed up or
        # drew other latents differ by the pixel std (tens of levels)
        check(float(diff.mean()) < 2.0 and float((diff <= 8).mean()) > 0.99,
              f"sharded and single-device images disagree: {extras}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                        default=1, help="4: run only the dp=4 image path "
                        "and its one-device comparison")
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="tests only: tiny models on the CPU backend "
                        "(a rehearsal of control flow, never a result "
                        "about the chip)")
    args = parser.parse_args()
    out = claim_stdout()
    report = Reporter(out)

    if args.cpu_rehearsal:
        from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

        pin_cpu_platform(virtual_devices=args.chips > 1,
                         device_count=args.chips)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        print(f"chip_smoke: jax found no TPU (devices: {devices}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax reports {len(devices)}", file=sys.stderr)
        return 2

    from cassmantle_tpu.config import FrameworkConfig, test_config
    from cassmantle_tpu.utils import jit_sentinel
    from cassmantle_tpu.utils.compile_cache import enable_compile_cache

    # counts every compile and its wall seconds (jit.compiles,
    # jit.compile_seconds) for the phase lines
    jit_sentinel.enable_sentinel()
    enable_compile_cache()  # as every pipeline does at build
    cfg = test_config() if args.cpu_rehearsal else FrameworkConfig()
    report.emit({"phase": "start", "device": device,
                 "jax": jax.__version__, "chips": args.chips,
                 "rehearsal": args.cpu_rehearsal,
                 "compile_cache_dir":
                     jax.config.jax_compilation_cache_dir})
    ok = True
    try:
        if args.chips == SHARDED_CHIPS:
            run_sharded(cfg, report, on_chip)
        else:
            asyncio.run(run_default(
                cfg, report, on_chip,
                REHEARSAL_ROUND_SECONDS if args.cpu_rehearsal
                else ROUND_SECONDS))
    except PhaseFailed as exc:
        print(f"chip_smoke: FAILED {exc}", file=sys.stderr)
        ok = False
    report.emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    # the result line is the last thing this process says, and a
    # disowned dispatch or probe thread (daemon by design,
    # serving/queue.py, utils/health.py) must not turn a finished run
    # into a hang at interpreter teardown
    os._exit(code)
