"""Stage-disaggregated image serving: step-level continuous batching.

The monolithic image path (serving/pipeline.py, serving/sdxl.py) runs
CLIP encode → the full denoise scan → VAE decode as ONE device dispatch
under the pipeline dispatch lock — a request arriving one step after a
dispatch starts waits an entire image's latency for a slot. This module
splits the path into a **stage graph** (the SwiftDiffusion decoupled-
stages / LegoDiffusion micro-serving argument, PAPERS.md; ROADMAP open
item 1):

- **encode** — CLIP (or SDXL dual-tower) conditioning, its own
  :class:`~cassmantle_tpu.serving.queue.BatchingQueue` + bucket ladder
  and a dedicated dispatch worker;
- **denoise** — a persistent jitted STEP function over a fixed-capacity
  slot tensor (latents, per-slot step index, per-slot conditioning —
  no dynamic shapes; live slots gather into a power-of-two width
  bucket per step, so each bucket compiles exactly once and per-step
  compute tracks occupancy). A new request's encoded conditioning is
  admitted into a free slot at the next step boundary; a finished slot
  retires to the decode stage immediately; an expired deadline frees
  its slot at the next boundary instead of finishing the image;
- **decode** — VAE decode + uint8 postprocess, again a BatchingQueue +
  bucket ladder + dedicated dispatch worker (the blur pyramid stays in
  the game layer's per-fetch cache, ops/blur.py).

Parity bar: for a solo request the staged output is **bit-identical**
to the monolithic path (same seed → same image). The slot stepper
re-uses the monolithic schedule arrays and step arithmetic verbatim
(ops/samplers.py::make_slot_sampler, ops/ddim.py::make_slot_denoiser),
and every per-row computation in the UNet/CLIP/VAE is independent of
its batch neighbors — so admission at a step boundary cannot perturb
another slot (tests/test_stages.py pins both properties). To the bit
this holds between programs that hand every operation the same operand
shapes: everywhere off the TPU, and on the TPU at equal width. There
the UNet's 3x3 convolutions take one spatial axis into the batch under
8 rows (models/layers.py::conv3x3_form), so a step at 4 live slots or
more (CFG batch 8) sums a convolution's products in another order than
the same rows at 1 or 2 slots or in the monolithic batch-1 program:
equal to bfloat16 rounding, not to the bit.

Control state (which slot is at which step, which are free) lives
entirely on the HOST as plain numpy mirrors maintained by the single
denoise thread: the step loop never reads device values back, so there
is **no host sync inside the step loop** (the static-analysis shape
pinned in tests/test_check_concurrency.py). The only device→host
transfers are the decode stage's one ``np.asarray`` per decoded batch.

``CASSMANTLE_NO_STAGED_SERVING=1`` is the runtime kill switch
(docs/DEPLOY.md §6): pipelines fall back to the monolithic dispatch.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import os
import queue as _thread_queue
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cassmantle_tpu.chaos import fault_point
from cassmantle_tpu.obs.recorder import flight_recorder
from cassmantle_tpu.obs.trace import current_ctx, tracer
from cassmantle_tpu.ops.ddim import initial_latents, make_slot_denoiser
from cassmantle_tpu.ops.samplers import make_slot_sampler
from cassmantle_tpu.serving import integrity
from cassmantle_tpu.serving.integrity import OutputInvalid, finite_verdict
from cassmantle_tpu.serving.queue import (
    BatchingQueue,
    DeadlineExceeded,
    DispatchTimeout,
    QueueStopped,
    _DispatchWorker,
)
from cassmantle_tpu.utils.locks import OrderedLock
from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("stages")

#: sampler kinds whose per-step arithmetic the slot stepper replays
#: bit-exactly (deterministic, coefficient-gatherable — see
#: ops/samplers.py::make_slot_sampler)
STAGEABLE_KINDS = ("ddim", "euler", "dpmpp_2m")


def staged_serving_disabled() -> bool:
    """Runtime kill switch (same env parse as the other serving
    switches): CASSMANTLE_NO_STAGED_SERVING=1 routes every generate
    through the proven monolithic dispatch."""
    return os.environ.get("CASSMANTLE_NO_STAGED_SERVING", "").lower() \
        not in ("", "0", "false", "no", "off")


class _Unit:
    """One latent row flowing encode → denoise → decode. ``done`` is
    resolved by the denoise thread with the finished latent row (or the
    preemption error); everything else is bookkeeping."""

    __slots__ = ("ids", "uncond_ids", "lat", "aux", "cond", "done",
                 "deadline", "ctx", "slot", "admit_step",
                 "t_ready", "t_admit", "wall_ready")

    def __init__(self, ids, uncond_ids, lat, aux, deadline) -> None:
        self.ids = ids
        self.uncond_ids = uncond_ids
        self.lat = lat
        self.aux = aux
        self.cond: Optional[dict] = None
        self.done: concurrent.futures.Future = concurrent.futures.Future()
        self.deadline = deadline
        self.ctx = None
        self.slot: Optional[int] = None
        self.admit_step: Optional[int] = None
        self.t_ready = 0.0
        self.t_admit = 0.0
        self.wall_ready = 0.0

    def remaining(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return max(0.01, self.deadline - time.monotonic())


class StagedImageServer:
    """The in-process stage scheduler one image pipeline owns when
    ``ServingConfig.staged_serving`` is on.

    The pipeline supplies its model-specific pieces as callables:

    - ``encode_fn(params, ids, uncond_ids) -> dict`` of conditioning
      arrays, each ``(B, ...)`` (SD1.5: ``ctx``/``uctx``; SDXL adds
      ``add``/``uadd``) — jitted here, one compile per encode bucket;
    - ``unet_apply`` + ``guidance_scale`` — wrapped by
      :func:`make_slot_denoiser` into the per-slot CFG step;
    - ``decode_fn(params, lat) -> uint8 images`` — jitted here, one
      compile per decode bucket;
    - ``tokenize(prompts) -> np.int32 ids`` — the pipeline's own
      tokenizer path, so staged and monolithic tokenize identically.

    ``generate`` keeps the monolithic call shape (sync, returns the
    stacked uint8 batch) so :class:`TPUContentBackend` and the bench
    drive either path unchanged.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        encode_fn: Callable,
        decode_fn: Callable,
        unet_apply: Callable,
        tokenize: Callable[[Sequence[str]], np.ndarray],
        vae_scale: int,
        supervisor=None,
    ) -> None:
        self.cfg = cfg
        self._params = params
        self._tokenize = tokenize
        self._vae_scale = vae_scale
        self._negative = cfg.sampler.negative_prompt
        self._supervisor = supervisor
        s = cfg.sampler
        assert s.kind in STAGEABLE_KINDS and s.eta == 0.0, (
            "staged serving supports deterministic ddim/euler/dpmpp_2m; "
            "the pipeline should have fallen back "
            f"to monolithic for {s.kind!r}")
        self.capacity = int(cfg.serving.denoise_slots)
        assert self.capacity >= 1
        # step-width bucket ladder: powers of two up to capacity (plus
        # capacity itself). The step gathers live slots into the
        # smallest bucket ≥ occupancy, so per-step UNet compute tracks
        # load instead of always paying the full slot width; each
        # bucket compiles once (tests pin the cache size).
        self._step_widths = []
        w = 1
        while w < self.capacity:
            self._step_widths.append(w)
            w *= 2
        self._step_widths.append(self.capacity)
        # few-step consistency serving rides the slot stepper through
        # its own make_slot_sampler variant (the deterministic re-noise
        # ladder folds each slot's OWN timestep, so mid-flight
        # admission replays exactly); with the kill switch set the
        # effective step count reverts to the teacher schedule, the
        # same bit-exact revert the monolithic path takes
        from cassmantle_tpu.ops.samplers import consistency_disabled
        from cassmantle_tpu.serving.pipeline import (
            effective_sampler_steps,
        )

        slot_kind = ("consistency"
                     if s.consistency and not consistency_disabled()
                     else s.kind)
        self._prepare, self._slot_step, self.num_steps = make_slot_sampler(
            slot_kind, effective_sampler_steps(s), eta=s.eta,
            teacher_steps=s.consistency_teacher_steps)
        self._denoise = make_slot_denoiser(unet_apply, s.guidance_scale)
        # jit surfaces — each compiles once per shape bucket and is the
        # ONLY dispatcher of its computation (one thread each), so no
        # compiled graph ever has two concurrent executions (the CPU-
        # backend deadlock the monolithic dispatch locks exist for)
        self._encode = jax.jit(encode_fn)
        self._decode = jax.jit(decode_fn)
        self._step = jax.jit(self._step_impl)
        self._admit = jax.jit(self._admit_impl)
        self._take = jax.jit(self._take_impl)
        self._fin_check = jax.jit(finite_verdict)
        self._fin_check_dec = jax.jit(finite_verdict)
        self._init = jax.jit(self._init_impl, static_argnums=0)
        # scheduler lifecycle only — never held across a device dispatch
        # or a cross-stage handoff (docs/STATIC_ANALYSIS.md rank 14)
        self._lock = OrderedLock("stage.scheduler", rank=14)
        self._started = False
        self._stop_evt = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._loop_thread: Optional[threading.Thread] = None
        self._denoise_thread: Optional[threading.Thread] = None
        self._encode_q: Optional[BatchingQueue] = None
        self._decode_q: Optional[BatchingQueue] = None
        self._enc_buckets = tuple(cfg.serving.stage_encode_batch_sizes)
        self._dec_buckets = tuple(cfg.serving.stage_decode_batch_sizes)
        # denoise-thread-owned state: slot device arrays + host mirrors
        self._admit_q: _thread_queue.Queue = _thread_queue.Queue()
        self._pend: deque = deque()
        # in-flight generate() futures; stop() waits for them to unwind
        # before killing the loop their coroutines resume on (set ops
        # are GIL-atomic — no lock needed)
        self._inflight: set = set()
        self._lat = None
        self._aux = None
        self._cond: Optional[Dict[str, jax.Array]] = None
        # per-slot finiteness verdict (integrity rung 2): a SEPARATE
        # tiny jitted reduction over the slot tensor, dispatched after
        # each step and read back lazily. Kept OUT of the step jit on
        # purpose — an extra consumer inside that graph changes XLA
        # fusion decisions and breaks the staged-vs-monolithic
        # bit-parity bar (tests/test_stages.py).
        self._finite = None
        self._fin_probes: deque = deque()
        self._steps = np.zeros((self.capacity,), dtype=np.int32)
        self._alive = np.zeros((self.capacity,), dtype=bool)
        self._slots: List[Optional[_Unit]] = [None] * self.capacity
        self._free = list(range(self.capacity - 1, -1, -1))
        self._active_n = 0
        self._probe = None  # (wall, array) wedge-watchdog probe window
        # single-writer (denoise thread) counters; the bench derives
        # mean slot occupancy as slot_steps / (steps * capacity)
        self.stats = {"steps": 0, "slot_steps": 0, "admissions": 0,
                      "retirements": 0, "preemptions": 0,
                      "quarantines": 0}
        self._on_step = None  # test seam: called once per loop iteration
        # roofline attribution: per-image denoise FLOPs, traced on a
        # background thread kicked off at the first retirement (needs
        # the cond shapes to exist; must never stall the step loop)
        self._flops_img = None
        self._flops_trace_started = False

    # -- jitted pieces -----------------------------------------------------

    def _init_impl(self, batch: int, rng):
        """Per-request solver-space entry state — the same
        ``initial_latents`` call (same key, same shape) the monolithic
        jit traces, then the sampler's prepare (identity for DDIM/DPM++,
        the sigma-max scale for Euler)."""
        size = self.cfg.sampler.image_size
        return self._prepare(initial_latents(rng, batch, size,
                                             self._vae_scale))

    def _step_impl(self, params, lat, aux, cond, idx, slots):
        """One denoise step for the OCCUPIED slots only: ``slots`` is a
        width-``w`` int32 vector of slot indices (the smallest width
        bucket ≥ occupancy, padded by REPEATING the first live slot —
        duplicate rows compute bit-identical values, so the duplicate
        scatter writes are idempotent). Gather → step → scatter keeps
        the slot tensor fixed-shape while the UNet batch tracks
        occupancy: one compile per width bucket, never per admission,
        and a solo request pays the same per-step compute as the
        monolithic scan instead of a capacity-wide batch. Per-slot
        timesteps and schedule coefficients gather from ``idx``; rows
        are computation-independent, so neighbors cannot perturb each
        other."""
        lat_g = lat[slots]
        aux_g = aux[slots]
        cond_g = {k: v[slots] for k, v in cond.items()}
        idx_g = idx[slots]

        def dn(x, t):
            return self._denoise(params["unet"], x, t,
                                 cond_g["ctx"], cond_g["uctx"],
                                 cond_g.get("add"), cond_g.get("uadd"))

        new_lat, new_aux = self._slot_step(dn, lat_g, aux_g, idx_g)
        return lat.at[slots].set(new_lat), aux.at[slots].set(new_aux)

    @staticmethod
    def _admit_impl(lat, aux, cond, slot, lat_row, aux_row, cond_rows):
        """Write one request's rows into slot ``slot``. ``slot`` is a
        TRACED scalar, so admission into any slot reuses one compiled
        graph — no recompiles at admission/retirement. The quarantine
        scrub reuses this same graph with zero rows."""

        def put(dst, row):
            return jax.lax.dynamic_update_slice(
                dst, row, (slot,) + (0,) * (row.ndim - 1))

        return (put(lat, lat_row), put(aux, aux_row),
                {k: put(cond[k], cond_rows[k]) for k in cond})

    @staticmethod
    def _take_impl(lat, slot):
        return jax.lax.dynamic_slice_in_dim(lat, slot, 1, axis=0)

    # -- lifecycle ---------------------------------------------------------

    def _ensure_started(self) -> None:
        with self._lock:
            if self._started:
                return
            self._stop_evt.clear()
            self._loop = asyncio.new_event_loop()
            self._loop_thread = threading.Thread(
                target=self._loop.run_forever, daemon=True,
                name="cassmantle-stage-loop")
            self._loop_thread.start()
            self._denoise_thread = threading.Thread(
                target=self._denoise_loop, daemon=True,
                name="cassmantle-stage-denoise")
            self._denoise_thread.start()
            self._started = True

    def _ensure_queues(self) -> None:
        """Built lazily ON the stage event loop (single-threaded there,
        so no lock needed): each stage queue gets its OWN dispatch
        worker — encode/decode batches must not serialize behind the
        process-global worker's score/prompt dispatches."""
        if self._encode_q is not None:
            return
        serving = self.cfg.serving
        sup = self._supervisor
        # default_deadline_s stays None: the monolithic image path has
        # no deadline, and a cold-cache compile can take minutes — the
        # dispatch watchdog (hang_timeout_s) covers wedges, and request
        # deadlines apply only when the caller passes one.
        self._encode_q = BatchingQueue(
            handler=self._encode_batch,
            max_batch=max(self._enc_buckets),
            max_delay_ms=serving.stage_max_delay_ms,
            max_pending=serving.max_pending,
            name="stage.encode",
            hang_timeout_s=serving.dispatch_hang_s,
            supervisor=sup,
            degraded_max_pending=serving.degraded_max_pending,
            dispatcher=_DispatchWorker("stage.encode_dispatch", rank=21),
        )
        self._decode_q = BatchingQueue(
            handler=self._decode_batch,
            max_batch=max(self._dec_buckets),
            max_delay_ms=serving.stage_max_delay_ms,
            max_pending=serving.max_pending,
            name="stage.decode",
            hang_timeout_s=serving.dispatch_hang_s,
            supervisor=sup,
            degraded_max_pending=serving.degraded_max_pending,
            dispatcher=_DispatchWorker("stage.decode_dispatch", rank=22),
        )

    def stop(self) -> None:
        """Tear the stage graph down; pending/in-flight requests fail
        with :class:`QueueStopped` rather than dangling.

        Ordering is load-bearing: units are failed and the stage queues
        stopped WHILE the stage event loop still runs — their waiters
        resume via ``asyncio.wrap_future`` callbacks scheduled on that
        loop, so failing them after the loop stops would strand callers
        in ``generate``'s ``cf.result()`` forever. The loop is stopped
        only after every in-flight request future has completed."""
        with self._lock:
            started = self._started
            self._started = False
        if not started:
            return
        self._stop_evt.set()
        if self._denoise_thread is not None:
            self._denoise_thread.join(timeout=10.0)
        # the denoise thread is down: its structures are safe to drain
        leftovers = list(self._pend)
        self._pend.clear()
        while True:
            try:
                leftovers.append(self._admit_q.get_nowait())
            except _thread_queue.Empty:
                break
        for i, u in enumerate(self._slots):
            if u is not None:
                leftovers.append(u)
                self._slots[i] = None
        self._free = list(range(self.capacity - 1, -1, -1))
        self._alive[:] = False
        self._active_n = 0
        self._probe = None
        for u in leftovers:
            self._fail_unit(u, QueueStopped("stage.denoise"))

        async def _shutdown():
            if self._encode_q is not None:
                await self._encode_q.stop()
            if self._decode_q is not None:
                await self._decode_q.stop()

        asyncio.run_coroutine_threadsafe(
            _shutdown(), self._loop).result(timeout=10.0)
        # every request coroutine now has its failure/result scheduled;
        # wait for them to unwind before the loop they run on dies. A
        # request whose encode completed BEFORE the queue stop can
        # still race its admit-queue put past the drain above — keep
        # draining while we wait so such a unit is failed promptly
        # instead of stranding its caller.
        deadline = time.monotonic() + 10.0
        for cf in list(self._inflight):
            while not cf.done() and time.monotonic() < deadline:
                try:
                    self._fail_unit(self._admit_q.get_nowait(),
                                    QueueStopped("stage.denoise"))
                except _thread_queue.Empty:
                    time.sleep(0.005)
            if not cf.done():  # pragma: no cover
                log.error("stage request future did not unwind in 10s")
        self._loop.call_soon_threadsafe(self._loop.stop)
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=10.0)
        self._encode_q = None
        self._decode_q = None

    # -- request entry -----------------------------------------------------

    def generate(self, prompts: Sequence[str], seed: int = 0,
                 deadline_s: Optional[float] = None) -> np.ndarray:
        """Monolithic-compatible entry: prompts -> (B, H, W, 3) uint8.
        Runs the request through the stage graph; blocks the calling
        (executor) thread until every row decodes. ``deadline_s`` is
        honored at STEP granularity inside the denoise stage."""
        self._ensure_started()
        cf = asyncio.run_coroutine_threadsafe(
            self._request(list(prompts), int(seed), deadline_s),
            self._loop)
        self._inflight.add(cf)
        cf.add_done_callback(self._inflight.discard)
        return cf.result()

    async def _request(self, prompts: List[str], seed: int,
                       deadline_s: Optional[float]) -> np.ndarray:
        self._ensure_queues()
        # no implicit deadline: the monolithic generate() has none, and
        # a cold-cache first dispatch (encode + per-width step buckets +
        # decode compiles) can legitimately take minutes — deadlines
        # apply only when the caller passes one
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        ids = self._tokenize(prompts)
        uncond = self._tokenize([self._negative] * len(prompts))
        # one normal draw for the WHOLE request, exactly the monolithic
        # shape (row i of a B-row draw, not B separate draws)
        lat0, aux0 = self._init(len(prompts), jax.random.PRNGKey(seed))
        units = [
            _Unit(ids[i:i + 1], uncond[i:i + 1],
                  lat0[i:i + 1], aux0[i:i + 1], deadline)
            for i in range(len(prompts))
        ]
        images = await asyncio.gather(*(self._process(u) for u in units))
        return np.concatenate(images, axis=0)

    async def _process(self, u: _Unit) -> np.ndarray:
        sup = self._supervisor
        u.ctx = current_ctx()
        u.cond = await self._encode_q.submit(
            (u.ids, u.uncond_ids), deadline_s=u.remaining())
        if sup is not None:
            sup.note_stage_progress("encode")
        if self._stop_evt.is_set():
            # the denoise thread is (being) torn down: enqueueing now
            # would strand this caller on a queue nobody pops. stop()'s
            # drain-while-waiting loop catches the tiny window between
            # this check and put().
            raise QueueStopped("stage.denoise")
        u.t_ready = time.monotonic()
        u.wall_ready = time.time()
        self._admit_q.put(u)
        row = await asyncio.wrap_future(u.done)
        img = await self._decode_q.submit(row, deadline_s=u.remaining())
        if sup is not None:
            sup.note_stage_progress("decode")
        return img

    # -- encode / decode stage handlers (their dispatch threads) -----------

    def _encode_batch(self, items):
        n = len(items)
        bucket = next((b for b in self._enc_buckets if n <= b), n)
        pad_len = items[0][0].shape[1]
        ids = np.zeros((bucket, pad_len), dtype=np.int32)
        uncond = np.zeros((bucket, pad_len), dtype=np.int32)
        for i, (row, urow) in enumerate(items):
            ids[i] = row[0]
            uncond[i] = urow[0]
        cond = self._encode(self._params, jnp.asarray(ids),
                            jnp.asarray(uncond))
        # per-item device-side row views — no transfer here; rows ride
        # to the denoise admission queue as device arrays
        return [{k: v[i:i + 1] for k, v in cond.items()}
                for i in range(n)]

    def _decode_batch(self, rows):
        n = len(rows)
        bucket = next((b for b in self._dec_buckets if n <= b), n)
        if bucket > n:
            rows = list(rows) + [jnp.zeros_like(rows[0])] * (bucket - n)
        lat = jnp.concatenate(rows, axis=0)
        # retirement verdict on the LATENTS, a separate tiny dispatch
        # before decode (a verdict output folded into the decode jit
        # would change fusion and break the staged-vs-monolithic
        # bit-parity bar); its own jit instance so this thread never
        # shares an executable with the denoise thread's slot check
        verdict = self._fin_check_dec(lat)
        images = self._decode(self._params, lat)
        # the ONE device->host transfer of the whole stage graph:
        # collect-once per decoded batch (the verdict vector is tiny
        # and already in flight)
        images = np.asarray(images)
        bad = set(integrity.invalid_members(
            np.asarray(verdict), images=images, n=n).tolist())
        if bad:
            # per-member failure: one poisoned row (e.g. a quarantine
            # race that retired before its verdict landed) fails ITS
            # request; neighbors in this decode batch still get images
            integrity.note_invalid("staged", "decode", sorted(bad))
        return [OutputInvalid("staged", "decode", [i]) if i in bad
                else images[i:i + 1] for i in range(n)]

    # -- denoise stage (its own thread) ------------------------------------

    def _drain_admissions(self, block: bool) -> None:
        try:
            if block:
                self._pend.append(self._admit_q.get(timeout=0.05))
            while True:
                self._pend.append(self._admit_q.get_nowait())
        except _thread_queue.Empty:
            pass

    def _denoise_loop(self) -> None:
        """The step-level continuous-batching loop. All control state is
        host-side (numpy mirrors, single thread); the loop dispatches
        jitted work and NEVER reads device values back — retirement
        hands a device-side row to the decode stage, whose handler does
        the one sync per decoded batch."""
        while not self._stop_evt.is_set():
            try:
                self._denoise_tick()
            except Exception as exc:  # noqa: BLE001 — contained below
                # a step/trace failure must fail the waiting callers,
                # not silently kill this thread and hang their futures;
                # the loop keeps serving (a later admission re-traces)
                log.exception("stage.denoise loop error")
                metrics.inc("stage.denoise.loop_errors")
                self._fail_inflight(exc)

    def _fail_inflight(self, exc: Exception) -> None:
        """Fail every admitted/pending unit after a loop error and reset
        the slot state so the next admission starts clean."""
        for slot, u in enumerate(self._slots):
            if u is not None:
                self._fail_unit(u, exc)
                self._free_slot(slot)
        while self._pend:
            self._fail_unit(self._pend.popleft(), exc)
        self._lat = self._aux = self._cond = self._finite = None
        self._fin_probes.clear()
        self._probe = None

    def _denoise_tick(self) -> None:
        # the test seam runs FIRST so a hook that holds this boundary
        # until a submission lands observes that admission drained and
        # admitted at this same boundary, not the next one
        hook = self._on_step
        if hook is not None:
            hook(self)
        # staged-tick fault point (docs/CHAOS.md): a raise exercises the
        # loop-error containment below (in-flight callers failed, loop
        # survives); a wedge holds the denoise thread so the stage
        # progress watchdog path is the thing that notices
        fault_point("stage.denoise.tick")
        idle = self._active_n == 0 and not self._pend
        self._drain_admissions(block=idle)
        now = time.monotonic()
        self._admit_pending(now)
        self._preempt_expired(now)
        if self._active_n == 0:
            return
        width = next(w for w in self._step_widths
                     if w >= self._active_n)
        live = np.flatnonzero(self._alive).astype(np.int32)
        slots = np.full((width,), live[0], dtype=np.int32)
        slots[: len(live)] = live
        # .copy() on the steps mirror is load-bearing: the CPU backend
        # may zero-copy ALIAS a numpy buffer handed to jnp.asarray, and
        # the step dispatch is async — _note_step mutates the mirror in
        # place right after dispatch, so an aliased buffer lets an
        # in-flight step read NEXT tick's indices (wrong schedule
        # coefficients, silently wrong images). A private copy per
        # dispatch is immune; ``slots``/``live`` are fresh per tick.
        idx = jnp.asarray(self._steps.copy())
        self._lat, self._aux = self._step(
            self._params, self._lat, self._aux, self._cond, idx,
            jnp.asarray(slots))
        # per-slot finiteness verdict as a SEPARATE tiny dispatch on
        # the step's output (a consumer inside the step jit would
        # change fusion and break the bit-parity bar); stale rows in
        # freed slots may read non-finite, but the probe only judges
        # units that still own their slot
        self._finite = self._fin_check(self._lat)
        # snapshot (verdict array, slot→unit) for the lazy quarantine
        # probe: units are judged only while they still own their slot
        self._fin_probes.append((self._finite, tuple(self._slots)))
        self._note_step()
        self._check_quarantine()
        self._retire_finished()
        self._watchdog_check()

    def _ensure_state(self, u: _Unit) -> None:
        if self._lat is not None:
            return
        c = self.capacity

        def zeros(row):
            return jnp.zeros((c,) + row.shape[1:], row.dtype)

        self._lat = zeros(u.lat)
        self._aux = zeros(u.aux)
        self._cond = {k: zeros(v) for k, v in u.cond.items()}

    def _admit_pending(self, now: float) -> None:
        while self._pend and self._free:
            u = self._pend.popleft()
            if u.deadline is not None and now >= u.deadline:
                self._preempt(u, "expired_before_admission")
                continue
            slot = self._free.pop()
            self._ensure_state(u)
            # device.poison drill lever: corrupts THIS request's latent
            # row at admission — detection must come from the per-step
            # verdict + quarantine path, never from the injection site
            lat_row = integrity.poison(u.lat, peer="stage")
            self._lat, self._aux, self._cond = self._admit(
                self._lat, self._aux, self._cond,
                jnp.int32(slot), lat_row, u.aux, u.cond)
            # the slot tensor now owns copies; dropping the unit's row
            # references releases the views that would otherwise pin
            # the whole encode batch (and the request's init draw) in
            # device memory for the entire denoise
            u.cond = None
            u.lat = None
            u.aux = None
            self._steps[slot] = 0
            self._alive[slot] = True
            self._slots[slot] = u
            self._active_n += 1
            u.slot = slot
            u.admit_step = self.stats["steps"]
            u.t_admit = now
            self.stats["admissions"] += 1
            metrics.inc("stage.denoise.admissions")
            metrics.observe("stage.denoise.queue_wait_s",
                            now - u.t_ready)
            flight_recorder.record(
                "stage.admit", stage="denoise", slot=slot,
                step=self.stats["steps"],
                occupancy=self._active_n)

    def _preempt(self, u: _Unit, reason: str) -> None:
        self.stats["preemptions"] += 1
        metrics.inc("stage.denoise.preemptions")
        flight_recorder.record(
            "stage.preempt", stage="denoise", reason=reason,
            slot=u.slot, step=self.stats["steps"],
            steps_done=int(self._steps[u.slot]) if u.slot is not None
            else 0)
        self._fail_unit(u, DeadlineExceeded("stage.denoise"))

    def _preempt_expired(self, now: float) -> None:
        """Deadline honor at STEP granularity: an expired request frees
        its slot at this boundary instead of finishing the image; the
        freed slot's stale rows cannot perturb neighbors (rows are
        independent and a freed slot is excluded from the gathered
        step)."""
        for slot, u in enumerate(self._slots):
            if u is None or u.deadline is None or now < u.deadline:
                continue
            self._preempt(u, "deadline")
            self._free_slot(slot)

    def _free_slot(self, slot: int) -> None:
        self._slots[slot] = None
        self._alive[slot] = False
        self._steps[slot] = 0  # hygiene: freed slots never enter the
        self._free.append(slot)  # gathered step until re-admitted
        self._active_n -= 1

    def _note_step(self) -> None:
        self.stats["steps"] += 1
        self.stats["slot_steps"] += self._active_n
        for slot, u in enumerate(self._slots):
            if u is not None:
                self._steps[slot] += 1
        metrics.inc("stage.denoise.steps")
        metrics.gauge("stage.denoise.slot_occupancy",
                      self._active_n / self.capacity)

    # -- slot quarantine (integrity rung 2) --------------------------------

    def _check_quarantine(self) -> None:
        """Quarantine slots whose latents went non-finite mid-flight,
        detected from the per-step verdict dispatch with NO
        blocking sync: only READY verdict arrays are read (the same
        non-blocking ``is_ready`` discipline as the wedge watchdog), so
        detection lags dispatch by however long the device pipeline
        runs deep — bounded, because a poisoned slot's verdict stays
        False every subsequent step (NaN propagates) until scrubbed.
        A poisoned row that retires before its verdict lands is caught
        by the retirement verdict instead (never reaches a player).
        Under ``CASSMANTLE_NO_INTEGRITY_CHECKS`` (read per tick) ready
        probes drain unjudged — no quarantines, matching the global
        kill-switch contract.
        """
        probes = self._fin_probes
        disabled = integrity.integrity_disabled()
        while probes and self._array_ready(probes[0][0]):
            fin, units = probes.popleft()
            # ready ⇒ copy-out, not a device wait
            # lint: ignore[host-sync] — is_ready-gated read of a (capacity,) bool vector
            verdict = np.asarray(fin)
            for slot, u in enumerate(units):
                if disabled or u is None or verdict[slot]:
                    continue
                if self._slots[slot] is not u:
                    # already retired/preempted; admission re-writes
                    # the rows, so stale state cannot leak forward
                    continue
                self._quarantine(slot, u)
        # drop stale unread probes: detection does not depend on any
        # single probe (the per-slot verdict is persistent), and an
        # unready backlog must not grow without bound
        while len(probes) > 32:
            probes.popleft()

    def _quarantine(self, slot: int, u: _Unit) -> None:
        """Retire a poisoned slot with OutputInvalid and scrub its
        rows (zero-fill through the same compiled admission graph)
        before the slot can be reused; repeated quarantines trip the
        content breaker via the supervisor, so a sick device reads as
        sick, not as a run of unlucky requests."""
        steps_done = int(self._steps[slot])
        self.stats["quarantines"] += 1
        metrics.inc("stage.denoise.quarantines")
        integrity.note_invalid("staged", "denoise", [slot])
        flight_recorder.record(
            "stage.quarantine", stage="denoise", slot=slot,
            step=self.stats["steps"], steps_done=steps_done)
        log.error("stage.denoise slot %d latents non-finite after %d "
                  "steps: quarantined", slot, steps_done)
        zero_lat = jnp.zeros((1,) + self._lat.shape[1:], self._lat.dtype)
        zero_aux = jnp.zeros((1,) + self._aux.shape[1:], self._aux.dtype)
        zero_cond = {k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                     for k, v in self._cond.items()}
        self._lat, self._aux, self._cond = self._admit(
            self._lat, self._aux, self._cond,
            jnp.int32(slot), zero_lat, zero_aux, zero_cond)
        self._fail_unit(u, OutputInvalid("staged", "denoise", [slot]))
        self._free_slot(slot)
        sup = self._supervisor
        if sup is not None:
            sup.content_breaker.record_failure()

    def _denoise_flops_per_image(self):
        """Analytic FLOPs of one request's full denoise residency (CFG
        denoiser × num_steps), traced once from the actual slot
        denoiser at width 1 (obs/costmodel.py — exact for this config).

        The jaxpr trace costs seconds for an SDXL-class UNet, and this
        is called from the single denoise-loop thread — tracing inline
        would stall EVERY co-resident slot's steps (and burn their
        step-granularity deadline budget) at the first retirement. So
        the first call only CAPTURES the shapes (cheap) and hands the
        trace to a daemon thread; retirements carry no attribution
        until it lands (None), then every later one uses the cached
        figure. 0.0 = tried and failed, permanently skipped."""
        if self._flops_img is not None:
            return self._flops_img or None
        if self._cond is None or self._lat is None \
                or self._flops_trace_started:
            return None
        self._flops_trace_started = True

        def one(a):
            return jax.ShapeDtypeStruct((1,) + a.shape[1:], a.dtype)

        lat1 = one(self._lat)
        cond1 = {k: one(v) for k, v in self._cond.items()}

        def run_trace():
            try:
                from cassmantle_tpu.obs import costmodel

                flops, _ = costmodel.trace_cost(
                    lambda p, x, t, c: self._denoise(
                        p, x, t, c["ctx"], c["uctx"],
                        c.get("add"), c.get("uadd")),
                    self._params["unet"], lat1,
                    jax.ShapeDtypeStruct((1,), jnp.int32), cond1)
                self._flops_img = flops * self.num_steps
            # lint: ignore[swallowed-error] — accounting-only degrade: retirements carry flops_est=0, which is itself visible in every stage.denoise.service span
            except Exception:
                log.exception("staged denoise cost trace failed; "
                              "retirements carry no FLOPs attribution")
                self._flops_img = 0.0

        threading.Thread(target=run_trace, daemon=True,
                         name="cassmantle-stage-costtrace").start()
        return None

    def _retire_finished(self) -> None:
        sup = self._supervisor
        for slot, u in enumerate(self._slots):
            if u is None or self._steps[slot] < self.num_steps:
                continue
            row = self._take(self._lat, jnp.int32(slot))
            self._free_slot(slot)
            self.stats["retirements"] += 1
            now = time.monotonic()
            metrics.observe("stage.denoise.service_s", now - u.t_admit)
            flight_recorder.record(
                "stage.retire", stage="denoise", slot=slot,
                step=self.stats["steps"], occupancy=self._active_n)
            # roofline attribution per retirement: the request's
            # denoise work is num_steps CFG forwards wherever its slot
            # sat. The mxu figure divides by residency (admit→retire),
            # a LOWER bound per unit — co-batched slots overlap, so the
            # per-pipeline gauge approaches truth as occupancy rises
            # (exactly the stage-serving occupancy argument,
            # docs/PERF_NOTES.md)
            unit_flops = self._denoise_flops_per_image()
            if unit_flops:
                from cassmantle_tpu.obs.costmodel import chip_peak_flops
                from cassmantle_tpu.obs.device import note_dispatch

                metrics.inc("request.device_flops", unit_flops,
                            labels={"pipeline": "staged_denoise"})
                service_s = now - u.t_admit
                peak = chip_peak_flops()
                if service_s > 0 and peak is not None:
                    metrics.gauge(
                        "pipeline.mxu_utilization",
                        unit_flops / service_s / peak,
                        labels={"pipeline": "staged_denoise"})
                note_dispatch("staged_denoise")
            if u.ctx is not None and u.ctx.sampled:
                wait_s = u.t_admit - u.t_ready
                tracer.record_span(
                    "stage.denoise.wait", tracer.child_ctx(u.ctx),
                    parent_id=u.ctx.span_id, start_wall=u.wall_ready,
                    duration_s=wait_s, attrs={"slot": slot})
                attrs = {"slot": slot, "steps": self.num_steps}
                if unit_flops:
                    attrs["flops_est"] = unit_flops
                tracer.record_span(
                    "stage.denoise.service", tracer.child_ctx(u.ctx),
                    parent_id=u.ctx.span_id,
                    start_wall=u.wall_ready + wait_s,
                    duration_s=now - u.t_admit,
                    attrs=attrs)
            if sup is not None:
                sup.note_stage_progress("denoise")
            # guarded: stop()/deadline/integrity can _fail_unit a slot
            # the denoise thread is concurrently retiring — the loser
            # of that race must not raise InvalidStateError here
            if not u.done.done():
                u.done.set_result(row)

    # -- wedge watchdog ----------------------------------------------------

    @staticmethod
    def _array_ready(arr) -> bool:
        ready = getattr(arr, "is_ready", None)
        return bool(ready()) if callable(ready) else True

    def _watchdog_check(self) -> None:
        """Per-stage dispatch health without a host sync: probe the
        NON-BLOCKING readiness of a recently dispatched state array. A
        probe still unready ``dispatch_hang_s`` after dispatch means the
        device wedged mid-denoise (the monolithic watchdog's condition,
        observed from outside the dispatch thread): flip the supervisor
        degraded and fail the in-flight slots — their callers must not
        hang on futures the device will never fill."""
        hang = self.cfg.serving.dispatch_hang_s
        if hang is None:
            return
        now = time.monotonic()
        if self._probe is None:
            self._probe = (now, self._lat)
            return
        t0, arr = self._probe
        if self._array_ready(arr):
            self._probe = None
            if self._supervisor is not None:
                self._supervisor.note_stage_progress("denoise")
            return
        if now - t0 <= hang:
            return
        log.error("stage.denoise step unready after %.1fs; failing %d "
                  "in-flight slots", hang, self._active_n)
        metrics.inc("stage.denoise.dispatch_hangs")
        flight_recorder.record("stage.dispatch_hang", stage="denoise",
                               hang_timeout_s=hang,
                               in_flight=self._active_n)
        if self._supervisor is not None:
            self._supervisor.note_dispatch_overrun("stage.denoise")
        exc = DispatchTimeout(
            f"stage.denoise step exceeded {hang}s")
        for slot, u in enumerate(self._slots):
            if u is not None:
                self._fail_unit(u, exc)
                self._free_slot(slot)
        self._probe = None

    @staticmethod
    def _fail_unit(u: _Unit, exc: Exception) -> None:
        if not u.done.done():
            u.done.set_exception(exc)
