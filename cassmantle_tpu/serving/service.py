"""Inference service: wires pipelines + batching queues into the game's
injection points (embed / similarity / blur / ContentBackend).

This is the production counterpart of the test wiring in
tests/test_pipeline.py: one object owning the TPU state that the server
layer (server/app.py) plugs into the engine.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from cassmantle_tpu.config import FrameworkConfig
from cassmantle_tpu.obs.trace import current_ctx, tracer
from cassmantle_tpu.ops.blur import device_blur
from cassmantle_tpu.ops.scorer import EmbeddingScorer
from cassmantle_tpu.serving import integrity
from cassmantle_tpu.serving.device_recovery import DeviceRecoveryManager
from cassmantle_tpu.serving.integrity import OutputInvalid
from cassmantle_tpu.serving.overload import (
    PRIORITY_BACKGROUND,
    make_admission,
    note_table_served,
)
from cassmantle_tpu.serving.pipeline import TPUContentBackend
from cassmantle_tpu.serving.queue import (
    BatchingQueue,
    DeadlineExceeded,
    DispatchTimeout,
    OverloadShed,
    QueueFull,
)
from cassmantle_tpu.serving.supervisor import ServingSupervisor
from cassmantle_tpu.utils.logging import get_logger, metrics
from cassmantle_tpu.utils.profiling import install_gc_region

log = get_logger("service")


def default_serving_mesh(cfg: FrameworkConfig):
    """Batch-DP mesh over all local devices when more than one is
    visible (the v5e-8 serving layout); None on a single chip."""
    import jax

    if jax.local_device_count() <= 1:
        return None
    from cassmantle_tpu.config import MeshConfig
    from cassmantle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshConfig(dp=-1))
    log.info("serving mesh: dp=%d", mesh.shape["dp"])
    return mesh


class InferenceService:
    def __init__(self, cfg: FrameworkConfig,
                 weights_dir: Optional[str] = None,
                 mesh=None,
                 backend: Optional[TPUContentBackend] = None,
                 supervisor: Optional[ServingSupervisor] = None) -> None:
        if mesh is None:
            mesh = default_serving_mesh(cfg)
        self.cfg = cfg
        # every collection of the cyclic collector as the host span
        # host.gc: a stopped dispatch thread is idle chip
        install_gc_region()
        # shared with the Game in production (build_game) so breaker
        # trips here and in the engine fuse into one /readyz signal
        self.supervisor = supervisor or ServingSupervisor()
        self.scorer = EmbeddingScorer(
            cfg.models.minilm,
            weights_dir=weights_dir,
            batch_buckets=cfg.serving.score_batch_sizes,
        )
        self.backend = backend or TPUContentBackend(
            cfg, weights_dir=weights_dir, mesh=mesh)
        # stage-disaggregated serving (serving/stages.py): the image
        # pipeline's per-stage queues/watchdogs report into the SAME
        # supervisor as the score/prompt queues, so stage dispatch
        # health fuses into the one /readyz signal
        t2i = getattr(self.backend, "t2i", None)
        if t2i is not None and hasattr(t2i, "supervisor"):
            t2i.supervisor = self.supervisor
        # device-loss recovery (serving/device_recovery.py, ISSUE 17):
        # dispatch exceptions from either queue (and the image path in
        # generate_content) are classified where they surface; a
        # classified loss flips the supervisor to ``device_lost`` and
        # kicks off the single-flight rebuild below
        self._warm_count = 0
        self.recovery = DeviceRecoveryManager(
            supervisor=self.supervisor,
            rebuild=self.rebuild_device_state,
            warm=self._warm_after_recovery,
        )
        # published on the supervisor so the server layer (which wires
        # DeviceHealth after this constructor) can connect probe raises
        # to the same classifier
        self.supervisor.recovery = self.recovery
        dh = getattr(self.supervisor, "device_health", None)
        if dh is not None and hasattr(dh, "on_probe_error"):
            # a dispatch-quiet worker still detects runtime loss: probe
            # raises ride the same classifier as dispatch exceptions
            dh.on_probe_error = self.recovery.note_probe_exception
        self.score_queue: BatchingQueue = BatchingQueue(
            handler=self._score_batch,
            max_batch=max(cfg.serving.score_batch_sizes),
            max_delay_ms=cfg.serving.max_queue_delay_ms,
            max_pending=cfg.serving.max_pending,
            name="score",
            default_deadline_s=cfg.serving.submit_deadline_s,
            hang_timeout_s=cfg.serving.dispatch_hang_s,
            supervisor=self.supervisor,
            degraded_max_pending=cfg.serving.degraded_max_pending,
            admission=make_admission("score", cfg),
            background_every=cfg.serving.background_every_batches,
            on_dispatch_error=self.recovery.note_dispatch_exception,
        )
        # Concurrent round generations (double-buffering overlapping a
        # live promotion, or several Game instances sharing one service)
        # coalesce their LM decodes into one batched greedy_decode
        # dispatch (PromptGenerator.decode_ids_batch) instead of
        # serializing single-prompt scans on the dispatch thread. The
        # queue binds its batch late: while a round of this service is
        # between its text and its image's RETURN (_generate_content
        # keeps the count) the device has that image ahead of any LM
        # program, and the prompts that arrive meanwhile ride one
        # dispatch. The count, not the image lock's state: the lock is
        # taken on an executor thread some ms after the text returns,
        # when the collector has already looked, and the round whose
        # image returns asks for its next text in the same turn of the
        # loop, so a release at the device's last instruction leaves
        # without it (tests/test_served_loop_model.py; the price is the
        # image's host tail, 4.6 ms of idle chip a dispatch). The hold
        # has no bound but its items' deadlines.
        from cassmantle_tpu.serving.pipeline import PromptGenerator

        self._rendering = 0

        self.prompt_queue: BatchingQueue = BatchingQueue(
            handler=self._prompt_batch,
            max_batch=max(PromptGenerator.BATCH_BUCKETS),
            max_delay_ms=cfg.serving.max_queue_delay_ms,
            max_pending=cfg.serving.max_pending,
            name="prompt",
            default_deadline_s=cfg.serving.submit_deadline_s,
            hang_timeout_s=cfg.serving.dispatch_hang_s,
            supervisor=self.supervisor,
            degraded_max_pending=cfg.serving.degraded_max_pending,
            admission=make_admission("prompt", cfg),
            background_every=cfg.serving.background_every_batches,
            on_dispatch_error=self.recovery.note_dispatch_exception,
            hold_while=lambda: self._rendering > 0,
        )

    # handlers run on the dispatch thread
    def _score_batch(self, pairs: Sequence[Tuple[str, str]]):
        """Batch handler with per-pair integrity (ISSUE 17): the scorer
        marks rows whose device encode came back non-finite as NaN
        similarities (never cached); those pairs fail individually with
        a retriable OutputInvalid via the queue's per-member exception
        distribution, while valid neighbors in the same batch still
        resolve. Counting happened at the scorer (pipeline=scorer)."""
        sims = self.scorer.similarity(list(pairs))
        if integrity.integrity_disabled():
            return sims
        bad = ~np.isfinite(np.asarray(sims))
        if not bad.any():
            return sims
        return [OutputInvalid("scorer", "similarity", [i]) if bad[i]
                else sims[i] for i in range(len(sims))]

    def _prompt_batch(self, seeds: Sequence[str]):
        # rows the integrity sentinel rejected come back as
        # OutputInvalid instances; the queue's per-member distribution
        # fails those futures while healthy rows still serve
        return self.backend.prompt_gen.generate_batch(list(seeds))

    # -- engine injection points -----------------------------------------
    def embed(self, words) -> np.ndarray:
        return self.scorer.embed(list(words))

    def pin_answers(self, words) -> int:
        """RoundManager promotion hook (engine/rounds.py): embed the
        round's answers once and pin them into the scorer's int8 table,
        so every (in-vocabulary guess, answer) pair that follows is
        rung-0-servable with zero device dispatches."""
        return self.scorer.pin_answers(list(words))

    async def similarity(self, pairs) -> np.ndarray:
        """SimilarityFn, ladder rung 0: pairs fully covered by the
        armed int8 embed table complete right here as host dot products
        — no queue submit, no admission check, no breaker consult (the
        limiter's capacity estimates should only ever see true device
        work; ``overload.table_served`` counts what bypassed it). Pairs
        with any OOV side keep the entire queued ladder below."""
        pairs = list(pairs)
        table = self.scorer.table_scores(pairs)
        if table is not None:
            scores, served = table
            if served.all():
                note_table_served(len(pairs))
                return scores
            if served.any():
                rest_idx = [i for i, s in enumerate(served) if not s]
                note_table_served(len(pairs) - len(rest_idx))
                rest = await self._queued_similarity(
                    [pairs[i] for i in rest_idx])
                for j, i in enumerate(rest_idx):
                    scores[i] = rest[j]
                return scores
        return await self._queued_similarity(pairs)

    async def _queued_similarity(self, pairs) -> np.ndarray:
        """The queued ladder: each pair rides the continuous-batching
        queue, so concurrent guesses from many players coalesce into one
        device batch. The score breaker wraps the dispatch: while open,
        guesses degrade to floor scores instantly (no queue, no device
        dial) and the HTTP layer sheds with 503 + Retry-After;
        deadline/watchdog failures count toward tripping it."""
        import asyncio

        pairs = list(pairs)
        breaker = self.supervisor.score_breaker
        if not breaker.allow():
            log.warning("score breaker open; floor scores for %d pairs",
                        len(pairs))
            return np.zeros((len(pairs),), dtype=np.float32)
        try:
            results = await asyncio.gather(
                *(self.score_queue.submit(p) for p in pairs)
            )
        except OverloadShed:
            # adaptive admission shed this request with a computed
            # Retry-After: propagate so the HTTP layer answers 503 +
            # Retry-After in <50 ms (ISSUE 13 acceptance) instead of
            # silently serving floor scores. Not a breaker failure —
            # shedding IS the healthy overload response.
            raise
        except QueueFull:
            # hard backpressure (static bound / degraded bound):
            # degrade to the min score rather than failing the request
            # (skip-don't-crash). Backpressure is load, not a device
            # failure — it doesn't count against the breaker.
            log.warning("score queue full; returning zeros for %d pairs",
                        len(pairs))
            return np.zeros((len(pairs),), dtype=np.float32)
        except (DeadlineExceeded, DispatchTimeout) as exc:
            breaker.record_failure()
            log.warning("score dispatch failed (%s); floor scores for %d "
                        "pairs", type(exc).__name__, len(pairs))
            return np.zeros((len(pairs),), dtype=np.float32)
        except OutputInvalid as exc:
            # the device produced garbage for at least one pair
            # (integrity verdict, serving/integrity.py): degrade the
            # request to floor scores — an invalid score must never
            # reach a player as a real one — and count toward the
            # breaker (repeated invalid output = sick scorer)
            breaker.record_failure()
            log.warning("invalid scorer output (%s); floor scores for "
                        "%d pairs", exc, len(pairs))
            return np.zeros((len(pairs),), dtype=np.float32)
        except Exception as exc:
            breaker.record_failure()
            # a dead runtime surfaces here too (gather re-raises the
            # dispatch exception): classify before propagating
            self.recovery.note_dispatch_exception(exc)
            raise
        breaker.record_success()
        return np.asarray(results, dtype=np.float32)

    @staticmethod
    def blur(image: np.ndarray, radius: float) -> np.ndarray:
        return device_blur(image, radius)

    async def generate_content(self, seed: str, is_seed: bool):
        """ContentBackend-compatible generate whose text decode rides
        the prompt queue: N rounds generating concurrently become one
        (N<=8)-row decode batch. Image generation still runs per round
        in the executor. Queue overload degrades to the backend's own
        single-prompt decode (skip-don't-crash).

        The round is one trace, whoever calls: ``round.content`` is a
        child of the ambient span (the engine's ``round.generate``) or
        a root of its own, and the queue's, the lock's and the
        pipelines' spans land under it (docs/OBSERVABILITY.md)."""
        with tracer.span("round.content", root=current_ctx() is None), \
                metrics.timer("round.content_s"):
            return await self._generate_content(seed, is_seed)

    async def _generate_content(self, seed: str, is_seed: bool):
        text = None
        if hasattr(self.backend, "prompt_gen"):
            try:
                # round generation is BACKGROUND-tier work: interactive
                # scoring preempts it in dispatch order, and it is the
                # first shed under pressure (its fallback below keeps
                # rounds rotating — the starvation bound guarantees the
                # queue path itself also keeps progressing)
                text = await self.prompt_queue.submit(
                    seed, priority=PRIORITY_BACKGROUND)
            except (QueueFull, DeadlineExceeded, DispatchTimeout,
                    OutputInvalid) as exc:
                # any queue-path failure (backpressure, missed deadline,
                # wedged dispatch, invalid decode output) degrades to
                # the in-backend decode — the fallback exists precisely
                # for a sick queue path, and OutputInvalid is retriable
                # by design (a fresh dispatch usually succeeds)
                log.warning(
                    "prompt queue failed (%s); decoding %r in-backend",
                    type(exc).__name__, seed[:40])
        # from here to the image's return the device has this round's
        # image ahead of the prompt queue, which holds its batch for it
        self._rendering += 1
        try:
            if text is not None:
                return await self.backend.generate(seed, is_seed,
                                                   text=text)
            # injected custom backends may not take a ``text`` kwarg
            return await self.backend.generate(seed, is_seed)
        except Exception as exc:
            # the image pipeline dispatches outside the queues, so its
            # exceptions classify here; rounds.py owns the retry ladder
            self.recovery.note_dispatch_exception(exc)
            raise
        finally:
            self._rendering -= 1
            self.prompt_queue.recheck_hold()

    @property
    def content_backend(self):
        """The ContentBackend the Game should own: same pipelines as
        ``self.backend``, but generate() coalesces concurrent LM decodes
        through the prompt queue. This is what server/app.py wires in —
        handing ``service.backend`` to the Game instead would silently
        bypass the batching."""
        return _QueuedContentBackend(self)

    # -- device-loss rebuild (serving/device_recovery.py) ------------------
    def rebuild_device_state(self) -> None:
        """ONE rebuild attempt, run on the recovery manager's thread:
        re-upload every pipeline's checkpoints through the
        fingerprint-verified load path (utils/checkpoint.py) and drop
        state that referenced the dead runtime (the staged slot server
        restarts lazily on the next generate). Raises on failure — the
        manager owns retries, backoff, and the retry budget."""
        for name in ("t2i", "sdxl", "prompt_gen"):
            pipe = getattr(self.backend, name, None)
            if pipe is not None and hasattr(pipe, "reload_params"):
                pipe.reload_params()
        if hasattr(self.scorer, "reload_params"):
            self.scorer.reload_params()
        dh = getattr(self.supervisor, "device_health", None)
        if dh is not None and hasattr(dh, "invalidate"):
            # the rebuilt runtime must be re-probed, not vouched for by
            # the dead one's cached verdict
            dh.invalidate()

    def _warm_after_recovery(self) -> None:
        """Post-rebuild warm: drive one real dispatch through the
        scorer inside a ``no_new_compiles`` window. Params re-enter the
        jits as ARGUMENTS (serving/pipeline.py __init__ note), so a
        rebuild must not recompile anything — if it does, the bucket
        key regressed and recovery fails loudly here instead of
        recompiling under live traffic. A fresh word each time keeps
        the scorer's host LRU from short-circuiting the device dial."""
        from cassmantle_tpu.utils import jit_sentinel

        self._warm_count += 1
        with jit_sentinel.no_new_compiles():
            self.scorer.embed([f"recovery warm {self._warm_count}"])

    async def stop(self) -> None:
        await self.score_queue.stop()
        await self.prompt_queue.stop()


class _QueuedContentBackend:
    """Thin ContentBackend adapter binding generate() to
    InferenceService.generate_content (prompt-queue-batched decode)."""

    def __init__(self, service: InferenceService) -> None:
        self._service = service
        # expose the underlying pipelines (tests and tools reach
        # backend.t2i / backend.prompt_gen through the Game)
        self.inner = service.backend

    def __getattr__(self, name):
        return getattr(self.inner, name)

    async def generate(self, seed: str, is_seed: bool):
        return await self._service.generate_content(seed, is_seed)
