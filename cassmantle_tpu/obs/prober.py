"""Synthetic canary prober (ISSUE 18): play the real game, constantly.

White-box health (breakers, watchdogs, device probes) answers "do the
parts report healthy"; the canary answers the only question a player
cares about — "can someone actually PLAY right now". Every worker runs
a background loop that exercises the full serving surface end-to-end
over real HTTP: ``/init`` → one ``/clock`` WebSocket tick →
``/fetch/contents`` (JPEG decode + mask-shape verification) →
``/compute_score`` on a known-answer probe room. One guess is the
exact answer (the deterministic 1.0 path); one is deliberately
non-exact, forcing the batched similarity rung — the int8 embed table
when armed, the device queue otherwise — so the probe covers the same
scoring ladder players ride.

The probe room (``engine/game.py PROBE_ROOM``) is isolated on every
axis: its store keys live under ``probe:<worker_id>:`` (no collision
with any room prefix), its Game emits no engine metrics (NULL_METRICS
— game.guesses, cache ratios, and the latency histograms feeding
admission capacity estimates never see probe traffic), it is absent
from the room directory/placement/heartbeats, and the HTTP layer
admits it only to cluster peers (``?room=__probe__`` answers 404 to
outsiders). Cross-worker probes walk the membership table with the
cluster token, so every worker also validates its peers' serving paths
— a black-box mesh check the white-box supervisor cannot fake.

Every probe runs under a traced root span marked for tail retention
("probe"), so a failed probe's full trace is always retrievable at
``/debugz?trace=<id>`` — and the ``probe.e2e_s`` histogram's bucket
exemplars link straight to it. Verdicts feed ``probe.ok`` /
``probe.failures`` / ``probe.e2e_s``, ``probe.fail`` flight-recorder
events, the ``canary`` block in ``/readyz``, and the two black-box SLO
objectives (obs/slo.py probe_success / probe_latency).

Kill switch: ``CASSMANTLE_NO_PROBER=1`` (checked at startup AND per
tick) leaves zero probe artifacts — no metrics, no store keys, no
background task. ``CASSMANTLE_PROBE_INTERVAL_S`` overrides the cadence
(docs/DEPLOY.md §6).
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from cassmantle_tpu.engine.masking import build_prompt_state
from cassmantle_tpu.engine.rounds import (
    COUNTDOWN_KEY,
    IMAGE_KEY,
    PROMPT_KEY,
    STORY_KEY,
)
from cassmantle_tpu.obs.recorder import flight_recorder
from cassmantle_tpu.obs.trace import format_traceparent, tracer
from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("prober")

# Fixed probe content: build_prompt_state is deterministic (no RNG), so
# every worker derives the SAME masks and answers from this sentence —
# a cross-worker probe knows the remote probe room's answers without
# reading the remote store.
PROBE_SENTENCE = (
    "a violet lighthouse hums beside the glass harbor while copper "
    "gulls drift over the quiet evening tide"
)
PROBE_IMAGE_SIZE = 64
# countdown TTL refreshed whenever it runs low: the probe room's clock
# must always read a live round, but never runs a round timer
PROBE_COUNTDOWN_S = 3600.0
# deliberately-wrong guess for one mask: the exact-match shortcut in
# GuessScorer must NOT fire, so the batched similarity path (table or
# device) is exercised on every probe (the word is not in the sentence)
PROBE_NEAR_GUESS = "harbinger"


class ProbeFailure(AssertionError):
    """One probe leg's verification failed (carries the leg name in
    the message; the verdict records which leg via span attrs)."""


def probe_image() -> np.ndarray:
    """Deterministic synthetic round image: a diagonal gradient the
    fetch leg can verify by exact shape after the decode+blur+encode
    round-trip."""
    g = np.arange(PROBE_IMAGE_SIZE, dtype=np.int32)
    grad = (np.add.outer(g, g) * 2 % 256).astype(np.uint8)
    return np.stack([grad, grad.T, 255 - grad], axis=-1)


def probe_state(game) -> Dict:
    """The probe round's prompt state, derived (and memoized) from the
    probe game's own embed fn — identical on every worker running the
    same model config."""
    state = getattr(game, "_probe_state", None)
    if state is None:
        state = build_prompt_state(
            PROBE_SENTENCE, game.rounds.embed, game.rounds.num_masked)
        game._probe_state = state
    return state


def probe_answers(state: Dict) -> Dict[str, str]:
    tokens = state["tokens"]
    return {str(m): str(tokens[int(m)]) for m in state["masks"]}


async def ensure_probe_round(game) -> Dict:
    """Seed the probe room's store with the known-answer round if it is
    missing (first probe on this worker, or a cross-worker probe
    landing on a cold peer), and keep its countdown alive. Idempotent
    and cheap once seeded (one hget + one ttl)."""
    from cassmantle_tpu.utils.codec import encode_jpeg

    # off the event loop: the first derivation device-embeds the probe
    # sentence (engine/rounds.py::_store_content has the same rule)
    state = await asyncio.to_thread(probe_state, game)
    store = game.store
    if await store.hget(PROMPT_KEY, "current") is None:
        await store.hset(PROMPT_KEY, "seed", PROBE_SENTENCE)
        await store.hset(PROMPT_KEY, "current", json.dumps(state))
        await store.hset(IMAGE_KEY, "current",
                         encode_jpeg(probe_image()))
        await store.hset(IMAGE_KEY, "version", "1")
        await store.hset(STORY_KEY, mapping={
            "title": "canary", "content": PROBE_SENTENCE})
        # pin the probe answers into the int8 embed table when one is
        # armed — the near-guess then rides the table-served rung, the
        # same rung 0 players hit (ops/embed_table.py)
        await game.rounds._notify_answers(state)
    if await store.ttl(COUNTDOWN_KEY) < 60.0:
        await store.setex(COUNTDOWN_KEY, PROBE_COUNTDOWN_S, "active")
    return state


def prober_disabled() -> bool:
    """CASSMANTLE_NO_PROBER truthy = no probes, no artifacts."""
    return os.environ.get("CASSMANTLE_NO_PROBER", "").lower() in (
        "1", "true", "yes", "on")


class CanaryProber:
    """The per-worker probe loop. ``self_addr`` is this worker's own
    HTTP address (loopback in production — the probe must traverse the
    real listener, middlewares included); cross-worker targets come
    from the membership table with the cluster token."""

    def __init__(self, fabric, cfg, self_addr: Optional[str] = None):
        self.fabric = fabric
        self.cfg = cfg
        self.self_addr = self_addr
        self._http = None
        # worker -> last verdict dict (the /readyz canary block)
        self._last: Dict[str, dict] = {}
        self._consecutive_failures = 0

    # -- config ------------------------------------------------------------
    def interval_s(self) -> float:
        raw = os.environ.get("CASSMANTLE_PROBE_INTERVAL_S", "")
        if raw:
            try:
                return max(0.5, float(raw))
            except ValueError:
                log.warning("bad CASSMANTLE_PROBE_INTERVAL_S=%r; using "
                            "config cadence", raw)
        return float(self.cfg.obs.probe_interval_s)

    # -- http --------------------------------------------------------------
    def _session(self):
        import aiohttp

        if self._http is None or self._http.closed:
            self._http = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(
                    total=float(self.cfg.obs.probe_timeout_s)))
        return self._http

    async def close(self) -> None:
        if self._http is not None and not self._http.closed:
            await self._http.close()
        self._http = None

    # -- one probe ---------------------------------------------------------
    async def probe_once(self, worker: Optional[str] = None,
                         addr: Optional[str] = None) -> dict:
        """Play the full game surface against one target worker and
        record the verdict. Returns the verdict dict (also kept for
        the /readyz canary block)."""
        if worker is None:
            worker = self.fabric.worker_id
        if addr is None:
            addr = self.self_addr or self.fabric.membership.addr
        verdict: Dict[str, object] = {
            "target": worker, "ok": False, "leg": None, "error": None,
            "e2e_s": None, "trace": None, "t": time.time(),
        }
        with tracer.span("probe.run", root=True,
                         attrs={"target": worker,
                                "worker": self.fabric.worker_id}) as span:
            # probes are always tail-retained: a failed probe's trace
            # must be retrievable, and a slow-but-passing one is the
            # earliest latency-regression evidence there is
            tracer.mark_retain("probe", span.ctx)
            verdict["trace"] = span.trace_id
            t0 = time.perf_counter()
            try:
                if not addr:
                    raise ProbeFailure("no probe target address")
                await self._play(worker, addr, span)
                verdict["ok"] = True
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                verdict["leg"] = span.attrs.get("leg", "connect")
                verdict["error"] = f"{type(exc).__name__}: {exc}"
                span.attrs["error"] = verdict["error"]
            dt = time.perf_counter() - t0
            verdict["e2e_s"] = round(dt, 6)
            # observed INSIDE the span: the ambient trace context tags
            # this observation's histogram bucket with an exemplar
            # pointing at exactly this probe's trace
            metrics.observe("probe.e2e_s", dt)
            if verdict["ok"]:
                metrics.inc("probe.ok")
                self._consecutive_failures = 0
            else:
                metrics.inc("probe.failures")
                self._consecutive_failures += 1
                flight_recorder.record(
                    "probe.fail", target=worker,
                    leg=verdict["leg"], error=verdict["error"],
                    trace=span.trace_id)
                log.warning("canary probe failed (target=%s leg=%s): %s",
                            worker, verdict["leg"], verdict["error"])
        self._last[worker] = verdict
        return verdict

    async def _play(self, worker: str, addr: str, span) -> None:
        """The four legs, in player order. Raises ProbeFailure (or any
        transport error) on the first leg that misbehaves; span.attrs
        ['leg'] names the leg in flight."""
        http = self._session()
        base = addr.rstrip("/")
        from cassmantle_tpu.engine.game import PROBE_ROOM
        from cassmantle_tpu.utils.codec import decode_jpeg

        state = await asyncio.to_thread(
            probe_state, self.fabric.probe_game())
        answers = probe_answers(state)
        session_id = f"canary-{self.fabric.worker_id}"
        params = {"room": PROBE_ROOM, "session": session_id}
        headers = {"traceparent": format_traceparent(span.ctx)}
        token = self.fabric.cluster_token()
        if token:
            headers["X-Cluster-Auth"] = token

        span.attrs["leg"] = "init"
        async with http.get(base + "/init", params=params,
                            headers=headers) as res:
            if res.status != 200:
                raise ProbeFailure(f"init answered {res.status}")
            data = await res.json()
            if data.get("session_id") != session_id:
                raise ProbeFailure("init echoed a foreign session id")

        span.attrs["leg"] = "clock"
        timeout = float(self.cfg.obs.probe_timeout_s)
        async with http.ws_connect(base + "/clock", params=params,
                                   headers=headers) as ws:
            tick = await ws.receive_json(timeout=timeout)
            missing = [k for k in ("time", "reset", "conns")
                       if k not in tick]
            if missing:
                raise ProbeFailure(f"clock tick missing {missing}")

        span.attrs["leg"] = "fetch"
        async with http.get(base + "/fetch/contents", params=params,
                            headers=headers) as res:
            if res.status != 200:
                raise ProbeFailure(f"fetch/contents answered {res.status}")
            data = await res.json()
        image = decode_jpeg(base64.b64decode(data["image"]))
        if image.shape != (PROBE_IMAGE_SIZE, PROBE_IMAGE_SIZE, 3):
            raise ProbeFailure(
                f"image decoded to shape {image.shape}, expected "
                f"({PROBE_IMAGE_SIZE}, {PROBE_IMAGE_SIZE}, 3)")
        prompt = data.get("prompt", {})
        if list(prompt.get("masks", [])) != list(state["masks"]):
            raise ProbeFailure(
                f"masks {prompt.get('masks')} != seeded "
                f"{state['masks']}")
        for m in state["masks"]:
            if prompt["tokens"][int(m)] != "*":
                raise ProbeFailure(f"mask {m} not redacted in prompt")
        if not data.get("story"):
            raise ProbeFailure("story block missing")

        span.attrs["leg"] = "score"
        inputs = dict(answers)
        near_mask: Optional[str] = None
        if len(inputs) > 1:
            # last mask gets the non-exact guess: the exact-match
            # shortcut must not fire, so this rides the batched
            # similarity path (table rung or device queue)
            near_mask = str(state["masks"][-1])
            inputs[near_mask] = PROBE_NEAR_GUESS
        async with http.post(base + "/compute_score", params=params,
                             json={"inputs": inputs},
                             headers=headers) as res:
            if res.status != 200:
                raise ProbeFailure(f"compute_score answered {res.status}")
            scores = await res.json()
        for m in answers:
            raw = scores.get(m)
            if raw is None:
                raise ProbeFailure(f"mask {m} missing from scores")
            val = float(raw)
            if m == near_mask:
                # similarity-path score: GuessScorer clamps into
                # [min_score, 0.999]. 1.0 would mean the exact-match
                # shortcut fired (device path unexercised); a score AT
                # the floor is the serving stack's degraded fallback
                # (breaker open, dispatch deadline, invalid device
                # output — all floor to min_score) — exactly the
                # player-visible degradation the canary exists to catch
                floor = float(self.cfg.game.min_score)
                if val <= floor:
                    raise ProbeFailure(
                        f"near-guess scored the {floor} floor — "
                        f"degraded (breaker/deadline/invalid-output) "
                        f"similarity serving")
                if val > 0.999:
                    raise ProbeFailure(
                        f"near-guess score {val} > 0.999: the "
                        f"similarity path was not exercised")
            elif val != 1.0:
                raise ProbeFailure(
                    f"exact answer for mask {m} scored {val}, not 1.0")

    # -- the loop ----------------------------------------------------------
    def _targets(self) -> List[Tuple[str, Optional[str]]]:
        targets: List[Tuple[str, Optional[str]]] = [
            (self.fabric.worker_id,
             self.self_addr or self.fabric.membership.addr or None)]
        for worker, info in sorted(
                self.fabric.membership.live_workers().items()):
            if worker == self.fabric.worker_id:
                continue
            peer_addr = info.get("addr")
            if peer_addr:
                targets.append((worker, peer_addr))
        return targets

    async def probe_all(self) -> None:
        """One probe pass: self first, then every live peer with an
        advertised address. A worker with no self address (no loopback
        known, nothing advertised) simply has no self-probe — peers
        still probe it from outside."""
        for worker, addr in self._targets():
            if not addr:
                continue
            await self.probe_once(worker, addr)

    async def run(self) -> None:
        """Background loop for create_app's on_startup. The kill switch
        is re-read every tick, so CASSMANTLE_NO_PROBER flipped on a
        live worker quiesces probing within one interval (and a boot
        with it set never creates this task at all)."""
        try:
            while True:
                await asyncio.sleep(self.interval_s())
                if prober_disabled():
                    continue
                try:
                    await self.probe_all()
                except asyncio.CancelledError:
                    raise
                except Exception:
                    # the prober observes the system; it must never
                    # take it down
                    log.exception("canary probe pass failed; continuing")
        finally:
            await self.close()

    # -- status ------------------------------------------------------------
    def status_block(self) -> Dict[str, object]:
        """The /readyz ``canary`` block: last verdict per target plus
        the consecutive-failure streak. Advisory (like the SLO block):
        a failing canary explains a drain, it does not cause one."""
        last = {w: dict(v) for w, v in self._last.items()}
        ok: Optional[bool] = None
        if last:
            ok = all(bool(v.get("ok")) for v in last.values())
        return {
            "enabled": not prober_disabled(),
            "interval_s": self.interval_s(),
            "ok": ok,
            "consecutive_failures": self._consecutive_failures,
            "targets": last,
        }
