"""HTTP/WS API surface (aiohttp) — the reference's FastAPI layer rebuilt.

Route-for-route parity with the reference (SURVEY.md §1 L4, §3.3-3.5):

- ``GET  /``               game page (static/index.html)
- ``GET  /init``           new session id in a cookie (main.py:47-53)
- ``GET  /client/status``  {won, needInitialization} (main.py:81-93)
- ``GET  /fetch/contents`` {image: b64 jpeg (per-session blur), prompt
                            json, story} (main.py:95-111)
- ``POST /compute_score``  {inputs: {mask_idx: guess}} -> scores
                            (main.py:113-120)
- ``WS   /clock``          1 Hz {time, reset, conns} push (main.py:55-79)
- ``GET  /metrics``        JSON snapshot by default; Prometheus text
                           exposition under ``Accept: text/plain``;
                           ``?scope=cluster`` federates every live
                           member's registry into one view and
                           ``?format=state`` is the peer wire format
                           (new; SURVEY.md §5.5, ISSUES 3+9)
- ``GET  /debugz``         flight-recorder event ring + trace lookup
                           (``?trace=<X-Trace-Id>``; ``&scope=cluster``
                           merges the trace across workers) — the
                           serving black box (new; ISSUES 3+9)
- ``GET  /sloz``           SLO burn-rate verdicts per objective
                           (obs/slo.py; advisory in /readyz) (new;
                           ISSUE 9)
- ``GET  /healthz``        liveness: process + store + device (new)
- ``GET  /readyz``         readiness: supervisor verdict — breakers,
                           dispatch watchdog, device health fused; 503 +
                           Retry-After while degraded (new; ISSUE 2)
- ``POST /debug/trace``    on-demand jax.profiler capture (new; §5.1;
                            loopback or cluster-token, single-flight)
- static mounts ``/static`` and ``/data`` (main.py:25-27)

Rate limits mirror the reference: 3/s default, 2/s API routes, per IP.
"""

from __future__ import annotations

import asyncio
import functools
import math
import os
import re
import tempfile
import uuid
from typing import Optional

from aiohttp import WSMsgType, web

from cassmantle_tpu import chaos
from cassmantle_tpu.chaos import afault_point
from cassmantle_tpu.config import FrameworkConfig, ObsConfig
from cassmantle_tpu.engine.game import PROBE_ROOM, Game
from cassmantle_tpu.fabric.rooms import RoomFabric
from cassmantle_tpu.obs import configure_observability, flight_recorder, tracer
from cassmantle_tpu.obs.device import device_metrics
from cassmantle_tpu.obs.process import ProcessMetrics
from cassmantle_tpu.obs.slo import SloEngine, default_objectives
from cassmantle_tpu.obs.trace import (
    current_ctx,
    current_marks,
    format_traceparent,
    parse_traceparent,
)
from cassmantle_tpu.serving import overload
from cassmantle_tpu.serving.queue import OverloadShed
from cassmantle_tpu.utils import leak_sentinel
from cassmantle_tpu.utils.logging import (
    NULL_METRICS,
    get_logger,
    merge_states,
    metrics,
)

log = get_logger("app")

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STATIC_DIR = os.path.join(_ROOT, "static")
DATA_DIR = os.path.join(_ROOT, "data")
MEDIA_DIR = os.path.join(_ROOT, "media")

_FABRIC = web.AppKey("fabric", RoomFabric)
_TRACE_STATE = web.AppKey("trace_state", dict)
_OBS_CFG = web.AppKey("obs_cfg", ObsConfig)
_SLO = web.AppKey("slo_engine", SloEngine)
_PROCESS = web.AppKey("process_metrics", ProcessMetrics)
# mutable holders (aiohttp freezes app keys at startup): the lazy peer
# ClientSession for cluster fan-outs, and the background obs tasks
_PEER_HTTP = web.AppKey("peer_http", dict)
_OBS_TASKS = web.AppKey("obs_tasks", list)
# mutable holder for the canary prober (None when CASSMANTLE_NO_PROBER
# disabled it at boot — /readyz then reports {"enabled": False})
_PROBER = web.AppKey("prober", dict)


def _env_flag_set(name: str) -> bool:
    """One truthy-parse for the obs kill switches (1/true/yes/on)."""
    return os.environ.get(name, "").lower() in ("1", "true", "yes",
                                                "on")


def _cluster_obs_enabled() -> bool:
    """CASSMANTLE_NO_CLUSTER_OBS=1 turns off the cross-worker surface:
    inbound trace contexts are ignored and cluster fan-outs answer
    worker-local — the kill switch for a fleet where the peer trust
    set (membership-advertised hosts) cannot be relied on."""
    return not _env_flag_set("CASSMANTLE_NO_CLUSTER_OBS")


def _client_ip(request: web.Request) -> str:
    peer = request.transport.get_extra_info("peername") if request.transport else None
    return peer[0] if peer else "?"


def _session_id(request: web.Request) -> Optional[str]:
    # the ?session= fallback keeps identity across a cross-worker 307:
    # cookies are host-scoped, a query param rides the Location header
    return request.cookies.get("session_id") or \
        request.query.get("session")


def _explicit_room(request: web.Request) -> Optional[str]:
    return request.query.get("room") or request.headers.get("X-Room") \
        or request.cookies.get("room")


def _room_of(request: web.Request) -> str:
    """The room this request belongs to: an explicit ?room= / X-Room /
    cookie wins; otherwise the session (or client IP) consistent-hashes
    onto the room list — the same room on every request, from any
    worker, with no stored mapping (fabric/directory.py)."""
    explicit = _explicit_room(request)
    if explicit:
        return explicit
    fabric = request.app[_FABRIC]
    principal = _session_id(request) or _client_ip(request)
    return fabric.directory.room_for_session(principal)


def _check_room_ownership(request: web.Request, fabric: RoomFabric,
                          room: str) -> None:
    """The ONE ownership gate for every room-scoped route: a room owned
    by another worker answers 307 to the owner's advertised address;
    with no advertised owner address the room serves locally — the
    per-room store locks keep that merely suboptimal, never unsafe.

    The Location pins the resolved room AND the session as query
    params: cookies are host-scoped and do not survive the hop, so a
    cookie-only client would otherwise re-resolve a DIFFERENT room on
    the target worker (redirect ping-pong between owners)."""
    if request.headers.get("X-Score-Hedge") == "1" and \
            _is_cluster_peer(request, fabric):
        # an authenticated scorer hedge from a sick peer (ISSUE 12):
        # the room's owner IS the worker that hedged here, so the
        # ownership redirect would bounce the request straight back.
        # Serve it locally — the shared store keeps the session/score
        # writes consistent, the same contract ownerless foreign
        # serves already rely on.
        metrics.inc("score.hedge_served")
        return
    if fabric.is_local(room):
        return
    addr = fabric.owner_addr(room)
    if not addr:
        metrics.inc("fabric.foreign_serves")
        return
    metrics.inc("fabric.redirects")
    url = request.rel_url.update_query(room=room)
    session = _session_id(request)
    if session:
        url = url.update_query(session=session)
    # the Location also pins the ACTIVE trace context (ISSUE 9): headers
    # don't survive a redirect, a query param does — the owner worker
    # continues this trace instead of starting a fresh one, so the hop
    # and the owner's device stages read as ONE trace. The redirect is
    # carried BACK by the (untrusted) client, whose IP proves nothing,
    # so the param travels with an HMAC signature under the store-
    # distributed cluster secret: the owner honors the signature, not
    # the bearer.
    ctx = current_ctx()
    if ctx is not None:
        tp = format_traceparent(ctx)
        url = url.update_query(traceparent=tp)
        sig = fabric.sign_trace(tp)
        if sig:
            url = url.update_query(tracesig=sig)
    raise web.HTTPTemporaryRedirect(location=addr.rstrip("/") + str(url))


async def _resolve_probe_game(request: web.Request,
                              fabric: RoomFabric):
    """(PROBE_ROOM, probe game) for an authenticated canary request
    (ISSUE 18). The probe room exists on EVERY worker (no directory
    entry, no ownership gate — a probe targets a specific worker and
    must be answered by it, never redirected), is invisible to
    outsiders (404, exactly like any unknown room), and lazily seeds
    its known-answer round so a cross-worker probe landing on a cold
    peer still plays a full game. The request's trace is marked: probe
    traffic bypasses admission control (serving/queue.py) and is
    always tail-retained."""
    from cassmantle_tpu.obs.prober import ensure_probe_round

    if not _is_cluster_peer(request, fabric):
        # indistinguishable from a nonexistent room: the probe surface
        # must not advertise itself to players
        raise web.HTTPNotFound(text=f"unknown room {PROBE_ROOM!r}")
    game = fabric.probe_game()
    await ensure_probe_round(game)
    ctx = current_ctx()
    if ctx is not None:
        ctx.marks["probe"] = True
    tracer.mark_retain("probe")
    return PROBE_ROOM, game


async def _resolve_game(request: web.Request):
    """(room, game) for this request, after the ownership gate."""
    fabric = request.app[_FABRIC]
    if _explicit_room(request) == PROBE_ROOM:
        return await _resolve_probe_game(request, fabric)
    room = _room_of(request)
    if not fabric.directory.has_room(room):
        raise web.HTTPNotFound(text=f"unknown room {room!r}")
    _check_room_ownership(request, fabric, room)
    try:
        return room, await fabric.game_for(room)
    except KeyError:
        raise web.HTTPNotFound(text=f"unknown room {room!r}")


def _is_loopback(request: web.Request) -> bool:
    """Fail closed: an unresolvable peer (unix socket behind a proxy)
    is NOT local — same rule as /debug/trace."""
    return request.remote in ("127.0.0.1", "::1")


def _is_cluster_peer(request: web.Request, fabric: RoomFabric) -> bool:
    """The cluster trust gate, three legs: loopback; the connecting
    host exactly matches a live member's advertised address
    (fabric.peer_hosts); or the request bears the cluster-secret
    token (``X-Cluster-Auth``, fabric.cluster_token — what peer
    fan-outs send, and the leg that works when advertised addresses
    are DNS names or egress is NATed). All three anchor in state the
    fleet already trusts (the process, the shared store). Guards the
    /debugz and cluster-federation surfaces; an outsider is counted
    and refused, never honored."""
    if _is_loopback(request):
        return True
    if request.remote in fabric.peer_hosts():
        return True
    token = request.headers.get("X-Cluster-Auth")
    return bool(token) and fabric.verify_cluster_token(token)


@web.middleware
async def cors_middleware(request: web.Request, handler):
    if request.method == "OPTIONS":
        response = web.Response()
    else:
        response = await handler(request)
    response.headers["Access-Control-Allow-Origin"] = "*"
    response.headers["Access-Control-Allow-Credentials"] = "true"
    response.headers["Access-Control-Allow-Methods"] = "GET, POST"
    response.headers["Access-Control-Allow-Headers"] = "*"
    return response


@web.middleware
async def tracing_middleware(request: web.Request, handler):
    """One root span per request; the trace ID returns as ``X-Trace-Id``
    (sampled traces are then queryable at ``/debugz?trace=<id>``).
    Static asset mounts and the probe/scrape surfaces skip tracing —
    a 1/s readiness probe plus a Prometheus scraper would otherwise
    FIFO-flush the bounded trace ring of the player-request traces an
    operator actually triages."""
    # /clock also skips: its WS handshake is prepared before the
    # middleware regains control (the header could never be returned)
    # and app.js's 2 s reconnect loop would mint a ring-flushing trace
    # per flap
    if request.path.startswith(("/static", "/data", "/media")) or \
            request.path in ("/healthz", "/readyz", "/metrics",
                             "/debugz", "/debug/trace", "/clock",
                             "/sloz"):
        return await handler(request)
    fabric = request.app[_FABRIC]
    # inbound trace context (ISSUE 9): a traceparent header (peer
    # fan-out, mesh) or query param (rides a cross-worker 307 Location
    # through the redirecting client) CONTINUES that trace — honored
    # from cluster members/loopback, or via the QUERY param when it
    # carries a valid ``tracesig`` (the redirecting worker's HMAC under
    # the cluster secret — an external player following a 307 keeps one
    # trace). The two channels are judged independently: an
    # OTel-instrumented client auto-injecting its own traceparent
    # HEADER must not shadow the signed query context the redirect
    # pinned. Anything that passes no leg is counted and ignored: a
    # client-minted context must not join foreign traces or pollute
    # the ring.
    remote_ctx = None
    header_tp = request.headers.get("traceparent")
    query_tp = request.query.get("traceparent")
    if (header_tp or query_tp) and _cluster_obs_enabled():
        chosen = None
        sig = request.query.get("tracesig")
        if query_tp and sig and fabric.verify_trace_sig(query_tp, sig):
            # a validly SIGNED query context wins over everything: the
            # signature binds it to this exact hop, where a header is
            # just ambient client instrumentation
            chosen = query_tp
        elif _is_cluster_peer(request, fabric):
            chosen = header_tp or query_tp
        remote_ctx = parse_traceparent(chosen) if chosen else None
        if remote_ctx is not None:
            metrics.inc("obs.trace_joins")
        else:
            metrics.inc("obs.trace_ctx_rejected")
    name = f"http.{request.method.lower()} {request.path}"
    with tracer.span(name, root=remote_ctx is None, parent=remote_ctx,
                     attrs={"worker": fabric.worker_id}) as span:
        try:
            response = await handler(request)
        except web.HTTPException as exc:
            span.attrs["status"] = exc.status
            exc.headers["X-Trace-Id"] = span.trace_id
            # tail-retention verdicts (ISSUE 18): a shed (503) is one
            # of the traces the pending ring exists to keep; routine
            # redirects/4xx (the 307 ownership hop, rate-limit 429s,
            # bad input) are healthy-baseline — retaining every one
            # would flush the durable ring with non-incidents
            if exc.status == 503:
                tracer.mark_retain("shed", span.ctx)
            elif exc.status < 500:
                tracer.mark_retain("baseline", span.ctx)
            raise
        except asyncio.CancelledError:
            raise
        except Exception:
            # a handler bug: answer the 500 OURSELVES so the response
            # still carries the trace id — the one trace an operator
            # most wants to look up from a user report. The log line
            # carries the same id (JSON formatter), replacing aiohttp's
            # anonymous error log.
            span.attrs["status"] = 500
            # the span exits cleanly (we return, not raise): mark it so
            # the tail verdict still reads this trace as an error
            tracer.mark_retain("error", span.ctx)
            log.exception("unhandled error serving %s %s",
                          request.method, request.path)
            return web.Response(
                status=500, text="500 Internal Server Error",
                headers={"X-Trace-Id": span.trace_id})
        span.attrs["status"] = response.status
        if response.status >= 500:
            # handler-returned 5xx (integrity failures surface this
            # way): same retention verdict as a raised one
            tracer.mark_retain("error", span.ctx)
        if not response.prepared:
            # a prepared response (WS handshake already sent) can't
            # take new headers
            response.headers["X-Trace-Id"] = span.trace_id
            tier = overload.current_tier()
            if tier:
                # honesty header (ISSUE 13): while the brownout ladder
                # is degrading quality, every game response says so —
                # clients and operators can tell a browned-out image
                # from a generation bug
                response.headers["X-Quality-Degraded"] = f"tier-{tier}"
                tracer.mark_retain("degraded", span.ctx)
        return response


def make_ratelimit_middleware(cfg: FrameworkConfig):
    from cassmantle_tpu.server.ratelimit import RateLimiter

    limiter = RateLimiter()
    api_routes = {"/init", "/client/status", "/fetch/contents",
                  "/compute_score"}

    @web.middleware
    async def ratelimit(request: web.Request, handler):
        if request.path in api_routes:
            rate = cfg.game.rate_limit_api
        else:
            rate = cfg.game.rate_limit_default
        # (client IP, room): a noisy room drains only its own quota,
        # not the same client's allowance in another room. The IP stays
        # the identity half — session ids are client-minted and would
        # let one abuser grow a fresh full-burst bucket per request —
        # and the room half only honors rooms that EXIST, so ?room=
        # can mint at most num_rooms buckets per client.
        fabric = request.app[_FABRIC]
        explicit = _explicit_room(request)
        if explicit and fabric.directory.has_room(explicit):
            room = explicit
        else:
            who = _session_id(request) or _client_ip(request)
            room = fabric.directory.room_for_session(who)
        principal = (_client_ip(request), room)
        if not limiter.allow(principal, request.path, rate):
            metrics.inc("http.rate_limited")
            # Retry-After computed from THIS bucket's actual refill
            # time (tokens missing / refill rate), not a constant 1 —
            # a client that obeys it is admitted on its next try
            # instead of bouncing off an empty bucket (ISSUE 13)
            retry = limiter.retry_after_s(principal, request.path)
            raise web.HTTPTooManyRequests(
                text="rate limit exceeded",
                headers={"Retry-After": str(max(1, math.ceil(retry)))})
        return await handler(request)

    return ratelimit


async def handle_root(request: web.Request) -> web.StreamResponse:
    return web.FileResponse(os.path.join(STATIC_DIR, "index.html"))


async def handle_init(request: web.Request) -> web.Response:
    # a fresh session has no cookie yet: the room still resolves
    # deterministically from the NEW session id, so the cookie pair
    # (session_id, room) this response sets stays self-consistent
    session_id = _session_id(request) or str(uuid.uuid4())
    fabric = request.app[_FABRIC]
    if _explicit_room(request) == PROBE_ROOM:
        # canary init (ISSUE 18): resets the probe session to the
        # unsolved known-answer round; no cookies (the prober carries
        # ?session=) and no http.init — probe traffic must be
        # invisible to player-facing counters
        room, game = await _resolve_probe_game(request, fabric)
        await game.init_client(session_id)
        return web.json_response(
            {"message": "Session initialized",
             "session_id": session_id, "room": room})
    room = _explicit_room(request) or \
        fabric.directory.room_for_session(session_id)
    if not fabric.directory.has_room(room):
        raise web.HTTPNotFound(text=f"unknown room {room!r}")
    # same ownership discipline as every other room-scoped route: init
    # on a non-owner must redirect, not quietly start a duplicate room
    # engine (and a second round clock) on this worker
    _check_room_ownership(request, fabric, room)
    game = await fabric.game_for(room)
    await game.init_client(session_id)
    response = web.json_response(
        {"message": "Session initialized", "session_id": session_id,
         "room": room}
    )
    response.set_cookie("session_id", session_id)
    response.set_cookie("room", room)
    metrics.inc("http.init")
    return response


async def handle_status(request: web.Request) -> web.Response:
    _, game = await _resolve_game(request)
    return web.json_response(await game.client_status(_session_id(request)))


async def handle_fetch_contents(request: web.Request) -> web.Response:
    room, game = await _resolve_game(request)
    session = _session_id(request) or str(uuid.uuid4())
    await game.ensure_client(session)
    # probe requests bypass the route histogram: the canary plays this
    # path constantly, and its timings must not dilute the player
    # latency series the SLOs and exemplars are built from (ISSUE 18)
    registry = NULL_METRICS if room == PROBE_ROOM else metrics
    with registry.timer("http.fetch_contents_s"):
        image_b64 = await game.fetch_masked_image_b64(session)
        prompt = await game.fetch_prompt_json(session)
        story = await game.fetch_story()
    response = web.json_response({
        "image": image_b64,
        "prompt": prompt,
        "story": story,
    })
    if not _session_id(request):
        response.set_cookie("session_id", session)
    return response


# Bounded hedge fan: a sick cluster must not retry-storm itself — at
# most this many peers are dialed per shed request, each under the
# cluster fan-out timeout, and a hedged request NEVER re-hedges.
SCORE_HEDGE_MAX_ATTEMPTS = 2


async def _hedge_score(request: web.Request, room: str, session: str,
                       payload: dict) -> Optional[dict]:
    """Cross-worker scorer failover (ISSUE 12): when the local score
    path is provably dark, dial a healthy fabric peer's /compute_score
    with the cluster token and the ``X-Score-Hedge`` marker (the peer
    serves the foreign room locally and never re-hedges, so a fully
    sick cluster degrades after one bounded fan instead of storming).
    Returns the peer's scores dict, or None when no peer answered —
    floor scores are the caller's LAST resort, not its first."""
    fabric = request.app[_FABRIC]
    token = fabric.cluster_token()
    if token is None:
        return None
    try:
        table = await fabric.membership.table()
    # lint: ignore[swallowed-error] — hedge is best-effort: None means "no peer answered" and the caller's floor-score path takes over
    except Exception:
        return None
    peers = []
    for worker, row in sorted(table.items()):
        if worker == fabric.worker_id or row["stale"] or \
                not row["info"].get("addr"):
            continue
        if row["info"].get("shed") or row["info"].get("btier"):
            # the peer's own heartbeat already advertises overload
            # (admission shedding / an engaged brownout tier,
            # serving/overload.py peer_advert): hedging into it would
            # trade a local floor score for a remote 503 — skip it
            metrics.inc("score.hedge_skipped_overloaded")
            continue
        peers.append((worker, row["info"].get("addr")))
    http = _peer_session(request)
    attempts = 0
    for worker, addr in peers:
        if attempts >= SCORE_HEDGE_MAX_ATTEMPTS:
            break
        attempts += 1
        metrics.inc("score.hedge_attempts")
        try:
            await afault_point("score.hedge", peer=worker)
            async with http.post(
                addr.rstrip("/") + "/compute_score",
                params={"room": room, "session": session},
                json=payload,
                headers={"X-Cluster-Auth": token,
                         "X-Score-Hedge": "1"},
            ) as res:
                if res.status != 200:
                    # a degraded peer sheds hedges with 503: try the
                    # next one, never loop back
                    metrics.inc("score.hedge_failures")
                    continue
                data = await res.json()
        except Exception:
            metrics.inc("score.hedge_failures")
            continue
        metrics.inc("score.hedge_success")
        flight_recorder.record("score.hedge", peer=worker, room=room)
        return data
    return None


async def handle_compute_score(request: web.Request) -> web.Response:
    room, game = await _resolve_game(request)
    supervisor = game.supervisor
    session = _session_id(request) or str(uuid.uuid4())
    try:
        data = await request.json()
        inputs = data["inputs"]
        assert isinstance(inputs, dict)
    except Exception:
        raise web.HTTPBadRequest(text="body must be {inputs: {idx: guess}}")
    if supervisor.shed_scores() or supervisor.device_unhealthy():
        # the local scorer is provably dark (breaker open / device
        # verdict false). Failover ladder (ISSUE 12): (1) a request
        # that IS someone else's hedge sheds 503 + Retry-After so the
        # origin tries its next peer — hedges must never cascade;
        # (2) hedge to a healthy fabric peer (real scores); (3) floor
        # scores as the LAST resort, honestly marked.
        if request.headers.get("X-Score-Hedge") == "1":
            metrics.inc("http.score_shed")
            raise web.HTTPServiceUnavailable(
                text="scoring degraded; retry shortly",
                headers={"Retry-After":
                         str(int(supervisor.retry_after_s()))})
        hedged = await _hedge_score(request, room, session,
                                    {"inputs": inputs})
        if hedged is not None:
            response = web.json_response(hedged)
            response.headers["X-Score-Hedged"] = "1"
            return response
        metrics.inc("score.hedge_floor")
        flight_recorder.record("score.floor", room=room)
        # fall through: the breaker-aware local path serves floor
        # scores (engine min_score), marked so clients/operators can
        # tell degradation from wrong guesses
    await game.ensure_client(session)
    # same exclusion as fetch: the canary's score timings stay out of
    # the player histogram (its own series is probe.e2e_s)
    registry = NULL_METRICS if room == PROBE_ROOM else metrics
    try:
        with registry.timer("http.compute_score_s"):
            scores = await game.compute_client_scores(session, inputs)
    except OverloadShed as exc:
        # adaptive admission shed this request (serving/overload.py):
        # answer in <50 ms with the COMPUTED Retry-After the limiter's
        # predicted-wait estimator produced — a well-behaved client
        # that obeys it lands when a slot is actually free
        metrics.inc("overload.score_shed")
        raise web.HTTPServiceUnavailable(
            text="overloaded; retry later",
            headers={"Retry-After":
                     str(max(1, math.ceil(exc.retry_after_s))),
                     "X-Overload-Shed": exc.reason})
    response = web.json_response(scores)
    if supervisor.shed_scores() or supervisor.device_unhealthy():
        response.headers["X-Score-Degraded"] = "floor"
    # client-side latency attribution: how long this request's guess
    # batch waited to coalesce vs how long the device batch it rode
    # took (filled by BatchingQueue into the request's trace marks;
    # absent on paths that never touched a queue, e.g. fake backends)
    marks = current_marks()
    if marks and "queue_wait_s" in marks:
        response.headers["X-Queue-Wait"] = f"{marks['queue_wait_s']:.6f}"
        response.headers["X-Service-Time"] = f"{marks['service_s']:.6f}"
    return response


async def handle_clock(request: web.Request) -> web.WebSocketResponse:
    # room-scoped BEFORE the handshake: a redirect (room owned
    # elsewhere) must go out as a plain 307 while headers can still be
    # sent — each room's WS feed carries that room's clock and player
    # count only
    _, game = await _resolve_game(request)
    session = _session_id(request)
    ws = web.WebSocketResponse(heartbeat=30.0)
    await ws.prepare(request)
    log.info("client %s connected", session)
    metrics.inc("ws.connections")

    async def sender() -> None:
        # first tick goes out immediately: a fresh client (or a canary
        # probe on a tight timeout) sees the clock without waiting out
        # the first sleep
        while not ws.closed:
            if session:
                await game.sessions.add_client(session)
            await ws.send_json(await game.clock_payload())
            await asyncio.sleep(1.0)

    send_task = asyncio.ensure_future(sender())
    try:
        # consume incoming frames until the client goes away
        async for msg in ws:
            if msg.type in (WSMsgType.CLOSE, WSMsgType.ERROR):
                break
    except (ConnectionResetError, asyncio.CancelledError):
        pass
    finally:
        send_task.cancel()
        try:
            await send_task
        except (asyncio.CancelledError, ConnectionResetError, Exception):
            pass
        log.info("client %s disconnected", session)
        if session:
            await game.sessions.remove_connection(session)
        metrics.inc("ws.disconnections")
    return ws


def _peer_session(request: web.Request):
    """Lazy per-app aiohttp ClientSession for cluster fan-outs (created
    on first use so it binds the serving loop; closed at app cleanup)."""
    import aiohttp

    holder = request.app[_PEER_HTTP]
    if holder.get("session") is None:
        obs_cfg = request.app[_OBS_CFG]
        holder["session"] = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(
                total=obs_cfg.cluster_fanout_timeout_s))
    return holder["session"]


async def _peer_fanout(request: web.Request, path: str, params: dict):
    """Fan one GET out to every live member CONCURRENTLY, self
    excluded (the whole fan-out costs ~one ``cluster_fanout_timeout_s``
    even with several dark peers, not one per). Returns ``(worker,
    row)`` pairs where row is ``{"status": ...}`` plus the decoded JSON
    body under ``data`` on success. Stale/dead/addressless peers are
    MARKED (status stale/no_addr/error/http_<code>) rather than
    silently dropped — the merged view must say who is missing from
    it. Requests carry the cluster token so the peer's gate admits us
    regardless of how its membership addresses resolve."""
    fabric = request.app[_FABRIC]
    session = _peer_session(request)
    headers = {}
    token = fabric.cluster_token()
    if token:
        headers["X-Cluster-Auth"] = token

    async def fetch(worker: str, addr: str):
        try:
            # peer-fan-out fault point: a worker-scoped partition marks
            # exactly that peer errored in the merged view while the
            # rest of the fleet stays readable (docs/CHAOS.md)
            await afault_point("fabric.peer_http", peer=worker)
            async with session.get(addr.rstrip("/") + path,
                                   params=params,
                                   headers=headers) as res:
                if res.status != 200:
                    return worker, {"status": f"http_{res.status}"}
                data = await res.json()
            return worker, {"status": "ok", "data": data}
        except Exception as exc:
            metrics.inc("obs.federation_peer_errors")
            return worker, {"status": "error",
                            "error": type(exc).__name__}

    results = []
    fetches = []
    table = await fabric.membership.table()
    for worker, row in sorted(table.items()):
        if worker == fabric.worker_id:
            continue
        if row["stale"]:
            results.append((worker, {"status": "stale",
                                     "age_s": row["age_s"]}))
            continue
        addr = row["info"].get("addr")
        if not addr:
            results.append((worker, {"status": "no_addr"}))
            continue
        fetches.append(fetch(worker, addr))
    results.extend(await asyncio.gather(*fetches))
    return results


async def _federated_metrics(request: web.Request):
    """(merged registry, federation block): this worker's full registry
    state plus every reachable peer's, merged per utils/logging.py
    merge_states — counters sum, gauges get a ``worker`` label,
    fixed-bucket histograms merge exactly. ``federation.peer_up``
    gauges in the merged registry mark each peer's reachability so a
    Prometheus scrape of the cluster view carries its own coverage."""
    fabric = request.app[_FABRIC]
    states = [(fabric.worker_id, metrics.dump_state())]
    federation = {fabric.worker_id: {"status": "self"}}
    for worker, row in await _peer_fanout(request, "/metrics",
                                          {"format": "state"}):
        state = row.get("data", {}).get("state") \
            if row["status"] == "ok" else None
        if state is not None:
            states.append((worker, state))
            federation[worker] = {"status": "ok"}
        elif row["status"] == "ok":
            # a 200 without the state payload (mid-rollout peer still
            # serving the legacy snapshot): mark it, don't 500 the
            # whole cluster scrape
            federation[worker] = {"status": "bad_payload"}
        else:
            federation[worker] = row
    cluster_metrics = merge_states(states)
    for worker, row in federation.items():
        cluster_metrics.gauge(
            "federation.peer_up",
            1.0 if row["status"] in ("self", "ok") else 0.0,
            labels={"worker": worker})
    return cluster_metrics, federation


async def handle_metrics(request: web.Request) -> web.Response:
    """Content-negotiated: Prometheus text exposition when the client
    asks for text/plain (a scraper's Accept header), the historical
    JSON snapshot otherwise — existing dashboards keep their shape.

    ``?scope=cluster`` federates: one scrape (or one curl) answers for
    the whole cluster — peers discovered via membership, counters
    summed, gauges worker-labeled, histogram buckets merged exactly,
    unreachable peers marked (``federation`` block / the
    ``federation.peer_up`` gauge). ``?format=state`` serves this
    worker's full-fidelity registry state — the peer-to-peer wire
    format the federation rides (and always worker-local: a peer's
    federation request must never recurse into a second fan-out).

    The plain per-worker scrape stays public (status quo); the two
    CLUSTER forms are gated like /debugz (loopback/members/token) —
    an open ``scope=cluster`` would hand any client an N-fold request
    amplifier against the whole fleet."""
    proc = request.app[_PROCESS]
    proc.sample()            # scrapes always see fresh process gauges
    device_metrics.sample()  # ...and fresh per-device HBM gauges
    fabric = request.app[_FABRIC]
    fmt_state = request.query.get("format") == "state"
    cluster = request.query.get("scope") == "cluster"
    if (fmt_state or cluster) and \
            not _is_cluster_peer(request, fabric):
        raise web.HTTPForbidden(
            text="cluster metrics: loopback or cluster peers only")
    if fmt_state:
        return web.json_response({"worker": fabric.worker_id,
                                  "state": metrics.dump_state()})
    federation = None
    registry = metrics
    if cluster:
        if _cluster_obs_enabled():
            registry, federation = await _federated_metrics(request)
        else:
            federation = {"disabled": True}
    accept = request.headers.get("Accept", "")
    if "application/openmetrics-text" in accept:
        # OpenMetrics exposition (ISSUE 18): same series as the plain
        # text form plus histogram-bucket exemplar annotations
        # ({trace_id=...} → /debugz?trace=) and the # EOF terminator
        return web.Response(
            body=registry.openmetrics().encode(),
            headers={"Content-Type": "application/openmetrics-text; "
                                     "version=1.0.0; charset=utf-8"})
    if "text/plain" in accept or "openmetrics" in accept:
        return web.Response(
            body=registry.prometheus().encode(),
            headers={"Content-Type":
                     "text/plain; version=0.0.4; charset=utf-8"})
    snap = registry.snapshot(
        exemplars=request.query.get("exemplars") == "1")
    if federation is not None:
        snap["federation"] = federation
    return web.json_response(snap)


async def handle_debugz(request: web.Request) -> web.Response:
    """The serving black box: ``?trace=<id>`` returns one trace's spans
    (the id a response's ``X-Trace-Id`` carried); otherwise the
    flight-recorder tail — breaker transitions, watchdog fires,
    deadline expiries, reserve rotations, round promotions — in causal
    order (``?n=`` limits, ``?kind=`` filters by kind or ``prefix.``).

    Operator surface, gated to loopback OR cluster members (the peer
    gate lets `?scope=cluster` fan-outs read each other): trace spans
    carry other players' request timings and the event ring exposes
    internal serving state — not a player-facing page.

    ``?trace=<id>&scope=cluster`` merges the trace across the fleet: a
    request that 307'd between workers leaves its spans split across
    their per-process rings; the cluster mode fans out to every live
    member (membership discovery), dedupes by span id, and returns one
    time-ordered view with a per-peer coverage block — the full story,
    readable from any worker."""
    if not _is_cluster_peer(request, request.app[_FABRIC]):
        raise web.HTTPForbidden(text="loopback or cluster peers only")
    trace_id = request.query.get("trace")
    if trace_id:
        if request.query.get("scope") == "cluster" and \
                _cluster_obs_enabled():
            return await _cluster_trace(request, trace_id)
        spans = tracer.get_trace(trace_id)
        if spans is None:
            raise web.HTTPNotFound(
                text=f"trace {trace_id!r} not resident (bounded ring "
                     f"keeps {tracer.capacity} traces)")
        spans.sort(key=lambda s: s["start_ts"])
        return web.json_response({"trace_id": trace_id, "spans": spans})
    try:
        n = int(request.query.get("n", "200"))
    except ValueError:
        raise web.HTTPBadRequest(text="n must be an integer")
    events = flight_recorder.tail(n, kind=request.query.get("kind"))
    return web.json_response({
        "events": events,
        "recorder": flight_recorder.stats(),
        "tracer": tracer.stats(),
        # newest last; each id is fetchable via ?trace=
        "recent_traces": tracer.trace_ids()[-25:],
    })


async def _cluster_trace(request: web.Request,
                         trace_id: str) -> web.Response:
    """The merged cross-worker trace view behind
    ``/debugz?trace=<id>&scope=cluster``. Peers answer their LOCAL
    trace lookup (never another fan-out); a peer without the trace is a
    ``miss`` (evicted or never sampled there), a dark peer is marked —
    partial coverage is reported, not hidden."""
    fabric = request.app[_FABRIC]
    merged = {s["span_id"]: s
              for s in (tracer.get_trace(trace_id) or [])}
    peers = {fabric.worker_id: {"status": "self", "spans": len(merged)}}
    for worker, row in await _peer_fanout(request, "/debugz",
                                          {"trace": trace_id}):
        if row["status"] == "ok":
            remote = row["data"].get("spans", [])
            for span in remote:
                merged.setdefault(span["span_id"], span)
            peers[worker] = {"status": "ok", "spans": len(remote)}
        elif row["status"] == "http_404":
            peers[worker] = {"status": "miss"}
        else:
            peers[worker] = row
    if not merged:
        raise web.HTTPNotFound(
            text=f"trace {trace_id!r} not resident on any reachable "
                 f"worker")
    spans = sorted(merged.values(), key=lambda s: s["start_ts"])
    return web.json_response({"trace_id": trace_id, "scope": "cluster",
                              "spans": spans, "peers": peers})


async def handle_sloz(request: web.Request) -> web.Response:
    """The SLO page: every objective's state (ok/burning), fast/slow
    burn rates, and targets — evaluated fresh on each hit (internally
    rate-limited) from the same registry `/metrics` serves. Advisory by
    design: `/readyz` embeds the same block without gating on it."""
    engine = request.app[_SLO]
    engine.evaluate()
    return web.json_response(engine.status())


async def _probe_store(fabric: RoomFabric) -> bool:
    try:
        await asyncio.wait_for(fabric.store.exists("healthz"), timeout=2.0)
        return True
    # lint: ignore[swallowed-error] — liveness probe: False IS the signal, surfaced as the /healthz verdict the orchestrator acts on
    except Exception:
        return False


async def handle_healthz(request: web.Request) -> web.Response:
    """LIVENESS: process up + store reachable + device responsive. Both
    probes carry deadlines (a wedged store connection or chip reports
    unhealthy instead of hanging the endpoint) and run concurrently.
    Carries the supervisor block for operators, but only store/device
    drive the status code — a degraded-but-serving worker must not be
    restarted by a liveness probe (that's `/readyz`'s job to report)."""
    fabric = request.app[_FABRIC]
    supervisor = fabric.supervisor
    store_ok, device_ok = await asyncio.gather(
        _probe_store(fabric), supervisor.probe_device())
    ok = store_ok and device_ok is not False
    return web.json_response(
        {
            "ok": ok,
            "store": store_ok,
            "device": device_ok is not False,
            "supervisor": supervisor.status(
                device_ok=device_ok, include_events=_is_loopback(request)),
        },
        status=200 if ok else 503,
    )


async def handle_readyz(request: web.Request) -> web.Response:
    """READINESS: can this worker produce fresh content and real scores
    right now? Fuses breaker states, the dispatch watchdog, and the
    device probe (ServingSupervisor.status) — plus, on a fabric worker,
    the cluster block (worker identity, room placement + per-worker
    room counts, live membership, replication leader + lag). Degraded
    -> 503 + Retry-After so load balancers drain the worker while the
    game keeps serving reserve rounds to players already on it."""
    fabric = request.app[_FABRIC]
    supervisor = fabric.supervisor
    store_ok, device_ok = await asyncio.gather(
        _probe_store(fabric), supervisor.probe_device())
    # the embedded event tail is internal serving state: loopback
    # operators only (the /debugz boundary) — remote probes/players get
    # the verdict without the history
    status = supervisor.status(
        device_ok=device_ok, include_events=_is_loopback(request))
    status["store"] = store_ok
    ready = bool(status["ready"]) and store_ok
    if fabric.draining:
        # graceful handoff in progress (SIGTERM): admission must stop —
        # load balancers drain NOW, while in-flight requests finish and
        # peers adopt the rooms (fabric/rooms.py RoomFabric.handoff)
        ready = False
        status["state"] = "draining"
    status["ready"] = ready
    # the SLO block is ADVISORY, never gating: burn rates tell the
    # operator where the error budget goes; draining a worker stays a
    # supervisor decision made on direct evidence (obs/slo.py).
    # Evaluate-on-read (internally rate-limited) so the block stays
    # live even with the background loop disabled (CASSMANTLE_NO_SLO)
    engine = request.app[_SLO]
    engine.evaluate()
    status["slo"] = engine.status()
    # the overload control plane's live state (ISSUE 13): the brownout
    # tier (also stamped on responses as X-Quality-Degraded) and every
    # queue's adaptive admission limit — advisory like the SLO block;
    # shedding/browning-out is the system WORKING, not a failure
    status["overload"] = overload.status_block()
    # device cost & capacity (ISSUE 14, obs/device.py): per-device HBM
    # (or the explicit "unavailable" marker on hosts without HBM
    # telemetry — never zeros), per-pipeline dispatch highwater, and
    # the jit sentinel's compile-cost summary. Advisory: the page that
    # drains a worker also says whether HBM pressure or a compile
    # storm explains it
    status["device_telemetry"] = device_metrics.device_block()
    # the canary block (ISSUE 18): last black-box probe verdict per
    # target worker. Advisory like the SLO block — a failing canary is
    # the "players can't play" smoking gun next to whatever white-box
    # verdict drained the worker
    prober = request.app[_PROBER].get("prober")
    if prober is not None:
        status["canary"] = prober.status_block()
    else:
        status["canary"] = {"enabled": False}
    if ready:
        return web.json_response(status)
    if status.get("state") != "draining":
        status["state"] = "degraded"
    retry_after = str(int(supervisor.retry_after_s()))
    return web.json_response(
        status, status=503, headers={"Retry-After": retry_after})


async def handle_debug_trace(request: web.Request) -> web.Response:
    """On-demand jax.profiler capture (SURVEY.md §5.1 — the reference has
    no tracing at all): ``POST /debug/trace?seconds=N[&name=subdir]``
    records N seconds of device+host activity to a TensorBoard trace
    directory while live traffic runs, and returns its path. Gated like
    `/debugz` — loopback OR the cluster-secret token (ISSUE 14: an
    operator triaging from another worker's shell, or tooling holding
    the token, can capture without an ssh hop) — an operator surface,
    never a player one. Single-flight: the ``active`` flag is
    checked-and-set before the first await, so a second concurrent
    capture answers 409 instead of interleaving ``start_trace`` /
    ``stop_trace`` (the profiler is process-global; interleaved
    captures corrupt both traces).

    The write path is never request-chosen: captures land under a fixed
    root (``CASSMANTLE_TRACE_ROOT`` env or the system tempdir), and the
    optional ``name`` selects only a single sanitized subdirectory —
    a same-host reverse proxy forwarding this route cannot turn it into
    an arbitrary-filesystem-write primitive."""
    if not _is_cluster_peer(request, request.app[_FABRIC]):
        raise web.HTTPForbidden(text="loopback or cluster peers only")
    try:
        seconds = min(60.0, float(request.query.get("seconds", "5")))
    except ValueError:
        raise web.HTTPBadRequest(text="seconds must be a number")
    name = request.query.get("name", "capture")
    if not re.fullmatch(r"[A-Za-z0-9._-]{1,64}", name) or ".." in name:
        raise web.HTTPBadRequest(text="name must be [A-Za-z0-9._-]{1,64}")
    root = os.environ.get(
        "CASSMANTLE_TRACE_ROOT",
        os.path.join(tempfile.gettempdir(), "cassmantle_trace"),
    )
    log_dir = os.path.join(root, name)
    trace_state = request.app[_TRACE_STATE]
    if trace_state["active"]:
        raise web.HTTPConflict(text="a trace capture is already running")
    trace_state["active"] = True
    try:
        import jax

        loop = asyncio.get_running_loop()
        # start/stop in an executor: the first profiler call can trigger
        # jax backend init, which must never block the serving event loop
        await loop.run_in_executor(
            None, jax.profiler.start_trace, log_dir)
        try:
            await asyncio.sleep(seconds)
        finally:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
    finally:
        trace_state["active"] = False
    metrics.inc("obs.profiler_captures")
    return web.json_response({"trace_dir": log_dir, "seconds": seconds})


# (wordlist tuple, payload bytes, quoted ETag) — keyed on the IDENTITY
# of load_wordlist()'s cached tuple. The strong reference pins the tuple
# alive, so its id can never be reused by a successor; payload and ETag
# (one sha256 over ~0.4 MB) are computed exactly once per lexicon
# object, not per request, and recompute if the assets cache is ever
# cleared and rebuilt (tests regenerating the lexicon).
_WORDLIST_CACHE: Optional[tuple] = None


def _wordlist_payload() -> bytes:
    """The ~38k-word response serialized ONCE: the lexicon is immutable
    at runtime and /wordlist is hit per page load — re-serializing
    ~0.4 MB of JSON (or re-hashing it for the ETag) on the event loop
    per request would stall the 1 Hz WS clock pushes."""
    global _WORDLIST_CACHE
    import hashlib
    import json

    from cassmantle_tpu.engine.masking import STOPWORDS
    from cassmantle_tpu.server.assets import load_wordlist

    words = load_wordlist()
    cache = _WORDLIST_CACHE
    if cache is not None and cache[0] is words:
        return cache[1]
    payload = json.dumps({
        "words": list(words),
        "stopwords": sorted(STOPWORDS),
        "min_len": 2,
    }).encode()
    etag = '"' + hashlib.sha256(payload).hexdigest()[:16] + '"'
    _WORDLIST_CACHE = (words, payload, etag)
    return payload


def _wordlist_etag() -> str:
    _wordlist_payload()
    return _WORDLIST_CACHE[2]


async def handle_wordlist(request: web.Request) -> web.Response:
    """Dictionary + stopwords for client-side spellcheck (replaces the
    reference's vendored hunspell dictionary + typo.js, §2 F3; the client
    runs static/spell.js check/suggest over these words).

    Served with a content-hash ETag and ``no-cache`` (= cache but
    revalidate): a plain max-age would keep a regenerated lexicon — and
    its suggestion ranking — stale in browsers for the full window after
    a redeploy, while revalidation costs one conditional request
    answered 304 with no body."""
    etag = _wordlist_etag()
    headers = {"Cache-Control": "no-cache", "ETag": etag}
    inm = request.headers.get("If-None-Match", "")
    # weak-aware, list-aware compare: a compressing reverse proxy may
    # weaken the validator to W/"..." and clients echo it back that
    # way; an exact string compare would silently defeat every 304
    client_tags = {t.strip().removeprefix("W/")
                   for t in inm.split(",") if t.strip()}
    if etag in client_tags or inm.strip() == "*":
        return web.Response(status=304, headers=headers)
    return web.Response(
        body=_wordlist_payload(),
        content_type="application/json",
        headers=headers,
    )


def create_app(game: "Game | RoomFabric", cfg: FrameworkConfig,
               start_timer: bool = True,
               device_health: bool = False,
               self_addr: Optional[str] = None) -> web.Application:
    """Build the aiohttp app over a Game (legacy single-room callers)
    or a RoomFabric (sharded multi-room serving). A bare Game wraps
    into a one-room fabric whose default room is that game — identical
    behavior to the pre-fabric server."""
    # apply the observability knobs before any route can record
    # (tracer/recorder/metrics are process globals; idempotent)
    configure_observability(cfg.obs)
    # arm (or disarm) the fault-injection plan: CASSMANTLE_CHAOS wins
    # over cfg.chaos.spec; disarmed, every fault point stays a no-op
    # (docs/CHAOS.md). /readyz + /healthz carry the chaos block while
    # armed, so a drill can never be mistaken for an incident.
    chaos.configure_from_env(cfg.chaos)
    if isinstance(game, RoomFabric):
        fabric = game
        fabric.start_timers = start_timer
    else:
        fabric = RoomFabric.for_game(game, cfg, start_timers=start_timer)
    # ratelimit OUTSIDE tracing: a client spamming to 429s must shed at
    # the limiter without minting root traces (ring-flush vector)
    app = web.Application(middlewares=[
        cors_middleware, make_ratelimit_middleware(cfg), tracing_middleware
    ])
    app[_FABRIC] = fabric
    # mutable holder created before the app starts: flipping a field at
    # request time is legal where reassigning an app key is not (aiohttp
    # deprecates, and 4.x forbids, mutating a started app's keys)
    app[_TRACE_STATE] = {"active": False}
    app[_OBS_CFG] = cfg.obs
    app[_PEER_HTTP] = {"session": None}
    app[_OBS_TASKS] = []
    app[_PROBER] = {"prober": None}
    app[_SLO] = SloEngine(
        default_objectives(cfg),
        fast_window_s=cfg.obs.slo_fast_window_s,
        slow_window_s=cfg.obs.slo_slow_window_s)
    # the SLO-driven brownout ladder (serving/overload.py) subscribes
    # to every evaluation pass; CASSMANTLE_NO_BROWNOUT=1 pins tier 0
    overload.configure_brownout(cfg, app[_SLO])
    app[_PROCESS] = ProcessMetrics()
    if device_health:
        from cassmantle_tpu.utils.health import DeviceHealth

        # the supervisor owns the prober and fuses its verdict into
        # /healthz and /readyz (supervisor.probe_device)
        dh = DeviceHealth()
        fabric.supervisor.device_health = dh
        recovery = getattr(fabric.supervisor, "recovery", None)
        if recovery is not None:
            # probe raises ride the device-loss classifier
            # (serving/device_recovery.py): a dispatch-quiet worker
            # still detects runtime loss through its health probes
            dh.on_probe_error = recovery.note_probe_exception
    app.router.add_get("/", handle_root)
    app.router.add_get("/init", handle_init)
    app.router.add_get("/client/status", handle_status)
    app.router.add_get("/fetch/contents", handle_fetch_contents)
    app.router.add_post("/compute_score", handle_compute_score)
    app.router.add_get("/clock", handle_clock)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/debugz", handle_debugz)
    app.router.add_get("/sloz", handle_sloz)
    app.router.add_get("/healthz", handle_healthz)
    app.router.add_get("/readyz", handle_readyz)
    app.router.add_get("/wordlist", handle_wordlist)
    app.router.add_post("/debug/trace", handle_debug_trace)
    if os.path.isdir(STATIC_DIR):
        app.router.add_static("/static", STATIC_DIR)
    if os.path.isdir(DATA_DIR):
        app.router.add_static("/data", DATA_DIR)
    if os.path.isdir(MEDIA_DIR):
        # brand/UI assets, the reference's third static mount
        # (main.py:25-27); all files here are original SVGs
        app.router.add_static("/media", MEDIA_DIR)

    async def _slo_loop(engine: SloEngine, interval_s: float) -> None:
        while True:
            await asyncio.sleep(interval_s)
            try:
                engine.evaluate()
            except Exception:
                # advisory machinery: an evaluation bug must never take
                # the loop (or anything else) down with it — but a
                # silently dead evaluator means burn-rate alerts stop
                # firing, so the failure itself must be countable
                metrics.inc("slo.eval_failures")
                log.exception("slo evaluation failed; continuing")

    async def on_startup(app_: web.Application) -> None:
        await fabric.startup()
        loop = asyncio.get_running_loop()
        tasks = app_[_OBS_TASKS]
        tasks.append(loop.create_task(
            app_[_PROCESS].run(cfg.obs.process_sample_interval_s)))
        # device HBM sampler: same cadence as the process self-metrics
        # (obs/device.py — a worker nobody scrapes still carries fresh
        # HBM gauges into its federation view)
        tasks.append(loop.create_task(
            device_metrics.run(cfg.obs.process_sample_interval_s)))
        if not _env_flag_set("CASSMANTLE_NO_SLO"):
            tasks.append(loop.create_task(
                _slo_loop(app_[_SLO], cfg.obs.slo_eval_interval_s)))
        # the synthetic canary (ISSUE 18): plays the real game surface
        # over this worker's own listener (self_addr) and every live
        # peer's. CASSMANTLE_NO_PROBER=1 at boot leaves ZERO probe
        # artifacts — no task, no metrics, no store keys, no /readyz
        # canary verdicts (the block reports enabled: false)
        if not _env_flag_set("CASSMANTLE_NO_PROBER"):
            from cassmantle_tpu.obs.prober import CanaryProber

            prober = CanaryProber(fabric, cfg, self_addr=self_addr)
            app_[_PROBER]["prober"] = prober
            tasks.append(loop.create_task(prober.run()))
        # opt-in leak census (CASSMANTLE_LEAK_SENTINEL=1): log-only —
        # thread/task origin tracking plus a periodic scan() that
        # counts leaks.* and flight-records leak.detected when the
        # live census grows past its high-water mark. Same cadence as
        # the process self-metrics: leak growth IS a process self-
        # metric.
        leak_sentinel.maybe_enable_from_env()
        if leak_sentinel.sentinel_active():
            async def _leak_scan_loop() -> None:
                while True:
                    await asyncio.sleep(cfg.obs.process_sample_interval_s)
                    leak_sentinel.scan()

            tasks.append(loop.create_task(_leak_scan_loop()))

    async def on_shutdown(app_: web.Application) -> None:
        # graceful SIGTERM handoff (ISSUE 12): leave membership, drain
        # rooms, wait for peers to adopt — BEFORE the process dies, so
        # the ring moves on a peer beat instead of after the staleness
        # TTL. aiohttp has already closed the listeners by this hook,
        # so new connections are refused (the LB's drain signal) while
        # in-flight requests finish under the shutdown grace. For an
        # operator-initiated drain with the listener still up, calling
        # RoomFabric.handoff() directly serves 307s to the adopters
        # and /readyz reports "draining" throughout.
        try:
            await fabric.handoff()
        # lint: ignore[swallowed-error] — best-effort drain while the process is exiting: the log is for the operator tailing the drain, and handoff() counts its own moves
        except Exception:
            log.exception("graceful handoff failed; shutting down anyway")

    async def on_cleanup(app_: web.Application) -> None:
        for task in app_[_OBS_TASKS]:
            task.cancel()
        for task in app_[_OBS_TASKS]:
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        session = app_[_PEER_HTTP].get("session")
        if session is not None:
            await session.close()
        await fabric.shutdown()

    app.on_startup.append(on_startup)
    app.on_shutdown.append(on_shutdown)
    app.on_cleanup.append(on_cleanup)
    return app


def _build_store(store_addr: Optional[str], cfg: FrameworkConfig):
    """The worker's shared store: MemoryStore (single process),
    MantleStore (``native[:port]`` — one shared node), or
    ReplicatedStore (``repl:host:port,host:port`` / the configured
    ``fabric.repl_endpoints`` / CASSMANTLE_REPL_ENDPOINTS — a
    leader+followers mantlestore cluster with lease failover)."""
    from cassmantle_tpu.engine.store import MemoryStore, ReplicatedStore

    endpoints = os.environ.get("CASSMANTLE_REPL_ENDPOINTS", "")
    endpoints = tuple(e.strip() for e in endpoints.split(",") if e.strip()) \
        or tuple(cfg.fabric.repl_endpoints)
    if store_addr and store_addr.startswith("repl:"):
        endpoints = tuple(
            e.strip() for e in store_addr[len("repl:"):].split(",")
            if e.strip())
        store_addr = None
    if endpoints:
        lease_ms = os.environ.get("CASSMANTLE_REPL_LEASE_MS")
        poll_ms = os.environ.get("CASSMANTLE_REPL_POLL_MS")
        return ReplicatedStore(
            list(endpoints),
            poll_interval_s=(float(poll_ms) / 1000.0 if poll_ms
                             else cfg.fabric.repl_poll_s),
            lease_timeout_s=(float(lease_ms) / 1000.0 if lease_ms
                             else cfg.fabric.repl_lease_s),
        )
    if store_addr:
        import re

        m = re.fullmatch(r"native(?::(\d+))?", store_addr)
        if not m:
            # fail loudly: a typo'd store string silently falling back
            # to a per-process MemoryStore would split-brain a
            # multi-worker fleet
            raise ValueError(
                f"unknown store address {store_addr!r} (expected "
                f"'native[:port]' or 'repl:host:port,host:port')")
        from cassmantle_tpu.native.client import MantleStore

        return MantleStore(port=int(m.group(1) or 7070))
    return MemoryStore()


def _serving_components(cfg: FrameworkConfig, fake: bool,
                        weights_dir: Optional[str], supervisor):
    """(backend, embed, similarity, blur_fn, pin_answers, stop) — built
    ONCE per worker and shared by every room's game, so N rooms' round
    generation funnels into the same batched device path (the fabric
    scales the game, not the model count). ``pin_answers`` is the
    RoundManager promotion hook that pins round answers into the int8
    embed table (ops/embed_table.py), or None when no table is armed.
    ``stop`` shuts the serving stack's queues down at worker shutdown
    (RoomFabric.shutdown), or None when the backend owns none."""
    if fake:
        from cassmantle_tpu.engine.content import (
            FakeContentBackend,
            hash_embed,
            hash_similarity,
        )

        similarity = hash_similarity
        pin_answers = None
        if cfg.serving.fake_score_batch_ms > 0:
            # overload-drill wiring (bench.py overload_drill): the fake
            # scorer rides a REAL BatchingQueue whose handler simulates
            # device batch cost, so synthetic load exercises the real
            # admission/priority/Retry-After machinery on a CPU host
            from cassmantle_tpu.serving.fake_scorer import (
                FakeQueuedScorer,
            )

            similarity = FakeQueuedScorer(cfg, supervisor).similarity
        from cassmantle_tpu.ops.embed_table import fake_table_enabled

        if fake_table_enabled():
            # A/B arm for the table rung on jax-free drill workers
            # (CASSMANTLE_FAKE_EMBED_TABLE=1, docs/DEPLOY.md §6): the
            # same EmbedTable + int8 math as production, rows from
            # hash_embed instead of MiniLM, in FRONT of whatever fake
            # ladder is armed above — in-vocabulary pairs skip the
            # queue exactly like production rung 0
            from cassmantle_tpu.ops.embed_table import (
                TableFirstSimilarity,
                build_fake_table,
                pin_answers_hash,
            )

            table = build_fake_table()
            similarity = TableFirstSimilarity(table, similarity)
            pin_answers = functools.partial(pin_answers_hash, table)
        return FakeContentBackend(image_size=256), hash_embed, \
            similarity, None, pin_answers, None
    from cassmantle_tpu.serving.service import InferenceService

    service = InferenceService(cfg, weights_dir=weights_dir,
                               supervisor=supervisor)
    return service.content_backend, service.embed, service.similarity, \
        service.blur, service.pin_answers, service.stop


def build_game(cfg: FrameworkConfig, fake: bool = False,
               weights_dir: Optional[str] = None,
               store_addr: Optional[str] = None) -> Game:
    """Assemble a single Game with real TPU serving or the fake backend.

    ``store_addr`` like ``"native:7070"`` connects to a shared mantlestore
    (multi-worker deployments, one store per host like the reference's
    Redis); default is the in-process MemoryStore. Multi-room serving
    goes through :func:`build_fabric` instead.
    """
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    # ONE supervisor per worker: the engine's content breaker and the
    # inference service's score breaker + queue watchdogs must fuse into
    # the same /readyz verdict
    supervisor = ServingSupervisor()
    store = _build_store(store_addr, cfg)
    backend, embed, similarity, blur_fn, pin_answers, _ = \
        _serving_components(cfg, fake, weights_dir, supervisor)
    return Game(cfg, store, backend, embed=embed, similarity=similarity,
                blur_fn=blur_fn, supervisor=supervisor,
                pin_answers=pin_answers)


def apply_fabric_env(cfg: FrameworkConfig) -> FrameworkConfig:
    """Fold runtime fabric env overrides into the config — applied by
    build_fabric AND by the server entry before create_app, so every
    consumer of cfg.fabric (room lists, middleware) sees ONE value."""
    import dataclasses

    rooms_env = os.environ.get("CASSMANTLE_ROOM_COUNT")
    if rooms_env:
        cfg = cfg.replace(fabric=dataclasses.replace(
            cfg.fabric, num_rooms=int(rooms_env)))
    return cfg


def build_fabric(cfg: FrameworkConfig, fake: bool = False,
                 weights_dir: Optional[str] = None,
                 store_addr: Optional[str] = None,
                 worker_id: Optional[str] = None,
                 advertise_addr: Optional[str] = None) -> RoomFabric:
    """Assemble the room fabric for one worker: a shared (possibly
    replicated) store, one serving stack, and per-room Games created on
    demand (fabric/rooms.py). Env overrides (docs/DEPLOY.md §6):
    CASSMANTLE_ROOM_COUNT, CASSMANTLE_ROOM_WORKER_ID,
    CASSMANTLE_ROOM_ADVERTISE, CASSMANTLE_REPL_ENDPOINTS,
    CASSMANTLE_REPL_LEASE_MS, CASSMANTLE_REPL_POLL_MS."""
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    cfg = apply_fabric_env(cfg)
    worker_id = (worker_id
                 or os.environ.get("CASSMANTLE_ROOM_WORKER_ID")
                 or cfg.fabric.worker_id
                 or f"{os.uname().nodename}:{os.getpid()}")
    advertise_addr = (advertise_addr
                      or os.environ.get("CASSMANTLE_ROOM_ADVERTISE")
                      or cfg.fabric.advertise_addr)
    supervisor = ServingSupervisor()
    store = _build_store(store_addr, cfg)
    backend, embed, similarity, blur_fn, pin_answers, serving_stop = \
        _serving_components(cfg, fake, weights_dir, supervisor)

    def game_factory(room: str, room_store) -> Game:
        # room= labels the game's engine metric series (game.guesses,
        # round.generate_s, ...) so N rooms on this worker stay
        # distinguishable on /metrics (docs/OBSERVABILITY.md)
        return Game(cfg, room_store, backend, embed=embed,
                    similarity=similarity, blur_fn=blur_fn,
                    supervisor=supervisor, room=room,
                    pin_answers=pin_answers)

    return RoomFabric(cfg, store, game_factory, worker_id=worker_id,
                      advertise_addr=advertise_addr,
                      supervisor=supervisor, serving_stop=serving_stop)


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description="cassmantle-tpu server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--fake", action="store_true",
                        help="deterministic fake content backend (no TPU)")
    parser.add_argument("--weights", default=None,
                        help="safetensors checkpoint directory")
    parser.add_argument("--round-seconds", type=float, default=None)
    parser.add_argument("--store", default=None,
                        help="'native[:port]' = shared C++ mantlestore "
                             "(spawn with native/build/mantlestore "
                             "[port] [snapshot_path [interval_s]]; a "
                             "snapshot path makes rounds survive store "
                             "restarts); 'repl:host:port,host:port' = "
                             "replicated mantlestore cluster (leader "
                             "writes + log-shipping + lease failover — "
                             "docs/DEPLOY.md multi-worker runbook)")
    parser.add_argument("--rooms", type=int, default=None,
                        help="concurrent game rooms (each with its own "
                             "round clock/content/scores, sessions "
                             "consistent-hashed across them; default 1 "
                             "= the classic single global round)")
    parser.add_argument("--worker-id", default=None,
                        help="stable worker identity for room placement "
                             "(default host:pid)")
    parser.add_argument("--advertise", default=None,
                        help="address peers redirect room traffic to, "
                             "e.g. http://10.0.0.3:8000 (unset = no "
                             "cross-worker redirects; foreign rooms "
                             "serve locally)")
    parser.add_argument("--preset", default="sd15",
                        choices=("sd15", "sdxl", "fast"),
                        help="model/sampler preset: sd15 = SD1.5-512 "
                             "DDIM-50; sdxl = SDXL-base 1024 (the "
                             "reference's image model); fast = SD1.5 "
                             "with DPM++(2M) @ 25 steps")
    parser.add_argument("--platform", default="auto",
                        choices=("auto", "cpu"),
                        help="'cpu' pins jax to host devices — e.g. "
                             "--fake serving on a box with no "
                             "accelerator")
    parser.add_argument("--lm", default="gpt2",
                        choices=("gpt2", "mistral"),
                        help="prompt-LM family: gpt2 (default) or a "
                             "Mistral-7B-class model (the reference's "
                             "actual LLM, reference backend.py:25)")
    parser.add_argument("--lm-int8", action="store_true",
                        help="weights-only int8 for the prompt LM "
                             "(ops/quant.py) — what fits Mistral-7B-"
                             "class weights + decode on one 16 GB chip")
    parser.add_argument("--workers", type=int, default=1,
                        help="worker processes sharing the port "
                             "(SO_REUSEPORT) and one --store "
                             "(required >1) — the multi-worker layout "
                             "the reference ran as multi-worker "
                             "uvicorn (main.py:37-40): every worker "
                             "runs the lock-guarded global timer, "
                             "exactly one generates per round")
    args = parser.parse_args()

    if args.platform == "cpu":
        from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

        pin_cpu_platform(virtual_devices=False)

    if args.preset == "sdxl":
        from cassmantle_tpu.config import sdxl_config

        cfg = sdxl_config()
    elif args.preset == "fast":
        from cassmantle_tpu.config import fast_serving_config

        cfg = fast_serving_config()
    else:
        cfg = FrameworkConfig()
    import dataclasses

    if args.round_seconds:
        cfg = cfg.replace(
            game=dataclasses.replace(cfg.game,
                                     time_per_prompt=args.round_seconds)
        )
    if args.lm == "mistral" or args.lm_int8:
        from cassmantle_tpu.config import MistralConfig

        models = cfg.models
        if args.lm == "mistral":
            models = dataclasses.replace(models, mistral=MistralConfig())
        if args.lm_int8:
            models = dataclasses.replace(models, lm_int8=True)
        cfg = cfg.replace(models=models)
    if args.workers > 1:
        import multiprocessing
        import signal
        import threading

        if not (args.store and args.store.startswith(("native", "repl:"))):
            parser.error("--workers > 1 requires --store native[:port] "
                         "or repl:... (a shared native store is the "
                         "coordination plane; per-process MemoryStores "
                         "would each run their own game)")
        if not (args.fake or args.platform == "cpu"):
            parser.error("--workers > 1 needs --fake or --platform cpu: "
                         "one accelerator chip has one owning process — "
                         "TPU-backed serving runs single-worker (the "
                         "inference queue already coalesces requests)")
        procs = []
        for _ in range(args.workers - 1):
            p = multiprocessing.Process(
                target=_run_worker, args=(args, cfg), daemon=True)
            p.start()
            procs.append(p)

        def _watch() -> None:
            # a silently-dead worker degrades capacity invisibly; wait
            # on ALL sentinels at once (a sequential join would sit on
            # the first worker while a later one dies unreported)
            from multiprocessing.connection import wait as mp_wait

            pending = {p.sentinel: p for p in procs}
            while pending:
                for sentinel in mp_wait(list(pending)):
                    p = pending.pop(sentinel)
                    p.join()
                    if p.exitcode not in (0, None, -signal.SIGINT,
                                          -signal.SIGTERM):
                        # a dead sibling is degraded capacity, not just
                        # a log line (ISSUE 12 satellite): count it,
                        # flight-record it, and let the supervisor's
                        # /readyz watchdog block surface the total
                        log.error("worker pid=%s died with exit code %s",
                                  p.pid, p.exitcode)
                        metrics.inc("server.worker_deaths")
                        flight_recorder.record(
                            "server.worker_death", pid=p.pid,
                            exitcode=p.exitcode)

        threading.Thread(target=_watch, daemon=True).start()
        try:
            _run_worker(args, cfg)
        finally:
            # graceful first (aiohttp on_cleanup -> game.shutdown drops
            # store locks); only then force-kill stragglers
            for p in procs:
                if p.is_alive():
                    os.kill(p.pid, signal.SIGINT)
            for p in procs:
                p.join(timeout=5.0)
            for p in procs:
                if p.is_alive():
                    p.terminate()
        return
    _run_worker(args, cfg)


def _run_worker(args, cfg: FrameworkConfig) -> None:
    import dataclasses

    if getattr(args, "rooms", None):
        cfg = cfg.replace(fabric=dataclasses.replace(
            cfg.fabric, num_rooms=args.rooms))
    # one cfg for everything: the env override must reach create_app's
    # consumers too, not just the fabric build
    cfg = apply_fabric_env(cfg)
    fabric = build_fabric(cfg, fake=args.fake, weights_dir=args.weights,
                          store_addr=args.store,
                          worker_id=getattr(args, "worker_id", None),
                          advertise_addr=getattr(args, "advertise", None))
    web.run_app(create_app(fabric, cfg, device_health=not args.fake,
                           # the canary dials this worker's own
                           # listener over loopback — the probe must
                           # traverse the real HTTP stack, middlewares
                           # included, not call handlers in-process
                           self_addr=f"http://127.0.0.1:{args.port}"),
                host=args.host, port=args.port,
                reuse_port=(args.workers > 1))


if __name__ == "__main__":
    main()
