"""Room fabric unit + acceptance tests (fast tier).

Covers the three legs of ISSUE 8: rooms + routing (directory hashing,
namespaced store isolation, room-scoped HTTP routes, cross-worker 307),
store replication (the leader-kill fault injection: killing the leader
mid-round promotes a follower within the lease TTL and the room's
state — prompt, image, scores — survives bit-for-bit), and membership
(staleness filtering, `/readyz` fabric block). The multi-process load
harness lives in tests/test_fabric_cluster.py (slow tier); a
small-N/M CPU smoke of the same harness runs here.
"""

import asyncio
import dataclasses
import json
import time

import pytest

from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.engine.content import (
    FakeContentBackend,
    hash_embed,
    hash_similarity,
)
from cassmantle_tpu.engine.game import Game
from cassmantle_tpu.engine.store import MemoryStore, ReplicatedStore
from cassmantle_tpu.fabric.directory import RoomDirectory, stable_hash
from cassmantle_tpu.fabric.membership import ClusterMembership
from cassmantle_tpu.fabric.rooms import NamespacedStore, RoomFabric, room_ids
from cassmantle_tpu.native.client import MantleStore, ensure_built, spawn_server

needs_native = pytest.mark.skipif(
    ensure_built() is None, reason="no C++ toolchain"
)


def make_cfg(num_rooms=2, time_per_prompt=30.0):
    cfg = _tiny_config()
    return cfg.replace(
        game=dataclasses.replace(
            cfg.game, time_per_prompt=time_per_prompt,
            rate_limit_default=1e6, rate_limit_api=1e6),
        fabric=dataclasses.replace(cfg.fabric, num_rooms=num_rooms),
    )


def make_fabric(cfg, store=None, worker_id="worker-0", advertise=""):
    store = store or MemoryStore()

    def factory(room, room_store):
        return Game(cfg, room_store, FakeContentBackend(image_size=32),
                    hash_embed, hash_similarity)

    return RoomFabric(cfg, store, factory, worker_id=worker_id,
                      advertise_addr=advertise, start_timers=False,
                      heartbeat=False)


@pytest.mark.asyncio
async def test_shutdown_stops_the_serving_stack_after_the_rooms():
    """A worker's one serving stack (the InferenceService queues and
    their dispatch threads, server/app.py::build_fabric) is stopped by
    RoomFabric.shutdown — after the rooms that feed it are drained,
    before the store closes. It used to be left running at exit."""
    cfg = make_cfg(num_rooms=1)
    store = MemoryStore()
    order = []

    def factory(room, room_store):
        return Game(cfg, room_store, FakeContentBackend(image_size=32),
                    hash_embed, hash_similarity)

    async def serving_stop():
        order.append(("serving_stop", len(fabric._games)))

    fabric = RoomFabric(cfg, store, factory, start_timers=False,
                        heartbeat=False, serving_stop=serving_stop)
    await fabric.startup()
    assert len(fabric._games) == 1
    await fabric.shutdown()
    assert order == [("serving_stop", 0)]


# -- directory ---------------------------------------------------------------

def test_session_to_room_is_stable_and_process_independent():
    rooms = [f"r{i}" for i in range(8)]
    d1 = RoomDirectory(rooms, workers=["w0"])
    d2 = RoomDirectory(rooms, workers=["w0"])  # a "second process"
    hits = set()
    for i in range(200):
        sid = f"session-{i}"
        room = d1.room_for_session(sid)
        assert room == d1.room_for_session(sid)   # per-request stability
        assert room == d2.room_for_session(sid)   # cross-worker agreement
        hits.add(room)
    assert len(hits) == 8  # 200 sessions spread over all rooms


def test_ring_moves_are_minimal_on_membership_change():
    rooms = [f"r{i}" for i in range(32)]
    d = RoomDirectory(rooms, workers=["a", "b", "c"])
    before = d.placement()
    moves = d.set_workers(["a", "b", "c", "d"])
    # only rooms that moved TO the new worker move; no shuffling among
    # the survivors (the consistent-hash property)
    assert moves
    for room, (old, new) in moves.items():
        assert new == "d"
        assert before[room] == old
    assert len(moves) < len(rooms) // 2
    # removing d sends exactly its rooms back to their previous owners
    moves_back = d.set_workers(["a", "b", "c"])
    assert set(moves_back) == set(moves)
    for room, (old, new) in moves_back.items():
        assert old == "d" and new == before[room]
    assert d.placement() == before


def test_worker_for_room_empty_ring_is_none():
    d = RoomDirectory(["r0"])
    assert d.worker_for_room("r0") is None
    assert d.rooms_owned_by("nobody") == []


# -- namespaced store --------------------------------------------------------

@pytest.mark.asyncio
async def test_namespaced_store_isolates_rooms():
    base = MemoryStore()
    a = NamespacedStore(base, "")             # the default room: legacy keys
    b = NamespacedStore(base, "room:r1:")
    await a.set("prompt", "A")
    await b.set("prompt", "B")
    assert await a.get("prompt") == b"A"
    assert await b.get("prompt") == b"B"
    assert await base.get("prompt") == b"A"   # default == un-prefixed
    assert await base.get("room:r1:prompt") == b"B"
    await a.hset("h", "f", "1")
    await b.hincrby("h", "f", 5)
    assert await a.hget("h", "f") == b"1"
    assert await b.hget("h", "f") == b"5"
    # locks are room-scoped: both rooms hold "startup_lock" at once
    async with a.lock("startup_lock", timeout=5.0, blocking_timeout=0.2):
        async with b.lock("startup_lock", timeout=5.0,
                          blocking_timeout=0.2):
            pass
    # close is a no-op on the view — the shared store stays usable
    await a.close()
    assert await b.get("prompt") == b"B"


# -- room isolation (acceptance) ---------------------------------------------

@pytest.mark.asyncio
async def test_two_rooms_one_worker_hold_independent_state():
    """N-room isolation acceptance: two rooms on one worker hold
    different prompts/images and independent clocks; a session hashes
    to the same room across requests."""
    cfg = make_cfg(num_rooms=2, time_per_prompt=30.0)
    fabric = make_fabric(cfg)
    game_a = await fabric.game_for(fabric.default_room)
    game_b = await fabric.game_for("room-1")
    try:
        prompt_a = await game_a.rounds.fetch_current_prompt()
        prompt_b = await game_b.rounds.fetch_current_prompt()
        assert prompt_a["tokens"] != prompt_b["tokens"]
        image_a = await game_a.rounds.fetch_current_image_bytes()
        image_b = await game_b.rounds.fetch_current_image_bytes()
        assert image_a != image_b
        # independent clocks: restarting room B's countdown leaves room
        # A's remaining time where it was
        await game_a.rounds.start_countdown()
        await asyncio.sleep(0.3)
        await game_b.rounds.start_countdown()
        rem_a = await game_a.rounds.remaining()
        rem_b = await game_b.rounds.remaining()
        assert rem_b > rem_a
        # scores are per (session, room): the same session id wins in
        # room A without touching its room-B state
        session = "both-rooms"
        await game_a.init_client(session)
        await game_b.init_client(session)
        masks_a = prompt_a["masks"]
        answers = {str(m): prompt_a["tokens"][m] for m in masks_a}
        result = await game_a.compute_client_scores(session, answers)
        assert result["won"] == 1
        status_b = await game_b.client_status(session)
        assert status_b["won"] == 0
    finally:
        await fabric.shutdown()


@pytest.mark.asyncio
async def test_http_routes_are_room_scoped():
    from aiohttp.test_utils import TestClient, TestServer

    from cassmantle_tpu.server.app import create_app

    cfg = make_cfg(num_rooms=2)
    fabric = make_fabric(cfg)
    app = create_app(fabric, cfg, start_timer=False)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        res = await client.get("/init", params={"room": "room-1"})
        data = await res.json()
        assert data["room"] == "room-1"
        res_a = await client.get("/fetch/contents",
                                 params={"room": fabric.default_room,
                                         "session": "s-a"})
        res_b = await client.get("/fetch/contents",
                                 params={"room": "room-1",
                                         "session": "s-b"})
        tokens_a = (await res_a.json())["prompt"]["tokens"]
        tokens_b = (await res_b.json())["prompt"]["tokens"]
        assert tokens_a != tokens_b
        # un-roomed requests resolve deterministically by session hash
        room = fabric.directory.room_for_session("sticky")
        res = await client.get("/init", params={"session": "sticky"})
        assert (await res.json())["room"] == room
        # unknown rooms 404 instead of silently minting state
        res = await client.get("/fetch/contents",
                               params={"room": "no-such-room"})
        assert res.status == 404
        # readyz carries the fabric block
        res = await client.get("/readyz")
        block = (await res.json())["fabric"]
        assert block["worker"] == "worker-0"
        assert set(block["rooms"]) == set(room_ids(cfg))
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_foreign_room_redirects_to_owner():
    from aiohttp.test_utils import TestClient, TestServer

    from cassmantle_tpu.server.app import create_app

    cfg = make_cfg(num_rooms=8)
    fabric = make_fabric(cfg, worker_id="me",
                         advertise="http://127.0.0.1:1")
    app = create_app(fabric, cfg, start_timer=False)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        # a peer joins: announce it in the membership table, rebuild the
        # ring the way the heartbeat loop would
        await fabric.store.hset(
            "fabric:workers", "peer",
            json.dumps({"addr": "http://127.0.0.1:9999", "rooms": 0,
                        "t": time.time()}))
        await fabric.membership.refresh()
        fabric.directory.set_workers(["me", "peer"])
        foreign = [r for r, w in fabric.directory.placement().items()
                   if w == "peer"]
        assert foreign, "8 rooms over 2 workers: peer must own some"
        res = await client.get(
            "/fetch/contents",
            params={"room": foreign[0], "session": "s1"},
            allow_redirects=False)
        assert res.status == 307
        assert res.headers["Location"].startswith("http://127.0.0.1:9999")
        # the Location pins room AND session: cookies are host-scoped,
        # so a cookie-only client must not re-resolve a different room
        # on the owner (redirect ping-pong)
        assert f"room={foreign[0]}" in res.headers["Location"]
        assert "session=s1" in res.headers["Location"]
        # /init follows the same ownership discipline — it must never
        # quietly start a duplicate room engine on a non-owner worker
        res = await client.get("/init", params={"room": foreign[0]},
                               allow_redirects=False)
        assert res.status == 307
        assert foreign[0] not in fabric._games
        # same room with NO advertised owner address: served locally
        # (resilience beats affinity), never an errored redirect
        await fabric.store.hdel("fabric:workers", "peer")
        await fabric.membership.refresh()
        res = await client.get(
            "/fetch/contents",
            params={"room": foreign[0], "session": "s1"},
            allow_redirects=False)
        assert res.status == 200
    finally:
        await client.close()


# -- membership --------------------------------------------------------------

@pytest.mark.asyncio
async def test_membership_filters_stale_workers():
    store = MemoryStore()
    t = [1000.0]
    m1 = ClusterMembership(store, "w1", addr="http://a", ttl_s=5.0,
                           clock=lambda: t[0])
    m2 = ClusterMembership(store, "w2", addr="http://b", ttl_s=5.0,
                           clock=lambda: t[0])
    await m1.heartbeat(room_count=3)
    await m2.heartbeat(room_count=1)
    live = await m1.refresh()
    assert set(live) == {"w1", "w2"}
    assert live["w1"]["rooms"] == 3
    assert m1.addr_of("w2") == "http://b"
    # w2 goes quiet: after the TTL it drops out of the live view
    t[0] += 6.0
    await m1.heartbeat(room_count=3)
    assert set(await m1.refresh()) == {"w1"}
    # graceful leave removes the row immediately
    await m1.leave()
    assert set(await m2.refresh()) == set()


@pytest.mark.asyncio
async def test_fabric_heartbeat_drains_moved_rooms():
    cfg = make_cfg(num_rooms=8)
    fabric = make_fabric(cfg, worker_id="me")
    try:
        for room in room_ids(cfg):
            await fabric.game_for(room)
        assert len(fabric._games) == 8
        # a peer worker appears in membership: the ring rebuild moves
        # some rooms to it and this worker drains them
        live = {"me": {"addr": "", "rooms": 8},
                "peer": {"addr": "http://p", "rooms": 0}}
        moves = fabric._apply_membership(live)
        await fabric._handle_moves(moves)
        moved = [r for r, (old, new) in moves.items() if new == "peer"]
        assert moved
        for room in moved:
            assert room not in fabric._games
        assert set(fabric.owned_rooms()).isdisjoint(moved)
    finally:
        await fabric.shutdown()


# -- replication (acceptance: leader-kill fault injection) -------------------

@needs_native
@pytest.mark.asyncio
async def test_leader_kill_midround_promotes_follower_and_keeps_state():
    """Kill the store leader mid-round: the follower is promoted within
    the lease TTL and the next /fetch/contents + /compute_score level
    reads see the SAME round (no regeneration) and the session's
    earlier scores."""
    leader = spawn_server(7611, repl=True, repl_id="A", lease_ms=500)
    follower = spawn_server(7612, follower=True, repl_id="B", lease_ms=500)
    store = ReplicatedStore([7611, 7612], poll_interval_s=0.02,
                            lease_timeout_s=0.5)
    try:
        await store.start()
        cfg = make_cfg(num_rooms=1, time_per_prompt=60.0)
        game = Game(cfg, store, FakeContentBackend(image_size=32),
                    hash_embed, hash_similarity)
        await game.startup()
        prompt_before = await game.rounds.fetch_current_prompt()
        image_before = await game.rounds.fetch_current_image_bytes()
        session = "p1"
        await game.init_client(session)
        masks = prompt_before["masks"]
        first = {str(masks[0]): prompt_before["tokens"][masks[0]]}
        res = await game.compute_client_scores(session, first)
        assert float(res[str(masks[0])]) == 1.0
        # replication caught up?
        lc, fc = MantleStore(port=7611), MantleStore(port=7612)
        for _ in range(250):
            _, lend, _ = await lc.repl_offset()
            _, _, fapp = await fc.repl_offset()
            if fapp >= lend:
                break
            await asyncio.sleep(0.02)
        assert fapp >= lend, "follower never caught up"
        await lc.close()
        await fc.close()

        leader.kill()
        leader.wait()
        t0 = time.monotonic()
        prompt_after = await game.rounds.fetch_current_prompt()
        failover_s = time.monotonic() - t0
        # no round regeneration: the surviving replica serves the SAME
        # prompt and image bytes
        assert prompt_after == prompt_before
        assert await game.rounds.fetch_current_image_bytes() == image_before
        # no lost scores: the pre-kill win is still on the session
        scores = await game.sessions.fetch_scores(session)
        assert float(scores[str(masks[0])]) == 1.0
        # and new guesses score against the surviving state
        res = await game.compute_client_scores(
            session, {str(masks[1]): prompt_before["tokens"][masks[1]]})
        assert res["won"] == 1
        st = store.status()
        assert st["leader"] == "127.0.0.1:7612"
        assert st["failovers"] == 1
        # promotion is lease-gated: well inside TTL + grace, not minutes
        assert failover_s < 5.0
    finally:
        await store.close()
        for proc in (leader, follower):
            try:
                proc.kill()
                proc.wait()
            except Exception:
                pass


@needs_native
@pytest.mark.asyncio
async def test_follower_rejects_writes_until_promoted():
    leader = spawn_server(7621, repl=True, repl_id="A", lease_ms=400)
    follower = spawn_server(7622, follower=True, repl_id="B", lease_ms=400)
    try:
        f = MantleStore(port=7622)
        with pytest.raises(RuntimeError, match="READONLY"):
            await f.set("x", "y")
        # promotion is refused while the replicated lease is live
        rs = ReplicatedStore([7621, 7622], poll_interval_s=0.02,
                             lease_timeout_s=0.4)
        await rs.start()
        await rs.set("seed", "1")  # ships the lease + data to B
        await asyncio.sleep(0.1)
        assert await f.repl_promote() is False
        holder, remaining = await f.repl_lease()
        assert holder == "A" and remaining > 0
        await rs.close()
        await f.close()
    finally:
        for proc in (leader, follower):
            proc.kill()
            proc.wait()


@pytest.mark.asyncio
async def test_replicated_store_close_lands_under_cancel_swallow():
    """py3.10's wait_for can swallow a cancellation that races the
    inner future's completion (gh-86296): one cancel() then left the
    pump loop alive and close() awaited it forever (reproduced under
    CPU contention, wedging tier-1). close() now re-delivers the
    cancel until the task actually ends — pinned here with a pump stub
    that swallows the first CancelledError the way the race does."""
    rs = ReplicatedStore([7070], pump=False)
    swallowed = [0]

    async def stubborn_pump():
        while True:
            try:
                await asyncio.sleep(3600)
            except asyncio.CancelledError:
                if swallowed[0] == 0:
                    swallowed[0] += 1
                    continue  # the gh-86296 shape: cancellation eaten
                raise

    rs._pump_task = asyncio.get_running_loop().create_task(stubborn_pump())
    # let the stub reach its sleep: a task cancelled before its first
    # step never runs its body, so there would be nothing to swallow
    await asyncio.sleep(0)
    await asyncio.wait_for(rs.close(), timeout=5.0)
    assert swallowed[0] == 1
    assert rs._pump_task is None


# -- rooms_load harness (CPU smoke of the bench entry) -----------------------

@needs_native
def test_rooms_load_smoke():
    """The bench harness at tiny N/M: real worker process, real store,
    real HTTP+WS load — sustained guesses land, the clock fans out,
    nothing errors."""
    import bench

    # minimal N/M and a short window: this is tier-1's proof the
    # harness works end-to-end, not a measurement (the measured runs
    # are tests/test_fabric_cluster.py [slow] and the bench entry)
    raw = bench.rooms_load_run(workers=1, rooms=2, sessions=2,
                               seconds=1.5, ws_conns=1,
                               base_port=8491, store_port=7491)
    assert raw["guesses"] > 0
    assert raw["errors"] == 0
    assert raw["ws_ticks"] >= 1
    assert len(raw["latencies"]) == raw["guesses"]


def test_room_ids_and_prefixes():
    from cassmantle_tpu.fabric.rooms import room_prefix

    cfg = make_cfg(num_rooms=3)
    assert room_ids(cfg) == ["lobby", "room-1", "room-2"]
    assert room_prefix("lobby", "lobby") == ""
    assert room_prefix("room-1", "lobby") == "room:room-1:"
    assert stable_hash("x") == stable_hash("x")
