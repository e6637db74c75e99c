"""Native C++ state store (mantlestore) end-to-end tests.

Builds the server with g++, spawns it on a test port, and drives it through
the asyncio RESP client — including the same contract cases MemoryStore
passes, plus cross-connection lock exclusion (the multi-worker property the
engine's double-buffer relies on)."""

import asyncio

import pytest

from cassmantle_tpu.engine.store import LockTimeout
from cassmantle_tpu.native.client import MantleStore, ensure_built, spawn_server

PORT = 7171

pytestmark = pytest.mark.skipif(
    ensure_built() is None, reason="no C++ toolchain"
)


@pytest.fixture(scope="module")
def server():
    proc = spawn_server(PORT)
    yield proc
    proc.terminate()
    proc.wait(timeout=5)


@pytest.fixture
def store(server):
    # NOTE: each async test runs in its own event loop (conftest runner),
    # so the client must connect inside the test; cleanup uses a fresh
    # client+loop of its own.
    yield MantleStore(port=PORT)

    async def cleanup():
        c = MantleStore(port=PORT)
        await c.flushall()
        await c.close()

    asyncio.run(cleanup())


@pytest.mark.asyncio
async def test_plain_keys_and_ttl(store):
    await store.setex("countdown", 0.2, "active")
    assert await store.exists("countdown")
    ttl = await store.ttl("countdown")
    assert 0.0 < ttl <= 0.2
    await asyncio.sleep(0.25)
    assert not await store.exists("countdown")
    assert await store.ttl("countdown") == -2.0

    await store.set("k", "v")
    assert await store.get("k") == b"v"
    assert await store.ttl("k") == -1.0
    await store.delete("k")
    assert await store.get("k") is None


@pytest.mark.asyncio
async def test_binary_values(store):
    blob = bytes(range(256)) * 3
    await store.hset("image", "current", blob)
    assert await store.hget("image", "current") == blob


@pytest.mark.asyncio
async def test_hash_ops(store):
    await store.hset("sess", mapping={"max": 0.01, "won": 0})
    await store.hset("sess", "attempts", 0)
    assert await store.hget("sess", "max") == b"0.01"
    assert set(await store.hgetall("sess")) == {"max", "won", "attempts"}
    assert await store.hincrby("sess", "attempts") == 1
    assert await store.hincrby("sess", "attempts", 4) == 5
    await store.hdel("sess", "max")
    assert await store.hget("sess", "max") is None


@pytest.mark.asyncio
async def test_set_ops(store):
    await store.sadd("sessions", "a", "b")
    assert await store.sismember("sessions", "a")
    await store.srem("sessions", "a")
    assert await store.smembers("sessions") == {"b"}


@pytest.mark.asyncio
async def test_lock_exclusion_across_connections(store):
    other = MantleStore(port=PORT)
    order = []

    async def holder():
        async with store.lock("l", timeout=5.0, blocking_timeout=1.0):
            order.append("h-in")
            await asyncio.sleep(0.2)
            order.append("h-out")

    async def waiter():
        await asyncio.sleep(0.05)
        async with other.lock("l", timeout=5.0, blocking_timeout=1.0):
            order.append("w-in")

    await asyncio.gather(holder(), waiter())
    assert order == ["h-in", "h-out", "w-in"]
    await other.close()


@pytest.mark.asyncio
async def test_lock_acquire_timeout(store):
    other = MantleStore(port=PORT)

    async def holder():
        async with store.lock("l2", timeout=5.0, blocking_timeout=0.5):
            await asyncio.sleep(0.4)

    async def contender():
        await asyncio.sleep(0.05)
        with pytest.raises(LockTimeout):
            async with other.lock("l2", timeout=5.0,
                                  blocking_timeout=0.1):
                pass

    await asyncio.gather(holder(), contender())
    await other.close()


@pytest.mark.asyncio
async def test_lock_self_expires(store):
    other = MantleStore(port=PORT)
    mgr = store.lock("l3", timeout=0.2, blocking_timeout=0.1)
    await mgr.__aenter__()  # simulated crash: never released
    await asyncio.sleep(0.25)
    async with other.lock("l3", timeout=1.0, blocking_timeout=0.5):
        pass
    await other.close()


@pytest.mark.asyncio
async def test_full_game_on_native_store(store):
    """The whole engine runs against the native store."""
    import dataclasses

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.engine.content import (
        FakeContentBackend,
        hash_embed,
        hash_similarity,
    )
    from cassmantle_tpu.engine.game import Game

    cfg = test_config()
    game = Game(cfg, store, FakeContentBackend(image_size=16),
                hash_embed, hash_similarity)
    await game.startup()
    await game.init_client("s1")
    prompt = await game.rounds.fetch_current_prompt()
    answers = {str(m): prompt["tokens"][m] for m in prompt["masks"]}
    result = await game.compute_client_scores("s1", answers)
    assert result["won"] == 1
    await game.rounds.buffer_contents()
    await game.rounds.promote_buffer()
    assert int((await game.fetch_story())["episode"]) == 2


@pytest.mark.asyncio
async def test_snapshot_durability(tmp_path):
    """State survives a SIGTERM + restart via the snapshot file — the
    worker-restart-resumes-round semantics the reference gets from Redis
    durability (SURVEY.md §5.4)."""
    import signal

    snap = str(tmp_path / "store.snap")
    port = PORT + 1
    proc = spawn_server(port, snapshot_path=snap)
    try:
        c = MantleStore(port=port)
        await c.set("prompt:current", "the stormy lighthouse")
        await c.hset("story", mapping={"title": "Salt Roads", "episode": "3"})
        await c.sadd("sessions", "s1", "s2")
        await c.setex("countdown", 30.0, "active")
        await c.setex("gone", 0.05, "x")
        await c.close()
        import asyncio as aio

        await aio.sleep(0.1)  # 'gone' expires before the snapshot
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=5)

        proc = spawn_server(port, snapshot_path=snap)
        c = MantleStore(port=port)
        assert await c.get("prompt:current") == b"the stormy lighthouse"
        story = await c.hgetall("story")
        assert story["title"] == b"Salt Roads" and story["episode"] == b"3"
        assert await c.smembers("sessions") == {"s1", "s2"}
        ttl = await c.ttl("countdown")
        assert 0.0 < ttl <= 30.0  # TTL persisted as REMAINING time
        assert not await c.exists("gone")
        await c.close()
    finally:
        proc.terminate()
        proc.wait(timeout=5)


@pytest.mark.asyncio
async def test_snapshot_chunks_large_collections(tmp_path):
    """Sets/hashes beyond the RESP 1024-arg parse cap replay losslessly
    (the snapshot writer chunks multi-member commands)."""
    import signal

    snap = str(tmp_path / "big.snap")
    port = PORT + 2
    proc = spawn_server(port, snapshot_path=snap)
    try:
        c = MantleStore(port=port)
        members = [f"player-{i}" for i in range(1500)]
        await c.sadd("sessions", *members)
        await c.hset("scores",
                     mapping={f"f{i}": str(i) for i in range(700)})
        await c.set("after", "still-here")  # key serialized after the big ones
        await c.close()
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=5)

        proc = spawn_server(port, snapshot_path=snap)
        c = MantleStore(port=port)
        assert await c.smembers("sessions") == set(members)
        scores = await c.hgetall("scores")
        assert len(scores) == 700 and scores["f699"] == b"699"
        assert await c.get("after") == b"still-here"
        await c.close()
    finally:
        proc.terminate()
        proc.wait(timeout=5)


@pytest.mark.asyncio
async def test_lock_expired_in_hold_detected(store):
    """The native client reports the same hazard classification MemoryStore
    detects: a hold past its TTL that nobody reclaimed is an 'overrun'
    (UNLOCK :2); one another worker reacquired is 'expired_in_hold'
    (UNLOCK :0)."""
    from cassmantle_tpu.utils.logging import metrics

    key = "store.lock_overrun"
    before = metrics.snapshot()["counters"].get(key, 0)
    async with store.lock("l4", timeout=0.2, blocking_timeout=0.1):
        await asyncio.sleep(0.3)   # hold past the TTL, unclaimed
    after = metrics.snapshot()["counters"].get(key, 0)
    assert after == before + 1

    other = MantleStore(port=PORT)
    key = "store.lock_expired_in_hold"
    before = metrics.snapshot()["counters"].get(key, 0)
    async with store.lock("l5", timeout=0.2, blocking_timeout=1.0):
        await asyncio.sleep(0.3)
        # generous blocking_timeout: the lock frees after its 0.2 s TTL,
        # but on a saturated host pure event-loop scheduling delay can
        # exceed a tight window and fail the ACQUISITION, which this
        # test is not about (observed flaking at 0.5 s under a full
        # parallel suite run)
        async with other.lock("l5", timeout=1.0, blocking_timeout=5.0):
            pass      # another worker reacquired the expired lock
    after = metrics.snapshot()["counters"].get(key, 0)
    assert after == before + 1
    await other.close()
