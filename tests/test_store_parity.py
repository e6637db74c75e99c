"""Store parity matrix: MemoryStore and the native mantlestore must
agree on the whole command surface.

Replication replay (native REPL verbs + engine/store.ReplicatedStore)
re-executes the leader's command log on followers, and tests routinely
swap MemoryStore for the native store — both only work if the two
backends compute IDENTICAL results for the same command script. One
table-driven script runs against each backend and the full result
traces are compared: strings/TTL, hashes (incl. strtoll-lenient
HINCRBY), sets, wrong-type read/write discipline, and the lock verbs
with the ``:2`` overrun and tombstone-grace hazard classification.

Divergences this matrix found (fixed in this round, pinned here):

- wrong-kind writes used to half-apply on the native store (HSET over
  a string key wrote fields no HGET could see) and ASSERT on
  MemoryStore; both now REPLACE the entry with a fresh one of the new
  kind (TTL cleared);
- wrong-kind reads used to assert on MemoryStore; both now read as a
  missing key;
- HINCRBY on a non-numeric field raised on MemoryStore but parsed a
  leading integer (C strtoll) natively; both are strtoll-lenient now.
"""

import asyncio

import pytest

from cassmantle_tpu.engine import store as store_mod
from cassmantle_tpu.engine.store import LockTimeout, MemoryStore
from cassmantle_tpu.native.client import MantleStore, ensure_built, spawn_server

PORT = 7181

pytestmark = pytest.mark.skipif(
    ensure_built() is None, reason="no C++ toolchain"
)


@pytest.fixture(scope="module")
def server():
    proc = spawn_server(PORT)
    yield proc
    proc.terminate()
    proc.wait(timeout=5)


async def _flush_native():
    c = MantleStore(port=PORT)
    await c.flushall()
    await c.close()


async def run_script(store, hazards):
    """The parity script. Every step appends a comparable record to the
    trace; lock-hazard telemetry lands in ``hazards`` via the patched
    reporter. TTL values are recorded coarsely (sign/zero class) — the
    two backends share a wall clock but not a microsecond."""
    out = []

    # -- strings + TTL -----------------------------------------------------
    await store.set("k", "v1")
    out.append(await store.get("k"))
    out.append(await store.exists("k"))
    out.append(await store.ttl("k"))                 # -1: no expiry
    await store.setex("tk", 0.25, "temp")
    out.append((await store.ttl("tk")) > 0)
    await store.expire("k", 0.25)
    out.append((await store.ttl("k")) > 0)
    await asyncio.sleep(0.35)
    out.append(await store.get("tk"))                # expired -> None
    out.append(await store.ttl("tk"))                # -2: missing
    out.append(await store.exists("k"))              # expired too
    await store.set("k", "v2")                       # rewrite clears TTL
    out.append(await store.ttl("k"))
    await store.delete("k", "never-existed")
    out.append(await store.get("k"))
    out.append(await store.get("missing"))

    # -- hashes ------------------------------------------------------------
    await store.hset("h", "f1", "a")
    await store.hset("h", mapping={"f2": "b", "f3": 3})
    out.append(await store.hget("h", "f1"))
    out.append(await store.hget("h", "nope"))
    out.append(sorted((await store.hgetall("h")).items()))
    await store.hdel("h", "f2", "ghost")
    out.append(sorted((await store.hgetall("h")).items()))
    out.append(await store.hincrby("h", "cnt", 5))
    out.append(await store.hincrby("h", "cnt", -2))
    # strtoll leniency: leading integer parses, garbage counts from 0
    await store.hset("h", "messy", "12abc")
    out.append(await store.hincrby("h", "messy", 5))
    await store.hset("h", "junk", "abc")
    out.append(await store.hincrby("h", "junk", 7))
    out.append(await store.hgetall("missing-hash"))

    # -- sets ----------------------------------------------------------------
    await store.sadd("s", "a", "b")
    await store.sadd("s", "b", "c")
    out.append(sorted(await store.smembers("s")))
    out.append(await store.sismember("s", "a"))
    out.append(await store.sismember("s", "z"))
    await store.srem("s", "a", "ghost")
    out.append(sorted(await store.smembers("s")))
    out.append(sorted(await store.smembers("missing-set")))

    # -- wrong-type discipline ---------------------------------------------
    # reads of another kind behave like a missing key
    out.append(await store.get("h"))                 # string-read of hash
    out.append(await store.hget("s", "f"))           # hash-read of set
    out.append(sorted(await store.smembers("h")))    # set-read of hash
    out.append(await store.hgetall("s"))             # hash-read of set
    # writes of another kind REPLACE the entry (fresh kind, TTL cleared)
    await store.setex("conv", 30.0, "stringval")
    await store.hset("conv", "f", "x")               # string -> hash
    out.append(await store.hget("conv", "f"))
    out.append(await store.get("conv"))
    out.append(await store.ttl("conv"))              # -1: fresh entry
    await store.sadd("conv", "m")                    # hash -> set
    out.append(sorted(await store.smembers("conv")))
    out.append(await store.hget("conv", "f"))
    await store.set("conv", "back")                  # set -> string
    out.append(await store.get("conv"))
    out.append(sorted(await store.smembers("conv")))
    out.append(await store.hincrby("conv", "n", 2))  # string -> hash again
    out.append(await store.get("conv"))

    # -- locks ---------------------------------------------------------------
    async with store.lock("L", timeout=5.0, blocking_timeout=0.2):
        out.append("held")
        try:
            async with store.lock("L", timeout=5.0, blocking_timeout=0.15):
                out.append("double-acquired")
        except LockTimeout:
            out.append("LockTimeout")
    # released: immediate re-acquire works
    async with store.lock("L", timeout=5.0, blocking_timeout=0.2):
        out.append("re-held")

    # overrun: hold past the TTL -> ':2' verdict -> "overrun" hazard
    async with store.lock("over", timeout=0.2, blocking_timeout=0.2):
        await asyncio.sleep(0.35)
    # expired mid-hold AND re-acquired by another holder -> ':0' ->
    # "expired_in_hold" (the tombstone grace is what keeps the lapsed
    # owner's verdict distinguishable on the native store)
    ctx = store.lock("steal", timeout=0.2, blocking_timeout=0.2)
    await ctx.__aenter__()
    await asyncio.sleep(0.3)
    async with store.lock("steal", timeout=5.0, blocking_timeout=0.3):
        out.append("stolen-after-expiry")
        await ctx.__aexit__(None, None, None)
    out.append(sorted(hazards))
    return out


@pytest.mark.asyncio
async def test_memory_and_native_store_agree(server, monkeypatch):
    traces = {}
    for kind in ("memory", "native"):
        hazards = []

        def record(h, name, _bucket=hazards):
            _bucket.append((h, name))

        # both backends report through the one shared reporter (the
        # polled lock protocol itself is shared, engine/store.py)
        monkeypatch.setattr(store_mod, "_report_lock_hazard", record)
        if kind == "memory":
            store = MemoryStore()
            traces[kind] = await run_script(store, hazards)
        else:
            await _flush_native()
            store = MantleStore(port=PORT)
            try:
                traces[kind] = await run_script(store, hazards)
            finally:
                await store.close()
                await _flush_native()
    assert traces["memory"] == traces["native"], (
        "backend divergence:\n  memory: %r\n  native: %r"
        % (traces["memory"], traces["native"])
    )


@pytest.mark.asyncio
async def test_wrong_type_discipline_memory_only():
    """The wrong-type rules hold on MemoryStore alone (the default test
    backend) even where the native arm is skipped for lack of a
    toolchain."""
    store = MemoryStore()
    await store.hset("h", "f", "v")
    assert await store.get("h") is None
    await store.set("h", "now-a-string")
    assert await store.hget("h", "f") is None
    assert await store.get("h") == b"now-a-string"
    assert await store.hincrby("weird", "n", 3) == 3
    await store.hset("weird", "s", "9 lives")
    assert await store.hincrby("weird", "s", 1) == 10
