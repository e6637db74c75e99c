"""Asyncio client for mantlestore (the native C++ state store).

Implements the same :class:`StateStore` contract as MemoryStore, so the
game engine can run multi-process: N server workers (like the reference's
multi-worker uvicorn) share one mantlestore exactly as the reference's
workers share one Redis (SURVEY.md §5.8). The wire protocol is a RESP2
subset; blocking lock acquisition is client-side polling against the
server's atomic LOCK/UNLOCK (token + TTL, self-expiring on holder crash).
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import subprocess
from typing import Dict, Optional, Set

from cassmantle_tpu.chaos import afault_point
from cassmantle_tpu.engine.store import (
    LockTimeout,
    StateStore,
    Value,
    polled_store_lock,
)

__all__ = ["LockTimeout", "MantleStore", "ensure_built", "spawn_server"]
from cassmantle_tpu.utils.logging import get_logger

log = get_logger("native.store")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BINARY = os.path.join(NATIVE_DIR, "build", "mantlestore")


def _binary_runs() -> bool:
    """True when the existing binary actually executes on THIS host. A
    binary built on a newer base image can be present but dead on
    arrival (GLIBC/GLIBCXX version mismatch): the dynamic loader refuses
    it at exec and it dies instantly with the complaint on stderr. A
    healthy mantlestore, by contrast, prints its "listening" line and
    serves until killed — so probe by spawning on port 0 (kernel picks
    an ephemeral port; never collides with a live server) and watching
    stderr briefly for either outcome."""
    import select

    try:
        proc = subprocess.Popen([BINARY, "0"], stdout=subprocess.DEVNULL,
                                stderr=subprocess.PIPE)
    # lint: ignore[swallowed-error] — "does the binary run" probe: False IS the answer, and callers rebuild or fall back on it
    except Exception:
        return False
    try:
        ready, _, _ = select.select([proc.stderr], [], [], 10.0)
        if not ready:  # neither died nor spoke: treat as unusable
            return False
        return b"listening" in proc.stderr.readline()
    # lint: ignore[swallowed-error] — same probe contract: an unreadable stderr means unusable, which is the False the caller acts on
    except Exception:
        return False
    finally:
        proc.kill()
        proc.wait()


def ensure_built() -> Optional[str]:
    """Build the server if needed; returns binary path or None. A
    present-but-unrunnable binary (toolchain mismatch with the build
    host) rebuilds from source like a missing one, and so does a binary
    older than mantlestore.cc (a stale build would silently drop source
    fixes — e.g. the lock-tombstone sweep semantics)."""
    source = os.path.join(NATIVE_DIR, "mantlestore.cc")
    runnable = os.path.exists(BINARY) and _binary_runs()
    stale = runnable and os.path.exists(source) and \
        os.path.getmtime(source) > os.path.getmtime(BINARY)
    if runnable and not stale:
        return BINARY
    try:
        subprocess.run(
            ["sh", os.path.join(NATIVE_DIR, "build.sh")],
            check=True, capture_output=True, timeout=120,
        )
        return BINARY if os.path.exists(BINARY) else None
    # lint: ignore[swallowed-error] — documented degrade ladder: stale binary beats no store, None falls back to the memory store; both logged and visible in the store banner
    except Exception as exc:  # no toolchain: callers fall back to memory
        if runnable:
            # a stale-but-runnable binary beats no store at all (git
            # checkouts don't preserve mtimes; a toolchain-less deploy
            # host must keep using the prebuilt binary)
            log.warning("mantlestore rebuild failed (%s); using the "
                        "existing binary despite newer source", exc)
            return BINARY
        log.warning("mantlestore build failed: %s", exc)
        return None


def spawn_server(port: int = 7070,
                 snapshot_path: Optional[str] = None,
                 snapshot_interval_s: float = 30.0,
                 repl: bool = False,
                 follower: bool = False,
                 repl_id: Optional[str] = None,
                 lease_ms: Optional[int] = None) -> subprocess.Popen:
    """Spawn mantlestore. With ``snapshot_path`` the server restores that
    snapshot at boot and persists to it periodically and on SIGTERM —
    the Redis-durability resume semantics of the reference (SURVEY §5.4).

    ``repl=True`` enables the replication log + leader lease heartbeat
    (the node boots as leader); ``follower=True`` boots it readonly,
    waiting for a pump to ship it the leader's log (engine/store.py
    ReplicatedStore). ``repl_id`` names the node in the lease;
    ``lease_ms`` sizes the leader lease TTL (failover detection time)."""
    binary = ensure_built()
    assert binary, "mantlestore binary unavailable"
    cmd = [binary, str(port)]
    if snapshot_path:
        cmd += [snapshot_path, str(snapshot_interval_s)]
    if repl or follower:
        cmd.append("--follower" if follower else "--repl")
        # ids must be UNIQUE per node: the PROMOTE lease fence skips the
        # liveness refusal for the lease holder's own id, so two nodes
        # sharing the binary's default id could promote past a live
        # leader (split brain). Default to a per-port id.
        cmd += ["--id", repl_id or f"node-{port}"]
        if lease_ms is not None:
            cmd += ["--lease-ms", str(int(lease_ms))]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )
    # wait for the listening line (restore logs precede it)
    while True:
        line = proc.stderr.readline().decode()
        assert line, "mantlestore exited before listening"
        if "listening" in line:
            return proc


def _b(v: Value) -> bytes:
    return v if isinstance(v, bytes) else str(v).encode()


class MantleStore(StateStore):
    def __init__(self, host: str = "127.0.0.1", port: int = 7070) -> None:
        self.host = host
        self.port = port
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._io_lock = asyncio.Lock()

    async def connect(self) -> "MantleStore":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port
        )
        assert await self._cmd(b"PING") == b"PONG"
        return self

    async def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            with contextlib.suppress(Exception):
                await self._writer.wait_closed()
            self._writer = None
            self._reader = None

    # -- protocol ---------------------------------------------------------
    async def _cmd(self, *args: bytes):
        # the store-boundary fault point (docs/CHAOS.md): latency here is
        # a slow store, partition (peer-scoped host:port) is a network
        # cut this client treats exactly like a refused connection
        await afault_point("store.client.op",
                           peer=f"{self.host}:{self.port}")
        if self._writer is None:
            await self.connect()
        async with self._io_lock:
            payload = b"*%d\r\n" % len(args)
            for a in args:
                payload += b"$%d\r\n%s\r\n" % (len(a), a)
            try:
                self._writer.write(payload)
                await self._writer.drain()
                return await self._read_reply()
            except asyncio.CancelledError:
                # a cancelled round trip (e.g. an aiohttp handler whose
                # client gave up) may leave this command's reply in
                # flight; the connection is shared, so the NEXT command
                # would read the stale reply and every later caller
                # desyncs. Drop the socket — the next op redials clean.
                writer, self._reader, self._writer = \
                    self._writer, None, None
                if writer is not None:
                    writer.close()
                raise

    async def raw_command(self, *args: bytes):
        """One command round trip — the public form of ``_cmd`` for
        composition (the shared lock protocol, ReplicatedStore)."""
        return await self._cmd(*args)

    async def _read_reply(self):
        line = await self._reader.readline()
        if not line:
            raise ConnectionError("mantlestore closed connection")
        kind, rest = line[:1], line[1:].strip()
        if kind == b"+":
            return rest
        if kind == b"-":
            raise RuntimeError(rest.decode())
        if kind == b":":
            return int(rest)
        if kind == b"$":
            n = int(rest)
            if n == -1:
                return None
            data = await self._reader.readexactly(n + 2)
            return data[:-2]
        if kind == b"*":
            return [await self._read_reply() for _ in range(int(rest))]
        raise RuntimeError(f"bad reply kind {kind!r}")

    # -- plain keys -------------------------------------------------------
    async def set(self, key, value):
        await self._cmd(b"SET", key.encode(), _b(value))

    async def get(self, key):
        return await self._cmd(b"GET", key.encode())

    async def setex(self, key, ttl, value):
        await self._cmd(b"SETEX", key.encode(),
                        str(int(ttl * 1000)).encode(), _b(value))

    async def delete(self, *keys):
        if keys:
            await self._cmd(b"DEL", *[k.encode() for k in keys])

    async def exists(self, key):
        return bool(await self._cmd(b"EXISTS", key.encode()))

    async def expire(self, key, ttl):
        await self._cmd(b"PEXPIRE", key.encode(),
                        str(int(ttl * 1000)).encode())

    async def ttl(self, key):
        ms = await self._cmd(b"PTTL", key.encode())
        if ms in (-1, -2):
            return float(ms)
        return ms / 1000.0

    # The server's RESP parser caps commands at 1024 args; multi-member
    # writes are chunked client-side so arbitrarily large collections
    # never wedge the connection (a too-long command would never parse
    # and the reply would never come).
    _CHUNK = 500

    async def _cmd_chunked(self, head, pairs_or_members, stride):
        for i in range(0, len(pairs_or_members), self._CHUNK * stride):
            await self._cmd(*head,
                            *pairs_or_members[i:i + self._CHUNK * stride])

    # -- hashes -----------------------------------------------------------
    async def hset(self, key, field=None, value=None, mapping=None):
        args = []
        if field is not None:
            args += [field.encode(), _b(value)]
        if mapping:
            for k, v in mapping.items():
                args += [k.encode(), _b(v)]
        if args:
            await self._cmd_chunked([b"HSET", key.encode()], args, 2)

    async def hget(self, key, field):
        return await self._cmd(b"HGET", key.encode(), field.encode())

    async def hgetall(self, key) -> Dict[str, bytes]:
        flat = await self._cmd(b"HGETALL", key.encode())
        return {
            flat[i].decode(): flat[i + 1] for i in range(0, len(flat), 2)
        }

    async def hdel(self, key, *fields):
        if fields:
            await self._cmd_chunked([b"HDEL", key.encode()],
                                    [f.encode() for f in fields], 1)

    async def hincrby(self, key, field, amount: int = 1) -> int:
        return await self._cmd(b"HINCRBY", key.encode(), field.encode(),
                               str(amount).encode())

    # -- sets -------------------------------------------------------------
    async def sadd(self, key, *members):
        if members:
            await self._cmd_chunked([b"SADD", key.encode()],
                                    [m.encode() for m in members], 1)

    async def srem(self, key, *members):
        if members:
            await self._cmd_chunked([b"SREM", key.encode()],
                                    [m.encode() for m in members], 1)

    async def smembers(self, key) -> Set[str]:
        return {m.decode() for m in await self._cmd(b"SMEMBERS",
                                                    key.encode())}

    async def sismember(self, key, member) -> bool:
        return bool(await self._cmd(b"SISMEMBER", key.encode(),
                                    member.encode()))

    # -- locks ------------------------------------------------------------
    def lock(self, name: str, timeout: float = 120.0,
             blocking_timeout: float = 2.0):
        # the shared polled protocol (engine/store.py): one definition
        # of the acquire loop and the :2/:0 hazard classification for both
        # the single-node and replicated transports
        return polled_store_lock(self._cmd, name, timeout,
                                 blocking_timeout)

    async def flushall(self) -> None:
        await self._cmd(b"FLUSHALL")

    # -- replication (REPL verbs; see native/mantlestore.cc header) --------
    async def repl_role(self) -> str:
        return (await self._cmd(b"REPL", b"ROLE")).decode()

    async def repl_offset(self) -> tuple:
        """(log_start, log_end, applied). On a healthy node
        applied == log_end; lag of a follower = leader log_end - this."""
        start, end, applied = await self._cmd(b"REPL", b"OFFSET")
        return start, end, applied

    async def repl_tail(self, offset: int, max_commands: int = 256):
        """(next_offset, raw command stream) from ``offset``; None when
        the log was trimmed past it (caller must full-resync via
        repl_dump/repl_reset)."""
        reply = await self._cmd(b"REPL", b"TAIL", str(offset).encode(),
                                str(max_commands).encode())
        if len(reply) == 1:
            return None
        return reply[0], reply[1]

    async def repl_apply(self, expected_offset: int, stream: bytes) -> int:
        """Replay ``stream`` iff this follower's offset == expected;
        returns the follower's applied offset either way (exactly-once
        under racing pumps)."""
        return await self._cmd(b"REPL", b"APPLY",
                               str(expected_offset).encode(), stream)

    async def repl_dump(self) -> tuple:
        """(log_end, full-state command stream incl. live locks)."""
        end, stream = await self._cmd(b"REPL", b"DUMP")
        return end, stream

    async def repl_reset(self, offset: int, stream: bytes) -> int:
        """Full resync: flush, replay ``stream`` unlogged, set offsets."""
        return await self._cmd(b"REPL", b"RESET", str(offset).encode(),
                               stream)

    async def repl_promote(self) -> bool:
        """Ask a follower to take leadership; True when it did (False =
        the replicated leader lease is still live — the leader was
        heartbeating within its TTL)."""
        return await self._cmd(b"REPL", b"PROMOTE") == b"OK"

    async def repl_lease(self) -> tuple:
        """(holder id or '', seconds remaining) of the leader lease as
        this node sees it."""
        holder, ms = await self._cmd(b"REPL", b"LEASE")
        return holder.decode(), ms / 1000.0
