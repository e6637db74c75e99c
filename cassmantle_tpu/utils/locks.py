"""OrderedLock: the runtime half of the lock-discipline defense.

The static pass (``cassmantle_tpu/analysis/lockorder.py``) proves what
it can see — same-module, ``with``-statement nesting. This wrapper
covers the rest at runtime: every acquisition is checked against the
documented lock hierarchy (``docs/STATIC_ANALYSIS.md``) and against the
acquisition orders actually observed so far, so an inversion that only
materializes across modules, threads, or dynamic call paths raises (or
logs) *at the acquisition that would deadlock*, with both stacks —
instead of wedging a serving fleet the way the PR 1 dispatch deadlock
did.

Checks, in order, when the sentinel is enabled:

1. **re-acquire** — acquiring a non-reentrant lock this thread already
   holds (guaranteed self-deadlock);
2. **rank** — each OrderedLock carries an optional ``rank``; a thread
   may only acquire a lock with rank *strictly greater* than any ranked
   lock it holds (the hierarchy table is the single source of ranks);
3. **observed inversion** — for rank-less locks: acquiring B while
   holding A after B→A has been observed anywhere records a cycle.

The sentinel is **off by default in production** (acquisitions then cost
one extra list append); ``CASSMANTLE_LOCK_SENTINEL=1`` arms it
log-only, and the test suite arms it in raising mode via an autouse
conftest fixture — the fast tier doubles as a deadlock sentinel.
Violations always count ``locks.order_violations`` and land in the
flight recorder.
"""

from __future__ import annotations

import contextlib
import os
import threading
import traceback
from typing import Dict, Iterator, List, Optional, Tuple

from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("locks")


class LockOrderViolation(RuntimeError):
    """An acquisition that breaks the lock hierarchy (would deadlock)."""


_tls = threading.local()

# (first_name, then_name) -> where that order was first observed
_graph_lock = threading.Lock()
_edges: Dict[Tuple[str, str], str] = {}

_enabled = os.environ.get("CASSMANTLE_LOCK_SENTINEL", "") not in ("", "0")
_raise_on_violation = False


def enable_sentinel(raise_on_violation: bool = True) -> None:
    global _enabled, _raise_on_violation
    _enabled = True
    _raise_on_violation = raise_on_violation


def disable_sentinel() -> None:
    global _enabled, _raise_on_violation
    _enabled = False
    _raise_on_violation = False


def sentinel_active() -> bool:
    return _enabled


def reset_observations() -> None:
    """Drop the observed-order graph (tests: one graph per test, so
    unrelated tests' acquisition orders can't cross-contaminate)."""
    with _graph_lock:
        _edges.clear()


def _held() -> List["OrderedLock"]:
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    return held


def _site() -> str:
    # the innermost non-locks.py frame — where the caller acquired
    for frame in reversed(traceback.extract_stack(limit=8)):
        if not frame.filename.endswith("locks.py"):
            return f"{frame.filename}:{frame.lineno} ({frame.name})"
    return "<unknown>"


class Turns:
    """The order in which threads pass an ``OrderedLock(in_turn=True)``,
    fixed where the order is known: tickets are taken on ONE thread (an
    event loop, in the order the work was handed over), each worker
    thread names its own with :meth:`holding`, and the lock lets them
    through strictly by ticket, whichever thread reached it first
    (threads started in one turn of a loop race for the interpreter, and
    a plain lock wakes its waiters in no promised order). A ticket that
    never reaches the lock is passed over when its ``holding`` ends, so
    a failed or lock-free path holds nobody up."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._issued = 0
        self._serving = 0
        self._left: set = set()

    def take(self) -> int:
        with self._cond:
            self._issued += 1
            return self._issued - 1

    @contextlib.contextmanager
    def holding(self, ticket: int) -> Iterator[None]:
        """This thread's acquisitions of in-turn locks wait for
        ``ticket``'s turn; the turn moves on at the lock's release, or
        here if the lock was never taken."""
        _tls.turn = (self, ticket)
        try:
            yield
        finally:
            _tls.turn = None
            self.leave(ticket)

    def _wait(self, ticket: int) -> None:
        with self._cond:
            self._cond.wait_for(lambda: self._serving >= ticket)

    def leave(self, ticket: int) -> None:
        """``ticket`` has passed, or never will (safe to say twice)."""
        with self._cond:
            if ticket >= self._serving:
                self._left.add(ticket)
            while self._serving in self._left:
                self._left.remove(self._serving)
                self._serving += 1
            self._cond.notify_all()


class OrderedLock:
    """Drop-in ``threading.Lock`` with hierarchy/order instrumentation.

    ``name`` identifies the lock in violations and the observed-order
    graph (instances sharing a name share an ordering identity);
    ``rank`` places it in the documented hierarchy — None means "order
    learned from observation only". ``wait_span`` names the wait for
    this lock: every acquisition is then timed from the request until
    the lock is held, to histogram ``<wait_span>_s``, a span in the
    ambient trace and a profiler annotation (utils/profiling.py
    ``host_span``; 0 when the lock is free). Only a lock whose wait is
    a term of a request's latency sets it — the image pipelines' and the
    prompt LM's dispatch locks — so no other acquisition pays the clock
    reads. A wait span's name ends ``_wait``: a reading of a trace by
    span name (docs/OBSERVABILITY.md) tells waits from work so.
    ``in_turn``: a thread that holds a ticket (:class:`Turns`) passes in
    its turn, the wait for it being part of the wait for the lock; a
    thread without one passes as at any lock.
    """

    __slots__ = ("name", "rank", "wait_span", "in_turn", "_inner")

    def __init__(self, name: str, rank: Optional[int] = None,
                 wait_span: Optional[str] = None,
                 in_turn: bool = False) -> None:
        self.name = name
        self.rank = rank
        self.wait_span = wait_span
        self.in_turn = in_turn
        self._inner = threading.Lock()

    def __repr__(self) -> str:
        return f"OrderedLock({self.name!r}, rank={self.rank})"

    # -- sentinel ---------------------------------------------------------

    def _violation(self, message: str) -> None:
        metrics.inc("locks.order_violations")
        # lazy import: utils never depends on obs at module scope (the
        # circuit-breaker rule)
        from cassmantle_tpu.obs.recorder import flight_recorder

        flight_recorder.record("locks.violation", lock=self.name,
                               message=message)
        if _raise_on_violation:
            raise LockOrderViolation(message)
        log.error("lock-order violation: %s", message)

    def _check(self, held: List["OrderedLock"]) -> None:
        if not held:
            return  # the common case: no stack extraction on the fast path
        site = _site()
        for h in held:
            if h is self:
                self._violation(
                    f"re-acquire of non-reentrant {self.name!r} already "
                    f"held by this thread at {site} (self-deadlock)")
                return
        for h in held:
            if self.rank is not None and h.rank is not None \
                    and h.rank >= self.rank:
                self._violation(
                    f"acquiring {self.name!r} (rank {self.rank}) while "
                    f"holding {h.name!r} (rank {h.rank}) at {site}: the "
                    f"hierarchy (docs/STATIC_ANALYSIS.md) requires "
                    f"strictly increasing ranks")
                return
        with _graph_lock:
            for h in held:
                if h.name == self.name:
                    continue
                reverse = _edges.get((self.name, h.name))
                if reverse is not None:
                    self._violation(
                        f"acquisition-order inversion: {h.name!r} -> "
                        f"{self.name!r} at {site}, but {self.name!r} -> "
                        f"{h.name!r} was acquired at {reverse} — these "
                        f"two paths deadlock under concurrency")
                    return
                _edges.setdefault((h.name, self.name), site)

    # -- threading.Lock surface -------------------------------------------

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if _enabled:
            # check BEFORE blocking on the inner lock: the violation
            # must raise instead of deadlocking the test that seeds it
            self._check(_held())
        if self.wait_span is None:
            acquired = self._acquire(blocking, timeout)
        else:
            # lazy import: utils.profiling pulls in jax
            from cassmantle_tpu.utils.profiling import host_span

            with host_span(self.wait_span):
                acquired = self._acquire(blocking, timeout)
        if acquired:
            _held().append(self)
        return acquired

    def _acquire(self, blocking: bool, timeout: float) -> bool:
        turn = getattr(_tls, "turn", None) if self.in_turn else None
        if turn is not None and blocking and timeout < 0:
            turn[0]._wait(turn[1])
        return self._inner.acquire(blocking, timeout)

    def release(self) -> None:
        self._inner.release()
        turn = getattr(_tls, "turn", None) if self.in_turn else None
        if turn is not None:
            turn[0].leave(turn[1])
        held = _held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is self:
                del held[i]
                break

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> "OrderedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False
