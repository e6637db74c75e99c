import asyncio
import threading
import time

import numpy as np
import pytest

from cassmantle_tpu.serving.queue import (
    BatchingQueue,
    DeadlineExceeded,
    DispatchTimeout,
    QueueFull,
    QueueStopped,
)


@pytest.mark.asyncio
async def test_coalesces_concurrent_submissions():
    batches = []

    def handler(items):
        batches.append(len(items))
        return [x * 2 for x in items]

    q = BatchingQueue(handler, max_batch=64, max_delay_ms=30)
    results = await asyncio.gather(*(q.submit(i) for i in range(10)))
    assert results == [i * 2 for i in range(10)]
    assert sum(batches) == 10
    assert len(batches) <= 3  # coalesced, not 10 singleton batches
    await q.stop()


@pytest.mark.asyncio
async def test_respects_max_batch():
    batches = []

    def handler(items):
        batches.append(len(items))
        return items

    q = BatchingQueue(handler, max_batch=4, max_delay_ms=50)
    await asyncio.gather(*(q.submit(i) for i in range(10)))
    assert max(batches) <= 4
    await q.stop()


@pytest.mark.asyncio
async def test_a_queue_that_does_not_wait_hands_over_one_item_a_dispatch():
    """With no hold signal ``max_delay_ms`` 0 closes the coalescing window
    before it opens: ten requests pending at once are ten dispatches of
    one, in order (``lfm2_game``'s file sets it for the prompt queue,
    whose batches since PR 35 form under the hold below instead)."""
    batches = []

    def handler(items):
        batches.append(list(items))
        return items

    q = BatchingQueue(handler, max_batch=4, max_delay_ms=0)
    results = await asyncio.gather(*(q.submit(i) for i in range(10)))
    assert results == list(range(10))
    assert batches == [[i] for i in range(10)]
    await q.stop()


# -- late binding: the batch stays open while the device has other work ----


class Device:
    """What a queue's ``hold_while`` reads in these tests, and who tells
    the queue when it changes."""

    def __init__(self, busy: bool) -> None:
        self.busy = busy
        self.queue = None

    def __call__(self) -> bool:
        return self.busy

    def set(self, busy: bool) -> None:
        self.busy = busy
        self.queue.recheck_hold()


def held_queue(busy: bool, batches: list, **kwargs):
    device = Device(busy)

    def handler(items):
        batches.append(list(items))
        return items

    kwargs.setdefault("max_delay_ms", 0)
    device.queue = BatchingQueue(handler, hold_while=device, **kwargs)
    return device, device.queue


def series(name: str) -> dict:
    """``reason -> value`` of one counter, or ``(sum, count)`` of one
    histogram, as the registry holds them now."""
    from cassmantle_tpu.utils.logging import metrics

    state = metrics.dump_state()
    found = {dict(labels).get("reason"): value
             for n, labels, value in state["counters"] if n == name}
    for n, _labels, _bounds, _counts, total, count in state["hists"]:
        if n == name:
            found = (total, count)
    return found


@pytest.mark.asyncio
@pytest.mark.parametrize("delay_ms", [0, 20], ids=["no_window", "window"])
async def test_a_busy_device_holds_the_batch_open_for_later_arrivals(
        delay_ms):
    """Items that arrive one by one while the device has other work ride
    ONE dispatch, bound when the device is free: also under a coalescing
    window of 0, which without the signal forms no batch at all."""
    batches = []
    device, q = held_queue(True, batches, max_batch=8, name="hold_a",
                           max_delay_ms=delay_ms)
    futs = []
    for i in range(3):
        futs.append(asyncio.ensure_future(q.submit(i)))
        await asyncio.sleep(0.03)       # past the window each time
    assert batches == [] and not any(f.done() for f in futs)
    device.set(False)
    assert await asyncio.gather(*futs) == [0, 1, 2]
    assert batches == [[0, 1, 2]]
    await q.stop()


@pytest.mark.asyncio
async def test_the_release_is_an_event_and_takes_what_came_with_it():
    """The news that the device is free reaches the collector within a
    turn of the loop, not at a poll; an item submitted in the same turn
    as the news (the room whose image just returned asks for its next
    text at once) still rides the batch."""
    batches = []
    device, q = held_queue(True, batches, max_batch=8, name="hold_b")
    first = asyncio.ensure_future(q.submit("held"))
    await asyncio.sleep(0.02)
    loop = asyncio.get_running_loop()

    async def room():
        device.set(False)               # its image is back, and in the
        return await q.submit("with the news")  # same turn it asks again

    t0 = loop.time()
    assert await asyncio.gather(first, room()) == ["held", "with the news"]
    assert loop.time() - t0 < 0.05      # a thread hop and back, no timer
    assert batches == [["held", "with the news"]]
    await q.stop()


@pytest.mark.asyncio
async def test_a_full_batch_is_dispatched_though_the_device_is_busy():
    batches = []
    device, q = held_queue(True, batches, max_batch=2, name="hold_c")
    before = series("hold_c.release")
    assert await asyncio.gather(q.submit(1), q.submit(2)) == [1, 2]
    assert batches == [[1, 2]] and device.busy
    after = series("hold_c.release")
    assert after["full"] - before.get("full", 0) == 1
    await q.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("signal", ["device_free", "none"])
async def test_nothing_is_held_with_the_device_free_or_with_no_signal(
        signal):
    """An idle device means no hold: a lone item is dispatched as fast as
    by a queue without the argument (the score queue), and a window of 0
    still hands over one item a dispatch."""
    batches = []
    if signal == "none":
        q = BatchingQueue(lambda items: batches.append(list(items)) or items,
                          max_batch=4, max_delay_ms=0, name="hold_d")
        assert q._hold_while is None
    else:
        _, q = held_queue(False, batches, max_batch=4, name="hold_d")
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    assert await q.submit("lone") == "lone"
    assert loop.time() - t0 < 0.05
    assert await asyncio.gather(*(q.submit(i) for i in range(3))) \
        == [0, 1, 2]
    assert batches == [["lone"], [0], [1], [2]]
    await q.stop()


@pytest.mark.asyncio
async def test_a_held_item_expires_at_its_deadline():
    """The hold is no exemption: a held item fails with DeadlineExceeded
    on time, and the batch that goes when the device is free carries only
    the items somebody still waits for."""
    batches = []
    device, q = held_queue(True, batches, max_batch=8, name="hold_e")
    impatient = asyncio.ensure_future(q.submit("late", deadline_s=0.05))
    patient = asyncio.ensure_future(q.submit("kept"))
    with pytest.raises(DeadlineExceeded):
        await impatient
    assert batches == [] and not patient.done()
    device.set(False)
    assert await patient == "kept"
    assert batches == [["kept"]]
    await q.stop()


@pytest.mark.asyncio
async def test_stop_fails_held_futures():
    device, q = held_queue(True, [], max_batch=8, name="hold_f")
    futs = [asyncio.ensure_future(q.submit(i)) for i in range(2)]
    await asyncio.sleep(0.02)           # both popped, both held
    assert q.depth() == 0
    await q.stop()
    for fut in futs:
        with pytest.raises(QueueStopped):
            await fut


@pytest.mark.asyncio
async def test_the_hold_is_counted_and_the_limiter_is_not_told_of_it():
    """``<name>.hold_s`` (first item bound -> dispatch) and
    ``<name>.release{reason}`` say how often the mechanism engaged; the
    adaptive limiter's wait signal leaves the held time out (a wait the
    queue chose is not load: a 1.2 s hold behind four images would walk
    ``<name>.admit_limit`` to its floor), while ``<name>.queue_wait_s``
    keeps the whole wait a member felt."""
    seen = []

    class Limiter:
        def admit(self, depth, priority, deadline_s):
            return None

        def observe_batch(self, wait_s, service_s, n):
            seen.append((wait_s, n))

    batches = []
    device, q = held_queue(True, batches, max_batch=8, name="hold_g",
                           admission=Limiter())
    before = (series("hold_g.release"), series("hold_g.hold_s") or (0.0, 0),
              series("hold_g.queue_wait_s") or (0.0, 0))
    fut = asyncio.ensure_future(q.submit("x"))
    await asyncio.sleep(0.1)
    late = asyncio.ensure_future(q.submit("joined the hold"))
    await asyncio.sleep(0.05)
    device.set(False)
    await asyncio.gather(fut, late)
    await q.submit("y")                 # the device free: no hold
    await q.stop()
    release, (total, count) = series("hold_g.release"), series("hold_g.hold_s")
    assert release["device_free"] - before[0].get("device_free", 0) == 1
    assert release["timer"] - before[0].get("timer", 0) == 1
    assert count - before[1][1] == 2 and total - before[1][0] >= 0.15
    waited, members = series("hold_g.queue_wait_s")
    assert members - before[2][1] == 3 and waited - before[2][0] >= 0.2
    assert seen[0][0] < 0.05 and seen[0][1] == 2, seen
    assert seen[1][0] < 0.05, seen


@pytest.mark.asyncio
async def test_handler_exception_propagates():
    def handler(items):
        raise ValueError("boom")

    q = BatchingQueue(handler, max_batch=4, max_delay_ms=5)
    with pytest.raises(ValueError):
        await q.submit(1)
    # queue stays alive for subsequent batches
    q.handler = lambda items: items
    assert await q.submit(7) == 7
    await q.stop()


@pytest.mark.asyncio
async def test_backpressure_queue_full():
    started = asyncio.Event()

    def slow_handler(items):
        return items

    q = BatchingQueue(slow_handler, max_batch=1, max_delay_ms=1,
                      max_pending=2)
    # saturate without draining: stop collector first
    q.start()
    await q.stop()
    q._task = object()  # prevent restart by submit
    q._queue.put_nowait((0, asyncio.get_event_loop().create_future()))
    q._queue.put_nowait((1, asyncio.get_event_loop().create_future()))
    with pytest.raises(QueueFull):
        await q.submit(2)


@pytest.mark.asyncio
async def test_latency_bounded_by_delay_window():
    def handler(items):
        return items

    q = BatchingQueue(handler, max_batch=1024, max_delay_ms=20)
    loop = asyncio.get_event_loop()
    t0 = loop.time()
    await q.submit("x")
    elapsed = loop.time() - t0
    assert elapsed < 1.0  # window + dispatch, far under a second
    await q.stop()


@pytest.mark.asyncio
async def test_stop_fails_pending_futures():
    """Shutdown with queued items must fail their futures, not leave the
    awaiting callers hanging forever (ISSUE 2 satellite)."""
    q = BatchingQueue(lambda items: items, max_batch=1, max_delay_ms=1,
                      max_pending=8, name="stoptest")
    # park items in the queue with no collector running
    loop = asyncio.get_running_loop()
    futs = [loop.create_future() for _ in range(3)]
    for i, fut in enumerate(futs):
        q._queue.put_nowait((i, fut))
    await q.stop()
    for fut in futs:
        assert fut.done()
        with pytest.raises(QueueStopped):
            fut.result()
    # QueueStopped degrades like backpressure at existing call sites
    assert issubclass(QueueStopped, QueueFull)


@pytest.mark.asyncio
async def test_stop_mid_collect_window_fails_popped_items():
    """stop() must also fail items the collector already popped off the
    queue (waiting out the coalescing window) — they are invisible to
    the queue drain and would otherwise dangle forever."""
    q = BatchingQueue(lambda items: items, max_batch=64,
                      max_delay_ms=10_000, name="midstop")
    fut = asyncio.ensure_future(q.submit("x"))
    await asyncio.sleep(0.05)       # collector popped "x", awaits window
    await q.stop()
    with pytest.raises(QueueStopped):
        await fut


@pytest.mark.asyncio
async def test_watchdog_ignores_queue_wait_behind_other_dispatch():
    """Time queued on the shared dispatch thread behind ANOTHER queue's
    legitimate slow handler must not count toward this queue's hang
    deadline — only a handler actually running can be declared wedged."""
    slow_started = threading.Event()

    def slow_but_legit(items):
        slow_started.set()
        time.sleep(0.6)
        return items

    qa = BatchingQueue(slow_but_legit, max_delay_ms=1, name="slowq")
    qb = BatchingQueue(lambda items: items, max_delay_ms=1,
                      hang_timeout_s=0.2, name="fastq")
    ta = asyncio.ensure_future(qa.submit("a"))
    await asyncio.to_thread(slow_started.wait, 2.0)   # slowq occupies it
    # qb's batch waits ~0.6s queued (> its 0.2s hang deadline) and must
    # still succeed rather than raise DispatchTimeout
    assert await qb.submit("b") == "b"
    assert await ta == "a"
    await qa.stop()
    await qb.stop()


@pytest.mark.asyncio
async def test_watchdog_hang_clock_arms_at_handler_start_not_submit():
    """A handler that STARTS late (behind another queue's slow-but-legit
    dispatch) gets its full hang budget from the moment it runs: the
    hang clock must arm at handler start, not at submit. Before the fix,
    the first watchdog window expiring after the late start declared the
    healthy handler wedged — failing the batch with DispatchTimeout and
    disowning a healthy in-flight dispatch — even though it had run for
    only a fraction of its budget."""
    slow_started = threading.Event()

    def slow_but_legit(items):
        slow_started.set()
        time.sleep(0.75)
        return items

    def healthy_but_late(items):
        # runs 0.45s — inside the 0.5s hang budget from ITS start, but
        # spanning the submit-relative window boundary at t=1.0
        time.sleep(0.45)
        return items

    qa = BatchingQueue(slow_but_legit, max_delay_ms=1, name="slowq2")
    qb = BatchingQueue(healthy_but_late, max_delay_ms=1,
                       hang_timeout_s=0.5, name="lateq")
    ta = asyncio.ensure_future(qa.submit("a"))
    await asyncio.to_thread(slow_started.wait, 2.0)
    assert await qb.submit("b") == "b"
    assert await ta == "a"
    await qa.stop()
    await qb.stop()


@pytest.mark.asyncio
async def test_submit_deadline_fails_future_under_hung_handler():
    """A wedged handler (hung XLA call) must not hang submitters: the
    per-request deadline fails the future on time (acceptance criterion:
    'fails pending submit futures at their deadline instead of hanging
    the test')."""
    release = threading.Event()

    def hung_handler(items):
        release.wait(timeout=10.0)
        return items

    q = BatchingQueue(hung_handler, max_batch=4, max_delay_ms=1,
                      hang_timeout_s=2.0, name="hungtest")
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        await q.submit("x", deadline_s=0.2)
    assert time.monotonic() - t0 < 1.5
    release.set()          # unwedge the dispatch thread for later tests
    await q.stop()


@pytest.mark.asyncio
async def test_watchdog_replaces_wedged_dispatch_thread():
    """The hang watchdog fails the wedged batch with DispatchTimeout,
    flips the supervisor degraded, and later batches dispatch on a FRESH
    thread — the wedge doesn't serialize the rest of serving behind it."""
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    release = threading.Event()
    calls = []

    def handler(items):
        calls.append(list(items))
        if items == ["wedge"]:
            release.wait(timeout=10.0)
        return items

    sup = ServingSupervisor(degraded_cooldown_s=30.0)
    q = BatchingQueue(handler, max_batch=1, max_delay_ms=1,
                      hang_timeout_s=0.3, supervisor=sup, name="wdtest")
    assert not sup.watchdog_degraded
    with pytest.raises(DispatchTimeout):
        await q.submit("wedge")
    assert sup.watchdog_degraded
    # the replacement thread serves the next batch while the old one is
    # still wedged
    assert await q.submit("after") == "after"
    release.set()
    await q.stop()


@pytest.mark.asyncio
async def test_degraded_supervisor_tightens_admission():
    """While degraded, the queue admits only degraded_max_pending items
    (shed early: deep backlogs behind a sick device are doomed work)."""
    from cassmantle_tpu.serving.supervisor import ServingSupervisor

    sup = ServingSupervisor(degraded_cooldown_s=60.0)
    q = BatchingQueue(lambda items: items, max_pending=64,
                      degraded_max_pending=2, supervisor=sup,
                      name="degradetest")
    q.start()
    await q.stop()
    q._task = object()      # park the collector so items pile up
    loop = asyncio.get_running_loop()
    q._queue.put_nowait((0, loop.create_future()))
    q._queue.put_nowait((1, loop.create_future()))
    # healthy: plenty of room under max_pending
    fut = asyncio.ensure_future(q.submit(2))
    await asyncio.sleep(0)
    assert not fut.done()
    sup.note_dispatch_overrun("degradetest")
    with pytest.raises(QueueFull):
        await q.submit(3)
    fut.cancel()
    q._task = None
    await q.stop()


@pytest.mark.slow
def test_concurrent_rounds_coalesce_prompt_decodes():
    """InferenceService.generate_content routes the LM decode through
    the prompt queue: 3 rounds generating concurrently become ONE
    batched generate_batch call (VERDICT r4 #4 — prompts no longer
    decode one per call), and each round's text matches what a single
    decode of its seed would have produced.

    slow (round 21): this test and the soak smoke below each build a
    full real-pipeline InferenceService (~50 s of compiles apiece on a
    1-core host) and had grown the default tier past its 870 s window —
    the same overflow the round-14 module demotions fixed. The queue's
    coalescing/backpressure/deadline semantics stay tier-1 via the
    mock-handler units above, and the service-integration path stays
    tier-1 via test_server's full-stack round; the full tier keeps the
    prompt-decode coalescing bar itself."""
    import asyncio

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.service import InferenceService

    svc = InferenceService(test_config())
    seen_batches = []
    orig = svc.backend.prompt_gen.generate_batch

    def spying(texts, max_new_tokens=None):
        seen_batches.append(list(texts))
        return orig(texts, max_new_tokens)

    svc.backend.prompt_gen.generate_batch = spying

    async def run():
        svc.prompt_queue.start()
        seeds = ["the storm rolled", "a quiet harbor", "the last train"]
        out = await asyncio.gather(
            *(svc.generate_content(s, False) for s in seeds))
        await svc.stop()
        return out

    contents = asyncio.run(run())
    svc.backend.prompt_gen.generate_batch = orig
    # one coalesced decode batch carried all three seeds (the queue may
    # split under scheduling jitter, but must not degrade to singletons)
    decode_batches = [b for b in seen_batches if len(b) > 1]
    assert decode_batches, f"no coalescing happened: {seen_batches}"
    assert sum(len(b) for b in seen_batches) == 3
    for content in contents:
        assert content.prompt_text and content.image is not None


@pytest.mark.slow
def test_soak_run_smoke():
    """The sustained-serving soak harness (bench.py:soak_run) drives N
    rounds of content generation under continuous guess pressure and
    returns latency samples — smoke-tested here at tiny config on CPU;
    the suite's `soak` entry reports p50/p99 from the same code path.

    slow (round 21): see test_concurrent_rounds_coalesce_prompt_decodes
    — the real-pipeline InferenceService build dominates; the harness
    code path itself is exercised by the bench suite's `soak` entry."""
    import asyncio

    from bench import soak_run
    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.service import InferenceService

    svc = InferenceService(test_config())
    elapsed, lats, errors = asyncio.run(soak_run(svc, rounds=2, workers=4))
    assert elapsed > 0
    assert len(lats) >= 4   # pressure loops actually scored guesses
    assert errors == 0
