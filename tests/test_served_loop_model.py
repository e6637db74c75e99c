"""The served loop as a deterministic model.

Four closed-loop rooms drive the REAL ``InferenceService.generate_content``
and the REAL prompt ``BatchingQueue`` on an event loop whose clock is
virtual; only the device is a model: one serial FIFO of programs that take
the times recorded on the chip. The image lock is FIFO and its holder
enqueues one sampler program and waits for it; the LM worker blocks on its
dispatch; a room asks for its next text in the same turn of the loop in
which its image returns, as ``benchmarks/harness/runner.py::room`` does.

What it pins: under the shipped rule (the prompt queue holds its batch
while a round of the service is between its text and its image) the loop
settles in ONE orbit, four rows a dispatch, from simultaneous and
staggered starts and under both coalescing windows a committed
configuration sets (25 ms; ``lfm2_game``: 0); and with one dispatch a
batch the work of a window does not depend on the order of titles, which
it does when a batch splits by prompt bucket (as ``decode_ids_batch``
did before it took position offsets).
"""

import asyncio
import concurrent.futures
import statistics
import threading

import pytest

from cassmantle_tpu.config import test_config as tiny_config

#: ms a dispatch at 1, 2 and 4 rows, as read on one v5e: the sparse LMs by
#: ``tools/moe_walk_timing.py --dispatch --bucket 64`` (PERF.md section 6),
#: GPT-2 from ``round_anatomy.py`` (it reads its 248 MB a step at any
#: batch). The narrower prompt bucket's shorter cache and prefill are a few
#: ms less, so that the order of titles can matter at all.
LM_MS = {
    "lfm2_rollover": {1: 187.4, 2: 274.4, 4: 439.3},
    "qwen3next_rollover": {1: 140.6, 2: 240.4, 4: 288.2},
    "sd15_rollover": {1: 28.2, 2: 28.6, 4: 29.4},
}
NARROWER_BUCKET_MS = {"lfm2_rollover": 6.0, "qwen3next_rollover": 5.0,
                   "sd15_rollover": 1.0}
IMAGE_MS = {"lfm2_rollover": 84.8, "qwen3next_rollover": 84.8,
            "sd15_rollover": 808.2}
CELLS = sorted(LM_MS)
IMAGE_HOST_MS = 4.6     # image_host_ms: result ready -> generate returns
TO_THE_LOCK_MS = 2.0    # the executor hop and the CLIP tokenizer
LM_HOST_MS = 1.0        # tokenizing and the dispatch itself
TITLE_BYTES = (34, 23, 34, 33, 25, 26, 33, 28, 37, 28, 28, 28, 36, 29, 24,
               32, 37)  # data/seeds.txt
ROOMS = 4


class VirtualClockLoop(asyncio.SelectorEventLoop):
    """An event loop whose ``time()`` jumps to the next timer instead of
    sleeping for it."""

    def __init__(self) -> None:
        super().__init__()
        self._now, self._idle = 0.0, 0
        select = self._selector.select

        def jump(timeout=None):
            events = select(0)
            if not events and timeout:
                self._now += timeout
            self._idle = 0 if (events or timeout is not None) \
                else self._idle + 1
            assert self._idle < 1000, "the model deadlocked"
            return events

        self._selector.select = jump

    def time(self) -> float:
        return self._now


class Device:
    """The chip: programs run one at a time, in the order enqueued."""

    def __init__(self) -> None:
        self.free_at = 0.0
        self.busy_s = 0.0

    async def run(self, seconds: float) -> None:
        loop = asyncio.get_running_loop()
        self.free_at = max(loop.time(), self.free_at) + seconds
        self.busy_s += seconds
        await asyncio.sleep(self.free_at - loop.time())


def bucket_of(title: str) -> int:
    return 32 if int(title.split(":")[1]) <= 32 else 64


class LMWorker:
    """In the dispatch thread's place (``queue._DispatchWorker.submit``):
    one LM program a batch, or one a prompt bucket of the batch."""

    def __init__(self, cell: str, device: Device, one_dispatch: bool):
        self.cell, self.device, self.one = cell, device, one_dispatch
        self.batches: list = []     # (t, rows, programs)
        self._tasks: set = set()

    def programs(self, titles) -> list:
        buckets = [bucket_of(t) for t in titles]
        groups = [(len(titles), max(buckets))] if self.one else [
            (buckets.count(b), b) for b in sorted(set(buckets))]
        return [LM_MS[self.cell][next(p for p in (1, 2, 4) if n <= p)]
                - (NARROWER_BUCKET_MS[self.cell] if b == 32 else 0.0)
                for n, b in groups]

    def submit(self, fn, *args):
        titles = args[-1]
        loop = asyncio.get_running_loop()
        done: concurrent.futures.Future = concurrent.futures.Future()
        started = threading.Event()
        started.set()
        programs = self.programs(titles)
        self.batches.append((loop.time(), len(titles), len(programs)))

        async def dispatch():
            for ms in programs:
                await asyncio.sleep(LM_HOST_MS / 1e3)
                await self.device.run(ms / 1e3)
            done.set_result([f"the story of {t}. it went on." for t in titles])

        task = loop.create_task(dispatch())
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return done, started

    def stop(self) -> None:
        for task in self._tasks:
            task.cancel()


class ImageBackend:
    """``TPUContentBackend.generate`` with the text given: to the lock,
    one sampler program under it, the host's tail."""

    prompt_gen = object()   # the service asks whether there is one

    def __init__(self, cell: str, device: Device) -> None:
        self.cell, self.device = cell, device
        self.lock = asyncio.Lock()      # FIFO

    async def generate(self, seed, is_seed, text=None):
        assert text is not None, "the prompt queue failed"
        await asyncio.sleep(TO_THE_LOCK_MS / 1e3)
        async with self.lock:
            await self.device.run(IMAGE_MS[self.cell] / 1e3)
        await asyncio.sleep(IMAGE_HOST_MS / 1e3)
        return text


def serve(cell: str, *, rounds: int, title_offset: int = 0,
          window_ms: float = 25.0, stagger_ms=(0, 0, 0, 0),
          one_dispatch: bool = True) -> dict:
    """Run the loop until ``rounds`` rounds are done; the times of the
    rounds' completions, the LM dispatches and the device's busy share."""
    from cassmantle_tpu.serving.service import InferenceService

    async def main():
        loop = asyncio.get_running_loop()
        device = Device()
        service = InferenceService(tiny_config(),
                                   backend=ImageBackend(cell, device))
        worker = LMWorker(cell, device, one_dispatch)
        service.prompt_queue._dispatcher = worker
        service.prompt_queue.max_delay_s = window_ms / 1e3
        generate = service.content_backend.generate
        completions: list = []

        async def room(r: int):
            await asyncio.sleep(stagger_ms[r] / 1e3)
            k = 0
            while True:
                # benchmarks/harness/traffic.py::story_title
                i = (title_offset + r + ROOMS * k) % len(TITLE_BYTES)
                await generate(f"title {i}:{TITLE_BYTES[i]}", True)
                completions.append(loop.time())
                k += 1

        rooms = [asyncio.ensure_future(room(r)) for r in range(ROOMS)]
        while len(completions) < rounds:
            await asyncio.sleep(0.05)
        for task in rooms:
            task.cancel()
        await asyncio.gather(*rooms, return_exceptions=True)
        await service.stop()
        return {"completions": completions[:rounds],
                "batches": worker.batches,
                "busy": device.busy_s / max(device.free_at, 1e-9)}

    loop = VirtualClockLoop()
    try:
        return loop.run_until_complete(main())
    finally:
        loop.close()


def settled(batches: list, after: int = 3) -> list:
    return [rows for _t, rows, _p in batches[after:]]


@pytest.mark.parametrize("window_ms", [0.0, 25.0], ids=["window0", "window25"])
@pytest.mark.parametrize("stagger_ms", [(0, 0, 0, 0), (0, 37, 120, 300),
                                        (0, 3, 90, 91)],
                         ids=["together", "staggered", "in_pairs"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_loop_settles_in_one_orbit_of_four_rows_a_dispatch(
        cell, stagger_ms, window_ms):
    """Whatever the start and the coalescing window: after at most three
    dispatches every LM dispatch carries all four rooms, the rounds of an
    orbit complete an image apart, and an orbit is one LM program and
    four images long."""
    out = serve(cell, rounds=40, stagger_ms=stagger_ms, window_ms=window_ms)
    rows = settled(out["batches"])
    assert len(rows) >= 6 and set(rows) == {4}, out["batches"]
    done = out["completions"][-8:]
    orbit_s = done[4] - done[0]
    assert orbit_s == pytest.approx(done[5] - done[1], rel=0.03)
    want_ms = LM_MS[cell][4] + ROOMS * IMAGE_MS[cell]
    # the host's part of an orbit: the last image's tail, the LM's own
    # dispatch, the way to the lock
    assert want_ms - NARROWER_BUCKET_MS[cell] < orbit_s * 1e3 \
        < want_ms + 12.0
    assert out["busy"] > 0.97


@pytest.mark.parametrize("cell", CELLS)
def test_with_one_dispatch_a_batch_the_order_of_titles_changes_nothing(cell):
    """Every seed sends the same titles in another order
    (``traffic.story_title``). With one dispatch a batch a window of 48
    rounds is 12 dispatches whatever the order, and takes the same time to
    a part in a thousand; where a batch splits by prompt bucket the order of titles decides how many programs a
    window runs and the rate follows it."""
    def windows(one_dispatch: bool):
        runs = [serve(cell, rounds=56, title_offset=offset,
                      window_ms=0.0, one_dispatch=one_dispatch)
                for offset in range(len(TITLE_BYTES))]
        programs = [sum(p for _t, _r, p in run["batches"][2:14])
                    for run in runs]
        seconds = [run["completions"][55] - run["completions"][7]
                   for run in runs]
        return programs, (max(seconds) - min(seconds)) \
            / statistics.median(seconds)

    programs, spread = windows(one_dispatch=True)
    assert set(programs) == {12} and spread < 0.002, (programs, spread)
    split_programs, split_spread = windows(one_dispatch=False)
    assert min(split_programs) > 12 and split_spread > spread


def test_a_lone_room_is_never_held():
    """One room in the loop: its prompt finds the device free of images
    every time, and is dispatched as before (a round is one LM program,
    one image and the host's part, nothing waits for anything)."""
    out = serve("qwen3next_rollover", rounds=6, stagger_ms=(0, 1e9, 1e9, 1e9))
    assert set(settled(out["batches"], after=0)) == {1}
    gaps = [b - a for a, b in zip(out["completions"], out["completions"][1:])]
    want_ms = LM_MS["qwen3next_rollover"][1] + IMAGE_MS["qwen3next_rollover"]
    # the host's part of a round: the coalescing window (25 ms), the way
    # to the lock, the image's tail, the LM's own dispatch
    host_ms = 25.0 + TO_THE_LOCK_MS + IMAGE_HOST_MS + LM_HOST_MS
    assert all(want_ms - NARROWER_BUCKET_MS["qwen3next_rollover"]
               < g * 1e3 < want_ms + host_ms + 1.0 for g in gaps)
