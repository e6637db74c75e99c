"""Rooms' images reach the device back to back (serving/pipeline.py
``ImageHandOver``): a dispatch passes the image dispatch lock, and the
room's turn with it, once its own program is enqueued and the program
ahead of it has finished, so the next room's program is in the device's
queue before the one ahead ends.

The hand-over is driven through both image pipelines' ``generate`` with a
fake sampler whose programs finish when the test says; one real run of
four concurrent rounds through ``InferenceService`` checks that the
images are those of the same dispatches made one at a time.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from cassmantle_tpu import chaos
from cassmantle_tpu.config import (
    test_config as tiny_config,
    test_sdxl_config as tiny_sdxl_config,
)
from cassmantle_tpu.utils.locks import Turns
from cassmantle_tpu.utils.logging import metrics

WAIT_S = 10.0


class Program:
    """A fake sampler program: its result is ready once ``finish`` is
    called, and raises at the sync if it ``failed``."""

    def __init__(self, room: int, rows: int, size: int) -> None:
        self.room = room
        self.failed = False
        self._done = threading.Event()
        rng = np.random.default_rng(room)
        self.images = rng.integers(0, 256, (rows, size, size, 3),
                                   dtype=np.uint8)

    def finish(self, failed: bool = False) -> None:
        self.failed = failed
        self._done.set()

    def is_ready(self) -> bool:
        return self._done.is_set()

    def block_until_ready(self):
        assert self._done.wait(WAIT_S), f"room {self.room} never finished"
        if self.failed:
            raise RuntimeError(f"room {self.room}'s program failed")
        return self

    def __getitem__(self, index):
        return self.images[index]


class FakeDevice:
    """Stands in for a pipeline's jitted sampler: each call is one
    program, kept in enqueue order; the room is the dispatch's seed."""

    def __init__(self, size: int) -> None:
        self.size = size
        self.programs = []
        self._grew = threading.Condition()

    def sample(self, params, ids, uncond_ids, rng):
        program = Program(int(np.asarray(rng)[-1]), ids.shape[0], self.size)
        with self._grew:
            self.programs.append(program)
            self._grew.notify_all()
        return program

    def enqueued(self, count: int, timeout: float = WAIT_S) -> bool:
        with self._grew:
            return self._grew.wait_for(
                lambda: len(self.programs) >= count, timeout)


@pytest.fixture(scope="module")
def pipelines():
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline
    from cassmantle_tpu.serving.sdxl import SDXLPipeline

    return {"t2i": Text2ImagePipeline(tiny_config()),
            "sdxl": SDXLPipeline(tiny_sdxl_config())}


@pytest.fixture(params=["t2i", "sdxl"])
def faked(request, pipelines, monkeypatch):
    """(pipeline, fake device, stage histogram) for each image pipeline."""
    pipe = pipelines[request.param]
    device = FakeDevice(pipe.cfg.sampler.image_size)
    monkeypatch.setattr(pipe, "_sample", device.sample)
    monkeypatch.setattr(pipe, "_dispatch_flops", lambda *a, **k: None)
    # a fresh hand-over: no program of another test ahead
    monkeypatch.setattr(pipe._hand_over, "_ahead", None)
    yield pipe, device, f"pipeline.{request.param}_s"
    for program in device.programs:     # nothing left waiting
        program.finish()


class Rooms:
    """Rooms that call ``generate`` each on its own thread, with turns
    taken here in room order; the later the room, the sooner its thread
    reaches the pipeline, so only the turns keep the order."""

    def __init__(self, pipe, count: int) -> None:
        self.results, self.errors = {}, {}
        turns = Turns()
        tickets = [turns.take() for _ in range(count)]

        def room(index):
            with turns.holding(tickets[index]):
                time.sleep(0.02 * (count - 1 - index))
                try:
                    self.results[index] = pipe.generate(
                        [f"room {index}"], seed=index)
                except Exception as err:    # the test reads it
                    self.errors[index] = err

        self.threads = [threading.Thread(target=room, args=(i,),
                                         daemon=True)
                        for i in range(count)]
        for thread in self.threads:
            thread.start()

    def join(self) -> None:
        for thread in self.threads:
            thread.join(WAIT_S)
        assert not any(thread.is_alive() for thread in self.threads)


class Observed:
    """The values observed into some histograms from here on, by name."""

    def __init__(self, monkeypatch, *names) -> None:
        self.values = {name: [] for name in names}
        observe = metrics.observe

        def spy(name, value, *args, **kwargs):
            if name in self.values:
                self.values[name].append(value)
            return observe(name, value, *args, **kwargs)

        monkeypatch.setattr(metrics, "observe", spy)


def test_the_next_room_is_enqueued_before_the_image_ahead_is_ready(
        faked, monkeypatch):
    """Three rooms: the second room's program is enqueued while the
    first runs; the third waits until the first is done (two on the
    device at most); rooms reach the device in turn order; the counter
    says which images went behind an unfinished one."""
    pipe, device, _stage = faked
    seen = Observed(monkeypatch, "pipeline.image_queued_behind_size")
    rooms = Rooms(pipe, 3)
    assert device.enqueued(2)
    first, second = device.programs
    assert not first.is_ready()
    # the second room waits for the first program under the lock: the
    # third cannot enqueue
    assert not device.enqueued(3, timeout=0.3)
    first.finish()
    assert device.enqueued(3)
    assert not second.is_ready()
    second.finish()
    device.programs[2].finish()
    rooms.join()
    assert rooms.errors == {}
    assert [p.room for p in device.programs] == [0, 1, 2]
    for room, program in enumerate(device.programs):
        np.testing.assert_array_equal(rooms.results[room], program.images)
    assert seen.values["pipeline.image_queued_behind_size"] == [0, 1, 1]


def test_a_lone_image_dispatches_as_before(faked, monkeypatch):
    """Nothing ahead, or the image ahead already finished: no wait for
    it, no queue, and the stage timer covers the enqueue."""
    pipe, device, stage = faked
    seen = Observed(monkeypatch, "pipeline.image_queued_behind_size",
                    "pipeline.image_ahead_wait_s", stage,
                    "pipeline.image_enqueue_s")
    for room in range(2):
        rooms = Rooms(pipe, 1)
        assert device.enqueued(room + 1)
        time.sleep(0.1)
        device.programs[room].finish()
        rooms.join()
        assert rooms.errors == {}
    assert seen.values["pipeline.image_queued_behind_size"] == [0, 0]
    assert seen.values["pipeline.image_ahead_wait_s"] == []
    for timed, enqueue in zip(seen.values[stage],
                              seen.values["pipeline.image_enqueue_s"]):
        assert timed >= 0.1 and timed >= enqueue


def test_a_queued_image_is_timed_from_the_end_of_the_image_ahead(
        faked, monkeypatch):
    """The stage time of an image that went behind an unfinished one
    leaves out its time in the queue: it runs from the moment its
    thread saw the image ahead finish."""
    pipe, device, stage = faked
    seen = Observed(monkeypatch, stage, "pipeline.image_ahead_wait_s")
    rooms = Rooms(pipe, 2)
    assert device.enqueued(2)
    time.sleep(1.0)                   # the second image queues behind
    device.programs[0].finish()
    time.sleep(0.1)                   # then runs for about 0.1 s
    device.programs[1].finish()
    rooms.join()
    assert rooms.errors == {}
    first, second = seen.values[stage]
    assert first >= 1.0
    assert 0.08 <= second < 0.7
    (waited,) = seen.values["pipeline.image_ahead_wait_s"]
    assert waited >= 0.9


def test_a_failed_dispatch_lets_the_next_room_through(faked, monkeypatch):
    """A dispatch failed at the ``device.lost`` fault point passes the
    lock and the turn on; the room behind it waits only for a program
    that is really on the device."""
    pipe, device, _stage = faked
    peer = "t2i" if _stage == "pipeline.t2i_s" else "sdxl"
    seen = Observed(monkeypatch, "pipeline.image_queued_behind_size")
    rooms = Rooms(pipe, 1)
    assert device.enqueued(1)
    chaos.configure(f"device.lost=raise:times=1,peer={peer}")
    try:
        failing = Rooms(pipe, 1)
        failing.join()
    finally:
        chaos.disarm()
    assert "device.lost" in str(failing.errors[0])
    after = Rooms(pipe, 1)
    assert device.enqueued(2)
    device.programs[0].finish()
    device.programs[1].finish()
    rooms.join()
    after.join()
    assert rooms.errors == {} and after.errors == {}
    assert len(device.programs) == 2
    # the failed dispatch found the first program unfinished too
    assert seen.values["pipeline.image_queued_behind_size"] == [0, 1, 1]


def test_a_program_that_failed_on_the_device_holds_nobody_up(
        faked, monkeypatch):
    """The image ahead failed on the device: the room behind it goes on
    and gets its own image; the failure is the failed room's alone."""
    pipe, device, _stage = faked
    rooms = Rooms(pipe, 2)
    assert device.enqueued(2)
    device.programs[0].finish(failed=True)
    device.programs[1].finish()
    rooms.join()
    assert "failed" in str(rooms.errors[0])
    assert list(rooms.results) == [1]
    np.testing.assert_array_equal(rooms.results[1],
                                  device.programs[1].images)


def test_a_device_loss_rebuild_forgets_the_program_ahead(faked,
                                                        monkeypatch):
    """A program left unfinished on a runtime that was lost is waited
    for by nobody once the pipeline has rebuilt its device state."""
    pipe, device, _stage = faked
    seen = Observed(monkeypatch, "pipeline.image_queued_behind_size")
    lost = Rooms(pipe, 1)
    assert device.enqueued(1)
    monkeypatch.setattr(pipe, "_param_loader", lambda: None)
    monkeypatch.setattr(pipe, "_publish_params", lambda: None)
    pipe.reload_params()
    after = Rooms(pipe, 1)
    assert device.enqueued(2)
    device.programs[1].finish()
    after.join()
    assert after.errors == {}
    assert seen.values["pipeline.image_queued_behind_size"] == [0, 0]
    device.programs[0].finish()
    lost.join()


def test_four_concurrent_rounds_serve_the_images_of_one_at_a_time(
        pipelines, monkeypatch):
    """Four rounds through the service at once, on the CPU backend with
    the real tiny sampler: each served image equals the same dispatch
    (prompts and seed) made alone afterwards, and nothing hangs."""
    from cassmantle_tpu.serving.pipeline import TPUContentBackend
    from cassmantle_tpu.serving.service import InferenceService

    t2i = pipelines["t2i"]
    backend = TPUContentBackend(tiny_config(), t2i=t2i)
    dispatches = []
    generate = t2i.generate

    def recorded(prompts, seed=0, deadline_s=None):
        out = generate(prompts, seed=seed, deadline_s=deadline_s)
        dispatches.append((list(prompts), seed, out))
        return out

    monkeypatch.setattr(t2i, "generate", recorded)
    before = metrics.hist_totals("pipeline.image_queued_behind_size")

    async def rounds():
        service = InferenceService(tiny_config(), backend=backend)
        try:
            return await asyncio.wait_for(asyncio.gather(*(
                service.content_backend.generate(f"The storm {i}", True)
                for i in range(4))), timeout=300)
        finally:
            await service.stop()

    served = asyncio.run(rounds())
    monkeypatch.setattr(t2i, "generate", generate)
    assert len(dispatches) == 4
    assert sorted(c.image.tobytes() for c in served) == \
        sorted(out[0].tobytes() for _p, _s, out in dispatches)
    after = metrics.hist_totals("pipeline.image_queued_behind_size")
    assert after[2] - (before[2] if before else 0) == 4
    for prompts, seed, out in dispatches:
        np.testing.assert_array_equal(generate(prompts, seed=seed), out)


def test_the_benchmark_reads_the_counter_the_program_observes():
    """``image_queued_behind_mean`` is the mean of the histogram the
    hand-over observes, and reads nothing from a program without it."""
    import json
    import pathlib

    from benchmarks.readers import hist_mean
    from cassmantle_tpu.analysis.metric_names import extract_sites
    from cassmantle_tpu.serving import pipeline

    root = pathlib.Path(__file__).resolve().parents[1]
    spec = json.loads((root / "benchmarks" / "layer_metrics" /
                       "image_queued_behind_mean.json").read_text())
    name = spec["args"]["hist"]
    source = pathlib.Path(pipeline.__file__).read_text()
    assert (name, "observe") in {
        (n, m) for n, m, _ in extract_sites(source, pipeline.__file__)}

    class Window:
        def __init__(self, hists):
            self.hists = hists

        def hist(self, hist):
            return self.hists.get(hist, (0.0, 0))

    assert spec["reader"] == "hist_mean"
    read = hist_mean.read
    assert read({"window": Window({name: (3.0, 4)})}, spec["args"]) == 0.75
    assert read({"window": Window({})}, spec["args"]) is None
