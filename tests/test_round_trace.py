"""A round is one trace, whoever calls it (ISSUE 26).

``InferenceService.generate_content`` opens ``round.content``: the queue's
wait/batch spans, the prompt decode, the wait for the image pipeline's
dispatch lock, the image dispatch and the host tail all land under it,
each beside the histogram observed at the same place — the terms a
benchmark adds up to a round's time — and so do the host's steps of each
dispatch that run while the device may be idle (preparation, enqueue,
tail). Tiny test size, real pipelines.
"""

import asyncio
import threading

import pytest

from cassmantle_tpu.config import test_config as tiny_config
from cassmantle_tpu.obs.trace import tracer
from cassmantle_tpu.utils.logging import metrics

# span -> parent span, as docs/OBSERVABILITY.md draws the round's tree
# (a span inside a block_timer is the ambient span's child: the stage's
# own span is recorded when it ends)
ROUND_TREE = {
    "prompt.queue_wait": "round.content",
    "prompt.batch": "round.content",
    "prompt.batch_service": "round.content",
    "pipeline.prompt_s": "prompt.batch",
    "pipeline.lm_lock_wait": "prompt.batch",
    "pipeline.lm_prep": "prompt.batch",
    "pipeline.lm_tail": "prompt.batch",
    "pipeline.image_prep": "round.content",
    "pipeline.image_lock_wait": "round.content",
    "pipeline.t2i_s": "round.content",
    "pipeline.image_enqueue": "round.content",
    "pipeline.image_host": "round.content",
}
#: spans a round records twice: the LM's tail, in decode_ids_batch inside
#: pipeline.prompt_s and in generate_batch after it
TWICE = ("pipeline.lm_tail",)
ROUND_HISTOGRAMS = (
    "round.content_s", "prompt.queue_wait_s", "prompt.batch_size",
    "pipeline.prompt_s", "pipeline.lm_lock_wait_s", "pipeline.lm_prep_s",
    "pipeline.lm_tail_s", "pipeline.image_prep_s",
    "pipeline.image_lock_wait_s", "pipeline.t2i_s",
    "pipeline.image_enqueue_s", "pipeline.image_batch_size",
    "pipeline.image_host_s")


def hist_count(name: str) -> int:
    totals = metrics.hist_totals(name)
    return totals[2] if totals is not None else 0


def hist_sum(name: str) -> float:
    return sum(total for n, _l, _b, _c, total, _count
               in metrics.dump_state()["hists"] if n == name)


@pytest.fixture(scope="module")
def backend():
    """The tiny pipelines, built (and compiled on first use) once."""
    from cassmantle_tpu.serving.pipeline import TPUContentBackend

    return TPUContentBackend(tiny_config())


@pytest.fixture()
def all_traces_kept():
    stats = tracer.stats()
    tracer.configure(sample_rate=1.0)
    yield
    tracer.configure(sample_rate=stats["sample_rate"])


def one_round(backend, ambient_root: bool):
    """(new trace ids, histogram counts before, after) of one round
    through the content backend the Game owns."""
    from cassmantle_tpu.serving.service import InferenceService

    async def run():
        service = InferenceService(tiny_config(), backend=backend)
        try:
            generate = service.content_backend.generate
            if ambient_root:
                with tracer.span("round.generate", root=True):
                    return await generate("The storm over the harbor", True)
            return await generate("The storm over the harbor", True)
        finally:
            await service.stop()

    known = set(tracer.trace_ids())
    before = {h: hist_count(h) for h in ROUND_HISTOGRAMS}
    content = asyncio.run(run())
    assert content.prompt_text and content.image is not None
    after = {h: hist_count(h) for h in ROUND_HISTOGRAMS}
    return [t for t in tracer.trace_ids() if t not in known], before, after


@pytest.mark.parametrize("ambient_root", [False, True],
                         ids=["called_bare", "under_round_generate"])
def test_a_round_is_one_trace_with_the_tables_spans(
        backend, all_traces_kept, ambient_root):
    new_traces, before, after = one_round(backend, ambient_root)
    # bare, round.content is the root; under the engine's
    # round.generate it joins that trace — one trace either way
    assert len(new_traces) == 1
    spans = tracer.get_trace(new_traces[0])
    by_id = {s["span_id"]: s for s in spans}
    by_name = {s["name"]: s for s in spans}
    tree = dict(ROUND_TREE,
                **{"round.content":
                   "round.generate" if ambient_root else None})
    if ambient_root:
        tree["round.generate"] = None
    assert sorted(s["name"] for s in spans) == sorted(list(tree) + list(TWICE))
    for span in spans:
        name = span["name"]
        assert span["trace_id"] == new_traces[0]
        got = by_id[span["parent_id"]]["name"] if span["parent_id"] else None
        assert got == tree[name], (name, got)
        assert span["start_ns"] == round(span["start_ts"] * 1e9)
    assert by_name["pipeline.t2i_s"]["attrs"]["padded_rows"] == 1
    # the terms lie inside the round, in the round's order
    inside = by_name["round.content"]
    end_ns = inside["start_ns"] + inside["duration_s"] * 1e9
    order = ["prompt.queue_wait", "pipeline.prompt_s",
             "pipeline.image_lock_wait", "pipeline.t2i_s",
             "pipeline.image_host"]
    starts = [by_name[n]["start_ns"] for n in order]
    assert starts == sorted(starts)
    assert inside["start_ns"] <= starts[0]
    last = by_name[order[-1]]
    assert last["start_ns"] + last["duration_s"] * 1e9 <= end_ns + 1e6

    def bounds(span):
        return span["start_ns"], span["start_ns"] + span["duration_s"] * 1e9

    def within(inner, outer):
        (i0, i1), (o0, o1) = bounds(inner), bounds(outer)
        return o0 - 1e6 <= i0 and i1 <= o1 + 1e6

    # the host's steps of each dispatch, where the docs draw them: the
    # LM's lock wait, preparation and first tail inside pipeline.prompt_s,
    # its second tail after it; the image's preparation before its lock,
    # its enqueue inside pipeline.t2i_s
    prompt_s, t2i = by_name["pipeline.prompt_s"], by_name["pipeline.t2i_s"]
    tails = sorted((s for s in spans if s["name"] == "pipeline.lm_tail"),
                   key=lambda s: s["start_ns"])
    for inner in ("pipeline.lm_lock_wait", "pipeline.lm_prep"):
        assert within(by_name[inner], prompt_s), inner
    assert within(tails[0], prompt_s)
    assert tails[1]["start_ns"] >= bounds(prompt_s)[1] - 1e6
    assert bounds(by_name["pipeline.lm_prep"])[1] <= tails[0]["start_ns"]
    assert bounds(by_name["pipeline.image_prep"])[1] <= \
        by_name["pipeline.image_lock_wait"]["start_ns"] + 1e6
    assert within(by_name["pipeline.image_enqueue"], t2i)
    # one observation in each histogram, at the same place (the LM's tail
    # in its two places)
    for hist in ROUND_HISTOGRAMS:
        twice = hist[:-2] in TWICE
        assert after[hist] - before[hist] == (2 if twice else 1), hist


@pytest.mark.parametrize("contended", [False, True],
                         ids=["uncontended", "contended"])
def test_lock_wait_is_observed_once_per_acquisition(backend, contended):
    """The image dispatch lock times the request for it until it is
    held: near 0 when free, the holder's remaining time when not."""
    lock = backend.t2i._hand_over.lock
    assert lock.wait_span == "pipeline.image_lock_wait"
    hist = "pipeline.image_lock_wait_s"
    if not contended:
        count, total = hist_count(hist), hist_sum(hist)
        with lock:
            pass
        assert hist_count(hist) == count + 1
        assert hist_sum(hist) - total < 0.05
        return
    holding, release = threading.Event(), threading.Event()

    def holder():
        with lock:
            holding.set()
            release.wait(5.0)

    thread = threading.Thread(target=holder)
    timer = threading.Timer(0.2, release.set)
    thread.start()
    try:
        assert holding.wait(5.0)
        # from here: the holder's own acquisition is counted
        count, total = hist_count(hist), hist_sum(hist)
        timer.start()
        with lock:                            # waits for the holder
            pass
    finally:
        release.set()
        thread.join(5.0)
        timer.join(5.0)
    assert hist_count(hist) == count + 1
    assert 0.15 < hist_sum(hist) - total < 5.0


def test_only_a_lock_that_names_its_wait_is_timed():
    from cassmantle_tpu.utils.locks import OrderedLock

    count = hist_count("pipeline.image_lock_wait_s")
    plain = OrderedLock("test.plain")
    assert plain.wait_span is None
    with plain:
        pass
    assert hist_count("pipeline.image_lock_wait_s") == count
    assert metrics.hist_totals("test.plain_s") is None


# -- threads pass an in-turn lock in the order their tickets were taken -----


def test_threads_pass_an_in_turn_lock_by_ticket_not_by_arrival():
    """Four workers take their tickets in order 0..3 and reach the lock
    in the reverse order (the later the ticket, the shorter its way
    there); they pass by ticket. A ticket that never reaches the lock
    (its work failed before) holds nobody up, a thread with no ticket
    passes as at any lock, and a lock that is not ``in_turn`` knows no
    tickets."""
    import time

    from cassmantle_tpu.utils.locks import OrderedLock, Turns

    turns, passed = Turns(), []
    lock = OrderedLock("test.in_turn", in_turn=True)
    plain = OrderedLock("test.plain")
    tickets = [turns.take() for _ in range(5)]

    def work(ticket):
        with turns.holding(ticket):
            time.sleep(0.02 * (4 - ticket))     # the last ticket is first
            if ticket == 2:
                return                          # never reaches the lock
            with plain:                         # no order here
                pass
            with lock:
                passed.append(ticket)
                time.sleep(0.01)

    threads = [threading.Thread(target=work, args=(t,)) for t in tickets]
    for thread in threads:
        thread.start()
    with lock:                                  # no ticket: no waiting
        passed.append("no ticket")
    for thread in threads:
        thread.join(timeout=5)
    assert not any(thread.is_alive() for thread in threads)
    assert passed == ["no ticket", 0, 1, 3, 4]
    turns.leave(4)                              # said twice: harmless
    late = turns.take()
    with turns.holding(late), lock:
        passed.append(late)
    assert passed[-1] == 5


def test_rounds_render_in_the_order_generate_was_called(backend,
                                                        monkeypatch):
    """Four rounds handed to the backend in one turn of the loop (the
    prompt queue delivers a batch's texts so) pass the image dispatch
    lock in the order of the calls, though the later the call the sooner
    its thread is there: the order of the rooms in one orbit is then
    their order in the next."""
    import time

    import numpy as np

    lock, rendered = backend.t2i._hand_over.lock, []

    def generate(prompts, seed=0, deadline_s=None):
        call = int(prompts[0].rsplit(" ", 1)[1])
        time.sleep(0.03 * (3 - call))
        with lock:
            rendered.append(call)
            time.sleep(0.01)
        return [np.zeros((8, 8, 3), np.uint8)]

    monkeypatch.setattr(backend.t2i, "generate", generate)

    async def rounds():
        await asyncio.gather(*(
            backend.generate(f"title {i}", True,
                             text=f"the keeper lit the lamp at dusk {i}")
            for i in range(4)))

    asyncio.run(rounds())
    assert rendered == [0, 1, 2, 3]
