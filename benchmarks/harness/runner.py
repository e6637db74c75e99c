"""One run of one cell: set-up, warm-up, the measured window, the check.

The window is driven through the two injection points the game engine is
wired to and nothing below them: ``service.content_backend.generate`` and
``service.similarity``. One process, one event loop that stays free (every
heavy call runs in a thread), few threads.

The window holds whole orbits. In a closed loop every room completes once
an orbit of the program (one LM dispatch and one image a room today; one
LM dispatch for all rooms and then their images, once a program gathers
them), so the window opens at a round completion and closes on a
completion of the SAME room (``window_close``): rounds over window is then
rooms over the orbit at whatever phase the window opened, whether the
completions come evenly spaced or in bursts.
"""

from __future__ import annotations

import asyncio
import gc
import importlib
import os
import shutil
import sys
import time

import numpy as np

from . import compare as cmp
from . import traffic as tr
from .manifest import ROOT, Cell
from .stack import (
    Abandoned,
    Book,
    build_service,
    check_sizes,
    framework_config,
    program_sizes,
)
from .weights import WeightBook

#: counters of the program that mean a round or a guess was not served by
#: the path the cell measures: each increment inside the window is a failure
#: (chip_smoke.py's MUST_STAY_ZERO and the prompt queue's rejections).
#: ``pipeline.text_fallbacks`` is not among them: with random weights under
#: the byte tokenizer one decode in some tens comes out as a run of one
#: letter, the program serves its template text in its place, and the round
#: costs the device what any other does. It is a round, and is counted in
#: the line's notes (PERF.md section 2).
FAILURE_COUNTERS = (
    "pipeline.output_invalid",
    "supervisor.dispatch_overruns", "overload.score_shed",
    "overload.loop_lag_sheds", "prompt.rejected", "prompt.rejected_overload",
    "prompt.rejected_predicted_late", "prompt.rejected_background",
    "prompt.rejected_degraded", "prompt.deadline_expired",
    "prompt.failures", "score.failures", "score.deadline_expired",
    "pipeline.brownout_images",
)
GIVE_UP_AFTER = 8
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


class Snapshot:
    """The program's counters and histogram sums at one instant."""

    def __init__(self) -> None:
        from cassmantle_tpu.utils.logging import metrics

        state = metrics.dump_state()
        self.counters: dict = {}
        for name, _labels, value in state["counters"]:
            self.counters[name] = self.counters.get(name, 0.0) + value
        self.hists: dict = {}
        for name, _labels, _bounds, _counts, total, count in state["hists"]:
            s, c = self.hists.get(name, (0.0, 0))
            self.hists[name] = (s + total, c + count)

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)


class Window:
    """Counter and histogram deltas between two snapshots."""

    def __init__(self, before: Snapshot, after: Snapshot) -> None:
        self.before, self.after = before, after

    def counter(self, name: str) -> float:
        return self.after.counter(name) - self.before.counter(name)

    def hist(self, name: str) -> tuple:
        s1, c1 = self.after.hists.get(name, (0.0, 0))
        s0, c0 = self.before.hists.get(name, (0.0, 0))
        return s1 - s0, c1 - c0


def mark(what: str) -> None:
    """Progress on standard error, with the seconds since the start."""
    print(f"[bench {time.perf_counter() - _T0:8.1f}s] {what}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def window_close(completions: list, n_open: int, seconds: float,
                 slack_s: float):
    """Which completion closes the window that ``completions[n_open]``
    opened: ``(index, "opener" | "any")``, or None while none of the
    completions so far does. ``completions`` holds ``(time, room, valid)``
    in order of time.

    The window closes at the first valid completion of the opener's own
    room at or after ``seconds``: a whole number of orbits. A room that
    only fails never closes it, so ``slack_s`` later the first completion
    of any room does, and the result line says so."""
    t_open, opener, _ = completions[n_open]
    for index in range(n_open + 1, len(completions)):
        t_done, room, valid = completions[index]
        if t_done - t_open < seconds:
            continue
        if room == opener and valid:
            return index, "opener"
        if t_done - t_open >= seconds + slack_s:
            return index, "any"
    return None


def valid_round(content, image_size: int) -> bool:
    image = getattr(content, "image", None)
    text = getattr(content, "prompt_text", None)
    return (isinstance(text, str) and bool(text.strip())
            and isinstance(image, np.ndarray) and image.dtype == np.uint8
            and image.shape == (image_size, image_size, 3)
            and float(image.std()) > 0.0)


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearsal: bool) -> None:
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.rehearsal = trace, rehearsal
        self.book = Book()
        self.completions: list = []   # (t_done, room, valid)
        self.round_errors: list = []  # (t, repr)
        self.lateness: list = []
        self.wake = None
        self.failed_in_a_row = 0

    # -- set-up ---------------------------------------------------------------
    def build(self):
        config = self.cell.config
        self.cfg = framework_config(config, self.rehearsal)
        running = program_sizes(self.cfg, config)
        if self.rehearsal:
            # the tiny test size: the program's own numbers under the
            # keys the file states
            self.sizes = running
            self.sizes["minilm"]["seq_len"] = min(
                self.sizes["minilm"]["seq_len"],
                self.sizes["minilm"]["max_positions"])
        else:
            self.sizes = config["sizes"]
            wrong = check_sizes(config["sizes"], running)
            if wrong:
                raise SystemExit("the configuration's file and the program "
                                 "disagree:\n  " + "\n  ".join(wrong))
        self.names = cmp.named(config, self.sizes)
        mark("building the serving stack")
        self.weights = WeightBook(self.seed)
        self.service = build_service(self.cfg, self.book, self.weights)
        self.backend = self.service.content_backend

    def build_word_table(self, wordlist: str):
        """The scorer's int8 table over the game's words, built from the
        weights in use through the program's own encode path and its own
        quantiser. A deployment builds it offline from its checkpoint; the
        committed one belongs to other weights (PERF.md section 7)."""
        from cassmantle_tpu.ops.embed_table import EmbedTable

        scorer = self.service.scorer
        scorer.table = None
        words = tr.lines(wordlist)
        scorer.table = EmbedTable.from_embeddings(words, scorer.embed(words))

    def warm_shapes(self):
        """Every shape the mix reaches and no other: LM (batch bucket x
        prompt bucket), scorer batch buckets. The image sampler and the
        host-side programs warm in the closed loop that follows."""
        mix = self.cell.traffic
        warm = mix["warm"]
        if mix.get("guesses"):
            self.build_word_table(mix["guesses"]["wordlist"])
        gen = self.service.backend.prompt_gen
        for n_bytes in warm["lm_prompt_bytes"]:
            for batch in warm["lm_batch"]:
                gen.generate_batch(["w" * n_bytes] * batch)
        for i, rows in enumerate(warm.get("score_rows", [])):
            self.service.scorer.embed(
                [f"warm up {i} {j}" for j in range(rows)])

    # -- the loops ------------------------------------------------------------
    async def room(self, room: int):
        mix = self.cell.traffic
        size = self.cfg.sampler.image_size
        n_round, seed_text, is_seed = 0, None, True
        while not self.book.closed:
            if n_round % mix["story_rounds"] == 0:
                seed_text, is_seed = tr.story_title(
                    mix, self.seed, room, n_round // mix["story_rounds"]), True
            try:
                content = await self.backend.generate(seed_text, is_seed)
            except Abandoned:
                return
            except Exception as exc:  # a failed round; the loop goes on
                self.round_errors.append(
                    (time.perf_counter(), f"{type(exc).__name__}: {exc}"))
                print(f"round failed: {type(exc).__name__}: {exc}",
                      file=sys.stderr)
                self.failed_in_a_row += 1
                self.wake.set()
                await asyncio.sleep(0.05)
                continue
            self.failed_in_a_row = 0
            ok = valid_round(content, size)
            self.completions.append((time.perf_counter(), room, ok))
            if ok:
                seed_text, is_seed = content.prompt_text, False
                n_round += 1
            self.wake.set()

    async def next_completion(self, n_before: int):
        """Time of completion number ``n_before`` (from 0). A system that
        only fails ends the run: there is nothing to measure."""
        while len(self.completions) <= n_before:
            if self.failed_in_a_row >= GIVE_UP_AFTER:
                raise SystemExit(
                    f"{self.failed_in_a_row} rounds failed in a row: "
                    f"{self.round_errors[-1][1]}")
            self.wake.clear()
            await self.wake.wait()
        return self.completions[n_before][0]

    async def one_guess(self, due: float, pairs, on_device: bool):
        try:
            scores = await self.service.similarity(pairs)
            scores = [float(s) for s in np.asarray(scores)]
        except Exception as exc:
            scores = None
            print(f"guess failed: {type(exc).__name__}: {exc}",
                  file=sys.stderr)
        self.book.scores.append(
            (due, pairs, scores, time.perf_counter() - due, on_device))

    async def guesses(self, t_open: float, answers, calls, tasks: list):
        for offset, room, guess, on_device in calls:
            due = t_open + offset
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            if self.book.closed:
                return
            self.lateness.append(time.perf_counter() - due)
            pairs = [(guess, a) for a in answers[room]]
            tasks.append(asyncio.ensure_future(
                self.one_guess(due, pairs, on_device)))

    # -- one run --------------------------------------------------------------
    async def measure(self) -> dict:
        import jax

        mix = self.cell.traffic
        self.wake = asyncio.Event()
        mark("warming the LM and scorer shapes")
        await asyncio.to_thread(self.warm_shapes)
        mark("warm-up rounds")
        slack = mix.get("close_slack_s", 30.0)
        horizon = self.seconds + slack
        answers, calls = tr.guess_schedule(mix, self.seed, horizon)
        warm_answers, warm_calls = tr.guess_schedule(
            mix, self.seed + 1, mix["warm"].get("guess_s", 0.0))
        for words in answers + warm_answers:
            await asyncio.to_thread(self.service.pin_answers, words)
        rooms = [asyncio.ensure_future(self.room(i))
                 for i in range(mix["rooms"])]
        warm_tasks: list = []
        warm_guesses = asyncio.ensure_future(self.guesses(
            time.perf_counter(), warm_answers, warm_calls, warm_tasks))
        # warm-up: the same closed loop, every room through its rounds
        n_warm = mix["warm"]["rounds_per_room"] * mix["rooms"]
        await self.next_completion(n_warm - 1)
        await warm_guesses
        await asyncio.gather(*warm_tasks)
        self.book.scores.clear()
        self.lateness.clear()
        # the window opens at a round completion (its room: the opener)...
        n_open = len(self.completions)
        t_open = await self.next_completion(n_open)
        before = Snapshot()
        mark("window open")
        tracing = None
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # the device's operations and the benchmark's own spans; no
            # Python call stacks, which make stopping take minutes
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            tracing = asyncio.ensure_future(self.stop_trace_after(
                mix["trace_slice_s"]))
        guess_tasks: list = []
        guessing = asyncio.ensure_future(self.guesses(
            t_open, answers, calls, guess_tasks))
        # ...and closes at the first one of the same room at or after
        # --seconds later
        while (closed := window_close(self.completions, n_open,
                                      self.seconds, slack)) is None:
            await self.next_completion(len(self.completions))
        t_close = self.completions[closed[0]][0]
        self.book.closed = True
        after = Snapshot()
        mark(f"window closed after {t_close - t_open:.2f}s")
        depth_at_close = self.service.score_queue.depth()
        if tracing is not None:
            await tracing
        await guessing
        if guess_tasks:
            await asyncio.wait(guess_tasks, timeout=60.0)
        for task in guess_tasks:
            if not task.done():
                task.cancel()
        for task in rooms:
            task.cancel()
        await asyncio.gather(*rooms, return_exceptions=True)
        await self.service.stop()
        return {"t_open": t_open, "t_close": t_close, "before": before,
                "after": after, "score_depth_at_close": depth_at_close,
                "window_closed_on": closed[1]}

    async def stop_trace_after(self, seconds: float):
        import jax

        await asyncio.sleep(seconds)
        await asyncio.to_thread(jax.profiler.stop_trace)

    # -- results --------------------------------------------------------------
    def results(self, m: dict) -> dict:
        """Everything the readers and the result line draw on."""
        t_open, t_close = m["t_open"], m["t_close"]
        window = Window(m["before"], m["after"])
        seconds = t_close - t_open
        done = [c for c in self.completions if t_open < c[0] <= t_close]
        errors = [e for e in self.round_errors if t_open < e[0] <= t_close]
        counted = {n: window.counter(n) for n in FAILURE_COUNTERS}
        counted = {n: int(v) for n, v in counted.items() if v}
        bad_rounds = (sum(1 for c in done if not c[2]) + len(errors)
                      + sum(counted.values()))
        rounds = sum(1 for c in done if c[2])
        calls = [c for c in self.book.scores if t_open <= c[0] <= t_close]
        failed_calls = [c for c in calls if c[2] is None]
        out = {
            "window": window, "window_s": seconds, "rounds": rounds,
            "attempted": len(done) + len(errors) + len(calls),
            "failed": bad_rounds + len(failed_calls),
            "failure_counters": counted, "round_errors": errors[:5],
            "text_fallbacks": int(window.counter("pipeline.text_fallbacks")),
            "guess_calls": len(calls), "guess_failed": len(failed_calls),
            "compiles_in_window": int(window.counter("jit.compiles")),
            "score_depth_at_close": m["score_depth_at_close"],
            "window_closed_on": m["window_closed_on"],
            "generator_late_p95_ms": (
                1e3 * tr.percentile(self.lateness, 95)
                if self.lateness else None),
        }
        if calls:
            worst = max(c[3] for c in calls)
            lat = [worst if c[2] is None else c[3] for c in calls]
            out["score_p95_ms"] = 1e3 * tr.percentile(lat, 95)
            out["score_p50_ms"] = 1e3 * tr.percentile(lat, 50)
            out["guess_shed_pct"] = 100.0 * len(failed_calls) / len(calls)
        return out


def device_block() -> dict:
    import jax

    devices = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def read_layer_metrics(cell: Cell, ctx: dict, sources=None) -> dict:
    """Each per-layer metric through its reader, of the ``sources`` given
    or of all; a reader that finds nothing to read returns None and the
    metric is left out."""
    out = {}
    for metric in cell.per_layer:
        if sources is not None and metric["source"] not in sources:
            continue
        spec = cell.reader_spec(metric["name"])
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_window(cell: Cell, seed: int, seconds: float, trace: bool,
               rehearsal: bool, t_start: float) -> dict:
    """Set-up, warm-up and the window; then the program's state is let go.
    Returns what the check and the result line draw on."""
    run = Run(cell, seed, seconds, trace, rehearsal)
    run.build()
    measured = asyncio.run(run.measure())
    res = run.results(measured)
    res["setup_s"] = measured["t_open"] - t_start
    res["device"] = device_block()
    ctx = dict(res, cell=cell, sizes=run.sizes, names=run.names,
               device_kind=res["device"]["kind"],
               trees=cmp.reference_trees(run.weights.trees, run.sizes,
                                         run.names))
    if trace:
        from . import trace as trace_mod

        mark("reading the trace")
        res["trace"] = ctx["trace"] = trace_mod.reduce_xplane(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # a rehearsal reads the program's counts and nothing that is a time or
    # a share of the device; an untraced run also the program's spans,
    # which cost it nothing, for its notes: where a run reads far off,
    # they say whether rounds, images or waits grew
    if rehearsal:
        sources = {"program_counter"}
    else:
        sources = None if trace else {"program_counter", "program_span"}
    res["layer"] = read_layer_metrics(cell, ctx, sources)
    mark("metrics read; freeing the program's state")
    # the program's state goes before the reference comes
    res.update(book=run.book, sizes=run.sizes, names=run.names,
               trees=run.weights.trees,
               span=(measured["t_open"], measured["t_close"]))
    run.service = run.backend = None
    gc.collect()
    return res


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            rehearsal: bool, t_start: float) -> dict:
    """The whole run; returns the result line as a dict."""
    res = run_window(cell, seed, seconds, trace, rehearsal, t_start)
    values = cmp.compare(res["book"], res["span"], res["trees"],
                         res["sizes"], res["names"], cell.config["check"],
                         seed)
    values["compiles_in_window"] = res["compiles_in_window"]
    limits = dict(cell.config["limits"], compiles_in_window=0)
    correct, checks = cmp.verdict(
        values, limits,
        cmp.required_numbers(cell.config, cell.traffic)
        + ["compiles_in_window"])

    e2e = {"rounds_per_s": res["rounds"] / res["window_s"],
           "setup_s": res["setup_s"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    device, trace_red = res["device"], res.get("trace")
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if rehearsal:
        # a CPU run times nothing: counts only, under no metric's name
        line["rehearsal"] = True
        line["metrics"] = {}
        line["counts"] = {
            "rounds": res["rounds"], "guess_calls": res["guess_calls"],
            "layer_metrics_read": sorted(res["layer"])}
    elif trace:
        line["metrics"] = res["layer"]
    else:
        line["metrics"] = {n: {"value": e2e[n], "unit": u}
                           for n, u in units.items() if e2e[n] is not None}
    if trace_red and not rehearsal:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        line["breakdown"] = {"device_ops": trace_red["device_ops"],
                             "idle_gaps": trace_red["idle_gaps"]}
    line["device"] = device
    line["notes"] = {k: res[k] for k in (
        "window_s", "rounds", "guess_calls", "guess_failed",
        "failure_counters", "round_errors", "text_fallbacks",
        "score_depth_at_close", "window_closed_on",
        "generator_late_p95_ms", "score_p50_ms") if res.get(k) is not None}
    if not trace and not rehearsal:
        line["notes"]["spans"] = {n: m["value"]
                                  for n, m in res["layer"].items()}
    line["checks"] = checks
    return line
