import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cassmantle_tpu.utils.codec import decode_jpeg, encode_jpeg, image_to_base64
from cassmantle_tpu.utils.text import (
    detokenize,
    format_clock,
    is_wordlike,
    tokenize_words,
)


def test_tokenize_roundtrip():
    text = "A lone lighthouse, battered by storms, glows faintly."
    tokens = tokenize_words(text)
    assert "lighthouse" in tokens and "," in tokens
    assert detokenize(tokens) == text


def test_tokenize_contractions():
    tokens = tokenize_words("It wasn't the captain's fault.")
    assert "wasn't" in tokens
    assert "captain's" in tokens


def test_token_indices_stable():
    tokens = tokenize_words("red fox, red sky")
    assert tokens == ["red", "fox", ",", "red", "sky"]
    # duplicate words keep distinct indices (fixes reference utils.py:102
    # first-occurrence bug noted in SURVEY.md §2 #9)
    assert tokens.index("red") == 0 and tokens[3] == "red"


def test_format_clock():
    assert format_clock(899) == "14:59"
    assert format_clock(0) == "00:00"
    assert format_clock(-3) == "00:00"


def test_is_wordlike():
    assert is_wordlike("storm")
    assert not is_wordlike(",")
    assert not is_wordlike("")


def test_jpeg_roundtrip():
    # smooth gradient: JPEG should round-trip it nearly losslessly
    y, x = np.mgrid[0:64, 0:64]
    img = np.stack([x * 4, y * 4, (x + y) * 2], axis=-1).astype(np.uint8)
    data = encode_jpeg(img, quality=95)
    back = decode_jpeg(data)
    assert back.shape == (64, 64, 3)
    assert back.dtype == np.uint8
    assert np.abs(back.astype(int) - img.astype(int)).mean() < 8


def test_base64():
    img = np.zeros((8, 8, 3), dtype=np.uint8)
    s = image_to_base64(img)
    assert isinstance(s, str) and len(s) > 0


def test_breaker_and_watchdog_metrics_names():
    """The supervision subsystem's counters/gauges land in the process
    metrics registry under stable names — what DEPLOY.md's degraded-mode
    runbook tells operators to alert on."""
    from cassmantle_tpu.serving.supervisor import ServingSupervisor
    from cassmantle_tpu.utils.circuit import CircuitBreaker
    from cassmantle_tpu.utils.logging import metrics

    b = CircuitBreaker("mtest", failure_threshold=1, reset_timeout_s=0.0)
    b.record_failure()          # closed -> open
    assert b.state == "half_open"   # reset_timeout 0: immediate probe
    assert b.allow()
    b.record_success()          # half_open -> closed
    b.allow()
    snap = metrics.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    assert counters["circuit.mtest.failures"] >= 1
    assert counters["circuit.mtest.opened"] >= 1
    assert counters["circuit.mtest.half_open"] >= 1
    assert counters["circuit.mtest.closed"] >= 1
    assert "circuit.mtest.state" in gauges

    sup = ServingSupervisor(degraded_cooldown_s=0.0)
    sup.note_dispatch_overrun("mtest-queue")
    sup.status()
    snap = metrics.snapshot()
    assert snap["counters"]["supervisor.dispatch_overruns"] >= 1
    assert "supervisor.degraded" in snap["gauges"]
    # every transition above also landed in the flight recorder
    # (ISSUE 3), and span/event volume self-reports
    assert snap["counters"]["obs.events"] >= 1


async def test_queue_instrumentation_metric_names():
    """The batch-shape instrumentation lands under stable names —
    what docs/OBSERVABILITY.md's catalog (and tools/check_metrics.py)
    pin for operators."""
    from cassmantle_tpu.serving.queue import BatchingQueue
    from cassmantle_tpu.utils.logging import metrics

    q = BatchingQueue(lambda items: list(items), max_delay_ms=1,
                      name="pinq")
    await q.submit(1)
    await q.stop()
    snap = metrics.snapshot()
    for counter in ("pinq.batches", "pinq.items"):
        assert snap["counters"][counter] >= 1
    for hist in ("pinq.batch_s", "pinq.queue_wait_s", "pinq.batch_size"):
        assert snap["timings"][hist]["count"] >= 1
    assert "pinq.depth" in snap["gauges"]


def test_retry_give_up_on_aborts_immediately():
    """retry_async(give_up_on=...) re-raises without further attempts —
    the breaker fast-fail contract (utils/circuit.py)."""
    import asyncio

    from cassmantle_tpu.utils.retry import retry_async

    calls = []

    class Abort(Exception):
        pass

    async def op():
        calls.append(1)
        raise Abort()

    async def run():
        with np.testing.assert_raises(Abort):
            await retry_async(op, max_retries=5, give_up_on=(Abort,),
                              backoff=lambda a: 0.0)

    asyncio.run(run())
    assert len(calls) == 1


# -- compile cache placement (utils/compile_cache.py) -----------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# run in a fresh process: placement is decided once per process, and
# jax reads JAX_COMPILATION_CACHE_DIR at import
_CACHE_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
set_in_code = []
real_update = jax.config.update
def recording_update(name, value):
    if name == "jax_compilation_cache_dir":
        set_in_code.append(value)
    return real_update(name, value)
jax.config.update = recording_update
from cassmantle_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
print(json.dumps({{"set_in_code": set_in_code,
                   "dir": jax.config.jax_compilation_cache_dir}}))
"""


@pytest.fixture(scope="module")
def cache_placement(tmp_path_factory):
    """{case: what a fresh process placed}, the three processes started
    together: the variable set; unset from a foreign cwd; unset from
    the checkout."""
    outside = str(tmp_path_factory.mktemp("outside_cache"))
    cases = {"env_set": (outside, REPO),
             "unset_elsewhere": (None, str(tmp_path_factory.mktemp("cwd"))),
             "unset_in_checkout": (None, REPO)}
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    procs = {}
    for name, (env_dir, cwd) in cases.items():
        env = dict(base) if env_dir is None else dict(
            base, JAX_COMPILATION_CACHE_DIR=env_dir)
        procs[name] = subprocess.Popen(
            [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=cwd)
    seen = {"outside": outside}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-2000:]
        seen[name] = json.loads(out.splitlines()[-1])
    return seen


def test_compile_cache_dir_comes_from_the_environment(cache_placement):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and NO code
    sets a directory over it (the chip tool keeps a repo's compiles
    from one call to the next only in the directory it names)."""
    assert cache_placement["env_set"] == {
        "set_in_code": [], "dir": cache_placement["outside"]}


def test_compile_cache_dir_defaults_to_the_checkout(cache_placement):
    want = os.path.join(REPO, ".jax_cache")
    assert cache_placement["unset_elsewhere"] == {
        "set_in_code": [want], "dir": want}


def test_compile_cache_dir_is_the_same_in_every_process(cache_placement):
    """The path is part of what a later process must agree on to hit:
    it may not depend on the cwd (or anything else a process varies)."""
    assert cache_placement["unset_elsewhere"]["dir"] == \
        cache_placement["unset_in_checkout"]["dir"]
