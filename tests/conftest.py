"""Test env: force JAX onto a virtual 8-device CPU mesh.

This is the multi-device-without-a-cluster strategy from SURVEY.md §4: all
collective/sharding tests exercise real XLA collectives on 8 host devices; the
real-chip path is covered by chip_smoke.py (run on the chip) and
tests/test_tpu_compile.py (the chip's compiler, without the chip).
"""

# 8 virtual CPU devices + raised collective timeouts (on few-core hosts
# the devices' programs serialize past XLA's default 40 s rendezvous
# timeout), pinned hermetically: the suite never initializes an
# accelerator backend, whatever JAX_PLATFORMS the environment carries.
# The ordering rules live in pin_cpu_platform.
from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

pin_cpu_platform(virtual_devices=True)

import jax  # noqa: E402, F401

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

from cassmantle_tpu.config import test_config  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio here)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (built-in runner)")


# -- test tiers (VERDICT r5 weak #6: whole-suite doesn't fit a short ---------
# verification window). Two module-level tiers, assigned centrally here so
# the map is one place, not 37 pytestmark lines:
#
# - ``fast``: the quick whole-repo smoke — every subsystem covered (engine,
#   server, ops incl. the Pallas kernels, models, serving, native store,
#   spell/text, parity), minutes not tens of minutes. Run:
#       JAX_PLATFORMS=cpu pytest -q -m fast
# - ``slow``: the wall-clock hogs (multi-minute compile/e2e paths) that
#   the tier-1 `-m 'not slow'` run excludes so the default tier finishes
#   inside its timeout on small hosts. They still run in a full
#   un-filtered `pytest` on capable machines.
#
# Times that justified the split are per-module isolated runs on a 2-core
# host; see ROADMAP.md for the tier commands.

FAST_MODULES = frozenset({
    "test_aux", "test_bench_harness",
    # bench regression sentinel + device/cost observability (ISSUE 14):
    # the bench_diff verdict grammar is stdlib-fast; test_obs_device
    # compiles two tiny pipelines for the roofline acceptance smoke and
    # regenerates the cost-model artifact (pure eval_shape, ~20s) —
    # both are acceptance bars that must run in every quick sweep
    "test_bench_diff", "test_obs_device",
    "test_chaos",
    "test_check_concurrency",
    "test_check_jax", "test_check_metrics",
    # exception-flow/lifecycle lints + leak sentinel (ISSUE 19): the
    # golden violating/fixed pairs (PR 6 stop-strand, PR 8 cancel-
    # swallow), the repo-lints-clean gate, and the seeded-leak sentinel
    # units are stdlib-fast acceptance bars for the leak defense
    "test_check_lifecycle",
    # consistency distillation + few-step serving (ISSUE 15): the
    # toy-geometry training smoke, checkpoint-layout pin, the ≤8-
    # forwards acceptance counter, and the brownout few-step tier are
    # acceptance bars that must run in every quick sweep; the
    # real-geometry distill compile test inside the module is marked
    # slow per-test (the marker loop below keeps it out of `-m fast`)
    "test_distill",
    # zero-device guess scoring (ISSUE 16): the artifact drift gate,
    # the int8-parity pin over the full wordlist (~25s tiny-encoder
    # embed, shared module-scoped), and the zero-queue/zero-device
    # counter pin are acceptance bars that must run in every quick
    # sweep — a stale committed table or a fast path that silently
    # dispatches device work must fail fast
    "test_embed_table",
    "test_eval",
    "test_fabric", "test_fault_injection",
    "test_flash_attention", "test_frontend", "test_fused_conv",
    "test_game",
    # output-integrity sentinels + device-loss recovery (ISSUE 17): the
    # verdict/poison units, device-loss classifier and recovery state
    # machine, the queue fail-fast and per-member exception pins, the
    # scorer poison-never-cached bar, the prompt-path range sentinel,
    # and the short in-process loss drill are acceptance bars for the
    # robustness plane — whole module measured ~9s on a 2-core host
    # (module-scoped tiny-encoder and tiny-GPT2 fixtures)
    "test_integrity",
    "test_js_runtime", "test_layers_norm", "test_masking",
    "test_masking_agreement", "test_multihost",
    "test_native_store", "test_obs", "test_obs_cluster", "test_ops",
    # canary prober (ISSUE 18): in-process HTTP probes, no device work
    "test_prober",
    # overload control plane (ISSUE 13): limiter/ladder/priority units
    # plus the ~10s spawned-worker goodput smoke — the overload
    # acceptance bar must run in every quick sweep
    "test_overload",
    "test_pipeline",
    "test_pipeline_parallel", "test_samplers", "test_scoring",
    "test_server", "test_spell", "test_store", "test_store_parity",
    "test_supervisor", "test_utils", "test_weights",
    # deliberately NOT fast (stay in the default tier):
    # test_spec_decode and test_stages — heavyweight parity suites
    # whose coverage the fast smoke doesn't need twice (test_pipeline
    # smokes the decode path). test_stages compiles three
    # pipeline-sized jits (staged encode/step/decode + the monolithic
    # reference) but MUST stay in tier-1: staged-vs-monolithic
    # bit-parity is an acceptance bar, and the autouse lock sentinel
    # only guards the stage scheduler's lock hierarchy if the module
    # actually runs in the default sweep. test_spec_decode stays for
    # the same reason: greedy/spec bit-parity + the jit-sentinel
    # steady-state assertions are tier-1 acceptance bars (PR 5/7).
    # test_sdxl, test_img2img, test_mistral and test_torch_parity are
    # in the default tier as well: the only tests that execute
    # serving/sdxl.py, the img2img sampler path, the model the
    # benchmark's own test fixture uses, and the zoo against an
    # outside implementation.
})

SLOW_MODULES = frozenset({
    "test_parallel",   # 8-device mesh collectives: ~6 min of compiles
    "test_cli",        # subprocess-per-test CLI runs: ~2.5 min
    "test_manifests",  # full converter grammars over manifests: ~1 min
    # multi-process fabric cluster runs (worker subprocesses + sustained
    # HTTP/WS load + the store-leader failover drill): ~15 s of pure
    # wall clock that the per-component fast-tier coverage in
    # test_fabric already smoke-tests in-process
    "test_fabric_cluster",
    # the seeded chaos drill smoke: multi-process fabric phases (store
    # spawns + worker subprocesses + SIGTERM handoff) beside
    # test_fabric_cluster; the fast in-process versions of every
    # behavior live in test_chaos / test_fault_injection /
    # test_chaos_recovery
    "test_chaos_drill",
    # ~75s of compile-bound distributed LM TRAINING steps — serving-
    # independent; the multi-device path keeps tier-1 smoke coverage
    # via test_multihost (fast) and full coverage via test_parallel
    # (slow).
    "test_lm_train",
})


def pytest_collection_modifyitems(config, items):
    import os

    for item in items:
        name = os.path.basename(str(item.fspath))
        if name.endswith(".py"):
            name = name[:-3]
        if name in FAST_MODULES and \
                item.get_closest_marker("slow") is None:
            # a per-test @pytest.mark.slow inside a fast module (e.g.
            # test_distill's real-geometry compile, test_queue's two
            # real-pipeline service builds — demoted round 21 when the
            # default tier outgrew its 870s window again; round 25
            # added test_pipeline's dp-mesh smoke, test_fused_conv's
            # pipeline flag parity, and test_w8a8's generate-level
            # kill-switch/SDXL-floor confirmations for the same
            # pressure, each with its tier-1 coverage duplicated — see
            # the demoted tests' docstrings) keeps that test out of
            # the `-m fast` sweep, not just out of tier-1
            item.add_marker(pytest.mark.fast)
        if name in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def decode_dispatches(monkeypatch):
    """The ``greedy_decode`` dispatches ``PromptGenerator`` makes while
    the test runs: (rows, prompt bucket) of the program and the rows'
    position offsets, one entry a dispatch."""
    import numpy as np

    from cassmantle_tpu.serving import pipeline

    seen, inner = [], pipeline.greedy_decode

    def recording(pair, params, ids, *args, **kwargs):
        seen.append((tuple(ids.shape),
                     np.asarray(kwargs["position_offset"]).tolist()))
        return inner(pair, params, ids, *args, **kwargs)

    monkeypatch.setattr(pipeline, "greedy_decode", recording)
    return seen


@pytest.fixture(autouse=True)
def _lock_sentinel():
    """Arm the OrderedLock deadlock sentinel (utils/locks.py) in raising
    mode for EVERY test: any hierarchy/order violation a test drives
    through the converted serving locks (queue, supervisor, breakers,
    pipeline dispatch) fails that test with both acquisition sites —
    the fast tier doubles as a runtime deadlock sentinel. The observed-
    order graph resets per test so unrelated tests' acquisition orders
    can't combine into a phantom inversion."""
    from cassmantle_tpu.utils import locks

    locks.reset_observations()
    locks.enable_sentinel(raise_on_violation=True)
    yield
    locks.disable_sentinel()
    locks.reset_observations()


@pytest.fixture(autouse=True)
def _jit_sentinel():
    """Arm the jit compile-count sentinel (utils/jit_sentinel.py) for
    EVERY test, with per-test count reset — the compile-cache
    counterpart of the lock sentinel above. Arming only counts; tests
    on steady-state serving paths opt into the hard assertion with
    ``with jit_sentinel.no_new_compiles():`` after their warmup
    dispatch, so a recompile regression (a bucket key quietly becoming
    per-call) fails tier-1 instead of shipping as a latency cliff."""
    from cassmantle_tpu.utils import jit_sentinel

    jit_sentinel.reset_counts()
    jit_sentinel.enable_sentinel()
    yield
    jit_sentinel.disable_sentinel()
    jit_sentinel.reset_counts()


@pytest.fixture(autouse=True)
def _leak_sentinel():
    """Arm the thread/task/fd leak sentinel (utils/leak_sentinel.py)
    for EVERY test — the lifecycle counterpart of the two sentinels
    above. Threads still alive and tasks still pending after teardown
    fail the test with their creation site (Thread.start/create_task
    are wrapped to stamp origin stacks while armed). Fd accounting is
    log-only here: lazy process-lifetime caches (the mmap'd embedding
    table, a jax backend initializing mid-suite) legitimately open fds
    that are not per-test leaks; seeded-fd-leak tests opt into
    fd_policy="raise" themselves. Autouse fixtures set up before the
    test's requested fixtures and so tear down after them — the
    verify here runs AFTER the test's own fixtures have stopped their
    servers/queues, which is exactly the window where a still-alive
    thread means a real shutdown bug, not work in progress. Tracking
    state resets per test so one test's leak (already reported)
    cannot fail its neighbors."""
    from cassmantle_tpu.utils import leak_sentinel

    leak_sentinel.reset()
    leak_sentinel.enable_sentinel()
    snap = leak_sentinel.snapshot()
    try:
        yield
    finally:
        try:
            leak_sentinel.verify(snap)
        finally:
            leak_sentinel.disable_sentinel()
            leak_sentinel.reset()


@pytest.fixture(scope="session")
def cfg():
    return test_config()
