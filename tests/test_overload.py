"""Overload control plane (ISSUE 13): adaptive admission, priority
tiers, the SLO brownout ladder, and the tier-1 goodput smoke.

Layers covered here:

- AdaptiveLimiter AIMD convergence units on an injectable clock
  (gradient clamp, additive probe, floor/cap, predicted-wait math,
  loop-lag shed);
- BatchingQueue priority-inversion regressions (interactive preempts
  background; the starvation bound keeps background progressing; shed
  order) and the submit-time predicted-late rejection;
- BrownoutLadder trip/recover hysteresis units on an injectable clock,
  the CASSMANTLE_NO_BROWNOUT pin, and the chaos flap lever;
- HTTP contract: /compute_score sheds 503 + COMPUTED Retry-After,
  429s carry the bucket's computed refill time, responses carry
  X-Quality-Degraded while a tier is engaged, /readyz carries the
  overload block, and the hedge path skips peers advertising overload;
- the tier-1 goodput smoke: `bench.py overload_drill` machinery at 2x
  sustained capacity on the CPU geometry — goodput plateaus, accepted
  p99 holds the deadline budget, rejects fail fast with a computed
  Retry-After, and a brownout tier engages AND recovers.
"""

import asyncio
import dataclasses

import pytest
from aiohttp.test_utils import TestClient, TestServer

from cassmantle_tpu import chaos
from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.serving import overload
from cassmantle_tpu.serving.overload import (
    DEFAULT_TIERS,
    AdaptiveLimiter,
    BrownoutLadder,
    BrownoutTier,
    degraded_sampler_cfg,
)
from cassmantle_tpu.serving.queue import (
    PRIORITY_BACKGROUND,
    BatchingQueue,
    OverloadShed,
    QueueFull,
)


@pytest.fixture(autouse=True)
def _reset_overload_globals():
    """The ladder/shed-stamp globals are process-wide (like the chaos
    plan): drop them after every test so a mid-assert failure can never
    leak an engaged tier into another module's pipeline tests."""
    yield
    overload._LADDER = None
    overload._LAST_SHED_T = None


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_limiter(**kw):
    kw.setdefault("target_s", 1.0)
    kw.setdefault("min_limit", 4)
    kw.setdefault("max_limit", 1024)
    kw.setdefault("loop_lag_fn", lambda: 0.0)
    return AdaptiveLimiter("t_overload", **kw)


# -- AdaptiveLimiter units ---------------------------------------------------

def test_limiter_starts_wide_open_and_admits_unloaded():
    """Before any signal the limit is max_limit and the predicted wait
    is 0 — unloaded behavior is exactly the old static bound."""
    lim = make_limiter()
    assert lim.limit() == 1024
    assert lim.predicted_wait_s(100) == 0.0
    assert lim.admit(100, "interactive", deadline_s=0.001) is None


def test_limiter_gradient_decrease_converges_in_one_step():
    """A latency breach clamps the limit toward throughput x target
    (Little's law) in ONE decrease — not log-many cooldowns down from
    max_pending while admitted work burns its deadline budget."""
    clock = FakeClock()
    lim = make_limiter(clock=clock)
    # 8 items served in 0.2s => 40 items/s; target 1.0s => est 40
    lim.observe_batch(wait_s=3.0, service_s=0.2, batch_size=8)
    assert lim.limit() == pytest.approx(40.0)
    # within the cooldown a second breach must NOT decrease again
    lim.observe_batch(wait_s=3.0, service_s=0.2, batch_size=8)
    assert lim.limit() == pytest.approx(40.0)
    # after the cooldown the multiplicative step applies (est is not
    # lower than limit*decrease here)
    clock.advance(2.0)
    lim.observe_batch(wait_s=3.0, service_s=0.2, batch_size=8)
    assert lim.limit() == pytest.approx(40.0 * 0.7)


def test_limiter_additive_increase_and_floor_cap():
    clock = FakeClock()
    lim = make_limiter(clock=clock, min_limit=4)
    # drive to the floor: repeated breaches with tiny throughput
    for _ in range(64):
        clock.advance(2.0)
        lim.observe_batch(wait_s=5.0, service_s=1.0, batch_size=1)
    assert lim.limit() == 4.0
    # healthy traffic probes back up additively, +1 per batch
    for i in range(10):
        lim.observe_batch(wait_s=0.0, service_s=0.1, batch_size=4)
        assert lim.limit() == pytest.approx(4.0 + i + 1)
    # and never exceeds the cap
    for _ in range(3000):
        lim.observe_batch(wait_s=0.0, service_s=0.1, batch_size=4)
    assert lim.limit() == 1024.0


def test_limiter_predicted_wait_and_retry_after():
    lim = make_limiter()
    # 4 items in 0.4s => 0.1 s/item
    lim.observe_batch(wait_s=0.0, service_s=0.4, batch_size=4)
    assert lim.predicted_wait_s(10) == pytest.approx(1.0)
    # Retry-After = predicted wait, floored at 1s
    assert lim.retry_after_s(30) == pytest.approx(3.0)
    assert lim.retry_after_s(1) == 1.0


def test_limiter_rejects_predicted_late_and_sheds_background_first():
    lim = make_limiter(background_fraction=0.5)
    lim.observe_batch(wait_s=0.0, service_s=0.4, batch_size=4)  # .1/item
    # force the limit to its floor (est = 1 item/s * 1s target = 1)
    lim.observe_batch(wait_s=5.0, service_s=1.0, batch_size=1)
    assert lim.limit() == 4.0
    # background sheds at half the limit; interactive still admits
    assert lim.admit(3, PRIORITY_BACKGROUND, None).reason == "background"
    assert lim.admit(3, "interactive", None) is None
    # at the limit interactive sheds too
    assert lim.admit(4, "interactive", None).reason == "overload"
    # predicted-late: deadline shorter than the predicted wait, at a
    # depth the limit itself would still admit
    verdict = lim.admit(2, "interactive", deadline_s=0.05)
    assert verdict is not None and verdict.reason == "predicted_late"


def test_limiter_loop_lag_sheds_background_before_queues():
    lag = [0.0]
    lim = make_limiter(loop_lag_shed_s=0.25, loop_lag_fn=lambda: lag[0])
    assert lim.admit(0, PRIORITY_BACKGROUND, None) is None
    lag[0] = 0.3
    verdict = lim.admit(0, PRIORITY_BACKGROUND, None)
    assert verdict is not None and verdict.reason == "loop_lag"
    # interactive survives moderate lag, sheds only at 4x
    assert lim.admit(0, "interactive", None) is None
    lag[0] = 1.1
    assert lim.admit(0, "interactive", None).reason == "loop_lag"


# -- queue priority + admission ----------------------------------------------

@pytest.mark.asyncio
async def test_interactive_preempts_background_in_dispatch_order():
    """Background items queued FIRST must still dispatch after the
    interactive ones (and ride later batches), not starve them."""
    order = []

    def handler(items):
        order.append(list(items))
        return items

    q = BatchingQueue(handler, max_batch=2, max_delay_ms=5,
                      name="t_prio")
    # park the collector so both tiers fill before any dispatch
    q.start()
    await q.stop()
    q._task = object()
    bg = [asyncio.ensure_future(
        q.submit(f"bg{i}", priority=PRIORITY_BACKGROUND))
        for i in range(2)]
    await asyncio.sleep(0)   # let submits enqueue
    ia = [asyncio.ensure_future(q.submit(f"ia{i}")) for i in range(2)]
    await asyncio.sleep(0)
    q._task = None
    q.start()
    await asyncio.gather(*bg, *ia)
    flat = [x for batch in order for x in batch]
    assert flat.index("ia0") < flat.index("bg0"), flat
    assert flat.index("ia1") < flat.index("bg1"), flat
    await q.stop()


@pytest.mark.asyncio
async def test_starvation_bound_keeps_background_progressing():
    """Under sustained interactive load, a pending background item
    heads a batch after at most ``background_every`` consecutive
    interactive batches — rounds keep rotating (ISSUE 13)."""
    order = []

    def handler(items):
        order.append(list(items))
        return items

    q = BatchingQueue(handler, max_batch=1, max_delay_ms=1,
                      name="t_starve", background_every=3)
    # park the collector; enqueue one background item UNDER a deep
    # interactive backlog
    q.start()
    await q.stop()
    q._task = object()
    bg_fut = asyncio.ensure_future(
        q.submit("bg0", priority=PRIORITY_BACKGROUND))
    await asyncio.sleep(0)
    ia = [asyncio.ensure_future(q.submit(f"ia{i}")) for i in range(10)]
    await asyncio.sleep(0)
    q._task = None
    q.start()
    await asyncio.wait_for(bg_fut, timeout=10.0)
    await asyncio.gather(*ia)
    # the background item dispatched within the bound, not at the tail
    bg_at = next(i for i, b in enumerate(order) if "bg0" in b)
    assert bg_at <= 3, order[:bg_at + 1]
    # and interactive work was never starved by it: everything served
    assert sum(len(b) for b in order) == 11
    await q.stop()


@pytest.mark.asyncio
async def test_submit_rejects_predicted_late_with_computed_retry_after():
    """A submission whose predicted wait already exceeds its deadline
    fails AT SUBMIT (fast) with the computed Retry-After — it never
    sits in the queue burning its budget."""
    import time as _time

    lim = make_limiter()
    lim.observe_batch(wait_s=0.0, service_s=1.0, batch_size=1)  # 1 s/item
    q = BatchingQueue(lambda items: items, max_batch=8, max_delay_ms=1,
                      name="t_predlate", admission=lim)
    q.start()
    await q.stop()
    q._task = object()               # park: keep depth in the queue
    loop = asyncio.get_running_loop()
    for i in range(4):
        q._queue.put_nowait((i, loop.create_future()))
    t0 = _time.monotonic()
    with pytest.raises(OverloadShed) as exc:
        await q.submit("late", deadline_s=0.5)
    assert _time.monotonic() - t0 < 0.05
    assert exc.value.reason == "predicted_late"
    assert exc.value.retry_after_s >= 1.0
    q._task = None
    await q.stop()


@pytest.mark.asyncio
async def test_overload_shed_is_queue_full_and_counts():
    """OverloadShed subclasses QueueFull (legacy degrade paths keep
    working) and the adaptive limit rejection carries Retry-After."""
    assert issubclass(OverloadShed, QueueFull)
    lim = make_limiter(min_limit=1)
    # force a tiny limit
    lim.observe_batch(wait_s=10.0, service_s=1.0, batch_size=1)
    q = BatchingQueue(lambda items: items, max_batch=8, max_delay_ms=1,
                      name="t_shed", admission=lim)
    q.start()
    await q.stop()
    q._task = object()
    loop = asyncio.get_running_loop()
    for i in range(int(lim.limit()) + 1):
        q._queue.put_nowait((i, loop.create_future()))
    with pytest.raises(OverloadShed) as exc:
        await q.submit("x")
    assert exc.value.reason == "overload"
    q._task = None
    await q.stop()


@pytest.mark.asyncio
async def test_chaos_server_admit_forces_shed():
    """The ``server.admit`` fault point (docs/CHAOS.md): a fired rule
    sheds the request with reason ``chaos`` and a Retry-After — the
    drill lever for mis-admission."""
    chaos.configure("server.admit=raise:times=1")
    try:
        q = BatchingQueue(lambda items: items, max_batch=4,
                          max_delay_ms=1, name="t_chaosadmit")
        with pytest.raises(OverloadShed) as exc:
            await q.submit("x")
        assert exc.value.reason == "chaos"
        # rule exhausted (times=1): the next submit serves normally
        assert await q.submit("y") == "y"
        await q.stop()
    finally:
        chaos.disarm()


# -- brownout ladder units ---------------------------------------------------

def make_ladder(clock, **kw):
    kw.setdefault("step_up_dwell_s", 1.0)
    kw.setdefault("step_down_dwell_s", 3.0)
    return BrownoutLadder(DEFAULT_TIERS, clock=clock, **kw)


def burn(name="score_latency", state="burning"):
    return {name: {"state": state, "fast_burn": 5.0, "slow_burn": 2.0}}


def ok(name="score_latency"):
    return {name: {"state": "ok", "fast_burn": 0.1, "slow_burn": 0.2}}


def test_brownout_trips_after_dwell_and_steps_per_dwell(monkeypatch):
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    clock = FakeClock()
    ladder = make_ladder(clock)
    ladder.on_slo_eval(burn())
    assert ladder.tier() == 0          # dwell not yet served
    clock.advance(1.1)
    ladder.on_slo_eval(burn())
    assert ladder.tier() == 1          # sustained burn -> tier 1
    ladder.on_slo_eval(burn())
    assert ladder.tier() == 1          # each rung re-earns its dwell
    clock.advance(1.1)
    ladder.on_slo_eval(burn())
    assert ladder.tier() == 2


def test_brownout_recovers_with_hysteresis(monkeypatch):
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    clock = FakeClock()
    ladder = make_ladder(clock)
    ladder.on_slo_eval(burn())       # arms the burn dwell
    for _ in range(2):
        clock.advance(1.1)
        ladder.on_slo_eval(burn())
    assert ladder.tier() == 2
    # recovery must DWELL: an immediate ok does not step down
    ladder.on_slo_eval(ok())
    assert ladder.tier() == 2
    clock.advance(3.1)
    ladder.on_slo_eval(ok())
    assert ladder.tier() == 1          # one rung per dwell, not a cliff
    # a burn mid-recovery resets the ok-dwell (hysteresis, no flap)
    clock.advance(1.5)
    ladder.on_slo_eval(burn())
    clock.advance(1.5)
    ladder.on_slo_eval(ok())
    assert ladder.tier() == 1
    clock.advance(3.1)
    ladder.on_slo_eval(ok())
    assert ladder.tier() == 0


def test_brownout_watches_only_configured_objectives(monkeypatch):
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    clock = FakeClock()
    ladder = make_ladder(clock, objectives=("score_latency",))
    clock.advance(1.1)
    ladder.on_slo_eval(burn("replication_lag"))
    clock.advance(1.1)
    ladder.on_slo_eval(burn("replication_lag"))
    assert ladder.tier() == 0          # unwatched objective: no tiers


def test_brownout_kill_switch_pins_tier_zero(monkeypatch):
    clock = FakeClock()
    ladder = make_ladder(clock)
    for _ in range(3):
        clock.advance(1.1)
        ladder.on_slo_eval(burn())
    assert ladder.tier() >= 2
    monkeypatch.setenv("CASSMANTLE_NO_BROWNOUT", "1")
    assert ladder.tier() == 0          # pinned immediately on read
    ladder.on_slo_eval(burn())
    assert ladder.status()["tier"] == 0 and ladder.status()["disabled"]
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT")


def test_chaos_brownout_forces_tier_flap(monkeypatch):
    """The ``overload.brownout`` fault point steps the tier up without
    any SLO burn — composed with recovery this drills tier flapping."""
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    clock = FakeClock()
    ladder = make_ladder(clock)
    chaos.configure("overload.brownout=raise:times=2")
    try:
        ladder.on_slo_eval(ok())
        assert ladder.tier() == 1
        ladder.on_slo_eval(ok())
        assert ladder.tier() == 2
        # rule exhausted: normal recovery takes over
        clock.advance(3.1)
        ladder.on_slo_eval(ok())
        clock.advance(0.1)
        ladder.on_slo_eval(ok())
        assert ladder.tier() == 2      # ok-dwell restarted post-chaos
        clock.advance(3.1)
        ladder.on_slo_eval(ok())
        assert ladder.tier() == 1
    finally:
        chaos.disarm()


def test_degraded_sampler_cfg_respects_invariants():
    cfg = _tiny_config()
    s = dataclasses.replace(cfg.sampler, num_steps=50, image_size=512)
    tier = BrownoutTier("t", num_steps_scale=0.6, image_size_scale=0.5)
    d = degraded_sampler_cfg(s, tier)
    assert d.num_steps == 30
    assert d.image_size == 256 and d.image_size % 16 == 0
    # the identity tier is a no-op config (callers skip the degraded
    # path => tier 0 is bit-for-bit the old behavior)
    assert degraded_sampler_cfg(s, BrownoutTier("full")) == s


def test_degraded_sampler_cfg_few_step_tier(monkeypatch):
    """The few-step tier swaps the sampling loop for the consistency
    student at 4 steps, carries the resolution delta of later rungs,
    ONLY engages
    when the deployment declares a distilled student checkpoint
    (consistency_available — an undistilled eps-net sampled 4-step is
    near-noise), and defers to the CASSMANTLE_NO_CONSISTENCY kill
    switch (degrading the TEACHER schedule instead)."""
    monkeypatch.delenv("CASSMANTLE_NO_CONSISTENCY", raising=False)
    from cassmantle_tpu.serving.overload import (
        CONSISTENCY_BROWNOUT_STEPS,
    )

    cfg = _tiny_config()
    # a stock (undistilled) deployment: the few-step delta must NOT
    # engage — the rung degrades like the previous one instead
    stock = dataclasses.replace(cfg.sampler, num_steps=50,
                                image_size=512)
    d_stock = degraded_sampler_cfg(
        stock, BrownoutTier("t", num_steps_scale=0.6, consistency=True))
    assert not d_stock.consistency and d_stock.num_steps == 30
    s = dataclasses.replace(cfg.sampler, num_steps=50, image_size=512,
                            consistency_available=True)
    tier = BrownoutTier("t", num_steps_scale=0.6, consistency=True)
    d = degraded_sampler_cfg(s, tier)
    assert d.consistency and d.num_steps == CONSISTENCY_BROWNOUT_STEPS
    assert d.image_size == 512                    # few-step BEFORE low-res
    low = BrownoutTier("t2", consistency=True, image_size_scale=0.5)
    assert degraded_sampler_cfg(s, low).image_size == 256
    # a config already serving the student keeps its step count
    s_lcm = dataclasses.replace(cfg.sampler, consistency=True,
                                num_steps=2)
    assert degraded_sampler_cfg(s_lcm, tier).num_steps == 2
    # kill switch: the tier degrades the teacher path instead
    monkeypatch.setenv("CASSMANTLE_NO_CONSISTENCY", "1")
    d_off = degraded_sampler_cfg(s, tier)
    assert not d_off.consistency and d_off.num_steps == 30
    s_lcm4 = dataclasses.replace(cfg.sampler, consistency=True,
                                 num_steps=4,
                                 consistency_teacher_steps=50)
    d_off2 = degraded_sampler_cfg(s_lcm4, tier)
    assert not d_off2.consistency and d_off2.num_steps == 30


def test_peer_advert_reflects_shed_and_tier(monkeypatch):
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    overload._LAST_SHED_T = None
    assert "shed" not in overload.peer_advert()
    overload.note_shed()
    assert overload.peer_advert().get("shed") == 1
    overload._LAST_SHED_T = None


# -- brownout actuation ------------------------------------------------------

def test_pipeline_actuates_brownout_tier_and_reverts_bit_exact(
        monkeypatch):
    """The tier-keyed degraded sampler: a resolution/step tier changes
    the served image (smaller, fewer steps), each engaged delta
    compiles ONCE (cached by key), and tier 0 returns the untouched
    default path — bit-for-bit the pre-brownout output."""
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    cfg = _tiny_config()
    pipe = Text2ImagePipeline(cfg)
    full = pipe.generate(["a storm rolls in"], seed=1)
    assert full.shape[1] == cfg.sampler.image_size
    clock = FakeClock()
    ladder = make_ladder(clock)
    monkeypatch.setattr(overload, "_LADDER", ladder)
    with ladder._lock:
        ladder._step_to(4, "test")  # low-res: few-step student, size x0.5
    degraded = pipe.generate(["a storm rolls in"], seed=1)
    assert degraded.shape[1] == max(32, cfg.sampler.image_size // 2)
    assert len(pipe._tier_fns) == 1
    pipe.generate(["a storm rolls in"], seed=1)
    assert len(pipe._tier_fns) == 1     # same delta -> cached variant
    with ladder._lock:
        ladder._step_to(0, "test")
    back = pipe.generate(["a storm rolls in"], seed=1)
    assert (back == full).all()         # tier 0 = the old path, bitwise


@pytest.mark.asyncio
async def test_fake_backend_and_blur_ladder_honor_tiers(monkeypatch):
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    from cassmantle_tpu.engine.content import FakeContentBackend

    clock = FakeClock()
    ladder = make_ladder(clock)
    monkeypatch.setattr(overload, "_LADDER", ladder)
    backend = FakeContentBackend(image_size=64)
    content = await backend.generate("seed", True)
    assert content.image.shape[0] == 64
    assert overload.blur_bucket_px() == 0.5
    with ladder._lock:
        ladder._step_to(5, "test")      # coarse-blur tier: all deltas
    content = await backend.generate("seed", True)
    assert content.image.shape[0] == 32
    assert overload.blur_bucket_px() == 2.0
    with ladder._lock:
        ladder._step_to(0, "test")


def test_blur_quantize_coarse_tiers_round_up_only(monkeypatch):
    """Review regression: the coarse-blur tier must only ever ADD
    blur. At the default quantum the legacy round-to-nearest buckets
    are bit-for-bit; a coarsened quantum rounds UP, so a near-winner's
    0.9 px reveal radius becomes a 2.0 px bucket — never the SHARP
    0.0 bucket nearest-rounding would have served."""
    monkeypatch.delenv("CASSMANTLE_NO_BROWNOUT", raising=False)
    from cassmantle_tpu.serving.overload import quantize_blur_radius

    monkeypatch.setattr(overload, "_LADDER", None)
    assert quantize_blur_radius(0.6) == 0.5     # legacy nearest
    assert quantize_blur_radius(0.2) == 0.0     # legacy sharp zone
    clock = FakeClock()
    ladder = make_ladder(clock)
    monkeypatch.setattr(overload, "_LADDER", ladder)
    with ladder._lock:
        ladder._step_to(5, "test")              # quantum 2.0 px
    assert quantize_blur_radius(0.9) == 2.0     # up, not down to sharp
    assert quantize_blur_radius(2.1) == 4.0
    assert quantize_blur_radius(0.0) == 0.0     # a true winner stays sharp
    with ladder._lock:
        ladder._step_to(0, "test")


@pytest.mark.asyncio
async def test_combined_priority_depth_bounded_at_max_pending():
    """Review regression: two priority tiers must not quietly double
    the static max_pending wall — the COMBINED depth is bounded."""
    q = BatchingQueue(lambda items: items, max_batch=1, max_delay_ms=1,
                      max_pending=2, name="t_combined")
    q.start()
    await q.stop()
    q._task = object()
    loop = asyncio.get_running_loop()
    q._queue.put_nowait((0, loop.create_future()))
    q._bg_queue.put_nowait((1, loop.create_future()))
    with pytest.raises(QueueFull):
        await q.submit(2)
    with pytest.raises(QueueFull):
        await q.submit(3, priority=PRIORITY_BACKGROUND)
    q._task = None
    await q.stop()


def test_transient_limiter_not_registered_in_status_block():
    """Review regression: constructing a limiter (config probes, lock
    tests) must not leak a phantom queue row into /readyz; only
    make_admission-wired limiters register."""
    AdaptiveLimiter("t_phantom_probe")
    assert "t_phantom_probe" not in overload.status_block()["queues"]
    from cassmantle_tpu.serving.overload import make_admission

    lim = make_admission("t_wired_probe", _tiny_config())
    assert lim is not None
    assert "t_wired_probe" in overload.status_block()["queues"]
    del overload._LIMITERS["t_wired_probe"]


# -- rate-limit Retry-After (satellite) --------------------------------------

def test_rate_limit_retry_after_computed_from_refill():
    from cassmantle_tpu.server.ratelimit import RateLimiter, TokenBucket

    bucket = TokenBucket(rate=2.0)
    while bucket.allow():
        pass
    # <1 token left at 2 tokens/s: refill to one token takes <= 0.5s
    ra = bucket.retry_after_s()
    assert 0.0 < ra <= 0.5
    limiter = RateLimiter()
    principal = (("1.2.3.4", "lobby"))
    assert limiter.allow(principal, "/compute_score", 1.0)
    assert not limiter.allow(principal, "/compute_score", 1.0)
    assert 0.0 < limiter.retry_after_s(principal, "/compute_score") <= 1.0
    # unknown bucket (evicted): 0, caller floors the header at 1
    assert limiter.retry_after_s(("9.9.9.9", "x"), "/y") == 0.0


# -- HTTP contract -----------------------------------------------------------

def _drill_cfg(batch_ms=40.0):
    cfg = _tiny_config()
    return cfg.replace(
        game=dataclasses.replace(cfg.game, time_per_prompt=30.0,
                                 rate_limit_default=1e6,
                                 rate_limit_api=1e6),
        serving=dataclasses.replace(
            cfg.serving, fake_score_batch_ms=batch_ms,
            score_batch_sizes=(4,), max_queue_delay_ms=2.0,
            submit_deadline_s=1.0, queue_latency_target_s=0.2,
            admission_min_pending=2, loop_lag_shed_s=10.0),
    )


async def _fabric_client(cfg):
    from cassmantle_tpu.server.app import build_fabric, create_app

    fabric = build_fabric(cfg, fake=True)
    app = create_app(fabric, cfg, start_timer=False)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, fabric


@pytest.mark.asyncio
async def test_compute_score_sheds_503_with_computed_retry_after():
    """The client-visible overload contract: a shed /compute_score is
    503 + computed Retry-After + X-Overload-Shed, answered fast."""
    import time as _time

    client, _ = await _fabric_client(_drill_cfg())
    try:
        await client.get("/init?session=s1")
        res = await client.get("/fetch/contents?session=s1")
        masks = (await res.json())["prompt"]["masks"] or [0]
        guess = {"inputs": {str(masks[0]): "w"}}
        # arm AFTER warmup: the fault point must fire on OUR submit
        chaos.configure("server.admit=raise:times=1")
        try:
            t0 = _time.monotonic()
            res = await client.post("/compute_score?session=s1",
                                    json=guess)
            elapsed = _time.monotonic() - t0
            assert res.status == 503
            assert int(res.headers["Retry-After"]) >= 1
            assert res.headers["X-Overload-Shed"] == "chaos"
            assert elapsed < 0.5     # no queueing, no deadline burn
            # next request is admitted and served normally
            res = await client.post("/compute_score?session=s1",
                                    json=guess)
            assert res.status == 200
        finally:
            chaos.disarm()
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_quality_degraded_header_and_readyz_overload_block():
    client, fabric = await _fabric_client(_drill_cfg())
    try:
        res = await client.get("/readyz")
        block = (await res.json())["overload"]
        assert block["brownout"]["tier"] == 0
        assert "score" in block["queues"]
        assert "limit" in block["queues"]["score"]
        res = await client.get("/init")
        assert "X-Quality-Degraded" not in res.headers
        # engage a tier directly on the live ladder
        ladder = overload.ladder()
        with ladder._lock:
            ladder._step_to(2, "test")
        res = await client.get("/init")
        assert res.headers["X-Quality-Degraded"] == "tier-2"
        res = await client.get("/readyz")
        block = (await res.json())["overload"]
        assert block["brownout"]["tier"] == 2
        with ladder._lock:
            ladder._step_to(0, "test")
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_hedge_skips_peer_advertising_overload():
    """A peer whose heartbeat advertises shedding must not be hedged
    into (counted score.hedge_skipped_overloaded); with no other peer
    the ladder bottoms out at marked floor scores."""
    from cassmantle_tpu.utils.logging import metrics

    client, fabric = await _fabric_client(_drill_cfg(batch_ms=0.0))
    try:
        await client.get("/init?session=s1")

        async def table():
            return {
                fabric.worker_id: {"info": {"addr": ""}, "stale": False,
                                   "age_s": 0.0},
                "sick-peer": {
                    "info": {"addr": "http://127.0.0.1:1",
                             "shed": 1},
                    "stale": False, "age_s": 0.0},
            }

        fabric.membership.table = table
        breaker = fabric.supervisor.score_breaker
        for _ in range(breaker.failure_threshold):
            breaker.record_failure()
        before = metrics.counter_total("score.hedge_skipped_overloaded")
        attempts = metrics.counter_total("score.hedge_attempts")
        res = await client.post("/compute_score?session=s1",
                                json={"inputs": {"0": "w"}})
        assert res.status == 200
        assert res.headers["X-Score-Degraded"] == "floor"
        assert metrics.counter_total(
            "score.hedge_skipped_overloaded") == before + 1
        # the sick peer was never dialed
        assert metrics.counter_total("score.hedge_attempts") == attempts
        breaker.record_success()
    finally:
        await client.close()


# -- the tier-1 goodput smoke (acceptance) -----------------------------------

def test_overload_drill_goodput_plateaus_and_brownout_cycles():
    """ISSUE 13 acceptance on the CPU smoke geometry: at 2x sustained
    capacity through the real fabric, goodput plateaus (>= 60% of the
    known single-arm capacity and >= the baseline phase's goodput),
    accepted p99 stays inside the deadline budget (1.5s), rejected
    requests fail fast with a computed Retry-After >= 1s, and at least
    one brownout tier engages under burn and steps back down by drill
    end (hysteresis observed end to end)."""
    from bench import overload_drill_run

    raw = overload_drill_run(batch_ms=100.0, bucket=4, base_port=8581,
                             baseline_s=2.5, overload_s=4.0,
                             recovery_s=4.5)
    phases = raw["phases"]
    base, over = phases["baseline"], phases["overload"]
    capacity = raw["capacity_per_s"]
    # plateau, not collapse: the 2x phase keeps serving at capacity
    # scale (0.6 leaves headroom for container CPU jitter; collapse
    # looks like ~0 goodput with every request expiring at deadline)
    assert over["goodput_per_s"] >= 0.6 * capacity, raw
    assert over["goodput_per_s"] >= base["goodput_per_s"], raw
    assert over["errors"] == 0, raw
    # accepted work keeps its latency contract (deadline budget 1.5s)
    accepted_p99 = sorted(over["accepted_ms"])[
        int(len(over["accepted_ms"]) * 0.99) - 1]
    assert accepted_p99 <= 1500.0, accepted_p99
    # rejected work fails fast with the computed Retry-After
    assert over["rejected_ms"], "2x load produced no rejections"
    rejected_p50 = sorted(over["rejected_ms"])[
        len(over["rejected_ms"]) // 2]
    assert rejected_p50 < 100.0, rejected_p50
    assert over["retry_after_s"] and min(over["retry_after_s"]) >= 1.0
    # the brownout ladder engaged under burn and recovered (hysteresis)
    assert over["max_tier"] >= 1.0, raw
    assert raw["final_tier"] < over["max_tier"], raw
    # /readyz carried the overload block throughout
    assert "brownout" in raw["overload_block"]


def test_no_brownout_env_keeps_drill_at_tier_zero(monkeypatch):
    """CASSMANTLE_NO_BROWNOUT pins tier 0 through the whole stack: the
    ladder ignores burn, no header, gauge stays 0. (The unloaded
    bit-for-bit contract is the tier-0 default path — pinned by the
    degraded_sampler_cfg identity test above and by every pre-existing
    serving test running at tier 0.)"""
    monkeypatch.setenv("CASSMANTLE_NO_BROWNOUT", "1")
    clock = FakeClock()
    ladder = make_ladder(clock)
    for _ in range(4):
        clock.advance(2.0)
        ladder.on_slo_eval(burn())
    assert ladder.tier() == 0
    assert ladder.status()["disabled"] is True
