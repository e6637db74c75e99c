"""The window's closing rule (runner.window_close) over synthetic
completion lists: no device, no clock. The old rule, the first completion
of ANY room at or after ``--seconds``, is kept here as the case that shows
why it went: on completions that come in bursts it reads a steady orbit as
a number that follows the phase the window opened at."""

import pytest

from benchmarks.harness.runner import window_close

ROOMS = 4
#: PR 35's programs gathered the four rooms' prompts into one LM dispatch:
#: four images complete at these offsets of every orbit, then none for one
#: LM program (ISSUE 36: its lfm2_rollover orbit 808 ms, qwen3next 655.5)
BURST_MS = (0.0, 87.0, 175.0, 263.0)
SECONDS = [9.5 + 0.05 * i for i in range(21)]


def bursts(orbit_s: float, orbits: int = 40) -> list:
    return [(k * orbit_s + off / 1e3, room, True)
            for k in range(orbits) for room, off in enumerate(BURST_MS)]


def evenly(gap_s: float = 0.270, n: int = 200) -> list:
    return [(i * gap_s, i % ROOMS, True) for i in range(n)]


def old_close(completions: list, n_open: int, seconds: float) -> int:
    t_open = completions[n_open][0]
    return next(i for i in range(n_open + 1, len(completions))
                if completions[i][0] - t_open >= seconds)


def rate(completions: list, n_open: int, index: int) -> float:
    """rounds_per_s as runner.results counts it."""
    t_open, t_close = completions[n_open][0], completions[index][0]
    rounds = sum(1 for t, _, ok in completions if ok and t_open < t <= t_close)
    return rounds / (t_close - t_open)


@pytest.mark.parametrize("n_open", range(ROOMS))
def test_evenly_spaced_completions_read_the_same_under_both_rules(n_open):
    done = evenly()
    for seconds in SECONDS:
        index, rule = window_close(done, n_open, seconds, 30.0)
        assert rule == "opener" and done[index][1] == done[n_open][1]
        new = rate(done, n_open, index)
        assert new == pytest.approx(
            rate(done, n_open, old_close(done, n_open, seconds)), abs=1e-9)
        assert new == pytest.approx(1 / 0.270, abs=1e-9)
        # up to three rounds longer than the old window, never shorter
        assert 0 <= index - old_close(done, n_open, seconds) <= ROOMS - 1


@pytest.mark.parametrize("orbit_s", [0.808, 0.6555],
                         ids=["lfm2_808ms", "qwen3next_655ms"])
@pytest.mark.parametrize("n_open", range(ROOMS))
def test_bursts_read_rooms_over_the_orbit_at_every_phase(orbit_s, n_open):
    done = bursts(orbit_s)
    for seconds in SECONDS:
        index, rule = window_close(done, n_open, seconds, 30.0)
        assert rule == "opener"
        assert rate(done, n_open, index) == pytest.approx(
            ROOMS / orbit_s, rel=1e-9)


@pytest.mark.parametrize("orbit_s", [0.808, 0.6555],
                         ids=["lfm2_808ms", "qwen3next_655ms"])
def test_the_old_rule_followed_the_phase_by_over_one_percent(orbit_s):
    """At the committed --seconds, and at most other lengths: only where
    --seconds later falls into the one LM program between two bursts do
    the four phases close on the same completion."""
    done = bursts(orbit_s)

    def spread(seconds: float) -> float:
        rates = [rate(done, n, old_close(done, n, seconds))
                 for n in range(ROOMS)]
        return (max(rates) - min(rates)) / (ROOMS / orbit_s)

    assert spread(10.0) > 0.03
    assert sum(spread(s) > 0.01 for s in SECONDS) > len(SECONDS) // 2


def test_an_opener_that_only_fails_closes_on_any_room_after_the_slack():
    done = [c for c in evenly() if c[1] != 1 or c[0] < 1.0]
    done += [(t, 1, False) for t in (5.1, 11.3, 14.9)]
    done.sort()
    n_open = next(i for i, c in enumerate(done) if c[1] == 1)
    index, rule = window_close(done, n_open, 10.0, 3.0)
    assert rule == "any" and done[index][1] != 1
    t_open = done[n_open][0]
    assert done[index][0] - t_open >= 13.0 > done[index - 1][0] - t_open


def test_an_invalid_round_of_the_opener_does_not_close_the_window():
    done = evenly()
    first_due = next(i for i, c in enumerate(done)
                     if c[1] == 0 and c[0] >= 10.0)
    done[first_due] = (done[first_due][0], 0, False)
    index, rule = window_close(done, 0, 10.0, 30.0)
    assert rule == "opener" and index == first_due + ROOMS


def test_open_until_a_completion_closes_it():
    done = evenly()
    cut = old_close(done, 0, 10.0)
    assert window_close(done[:cut + 1], 0, 10.0, 30.0) is None
    assert window_close(done[:1], 0, 10.0, 30.0) is None
