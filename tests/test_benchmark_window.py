"""The benchmark window's closing rule as a tier-1 test: every claim on
``rounds_per_s`` of a program that completes its rounds in bursts (four
images back to back, then one LM program) rests on
``benchmarks/harness/runner.py::window_close`` holding whole orbits. The
cases are the benchmark's own (``benchmarks/tests/test_window.py``, which
the tier-1 command does not collect), run here under this file's name."""

from benchmarks.tests.test_window import (  # noqa: F401
    test_an_invalid_round_of_the_opener_does_not_close_the_window,
    test_an_opener_that_only_fails_closes_on_any_room_after_the_slack,
    test_bursts_read_rooms_over_the_orbit_at_every_phase,
    test_evenly_spaced_completions_read_the_same_under_both_rules,
    test_open_until_a_completion_closes_it,
    test_the_old_rule_followed_the_phase_by_over_one_percent,
)
