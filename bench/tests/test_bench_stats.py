"""Window arithmetic: every token in the window counts, tails are taken
over every sample, and time to first token runs from the due time."""
import pytest

from bench import stats


def test_rate_counts_unfinished_requests_and_nothing_outside():
    times = {
        0: [0.5, 1.5, 2.5],          # one token before the window
        1: [1.0, 1.9],               # finished inside
        2: [2.9, 3.0, 3.5],          # still running at the close (3.0)
        3: [],                       # never started
    }
    assert stats.tokens_in_window(times, 1.0, 3.0) == 5
    assert stats.rate(times, 1.0, 3.0) == pytest.approx(2.5)


def test_gaps_need_both_tokens_in_the_window():
    times = {0: [0.5, 1.5, 2.0, 3.5], 1: [1.2, 1.4]}
    assert sorted(stats.inter_token_gaps(times, 1.0, 3.0)) == \
        pytest.approx([0.2, 0.5])


def test_tail_over_all_samples():
    xs = list(range(1, 201))          # 1..200
    assert stats.tail(xs, 0.95) == 190
    assert stats.tail(list(reversed(xs)), 0.90) == 180


def test_tail_refuses_too_few_beyond():
    with pytest.raises(stats.TooFewSamples):
        stats.tail(range(99), 0.90)      # 9 beyond the p90
    assert stats.tail(range(100), 0.90) == 89


def test_first_token_wait_runs_from_due_time():
    due = {0: 1.0, 1: 1.0, 2: 5.0, 3: 0.0}
    times = {0: [1.25, 1.3], 1: [2.0], 2: [5.5], 3: [0.1]}
    waits = stats.first_token_waits(times, due, 1.0, 5.0)
    assert sorted(waits) == pytest.approx([0.25, 1.0])


def test_gc_log_counts_the_passes_that_start_in_the_window():
    import gc
    import time

    from bench import harness
    with harness.GcLog() as log:
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
    assert log.counters(t0, t1)["gc_full_passes"] >= 1
    log.passes = [(0, 0.5, 0.6), (2, 1.0, 1.25), (1, 2.0, 2.01),
                  (2, 3.0, 3.5)]
    assert log.counters(1.0, 3.0) == {"gc_passes": 2, "gc_full_passes": 1,
                                      "gc_longest_s": 0.25}
