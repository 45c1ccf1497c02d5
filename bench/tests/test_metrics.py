"""The end-to-end metrics: tails over every request; rates over all the
work and all of the window."""
from types import SimpleNamespace

import pytest

from bench import harness, stats

import conftest  # noqa: F401  (puts the checkout on sys.path)


def rec(due, times, prompt=8, done=True):
    planned = SimpleNamespace(prompt=[0] * prompt, max_new_tokens=len(times))
    req = SimpleNamespace(uid=id(planned), done=done, out_tokens=[0] * len(times))
    return harness.Record(planned, req, due, due, list(times))


def run(records, t_open=0.0, t_close=10.0, attempted=None, t_end=None,
        judged_until=None):
    t_end = t_end if t_end is not None else t_close
    return harness.RunData(spec=None, seconds=t_close - t_open, setup_s=1.5,
                           t_open=t_open, t_close=t_close, t_end=t_end,
                           judged_until=judged_until if judged_until is not None else t_end,
                           records=records,
                           attempted=records if attempted is None else attempted,
                           peaks={})


def test_percentile_is_nearest_rank_over_every_value():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 90) == 90
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_itl_covers_every_gap_of_every_request():
    # 4 requests x 19 gaps of 0.1 s; 7 of the 76 gaps stretched to 0.6 s
    # leave the 90th percentile (rank 69) short, and one more long gap
    # anywhere makes it long
    recs = [rec(0.0, [0.1 * k for k in range(1, 21)]) for _ in range(4)]

    def stretch(r, k):
        r.times[k:] = [t + 0.5 for t in r.times[k:]]

    for k in (3, 7, 11, 15):
        stretch(recs[0], k)
    for k in (2, 5, 9):
        stretch(recs[3], k)
    read = harness.load_reader("itl_p90_ms")
    assert read(run(recs, t_close=20.0)) == pytest.approx(100.0)
    stretch(recs[2], 9)
    assert read(run(recs, t_close=20.0)) == pytest.approx(600.0)


def test_itl_ignores_gaps_that_end_before_the_window():
    recs = [rec(0.0, [-2.0, -1.0, 0.5, 0.6])]
    read = harness.load_reader("itl_p90_ms")
    assert read(run(recs)) == pytest.approx(1500.0)      # gaps 1.5 and 0.1


def test_itl_ignores_gaps_after_the_judged_span():
    # a closed loop steps on after the window for the check; those gaps
    # are no run's measure
    recs = [rec(0.0, [0.5, 0.6, 0.7, 12.0, 30.0])]
    read = harness.load_reader("itl_p90_ms")
    assert read(run(recs, t_end=31.0, judged_until=10.0)) == pytest.approx(100.0)
    assert read(run(recs, t_end=31.0)) == pytest.approx(18000.0)


def test_rate_is_all_tokens_in_the_window_over_the_whole_window():
    recs = [rec(0.0, [1.0, 2.0, 3.0]), rec(0.0, [9.0, 11.0]), rec(0.0, [-1.0, 4.0])]
    read = harness.load_reader("tokens_per_s")
    assert read(run(recs, t_open=0.0, t_close=10.0)) == pytest.approx(5 / 10.0)


def test_setup_is_reported_as_measured():
    assert harness.load_reader("setup_s")(run([])) == 1.5
