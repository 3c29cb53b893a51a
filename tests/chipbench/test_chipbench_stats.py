"""Metric arithmetic: tails over every request, censored TTFT, the rate
over the window."""
import pytest

import numpy as np

import tinybench  # noqa: F401  (puts the repository root on sys.path)
from chipbench import check, stats
from chipbench.costs import Dims
from chipbench.serving import Flight, Step, Window


def _win():
    w = Window(t_open=100.0, t_close=110.0)
    w.flights = [
        Flight(0, [1] * 4, 3, due=100.0, rid=0, times=[100.5, 100.7, 101.0],
               tokens=[1, 2, 3]),
        Flight(1, [1] * 4, 3, due=101.0, rid=1, times=[103.0],
               tokens=[5]),
        Flight(2, [1] * 4, 3, due=104.0, rid=2),            # still waiting
        Flight(3, [1] * 4, 3, due=105.0, rejected=True),     # refused
        Flight(4, [1] * 4, 3, due=109.0),                    # not submitted
    ]
    w.steps = [Step(100.0, 100.5, [], 1), Step(100.5, 100.7, [5], 0),
               Step(100.7, 101.0, [6], 0), Step(101.0, 103.0, [], 1)]
    return w


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.percentile([4, 1, 3, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_every_due_request_with_censoring():
    got = stats.ttfts(_win())
    assert got == pytest.approx([0.5, 2.0, 6.0, 5.0, 1.0])


def test_itl_pools_every_gap():
    assert stats.itls(_win()) == pytest.approx([0.2, 0.3])


def test_rate_is_window_tokens_over_window_length():
    w = _win()
    assert stats.window_tokens(w) == 4
    assert stats.output_rate(w) == pytest.approx(0.4)


def test_lateness_summary():
    assert stats.lateness_summary([]) is None
    s = stats.lateness_summary([0.1, 0.3, 0.2])
    assert s == {"median_s": 0.2, "max_s": 0.3}


def test_gap_numbers_and_judge():
    gaps = [np.array([0.0, 0.5]), np.array([0.0, 0.0, 0.1])]
    got = check.gap_numbers(gaps)
    assert got["widest_gap"] == 0.5
    assert got["mean_gap"] == pytest.approx(0.12)
    assert got["flip_share"] == pytest.approx(0.4)
    assert check.gap_numbers([])["widest_gap"] == float("inf")
    w = _win()
    dims = Dims(2, 8, 2, 1, 4, 8, 10, 1e4, 1e-6, True, False)
    nums = check.judge(w.flights, w.flights[:1], dims, gaps,
                       {"mean_gap": 0.2})
    assert set(nums) == {"mean_gap", "sampled_tokens", "overlong_requests",
                         "ids_outside_vocab"}
    assert check.holds(nums)
    nums["mean_gap"]["limit"] = 0.1
    assert not check.holds(nums)


def test_sweep_backlog_counts_requests_waiting_for_a_first_token():
    from chipbench.sweep import backlog_at
    w = _win()
    assert backlog_at(w, 100.4) == 1          # 0 is due and waiting
    assert backlog_at(w, 102.0) == 1          # 1 waits until 103.0
    assert backlog_at(w, 109.5) == 3          # 2, 3, 4 never served


def test_sample_keeps_the_longest_and_takes_all_when_asked():
    w = _win()
    w.flights[1].tokens = [5, 6, 7]
    w.flights[1].max_new = 3
    done = [f for f in w.flights if f.done]
    assert len(check.sample(w.flights, 1, "all")) == len(done) == 2
    picked = check.sample(w.flights, 1, 1)
    assert picked == [w.flights[1]]           # 4 + 3 tokens, later index
    w.flights[2].tokens = [1]                 # served, not finished
    assert len(check.sample(w.flights, 1, 3)) == 3
