"""End-to-end arithmetic on synthetic records."""

import math

from bench import e2e
from bench.driver import ReqRecord, RunRecord, StepRecord
from bench.traffic import Turn


def rec(due, times, *, refused=False, truncated=False, submit=None):
    r = ReqRecord(Turn(0, 0, due, [1, 2], 4, False), due,
                  submit=due if submit is None else submit)
    r.token_times = list(times)
    r.refused, r.truncated = refused, truncated
    r.done = bool(times) or refused
    return r


def run(reqs, window=(10.0, 20.0), open_loop=True, gave_up=30.0):
    return RunRecord(reqs=reqs, steps=[], window=window,
                     due_window=window, gave_up=gave_up, late_s=[],
                     prefix_before={}, prefix_after={},
                     admitted_prompt_tokens=0, waiting_at_open=0,
                     waiting_at_close=0, compiles_in_window=0,
                     open_loop=open_loop)


def test_ttft_counts_from_the_due_time_and_failures_wait():
    reqs = [rec(9.0, [9.5]),                  # due before the window
            rec(10.0, [10.5, 11.0], submit=10.2),
            rec(12.0, [15.0]),
            rec(13.0, [], refused=True),
            rec(14.0, [14.4], truncated=True),
            rec(19.0, []),                    # never got a token
            rec(20.0, [20.1])]                # due after the window
    r = run(reqs)
    ttft, failed = e2e.ttft_s(r)
    # failures count as the wait they got, until the run gave up at 30
    assert sorted(ttft) == sorted([0.5, 3.0, 17.0, 16.0, 11.0])
    assert failed == 3
    assert e2e.attempted_failed(r) == (5, 3)
    assert math.isclose(e2e.metric("ttft_p90_ms", r),
                        1e3 * e2e.percentile(ttft, 90))
    assert math.isclose(e2e.metric("ttft_p75_ms", r),
                        1e3 * e2e.percentile(ttft, 75))


def test_itl_takes_gaps_with_both_tokens_in_the_window():
    reqs = [rec(5.0, [9.0, 10.5, 11.0, 13.0]),     # 9.0 is before
            rec(10.0, [12.0, 12.25, 21.0]),        # 21.0 is after
            rec(11.0, [19.0, 20.0])]
    gaps = sorted(e2e.itl_s(run(reqs)))
    assert gaps == sorted([0.5, 2.0, 0.25, 1.0])


def test_out_tok_s_counts_tokens_in_the_window_over_its_length():
    reqs = [rec(5.0, [9.0, 10.0, 10.5, 11.0]), rec(6.0, [19.5, 20.0, 20.5])]
    r = run(reqs, window=(10.0, 20.0))
    # (10, 20]: 10.5, 11.0, 19.5, 20.0
    assert e2e.out_tok_s(r) == 4 / 10.0


def test_backlog_counts_no_queued_request_as_failed():
    reqs = [rec(5.0, [11.0]), rec(6.0, [], submit=15.0),
            rec(7.0, [], truncated=True, submit=12.0)]
    r = run(reqs, open_loop=False)
    assert e2e.attempted_failed(r) == (3, 1)


def test_empty_percentile_is_nan():
    assert math.isnan(e2e.percentile([], 90))


def test_steps_are_kept_apart():
    s = StepRecord(1.0, 2.0, True, [(128, 0)], 1)
    assert s.prefill and s.fed == [(128, 0)]
