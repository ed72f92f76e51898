"""End-to-end arithmetic over a run's records: what a user sees.

  ttft   from each turn's *due* time to the return of the engine step that
         produced its first token, over every turn due in the window.  A
         turn that is refused, truncated or never gets a first token has
         failed; its latency counts as the wait it got, up to when the run
         gave up on it.
  itl    every gap between consecutive output tokens of one request, both
         tokens inside the window, over all requests.
  rate   output tokens appended inside the window / window seconds.

Percentiles are numpy's linear interpolation over all values.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def percentile(values: List[float], q: float) -> float:
    """NaN where there is nothing to take it over."""
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(run) -> list:
    lo, hi = run.due_window
    return [r for r in run.reqs if lo <= r.due < hi]


def failed(rec) -> bool:
    return rec.refused or rec.truncated or not rec.token_times


def ttft_s(run) -> Tuple[List[float], int]:
    """TTFT of each turn due in the window (seconds), and how many failed."""
    out, n_failed = [], 0
    for r in due_in_window(run):
        if failed(r):
            n_failed += 1
            out.append(run.gave_up - r.due)
        else:
            out.append(r.token_times[0] - r.due)
    return out, n_failed


def itl_s(run) -> List[float]:
    lo, hi = run.window
    gaps: List[float] = []
    for r in run.reqs:
        ts = [t for t in r.token_times if lo <= t <= hi]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps


def out_tok_s(run) -> float:
    lo, hi = run.window
    n = sum(1 for r in run.reqs for t in r.token_times if lo < t <= hi)
    return n / (hi - lo)


def attempted_failed(run) -> Tuple[int, int]:
    """Open loop: the turns due in the window.  Backlog: the requests
    submitted before the window closed that were still live in it."""
    if run.open_loop:
        reqs = due_in_window(run)
    else:
        lo, hi = run.window
        reqs = [r for r in run.reqs if r.submit < hi and (
            not r.token_times or r.token_times[-1] > lo)]
    return len(reqs), sum(1 for r in reqs if failed(r) and (
        r.refused or r.truncated or run.open_loop))


def metric(name: str, run) -> float:
    """An end-to-end metric by its name: ``ttft_p<q>_ms``, ``itl_p<q>_ms``
    (the q-th percentile, in ms) or ``out_tok_s``."""
    if name == "out_tok_s":
        return out_tok_s(run)
    kind, q, unit = name.split("_")
    if unit != "ms" or not q.startswith("p"):
        raise KeyError(name)
    values = {"ttft": lambda: ttft_s(run)[0], "itl": lambda: itl_s(run)}
    return percentile(values[kind](), float(q[1:])) * 1e3


def samples(run) -> Dict[str, int]:
    """How many values each statistic was taken over."""
    return {"ttft": len(due_in_window(run)), "itl": len(itl_s(run)),
            "out_tokens": int(round(out_tok_s(run)
                                    * (run.window[1] - run.window[0])))}
