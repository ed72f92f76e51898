"""The program's own spans, reduced to what the per-layer metrics of the
engine step loop read.

While a profiler session is active, the engine records each phase of a
step as a ``serve.*`` span, ``(name, t0_ns, t1_ns, args)`` on
``time.perf_counter_ns``, into a process-wide ring
(``repro.obs.trace.program_tracer``).  The harness's traced window is that
session, and ``RunRecord.window`` is on the same clock in seconds.  A
program without the ring reads as no spans, and each metric is left out.
"""

from __future__ import annotations

from bisect import bisect_left
from typing import Dict, List, Optional, Tuple

import numpy as np

from .tracing import _self_times

Span = Tuple[str, int, int, Optional[dict]]

STEP = "serve.step"


def ring() -> List[Span]:
    """Every span the program's ring holds; none from a program that
    keeps no ring."""
    try:
        from repro.obs import trace
        return trace.program_tracer().spans()
    except (ImportError, AttributeError):
        return []


def window_spans(run, spans: Optional[List[Span]] = None) -> List[Span]:
    """The spans that start and end inside the run's window, by start."""
    lo, hi = (t * 1e9 for t in run.window)
    spans = ring() if spans is None else spans
    return sorted((s for s in spans if lo <= s[1] and s[2] <= hi),
                  key=lambda s: (s[1], -s[2]))


def wide_steps(spans: List[Span]) -> List[Dict]:
    """Each ``serve.step`` that ran the C-wide program (width > 1), with
    its spans: ``ns`` per name (summed over repeats, the step included),
    ``self_ns`` per name (less what nests inside it), and ``bytes`` per
    name from the spans' args."""
    starts = [s[1] for s in spans]
    out = []
    for name, t0, t1, args in spans:
        if name != STEP or (args or {}).get("width", 0) <= 1:
            continue
        inner = []
        for s in spans[bisect_left(starts, t0):]:
            if s[1] > t1:
                break
            if s[2] <= t1:
                inner.append(s)
        ns: Dict[str, float] = {}
        nbytes: Dict[str, int] = {}
        for n, a, b, ar in inner:
            ns[n] = ns.get(n, 0) + (b - a)
            if ar and "bytes" in ar:
                nbytes[n] = nbytes.get(n, 0) + ar["bytes"]
        out.append({"ns": ns, "bytes": nbytes,
                    "self_ns": _self_times([(a, b, n)
                                            for n, a, b, _ in inner])})
    return out


def _mean_ms(xs: List[float]) -> Optional[float]:
    return float(np.mean(xs)) / 1e6 if xs else None


def logits_to_host_ms(run, spans: Optional[List[Span]] = None
                      ) -> Optional[float]:
    """Mean time of ``serve.logits_to_host`` over the window's C-wide
    steps."""
    return _mean_ms([s["ns"].get("serve.logits_to_host", 0)
                     for s in wide_steps(window_spans(run, spans))])


def host_step_ms(run, spans: Optional[List[Span]] = None) -> Optional[float]:
    """Mean over the window's C-wide steps of ``serve.step`` less
    ``serve.device_wait`` and ``serve.logits_to_host``: the engine's own
    host work."""
    return _mean_ms([s["ns"][STEP] - s["ns"].get("serve.device_wait", 0)
                     - s["ns"].get("serve.logits_to_host", 0)
                     for s in wide_steps(window_spans(run, spans))])


def logits_host_mb_per_step(run, spans: Optional[List[Span]] = None
                            ) -> Optional[float]:
    """Bytes of logits copied to the host per C-wide step, in MB."""
    steps = [s for s in wide_steps(window_spans(run, spans))
             if "serve.logits_to_host" in s["bytes"]]
    if not steps:
        return None
    total = sum(s["bytes"]["serve.logits_to_host"] for s in steps)
    return total / len(steps) / 1e6


def admit_wait_p90_ms(run, spans: Optional[List[Span]] = None
                      ) -> Optional[float]:
    """p90 of ``serve.queued`` (submit to slot assignment) over the slots
    assigned in the window."""
    lo, hi = (t * 1e9 for t in run.window)
    spans = ring() if spans is None else spans
    xs = [t1 - t0 for name, t0, t1, _ in spans
          if name == "serve.queued" and lo <= t1 <= hi]
    return float(np.percentile(xs, 90)) / 1e6 if xs else None
