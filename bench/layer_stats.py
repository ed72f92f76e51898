"""Arithmetic the per-layer metric readers share.  Each reader under
``bench/metrics/`` is a file named after its metric that calls one of
these; a value that has nothing to be read from is None, and the harness
then leaves the metric out of the result line.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from . import flops


def window_steps(run) -> list:
    lo, hi = run.window
    return [s for s in run.steps if lo <= s.t0 and s.t1 <= hi]


def step_ms(run, prefill: bool) -> Optional[float]:
    """Mean host wall time of ``engine.step()`` over the window's steps
    that ran (prefill: some slot fed prompt tokens, the C-wide program;
    else decode only, the width-1 program)."""
    xs = [s.t1 - s.t0 for s in window_steps(run) if s.prefill == prefill]
    return float(np.mean(xs)) * 1e3 if xs else None


def queue_wait_p90_ms(run) -> Optional[float]:
    """Submit to the end of the step that gave the turn a slot, p90 over
    the turns due in the window that got one."""
    lo, hi = run.due_window
    xs = [r.admitted - r.submit for r in run.reqs
          if lo <= r.due < hi and r.admitted is not None]
    return float(np.percentile(xs, 90)) * 1e3 if xs else None


def prefix_hit_share(run) -> Optional[float]:
    """Prompt tokens the prefix cache served over prompt tokens admitted,
    in the window, as a percentage."""
    if not run.prefix_after or run.admitted_prompt_tokens <= 0:
        return None
    saved = run.prefix_after["tokens_saved"] - \
        run.prefix_before["tokens_saved"]
    return 100.0 * saved / run.admitted_prompt_tokens


def mfu(run) -> Optional[float]:
    """Model FLOPs of the valid tokens the window's steps processed, as
    the family counts them, over window seconds times the chip's bf16
    peak, as a percentage."""
    # the trace's window is the host span bench.window: the driver's
    # window on the profiler's clock, so the window's steps are traced
    tr, steps = run.trace, window_steps(run)
    if tr is None or not steps or not tr.devices:
        return None
    family, cfg, peaks = (run.cell[k] for k in ("family", "config", "peaks"))
    total = sum(family.model_flops(cfg, s.fed) for s in steps)
    return 100.0 * total / (tr.window_s * peaks["bf16_flops"] * tr.devices)


def roofline(run, kernel: str) -> Optional[float]:
    """The least time the kernel's calls need (the larger of FLOPs over
    peak and bytes over HBM bandwidth, from each call's live lengths, as
    the family counts them) over the summed device time of its trace
    events, as a percentage."""
    tr, steps = run.trace, window_steps(run)
    cost = run.cell["family"].KERNEL_COSTS.get(kernel)
    if cost is None or tr is None or not steps or not tr.kernel_ns.get(kernel):
        return None
    cfg, peaks = run.cell["config"], run.cell["peaks"]
    f = b = 0
    for s in steps:
        sf, sb = cost(cfg, s.fed)
        f, b = f + sf, b + sb
    least, _ = flops.roofline_seconds(f, b, peaks["bf16_flops"],
                                      peaks["hbm_bytes_per_s"])
    return 100.0 * least / (tr.kernel_ns[kernel] / 1e9)


def idle_share(run) -> Optional[float]:
    """1 - busy / window of the traced window, as a percentage."""
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
