"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time, the time of each kernel, the device
operations that took most time, and the idle gaps by the host span open
in them.

The device is the plane ``/device:TPU:0`` (one per chip); its line ``XLA
Ops`` holds one event per HLO operation, named ``%<op> = <shape> ...``.
Host spans are the benchmark's own ``bench.*`` annotations.  Host and
device events share one clock in the trace.  The traced window is the
host span ``bench.window``.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    window_ns: Tuple[float, float]
    devices: int
    busy_ns: float                     # union of op intervals, mean per chip
    kernel_ns: Dict[str, float]        # op base name -> summed duration
    kernel_calls: Dict[str, int]
    self_ns: Dict[str, float]          # op label -> self time (all chips)
    gaps: List[Tuple[float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.self_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[n, t / 1e9] for t, n in gaps]}


def op_name(event_name: str) -> str:
    """``%paged_attention_chunk.6 = bf16[...] ...`` -> ``paged_attention_chunk.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def base_name(op: str) -> str:
    """Drop the HLO uniquifier: ``paged_attention_chunk.6`` ->
    ``paged_attention_chunk``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def op_label(event_name: str) -> str:
    """The op and its result shape, e.g. ``fusion.66 = bf16[32,128,151936]``."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} = {shape}" if shape else head.lstrip("%")


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def idle_intervals(intervals: Sequence[Tuple[float, float]],
                   lo: float, hi: float) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per label: an op's duration less that of the ops nested
    inside it on the same line (a while loop holds its body's ops)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []            # [end, label, child_ns]
    for s, e, label in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, lab, child = stack.pop()
            out[lab] -= child
        if stack:
            stack[-1][2] += e - s
        out[label] += e - s
        stack.append([e, label, 0.0])
    while stack:
        _, lab, child = stack.pop()
        out[lab] -= child
    return dict(out)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def summarize(path: str, kernels: Sequence[str] = (),
              window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Read ``path`` and reduce it over the host span ``window_span``.
    ``kernels`` are op base names whose device time is summed apart."""
    from jax.profiler import ProfileData      # only a traced run needs jax

    pd = ProfileData.from_file(path)
    host: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    device_events: List[List[Tuple[float, float, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs.extend((e.start_ns, e.end_ns, e.name)
                           for e in line.events)
            if evs:
                device_events.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_span:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"trace holds no host span {window_span!r}")
    lo, hi = window
    busy = []
    kernel_ns: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    self_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    host.sort()
    starts = [s for s, _, _ in host]
    for evs in device_events:
        labelled: List[Tuple[float, float, str]] = []
        clipped = [(max(s, lo), min(e, hi), name) for s, e, name in evs
                   if e > lo and s < hi]
        spans = [(s, e) for s, e, _ in clipped]
        busy.append(union_ns(spans))
        for s, e, name in clipped:
            base = base_name(op_name(name))
            if base in kernels:
                kernel_ns[base] += e - s
                kernel_calls[base] += 1
            labelled.append((s, e, op_label(name)))
        for label, t in _self_times(labelled).items():
            self_ns[label] += t
        for s, e in idle_intervals(spans, lo, hi):
            gaps.append((e - s, _host_span_at(host, starts, (s + e) / 2)))
    return TraceSummary(
        window_ns=window, devices=len(device_events),
        busy_ns=sum(busy) / max(len(busy), 1), kernel_ns=dict(kernel_ns),
        kernel_calls=dict(kernel_calls), self_ns=dict(self_ns),
        gaps=gaps)


def _host_span_at(host: List[Tuple[float, float, str]],
                  starts: List[float], t: float) -> str:
    """The latest-opened benchmark span still open at ``t``, or ``none``.
    The benchmark's spans do not nest, so the last one to open before
    ``t`` is the only candidate."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and host[i][1] >= t:
        return host[i][2]
    return "none"
