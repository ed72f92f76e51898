"""Span tracing: the engine's step phases as profiler annotations, an
in-memory ring of them while a profiler session is active, and the
Chrome trace-event JSON export (``chrome://tracing`` / Perfetto).

``span(name, tracer)`` is the one call site per phase.  It always enters
a ``jax.profiler.TraceAnnotation`` of exactly ``name``, so a device
trace carries the host phase on the device's clock.  While a profiler
session is active it also records ``(name, t0_ns, t1_ns, args)`` on
``time.perf_counter_ns`` into the process-wide ring
(``program_tracer()``), and with an ``Obs(trace=True)`` tracer it records
there too.  Spans nest on the engine's thread (DESIGN.md §10):

    serve.step             one ``ServingEngine.step`` call
      serve.admit          slot admission (serve.adopt_prefix inside)
      serve.stage          drafting (serve.draft) and the feed loop
      serve.sync_page_table
      serve.dispatch       the uploads and the jitted step: the enqueue
      serve.promote_flip   staged host-tier promotions, when any
      serve.device_wait    block_until_ready on the step's token ids
      serve.logits_to_host the copy to NumPy of the ids, and of the last
                           rows when a stochastic request samples
                           (args: bytes copied, rows host-sampled)
      serve.sample         the post-step loop (serve.publish,
                           serve.verify, serve.rollback inside)

``serve.queued`` (submit to slot assignment) crosses steps, so it is
recorded by ``interval`` without an annotation; the Chrome export draws
it as an async event.  The Chrome export's other lanes:

    tid 2          host-tier demotions (D2H page spills, DESIGN.md §8a)
    tid 100+slot   request lifetimes: one span from admission to
                   finish, args carry the per-request overhead ledger
    tid 200+slot   host-tier promotions: one [enqueue -> page-table
                   flip] span per staged adoption — it OVERLAPS the
                   engine lane's serve.dispatch on purpose (the proof
                   the H2D copy ran concurrent with compute), which is
                   why it lives on its own lane (spans nest per tid)
    instants       submit (arrival at the front door), cancel

Storage is allocation-light: one tuple per event in a bounded ring,
rendered to dicts only at ``dump()``.  When full, the oldest event goes
and ``dropped`` counts it.  The ring holds numbers and small dicts only,
never a reference to an engine, its arrays or its requests."""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# event tuple: (name, cat, ph, ts_ns, dur_ns, tid-or-async-id, args-or-None)
_Event = Tuple[str, str, str, int, int, int, Optional[dict]]


class SpanTracer:
    def __init__(self, *, max_events: int = 200_000) -> None:
        self._t0 = time.perf_counter_ns()
        self._events: Deque[_Event] = deque(maxlen=max_events)
        self.max_events = max_events
        self.dropped = 0

    # ------------------------------------------------------------- clock

    def now_ns(self) -> int:
        """Monotonic ns since tracer start (span begin/end timestamps)."""
        return time.perf_counter_ns() - self._t0

    def rel(self, raw_ns: int) -> int:
        """Convert a raw ``time.perf_counter_ns()`` stamp to tracer-relative
        ns — lets callers take ONE stamp and reuse it for both ledger
        arithmetic (raw deltas) and span timestamps."""
        return raw_ns - self._t0

    # ------------------------------------------------------------- record

    def _push(self, ev: _Event) -> None:
        if len(self._events) >= self.max_events:
            self.dropped += 1                  # the deque drops the oldest
        self._events.append(ev)

    def complete(self, name: str, cat: str, t0_ns: int, t1_ns: int, *,
                 tid: int = 0, args: Optional[dict] = None) -> None:
        """One finished span [t0_ns, t1_ns] (from ``now_ns`` readings)."""
        self._push((name, cat, "X", t0_ns, max(t1_ns - t0_ns, 0), tid, args))

    def async_span(self, name: str, cat: str, t0_ns: int, t1_ns: int, *,
                   async_id: int, args: Optional[dict] = None) -> None:
        """A finished interval that may overlap the thread's spans (it is
        exported as an async begin/end pair, off the nesting lanes)."""
        self._push((name, cat, "b", t0_ns, max(t1_ns - t0_ns, 0), async_id,
                    args))

    def instant(self, name: str, cat: str, *, tid: int = 0,
                args: Optional[dict] = None) -> None:
        self._push((name, cat, "i", self.now_ns(), 0, tid, args))

    # ------------------------------------------------------------- export

    def __len__(self) -> int:
        return len(self._events)

    def spans(self) -> List[Tuple[str, int, int, Optional[dict]]]:
        """Every recorded interval as ``(name, t0_ns, t1_ns, args)`` on
        raw ``time.perf_counter_ns``."""
        t0 = self._t0
        return [(name, t0 + ts, t0 + ts + dur, args)
                for name, _, ph, ts, dur, _, args in self._events
                if ph != "i"]

    def events(self) -> List[dict]:
        """Chrome trace-event dicts (``ts``/``dur`` in microseconds, the
        format's unit)."""
        out = []
        for name, cat, ph, ts, dur, tid, args in self._events:
            ev: Dict[str, object] = {
                "name": name, "cat": cat, "ph": ph,
                "ts": ts / 1e3, "pid": 0, "tid": tid,
            }
            if ph == "X":
                ev["dur"] = dur / 1e3
            if ph == "i":
                ev["s"] = "t"                  # thread-scoped instant
            if ph == "b":                      # async pair, keyed by id
                ev["tid"] = 0
                ev["id"] = tid
            if args:
                ev["args"] = args
            out.append(ev)
            if ph == "b":
                out.append({"name": name, "cat": cat, "ph": "e",
                            "ts": (ts + dur) / 1e3, "pid": 0, "tid": 0,
                            "id": tid})
        return out

    def to_chrome(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# ---------------------------------------------------------------- phases

# ~15 events per C-wide step and a few per decode step: a 51-s window of
# either fits many times over
PROGRAM_RING_EVENTS = 200_000
_PROGRAM = SpanTracer(max_events=PROGRAM_RING_EVENTS)


def program_tracer() -> SpanTracer:
    """The process-wide ring that ``span`` and ``interval`` fill while a
    profiler session is active (``spans()`` reads it)."""
    return _PROGRAM


class span:
    """One phase: ``with span("serve.admit", tracer, step=n) as s:``.

    Always a ``TraceAnnotation`` of exactly ``name`` (no kwargs: they
    would rename the event to ``name#k=v#``).  Recorded into the program
    ring while a profiler session is active, and into ``tracer`` when one
    is given.  ``t0``/``t1`` hold the raw stamps after exit, and
    ``args`` (numbers only) may be filled in inside the block."""

    __slots__ = ("name", "tracer", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, tracer: Optional[SpanTracer] = None,
                 **args: int) -> None:
        self.name = name
        self.tracer = tracer
        self.args = args
        self.t0 = self.t1 = 0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        for tr in (_PROGRAM if TraceAnnotation.is_enabled() else None,
                   self.tracer):
            if tr is not None:
                tr.complete(self.name, "serve", tr.rel(self.t0),
                            tr.rel(self.t1), args=self.args)

    @property
    def ns(self) -> int:
        return self.t1 - self.t0


def interval(name: str, t0_ns: int, t1_ns: int,
             tracer: Optional[SpanTracer] = None, **args: int) -> None:
    """A finished interval that crosses steps (``serve.queued``): into the
    program ring while profiling and into ``tracer`` as an async span
    keyed by ``rid``, never an annotation — it would break the nesting
    of the thread's spans."""
    if TraceAnnotation.is_enabled():
        _PROGRAM.complete(name, "serve", _PROGRAM.rel(t0_ns),
                          _PROGRAM.rel(t1_ns), args=args)
    if tracer is not None:
        tracer.async_span(name, "serve", tracer.rel(t0_ns), tracer.rel(t1_ns),
                          async_id=args.get("rid", 0), args=args)


def validate_chrome_trace(doc: dict) -> List[str]:
    """Structural checks for an exported trace (used by tests and the CI
    smoke cell).  Returns a list of problems (empty == valid):

      * ``traceEvents`` is a non-empty list of well-formed events;
      * complete events carry non-negative ``ts``/``dur``;
      * per ``(pid, tid)``, complete spans NEST — any two either are
        disjoint or one contains the other (the viewer's flame-graph
        precondition)."""
    problems: List[str] = []
    evs = doc.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    lanes: Dict[Tuple[int, int], List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict) or "ph" not in ev or "name" not in ev:
            problems.append(f"event {i}: not a trace event")
            continue
        if "ts" not in ev:
            problems.append(f"event {i} ({ev['name']}): missing ts")
            continue
        if ev["ph"] == "X":
            if ev.get("dur", -1) < 0 or ev["ts"] < 0:
                problems.append(f"event {i} ({ev['name']}): bad ts/dur")
                continue
            lanes.setdefault((ev.get("pid", 0), ev.get("tid", 0)), []).append(
                (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]),
                 str(ev["name"])))
    eps = 1e-3                                   # 1 ns at us granularity
    for lane, spans in lanes.items():
        # parents before children at equal start times (longest first)
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack: List[Tuple[float, float, str]] = []
        for t0, t1, name in spans:
            while stack and stack[-1][1] <= t0 + eps:
                stack.pop()
            if stack and t1 > stack[-1][1] + eps:
                problems.append(
                    f"tid {lane[1]}: span {name!r} [{t0},{t1}] overlaps "
                    f"{stack[-1][2]!r} ending {stack[-1][1]}")
            stack.append((t0, t1, name))
    return problems
