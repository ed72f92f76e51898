"""Drives the program's own serving path with a mix's turns and records
what the end-to-end and per-layer metrics read.

The window calls only ``Session.submit`` and ``ServingEngine.step`` of the
program.  Each call is wrapped in a host span (``jax.profiler
.TraceAnnotation``) named ``bench.submit``, ``bench.step``, ``bench.observe``
or ``bench.sleep``, so a trace can say what the host was doing in each idle
gap of the device.

Open loop (poisson mixes): a turn is submitted once its due time has
passed, whatever the engine is doing; its latencies count from the due
time.  Backlog: before every step the driver tops the waiting queue up to
its target.  Either way it measures for ``seconds`` after ``lead_in_s``,
then keeps stepping until every turn due in the window has its first
token, or ``drain_s`` has passed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from .traffic import Turn


@dataclass
class ReqRecord:
    turn: Turn
    due: float                     # host clock, seconds
    submit: float = 0.0
    req: object = None             # the program's Request
    refused: bool = False          # submit raised
    admitted: Optional[float] = None   # end of the step that gave it a slot
    token_times: List[float] = field(default_factory=list)
    done: bool = False
    truncated: bool = False
    # what the previous observation saw, to count each step's valid tokens
    seen_pos: int = 0
    seen_out: int = 0
    seen_slot: bool = False


@dataclass
class StepRecord:
    t0: float
    t1: float
    prefill: bool
    fed: List[Tuple[int, int]]     # (tokens fed, cached length before), per slot
    out_tokens: int


@dataclass
class RunRecord:
    """All that one run observed; the metric readers take it whole."""
    reqs: List[ReqRecord]
    steps: List[StepRecord]
    window: Tuple[float, float]    # host clock: open, close
    due_window: Tuple[float, float]  # turns due in here are the window's
    gave_up: float                 # when the drain stopped waiting
    late_s: List[float]            # submit time minus due time, open loop
    prefix_before: Dict[str, int]
    prefix_after: Dict[str, int]
    admitted_prompt_tokens: int    # prompt tokens admitted in the window
    waiting_at_open: int
    waiting_at_close: int
    compiles_in_window: int
    open_loop: bool
    trace: Optional[object] = None  # tracing.TraceSummary of a traced run
    cell: Optional[Dict] = None


class CompileCounter:
    """Counts XLA backend compiles in this process (JAX reports each one
    as a duration event); one counter per process, as JAX's listeners
    cannot be taken back."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, *args, **kw) -> None:
        if "backend_compile" in name:
            self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = CompileCounter()
        return cls._instance


class Driver:
    def __init__(self, engine, sessions: Dict[bool, object]) -> None:
        self.engine = engine
        self.sessions = sessions        # strict? -> Session
        self.reqs: List[ReqRecord] = []
        self.live: List[ReqRecord] = []
        self.steps: List[StepRecord] = []

    # ------------------------------------------------------------ submit

    def _submit(self, turn: Turn, due: float) -> ReqRecord:
        rec = ReqRecord(turn, due)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.submit = time.perf_counter()
            try:
                rec.req = self.sessions[turn.strict].submit(
                    turn.prompt, turn.max_new_tokens)
            except ValueError:
                rec.refused = True
        self.reqs.append(rec)
        if not rec.refused:
            self.live.append(rec)
        return rec

    # ------------------------------------------------------------ step

    def _step(self) -> None:
        eng = self.engine
        with jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            eng.step()
            t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.observe"):
            self._observe(t0, t1)

    def _observe(self, t0: float, t1: float) -> None:
        fed: List[Tuple[int, int]] = []
        prefill = False
        out_tokens = 0
        still: List[ReqRecord] = []
        for rec in self.live:
            r = rec.req
            if r.slot is not None and not rec.seen_slot:
                rec.seen_slot = True
                rec.admitted = t1
                rec.seen_pos = r.prefix_tokens
            d_pos = r.prompt_pos - rec.seen_pos
            d_out = len(r.output) - rec.seen_out
            if d_pos > 0:
                prefill = True
                fed.append((d_pos, rec.seen_pos))
            elif d_out > 0:
                # a decode step feeds the last output token, whose position
                # is prompt + earlier outputs - 1
                fed.append((1, len(r.prompt) + rec.seen_out - 1))
            rec.token_times.extend([t1] * d_out)
            out_tokens += d_out
            rec.seen_pos, rec.seen_out = r.prompt_pos, len(r.output)
            if r.done:
                rec.done = True
                rec.truncated = bool(r.truncated or r.cancelled)
            else:
                still.append(rec)
        self.live = still
        self.steps.append(StepRecord(t0, t1, prefill, fed, out_tokens))

    # ------------------------------------------------------------ loops

    def run(self, *, turns: Optional[Sequence[Turn]] = None,
            backlog: Optional[Iterator[Turn]] = None,
            waiting_target: int = 0, lead_in_s: float, seconds: float,
            drain_s: float = 60.0, on_open=None, on_close=None) -> RunRecord:
        """Drive the engine; ``on_open``/``on_close`` run at the window's
        edges (the profiler starts and stops there in a traced run)."""
        eng = self.engine
        prefix = eng.prefix_cache
        compiles = CompileCounter.get()
        t_start = time.perf_counter()
        w_open = t_start + lead_in_s
        w_close = w_open + seconds
        opened = closed = False
        pending = list(turns or [])
        i = 0
        stats0: Dict[str, int] = {}
        stats1: Dict[str, int] = {}
        admitted0 = admitted1 = waiting0 = waiting1 = 0
        compiles0 = compiles1 = 0
        window = (w_open, w_close)
        while True:
            now = time.perf_counter()
            if not opened and now >= w_open:
                opened = True
                if on_open is not None:
                    on_open()
                now = time.perf_counter()
                w_close = now + seconds
                window = (now, w_close)
                stats0 = dict(prefix.stats()) if prefix else {}
                admitted0 = self._admitted_prompt_tokens()
                waiting0 = len(eng.waiting)
                compiles0 = compiles.count
            if opened and not closed and now >= w_close:
                closed = True
                # the window closes with the last step that began in it
                last = self.steps[-1].t1 if self.steps and \
                    self.steps[-1].t0 < w_close else w_close
                window = (window[0], max(last, w_close))
                stats1 = dict(prefix.stats()) if prefix else {}
                admitted1 = self._admitted_prompt_tokens()
                waiting1 = len(eng.waiting)
                compiles1 = compiles.count
                if on_close is not None:
                    on_close()
            if closed:
                due_in = [r for r in self.reqs
                          if window[0] <= r.due < w_close]
                if backlog is not None or all(
                        r.refused or r.token_times or r.done
                        for r in due_in) or now > w_close + drain_s:
                    break
            # submit what is due
            if backlog is not None:
                while len(eng.waiting) < waiting_target:
                    turn = next(backlog)
                    self._submit(turn, time.perf_counter())
            else:
                while i < len(pending) and t_start + pending[i].due_s <= now:
                    due = t_start + pending[i].due_s
                    self._submit(pending[i], due)
                    i += 1
            if eng.active or eng.waiting:
                self._step()
            elif i < len(pending):
                nap = min(t_start + pending[i].due_s - time.perf_counter(),
                          0.05)
                if nap > 0:
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(nap)
            elif closed:
                break
            else:
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(0.005)
        return RunRecord(
            reqs=self.reqs, steps=self.steps, window=window,
            due_window=(window[0], w_close), gave_up=time.perf_counter(),
            late_s=[r.submit - r.due for r in self.reqs
                    if window[0] <= r.due < w_close],
            prefix_before=stats0, prefix_after=stats1,
            admitted_prompt_tokens=admitted1 - admitted0,
            waiting_at_open=waiting0, waiting_at_close=waiting1,
            compiles_in_window=compiles1 - compiles0,
            open_loop=backlog is None)

    def _admitted_prompt_tokens(self) -> int:
        return sum(len(r.turn.prompt) for r in self.reqs
                   if r.admitted is not None)
