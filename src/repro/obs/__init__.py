"""repro.obs — low-overhead observability for the serving path
(DESIGN.md §10): the engine's step phases as profiler annotations (kept
in memory while a profiler session is active) and as Chrome trace-event
JSON, a counter/gauge registry over the stack's existing plain-int
stats, an SPFS-style windowed profiler, and the SplitFS
software-overhead ledger (client / scheduler / device / persistence
attribution).

The annotations are always entered; everything else hangs off one
optional ``Obs`` bundle."""

from .bundle import Obs, OverheadLedger, attach_fault, attach_serving
from .profiler import Window, WindowedProfiler
from .registry import Counter, Gauge, Registry
from .trace import (SpanTracer, interval, program_tracer, span,
                    validate_chrome_trace)

__all__ = [
    "Obs", "OverheadLedger", "attach_fault", "attach_serving",
    "Window", "WindowedProfiler", "Counter", "Gauge", "Registry",
    "SpanTracer", "interval", "program_tracer", "span",
    "validate_chrome_trace",
]
