"""CI gate for the observability plane (DESIGN.md §10).

Checks two things after the obs smoke cell and serve microbench ran:

  1. the dry-run trace (``runs/ci-dryrun/serve_trace.json``) is valid
     Chrome trace-event JSON with properly nested spans and carries the
     expected span taxonomy;
  2. the measured ENABLED instrumentation cost from ``BENCH_serve.json``
     (``obs_cost.enabled_overhead_frac``, min-of-reps decode obs-on vs
     obs-off) stays under the bound — stricter than the ISSUE's
     disabled-by-default <2% requirement, which holds by construction.

  PYTHONPATH=src python tools/check_obs.py [trace.json] [BENCH_serve.json]

The bound is overridable via OBS_OVERHEAD_BOUND (fraction, default 0.02)
for noisy shared CI hosts.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.obs import validate_chrome_trace  # noqa: E402

REQUIRED_SPANS = {
    # the engine step's phases (DESIGN.md §10), one span each, nested in
    # serve.step; serve.queued is each request's submit -> slot wait
    "serve.step", "serve.admit", "serve.stage", "serve.sync_page_table",
    "serve.dispatch", "serve.device_wait", "serve.logits_to_host",
    "serve.sample", "serve.queued",
    # speculative decoding taxonomy: drafting (client-side guesswork),
    # the verify pass over the target logits, and the metadata-only
    # rollback of rejected tails
    "serve.draft", "serve.verify", "serve.rollback",
    # host-tier taxonomy (DESIGN.md §8a): D2H spills on tid 2, the flip
    # phase of the step, and [enqueue -> flip] promotion spans on the
    # per-slot 200+ lanes (overlapping serve.dispatch by design)
    "demote", "serve.promote_flip", "promote"}

# cluster-plane taxonomy (DESIGN.md §12): route instants at submit, and
# per-session snapshot spans nested inside each migrate span.  The
# cluster trace carries CONTROL-plane events only (the engines' data
# planes are separately instrumented), so no request lanes are required.
CLUSTER_REQUIRED_SPANS = {"route", "snapshot", "migrate", "kill"}


def check_trace(path: Path) -> None:
    doc = json.loads(path.read_text())
    problems = validate_chrome_trace(doc)
    if problems:
        raise SystemExit(f"[check_obs] trace {path} invalid: "
                         + "; ".join(problems[:5]))
    names = {ev["name"] for ev in doc["traceEvents"]}
    missing = REQUIRED_SPANS - names
    if missing:
        raise SystemExit(f"[check_obs] trace {path} missing spans: "
                         f"{sorted(missing)}")
    if not any(ev.get("tid", 0) >= 100 for ev in doc["traceEvents"]):
        raise SystemExit(f"[check_obs] trace {path} has no request lanes")
    print(f"[check_obs] trace ok: {len(doc['traceEvents'])} events, "
          f"spans nest, request lanes present")


def check_cluster_trace(path: Path) -> None:
    doc = json.loads(path.read_text())
    problems = validate_chrome_trace(doc)
    if problems:
        raise SystemExit(f"[check_obs] cluster trace {path} invalid: "
                         + "; ".join(problems[:5]))
    names = {ev["name"] for ev in doc["traceEvents"]}
    missing = CLUSTER_REQUIRED_SPANS - names
    if missing:
        raise SystemExit(f"[check_obs] cluster trace {path} missing spans: "
                         f"{sorted(missing)}")
    print(f"[check_obs] cluster trace ok: {len(doc['traceEvents'])} events, "
          f"route/snapshot/migrate present")


def check_overhead(path: Path, bound: float) -> None:
    bench = json.loads(path.read_text())
    oc = bench.get("obs_cost")
    if not oc:
        raise SystemExit(f"[check_obs] {path} has no obs_cost section")
    frac = oc["enabled_overhead_frac"]
    if frac >= bound:
        raise SystemExit(
            f"[check_obs] enabled instrumentation costs {frac:.2%} on the "
            f"decode hot path (bound {bound:.0%}): "
            f"{oc['decode_s_obs_off']:.4f}s -> {oc['decode_s_obs_on']:.4f}s")
    so = bench.get("software_overhead", {})
    for stage in ("prefill", "decode"):
        if stage not in so:
            raise SystemExit(f"[check_obs] software_overhead missing "
                             f"{stage} stage")
        shares = so[stage]["shares"]
        total = sum(shares.values())
        if abs(total - 1.0) > 1e-6:
            raise SystemExit(f"[check_obs] {stage} shares sum to {total}")
    print(f"[check_obs] overhead ok: enabled cost {frac:.2%} < "
          f"{bound:.0%}; per-stage shares well-formed")


def main() -> None:
    trace = Path(sys.argv[1] if len(sys.argv) > 1
                 else "runs/ci-dryrun/serve_trace.json")
    bench = Path(sys.argv[2] if len(sys.argv) > 2 else "BENCH_serve.json")
    cluster = Path(sys.argv[3] if len(sys.argv) > 3
                   else "runs/ci-dryrun/cluster_trace.json")
    bound = float(os.environ.get("OBS_OVERHEAD_BOUND", "0.02"))
    check_trace(trace)
    check_cluster_trace(cluster)
    check_overhead(bench, bound)
    print("[check_obs] ok")


if __name__ == "__main__":
    main()
