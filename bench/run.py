#!/usr/bin/env python3
"""Chip benchmark of the serving path: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in BENCHMARK.json.  The run makes
weights and traffic from ``--seed``, warms up the cell's two step widths,
runs the traffic's lead-in, measures for ``--seconds``, checks the served
tokens against the plain reference, and prints one JSON line last on
standard output: the end-to-end metrics with ``--trace 0``, the per-layer
metrics (from a profiler trace of the window) with ``--trace 1``.

It exits non-zero, printing no result, where the configuration file
states a key that nothing accounts for (its family module under
bench/families/, its ``reduced`` or ``assumed``), or JAX finds no TPU,
fewer chips than the cell asks for, a device kind missing from
bench/peaks.json, or kernels that would not be the Pallas ones.

``--control 1`` runs the control instead of the check: the float8
reference's first tokens are scored in place of the served ones, so a
sound limit makes ``correct`` false (for setting the cell's limit; the
benchmark's own runs leave it off).
"""

from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import repro  # noqa: F401
        from bench import harness
    except ImportError as e:
        print(f"bench: the system under test is not here: {e}",
              file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    peaks = harness.device_peaks(cell, int(cell.entry["chips"]))
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_process0=T_PROCESS0, peaks=peaks,
                         control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
