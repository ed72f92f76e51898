"""Each cell end to end on the CPU at a small size, through the same
harness code the chip runs, with the Pallas kernels in interpret mode.
Only the look for a chip is left out: the test calls the harness
directly.  The rehearsal cells drive the paths no listed cell takes yet
(shared prefixes with STRICT sessions, and a saturated backlog)."""

import time

import pytest

from bench import harness
from bench.tests.smoke import PEAKS, rehearsal_cell, small_cell

CELLS = {"starcoder2-7b.code_complete": lambda: small_cell(
             "starcoder2-7b.code_complete"),
         "rehearsal.shared_prefix_strict": lambda: rehearsal_cell(
             "shared_prefix_strict"),
         "rehearsal.backlog": lambda: rehearsal_cell("backlog")}


@pytest.mark.parametrize("name", list(CELLS))
def test_cell_runs_correct_in_interpret_mode(name, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    cell = CELLS[name]()
    res = harness.run(cell, 2**31 + 7, 1.5, False,
                      t_process0=time.perf_counter(), peaks=PEAKS)
    assert res["correct"], res["limits"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["diagnostics"]["compiles_in_window"] == 0
    assert list(res)[-1] == "limits"
    assert res["limits"]["strict_log_mismatch"]["value"] == 0


def test_strict_sessions_publish_and_adopt_prefixes(monkeypatch):
    """The shared-prefix rehearsal reaches the layers it is there for:
    its sample holds a request that adopted a prefix and a STRICT one,
    and the operation-log check has STRICT publishes to compare."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    cell = rehearsal_cell("shared_prefix_strict")
    res = harness.run(cell, 5, 1.5, False, t_process0=time.perf_counter(),
                      peaks=PEAKS)
    assert res["correct"], res["limits"]
    assert res["diagnostics"]["reference"]["adopted"] >= 1
    assert res["diagnostics"]["reference"]["strict"] >= 1
    assert res["diagnostics"]["strict_publishes"] > 0
