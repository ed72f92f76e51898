"""The control fails the cell's comparison: the plain reference computed
in float8 (the precision below the served bfloat16), put in the program's
place, comes out as not correct through the harness's own check.

The chip readings that set the limit are in PERF.md; this test repeats
the control at a size the CPU holds, on three seeds: a whole run of the
cell, whose finished requests are scored with the float8 forward's first
token at each served position in place of the served one."""

import time

import pytest

from bench import harness
from bench.tests.smoke import PEAKS, small_cell

CELLS = ["starcoder2-7b.code_complete"]
# wide enough that float8 rounding moves the first token, as at full size
SIZE = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
            vocab_size=32768)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(name, seed, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    cell = small_cell(name)
    cell.config = dict(cell.config, **SIZE)
    cell.traffic = dict(cell.traffic,
                        output=dict(cell.traffic["output"], median=32,
                                    min=16, max=48))
    sound = harness.run(cell, seed, 1.5, False,
                        t_process0=time.perf_counter(), peaks=PEAKS)
    control = harness.run(cell, seed, 1.5, False,
                          t_process0=time.perf_counter(), peaks=PEAKS,
                          control=True)
    assert sound["correct"], sound["limits"]
    assert not control["correct"], control["limits"]
    gap = control["limits"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
