"""prefill_step_ms.open: Engine step loop: mean wall time of steps that ran the C-wide program."""

from bench import layer_stats


def read(run):
    return layer_stats.step_ms(run, prefill=True)
