"""queue_wait_p90_ms.open: Front end, admission: submit to the step that gave the turn a slot, p90."""

from bench import layer_stats


def read(run):
    return layer_stats.queue_wait_p90_ms(run)
