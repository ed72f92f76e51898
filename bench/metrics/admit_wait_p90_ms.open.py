"""admit_wait_p90_ms.open: Front end, admission: serve.queued (submit to slot assignment) p90, slots assigned in the window."""

from bench import program_spans


def read(run):
    return program_spans.admit_wait_p90_ms(run)
