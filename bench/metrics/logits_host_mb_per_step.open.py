"""logits_host_mb_per_step.open: Engine step loop, work count: logits bytes copied to the host per C-wide step, MB."""

from bench import program_spans


def read(run):
    return program_spans.logits_host_mb_per_step(run)
