"""logits_to_host_ms.open: Engine step loop, device to host: mean serve.logits_to_host time of the C-wide steps."""

from bench import program_spans


def read(run):
    return program_spans.logits_to_host_ms(run)
