"""host_step_ms.open: Engine step loop: mean serve.step less serve.device_wait and serve.logits_to_host, C-wide steps."""

from bench import program_spans


def read(run):
    return program_spans.host_step_ms(run)
