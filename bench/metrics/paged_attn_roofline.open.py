"""paged_attn_roofline.open: Kernels: paged attention's least time / its summed device time."""

from bench import layer_stats


def read(run):
    return layer_stats.roofline(run, "paged_attention_chunk")
