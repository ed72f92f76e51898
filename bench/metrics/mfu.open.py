"""mfu.open: Step program: model FLOPs of valid tokens / (traced window x bf16 peak)."""

from bench import layer_stats


def read(run):
    return layer_stats.mfu(run)
