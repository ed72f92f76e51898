"""device_idle_share.open: Device: 1 - busy / traced window."""

from bench import layer_stats


def read(run):
    return layer_stats.idle_share(run)
