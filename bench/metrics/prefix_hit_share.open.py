"""prefix_hit_share.open: Metadata plane: prompt tokens served by the prefix cache / prompt tokens admitted."""

from bench import layer_stats


def read(run):
    return layer_stats.prefix_hit_share(run)
