"""All the time of the trees completed in the window, over their count."""

from chipbench.stats import mean_time


def read(rec):
    durations = rec.get("tree_durations_s")
    return mean_time(durations) if durations else None
