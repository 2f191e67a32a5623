"""Device-busy seconds per completed tree: the union of the device
operations' intervals in the traced window over the trees completed."""


def read(rec):
    trace, trees = rec.get("trace"), rec.get("tree_durations_s")
    if trace is None or not trees:
        return None
    return trace.busy_s / len(trees)
