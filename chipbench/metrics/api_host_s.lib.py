"""Host seconds per tree in ``cluster`` outside its engine: the mean over
the window's trees of the ``repro/cluster`` span less its
``cluster/engine`` span (input, fetch, ``canonical_order``, truncation,
the result's linkage matrix)."""

from chipbench.program import window_values


def read(rec):
    whole = window_values(rec, "cluster_phase_seconds", phase="cluster")
    engine = window_values(rec, "cluster_phase_seconds",
                           phase="cluster/engine")
    if whole is None or engine is None:
        return None
    return (sum(whole) - sum(engine)) / len(whole)
