"""Microseconds per NN-chain trip: the window's ``cluster/engine`` seconds
over its chain trips.  The engine span runs from the engine call to its
result on the device, so it holds the copy of the points too."""

from chipbench.program import window_values


def read(rec):
    engine = window_values(rec, "cluster_phase_seconds",
                           phase="cluster/engine")
    trips = window_values(rec, "chain_trips")
    if engine is None or trips is None or sum(trips) <= 0:
        return None
    return sum(engine) / sum(trips) * 1e6
