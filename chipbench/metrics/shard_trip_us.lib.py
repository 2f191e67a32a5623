"""Microseconds per trip of the sharded NN-chain: the window's
``sharded_chain/loop`` seconds over its chain trips.  The loop span runs
from the first segment's dispatch to the last segment's merge count, so
it leaves out placing the points and the state on the mesh."""

from chipbench.program import window_values


def read(rec):
    loop = window_values(rec, "cluster_phase_seconds",
                         phase="sharded_chain/loop")
    trips = window_values(rec, "chain_trips")
    if loop is None or trips is None or sum(trips) <= 0:
        return None
    return sum(loop) / sum(trips) * 1e6
