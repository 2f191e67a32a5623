"""NN-chain loop trips per tree, the mean over the window's trees, as the
program counts them (``ChainResult.iters``)."""

from chipbench.program import window_values


def read(rec):
    trips = window_values(rec, "chain_trips")
    return None if trips is None else sum(trips) / len(trips)
