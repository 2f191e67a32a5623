"""Sharded NN-chain trips per tree, the mean over the window's trees, as
the program counts them; nothing unless every window tree ran over all
the chips of the cell (``chain_shards`` against the record's ``chips``)."""

from chipbench.program import window_values


def read(rec):
    trips = window_values(rec, "chain_trips")
    shards = window_values(rec, "chain_shards")
    chips = rec.get("chips")
    if trips is None or shards is None or set(shards) != {chips}:
        return None
    return sum(trips) / len(trips)
