"""Driver for library configurations on a mesh: trees back to back
through ``cluster``, as ``library`` runs them, except that a traced
run's window lasts ``TRACED_SECONDS``, so that it holds the one tree
started at its opening.

The profiler collects an event for every device operation on every
chip, and each trip of the sharded chain runs tens of operations on
each chip.  A run of the four-chip sharded chain under ``library`` with
a 40 s traced window had not ended 380 s after its start (four v5e),
where a traced run of the one-chip ``corpus-ward`` ends in about 300 s;
under this driver a traced run of one tree at n = 36,864 ended in
336 s, 211 s of which went to stopping the profiler.
A traced run reports only the per-layer metrics, which need one window
tree beside the warm-up tree; the end-to-end metrics come from the
untraced runs, whose window is ``library``'s.  A traced run's result
line says how long its window was allowed to be (``traced_seconds``).

The record also carries the cell's ``chips``, so that a reader can tell
whether the chain ran over every chip of the cell.
"""

from __future__ import annotations

from chipbench.drivers import library

#: Seconds of a traced window.  A tree started inside it runs to its
#: end, so the window holds every tree started in its first 0.5 s: one
#: on the chip, where a tree takes seconds.
TRACED_SECONDS = 0.5

check_inputs = library.check_inputs


def run(run) -> dict:
    if run.trace:
        run.seconds = min(run.seconds, TRACED_SECONDS)
    rec = library.run(run)
    rec["chips"] = run.cell.workload["chips"]
    if run.trace:
        rec["notes"]["traced_seconds"] = run.seconds
    return rec
