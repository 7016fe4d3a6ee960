"""Garbage collection per thousand rows: the pauses the program charged
to the timed passes' scan traces (their ``gc`` tally). The pauses fall
inside the stages, so this overlaps the other scanner metrics."""

from benchmark import scanspans
from benchmark.stats import per_krow_ms


def read(run):
    traces = scanspans.timed_passes(run)
    if traces is None or not all(hasattr(t, "gc_s") for t in traces):
        return None
    return per_krow_ms(sum(t.gc_s for t in traces), scanspans.rows(run))
