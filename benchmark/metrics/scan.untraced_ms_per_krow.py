"""The scan span's self time per thousand rows: each timed pass's scan
trace less its six top-level stage spans (snapshot, flatten, device
dispatch, host resolve, persist, tally). Garbage collection is not
taken off: it falls inside the stages."""

from benchmark import scanspans
from benchmark.stats import per_krow_ms


def read(run):
    traces = scanspans.timed_passes(run)
    if traces is None:
        return None
    staged = scanspans.span_seconds(traces, scanspans.STAGES)
    if staged is None:
        return None
    return per_krow_ms(sum(t.duration_s for t in traces) - staged,
                       scanspans.rows(run))
