"""The program's own spans over a scan cell's timed passes.

Every ``BackgroundScanner.scan()`` is one ``scan`` trace in the program's
flight recorder (``kyverno_tpu.runtime.tracing``), with a span per stage
of the pass and the pass's garbage-collection tally. After the window the
timed passes are the recorder's newest ``len(run.passes)`` scan traces;
they are matched to the passes by row count, and a mismatch reads as
nothing. A program that records none of the spans a reader asks for, or
no garbage-collection tally, reads as nothing too.
"""

from __future__ import annotations

from benchmark.stats import per_krow_ms

#: the top-level stages of a full pass on the single-device lane; they
#: do not overlap, and what falls outside them is the scan span's self
#: time
STAGES = ("scan_snapshot", "flatten", "device_dispatch", "host_resolve",
          "scan_persist", "scan_tally")


def timed_passes(run) -> list | None:
    """The scan traces of the run's timed passes, oldest first."""
    if run.kind != "scan" or not run.passes:
        return None
    from kyverno_tpu.runtime import tracing

    rec = tracing.recorder()
    scans = [t for t in rec.traces(rec.ring_size) if t.kind == "scan"]
    timed = scans[:len(run.passes)][::-1]
    if [t.labels.get("resources") for t in timed] != \
            [p["rows"] for p in run.passes]:
        return None
    if any(t.spans_dropped for t in timed):
        return None
    return timed


def span_seconds(traces: list, stages: tuple) -> float | None:
    """Summed seconds of the named spans; None where a pass lacks one."""
    total = 0.0
    for t in traces:
        if not set(stages) <= {s.name for s in t.spans}:
            return None
        total += sum(s.duration_s for s in t.spans if s.name in stages)
    return total


def rows(run) -> int:
    return sum(p["rows"] for p in run.passes)


def stage_ms_per_krow(run, stages: tuple) -> float | None:
    """The named spans of the timed passes, in ms per thousand rows."""
    traces = timed_passes(run)
    if traces is None:
        return None
    seconds = span_seconds(traces, stages)
    return None if seconds is None else per_krow_ms(seconds, rows(run))
