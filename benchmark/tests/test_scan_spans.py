"""The scanner's span readers (``scan.snapshot_ms_per_krow``,
``scan.bookkeeping_ms_per_krow``, ``scan.gc_ms_per_krow``,
``scan.untraced_ms_per_krow``) on synthesized flight-recorder traces and
on a whole traced run, and the recorded device trace reduced to the
numbers it gave when it was recorded."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import devtrace, scanspans, spec
from benchmark.harness import RunData
from benchmark.tests.cells import run_cell, small_cell

DATA = Path(__file__).resolve().parent / "data"
READERS = ("scan.snapshot_ms_per_krow", "scan.bookkeeping_ms_per_krow",
           "scan.gc_ms_per_krow", "scan.untraced_ms_per_krow")


def _span(name, seconds):
    return SimpleNamespace(name=name, duration_s=seconds)


def _trace(rows, seconds, spans, gc_s=None, kind="scan"):
    t = SimpleNamespace(kind=kind, labels={"resources": rows},
                        duration_s=seconds, spans_dropped=0,
                        spans=[_span(n, s) for n, s in spans])
    if gc_s is not None:
        t.gc_s = gc_s
    return t


class _Recorder:
    """The recorder's read surface over fixed traces, newest first."""

    ring_size = 256

    def __init__(self, traces):
        self._traces = traces

    def traces(self, n):
        return self._traces[:n]


@pytest.fixture
def recorded(monkeypatch):
    from kyverno_tpu.runtime import tracing

    def install(traces):
        monkeypatch.setattr(tracing, "recorder", lambda: _Recorder(traces))
    return install


def _pass_spans(snapshot, persist, tally):
    return [("scan_snapshot", snapshot), ("scan_evaluate", 1.0),
            ("flatten", 0.25), ("device_dispatch", 0.125),
            ("host_resolve", 0.0625), ("scan_tally", tally),
            ("scan_persist", persist), ("row_split", 0.5 * persist)]


def _run(*rows):
    return RunData(kind="scan", seconds=10.0, setup_s=1.0,
                   passes=[{"rows": r, "seconds": 5.0, "stage_s": {}}
                           for r in rows])


def test_readers_sum_the_timed_passes_spans(recorded):
    warm = _trace(2000, 9.0, _pass_spans(7.0, 1.0, 0.5), gc_s=3.0)
    older = _trace(2000, 4.0, _pass_spans(2.0, 0.5, 0.25), gc_s=1.0)
    newer = _trace(2000, 3.0, _pass_spans(1.5, 0.25, 0.125), gc_s=0.5)
    flush = _trace(2000, 8.0, [], kind="flush")
    # newest first, as the ring reads; the warm pass is not timed
    recorded([newer, flush, older, warm])
    run = _run(2000, 2000)
    got = {m: spec.load_reader(m)(run) for m in READERS}
    # 4 krows: snapshot 3.5 s, persist + tally 1.125 s, gc 1.5 s; the
    # six stages of each pass add 0.4375 s to its snapshot, persist and
    # tally, so the self time is 7 - 3.5 - 1.125 - 0.875 = 1.5 s
    assert got["scan.snapshot_ms_per_krow"] == pytest.approx(875.0)
    assert got["scan.bookkeeping_ms_per_krow"] == pytest.approx(281.25)
    assert got["scan.gc_ms_per_krow"] == pytest.approx(375.0)
    assert got["scan.untraced_ms_per_krow"] == pytest.approx(375.0)


def test_readers_read_nothing_from_a_program_without_the_spans(recorded):
    # the scan trace of a program that times only the evaluation, and
    # keeps no garbage-collection tally
    old = [_trace(2000, 4.0, [("scan_evaluate", 1.0), ("row_split", 0.5)])
           for _ in range(2)]
    recorded(old)
    for m in READERS:
        assert spec.load_reader(m)(_run(2000, 2000)) is None, m


def test_readers_read_nothing_when_the_passes_do_not_match(recorded):
    traces = [_trace(2000, 4.0, _pass_spans(2.0, 0.5, 0.25), gc_s=1.0)
              for _ in range(2)]
    recorded(traces)
    assert scanspans.timed_passes(_run(2000, 2000)) is not None
    for run in (_run(2000, 1999), _run(2000, 2000, 2000),
                RunData(kind="admission", seconds=1, setup_s=1)):
        for m in READERS:
            assert spec.load_reader(m)(run) is None, m
    traces[0].spans_dropped = 1
    assert scanspans.timed_passes(_run(2000, 2000)) is None


def test_traced_scan_run_reports_the_scanner_spans(capsys):
    out = run_cell(small_cell("bp-scan-fresh"), capsys, seconds=0.5,
                   trace=True)
    assert out["correct"], out["checks"]
    got = {m: out["metrics"][m]["value"] for m in READERS}
    assert got["scan.snapshot_ms_per_krow"] > 0
    assert got["scan.bookkeeping_ms_per_krow"] > 0
    assert got["scan.gc_ms_per_krow"] >= 0
    assert got["scan.untraced_ms_per_krow"] >= 0
    # the residual the harness takes from outside holds the same time
    glue = out["metrics"]["scan.scanner_ms_per_krow"]["value"]
    parts = (got["scan.snapshot_ms_per_krow"]
             + got["scan.bookkeeping_ms_per_krow"]
             + got["scan.untraced_ms_per_krow"])
    assert parts == pytest.approx(glue, rel=0.1)


def test_recorded_tpu_trace_reduces_as_recorded():
    """Busy, window, program and op seconds of the recorded v5e trace:
    what the device metrics read must not move with how gaps are
    named."""
    r = devtrace.reduce(json.loads((DATA / "tpu_scan_trace.json")
                                   .read_text()))
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.001159573, abs=1e-12)
    assert r["window_s"] == pytest.approx(0.281895309, abs=1e-12)
    assert r["programs"] == {
        "jit_evaluate_blob": pytest.approx(0.015337722, abs=1e-12)}
    assert [name for name, _ in r["ops"]][:3] == [
        "while.2", "or_select_fusion.2", "and_or_fusion.2"]
    assert r["ops"][0][1] == pytest.approx(0.001123202, abs=1e-12)
    assert [round(s, 9) for _, s in r["gaps"]] == [0.202543898,
                                                   0.078191812]
