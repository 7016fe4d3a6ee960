"""The scan runner keeps its samples out of the collector's heap: every
timed pass starts on a heap of the same size, so the threshold of the
interpreter's full collections does not rise from pass to pass with the
rows kept for the check."""

import gc

from benchmark.tests.cells import run_cell, small_cell


def test_heap_at_each_pass_start_does_not_grow_with_the_samples(
        capsys, monkeypatch):
    from kyverno_tpu.runtime.background import BackgroundScanner

    orig = BackgroundScanner.scan
    tracked: list = []
    rows: list = []

    def counted(self, resources=None):
        tracked.append(len(gc.get_objects()))
        result = orig(self, resources)
        rows.append(result.resources_scanned)
        return result

    monkeypatch.setattr(BackgroundScanner, "scan", counted)
    cell = small_cell("bp-scan-fresh")
    cell.traffic["max_passes"] = 6
    out = run_cell(cell, capsys, seconds=60.0)
    assert out["correct"], out["checks"]
    # the warm pass, then six timed passes that each sample every row
    assert len(tracked) == 7 and min(rows) >= 150
    timed = tracked[1:]
    # kept as objects, each pass's sampled bodies (about 24 tracked
    # objects each) and verdicts would add tens of objects a row; the
    # program's own rings add a few hundred a pass until they are full
    assert (timed[-1] - timed[0]) / (len(timed) - 1) < rows[1], tracked
