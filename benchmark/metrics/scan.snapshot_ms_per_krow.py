"""The scanner's snapshot per thousand rows: the program's
``scan_snapshot`` spans (listing and copying every body) over the timed
passes."""

from benchmark import scanspans


def read(run):
    return scanspans.stage_ms_per_krow(run, ("scan_snapshot",))
