"""The scanner's bookkeeping per thousand rows: the program's
``scan_persist`` (memo rows and the delta state) and ``scan_tally``
(verdict counts) spans over the timed passes."""

from benchmark import scanspans


def read(run):
    return scanspans.stage_ms_per_krow(run, ("scan_persist", "scan_tally"))
