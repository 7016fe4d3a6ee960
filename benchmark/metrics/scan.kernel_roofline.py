"""Share of the memory roofline the scan program reaches in the traced
pass: the least bytes the pass must move (benchmark/roofline.py, counted
from the corpus at the paths the configuration's reference reads) over
the peak HBM bandwidth, divided by the summed device time of the scan
program's events.

The program matched is the jitted ``evaluate_blob`` function
(``build_eval_fn_blob`` in ops/eval.py), which scores every scan chunk.
"""

from benchmark.roofline import least_bytes

PROGRAM = "jit_evaluate_blob"


def read(run):
    if run.kind != "scan" or run.profile is None or not run.traced_rows \
            or run.peaks is None:
        return None
    kernel_s = run.profile["programs"].get(PROGRAM, 0.0)
    if kernel_s <= 0:
        return None
    least = least_bytes(run.traced_rows, run.read_paths, run.n_rules)
    return 100.0 * (least / run.peaks["hbm_bytes_per_s"]) / kernel_s
