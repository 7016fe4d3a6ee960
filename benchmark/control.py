"""The control of a cell's correctness check: the plain reference with one
of the configuration's guarantees broken (the configuration's
``control``), put in the program's place and judged by the same
comparison at the cell's own size. It has to come out not correct.

    python3 benchmark/control.py --workload bp-scan-fresh --seeds 11,12,13

Admission cells: every request of the window the cell's rate and
``run_seconds`` give, answered by the control. Scan cells: as many rows
as a run samples (``check_rows_per_pass`` for each of ``passes``
passes), drawn from the seed out of full-size passes. Prints one line of
compared numbers per seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT)] + [p for p in sys.path
                             if Path(p or ".").resolve() != ROOT / "benchmark"]

from benchmark import arrivals, clusters, spec  # noqa: E402


def admission_checks(cell: spec.Cell, seed: int, seconds: float) -> list:
    from benchmark.runners.admission import check

    cfg, tr = cell.config, cell.traffic
    docs = cell.policies()
    window = {"name": "window", "rate": tr["rate_rps"], "seconds": seconds,
              "salt": f"s{seed}", "order_seed": seed}
    Library = cell.reference().Library
    control = Library(docs, control=cfg["control"])
    rows = []
    for due, review in arrivals.requests(window, cfg["cluster"]):
        denied = sorted(f"{p}/{r}" for p, r in
                        control.denials(review["request"]))
        rows.append([due, due, due, 200, not denied, denied])
    return check(Library(docs), window, cfg, rows)


def scan_checks(cell: spec.Cell, seed: int, passes: int) -> list:
    from benchmark.runners.scan import check

    cfg, tr = cell.config, cell.traffic
    docs = cell.policies()
    params = cfg["cluster"]
    gen = clusters.generator(params["generator"])
    n = gen.size(params)
    Library = cell.reference().Library
    control = Library(docs, control=cfg["control"])
    keys = [(r.policy, r.name) for r in control.rules]
    rng = random.Random(seed)
    samples = []
    for p in range(1, passes + 1):
        for i in rng.sample(range(n), min(tr["check_rows_per_pass"], n)):
            body = gen.resource(i, f"s{seed}p{p}", params)
            samples.append((body, dict(zip(keys, control.row(body)))))
    return check(Library(docs), samples, 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--passes", type=int, default=3)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["kind"] == "admission":
            checks = admission_checks(cell, seed, bench["run_seconds"])
        else:
            checks = scan_checks(cell, seed, args.passes)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "control": cell.config["control"],
            "correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
