"""The benchmark's cells cut to sizes a CPU test can hold, the
admission cell that the harness serves but BENCHMARK.json does not hold
yet (the program compiles inside its window; PERF.md, section 7), and a
toy plain reference with the interface ``spec.load_reference`` asks
for."""

import json
import time

from benchmark import harness, spec

ADMIT = {"name": "bp-admit-steady", "config": "best-practices-enforce",
         "traffic": "admit-steady", "chips": 1, "why": "test"}
ADMIT_METRICS = ("admit.webhook_ms", "admit.coalesce_wait_ms",
                 "admit.device_decided_share", "admit.flatten_ms",
                 "admit.device_dispatch_ms", "admit.host_resolve_ms",
                 "device_idle.admit")


def bench() -> dict:
    """BENCHMARK.json with the admission cell and its metrics added."""
    out = spec.load_benchmark()
    out["workloads"].append(dict(ADMIT))
    out["end_to_end"].insert(0, {
        "name": "admission_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": [ADMIT["name"]]})
    out["per_layer"] += [
        {"name": m, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "admission",
         "moves": "admission_p95_ms", "workloads": [ADMIT["name"]]}
        for m in ADMIT_METRICS]
    return out


def small_cell(name: str) -> spec.Cell:
    cell = spec.Cell(bench(), name)
    cluster = cell.config["cluster"]
    cluster["namespaces"] = 1
    cluster["controllers"] = [dict(g, count=max(1, g["count"] // 25))
                              for g in cluster["controllers"]]
    if cell.traffic["kind"] == "admission":
        cell.traffic.update(rate_rps=4.0, warmup_s=3, drain_s=30)
    else:
        cell.traffic["check_rows_per_pass"] = 10 ** 6   # every row
    return cell


def run_cell(cell: spec.Cell, capsys, seconds: float, seed: int = 7,
             trace: bool = False) -> dict:
    """One run, past the harness's look for a chip; its result line."""
    capsys.readouterr()
    rc = harness.execute(cell, seed, seconds, trace, time.perf_counter(),
                         require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


TOY_REFERENCE = """
CONTROLS = ("first_container_only",)


class Unsupported(ValueError):
    pass


class Library:
    TOY = True

    def __init__(self, docs, control=None):
        self.rules, self.index = [], {}

    def row(self, body, request=None):
        return []

    def denials(self, request):
        return set()

    def read_paths(self):
        return {("metadata", "name")}
"""
