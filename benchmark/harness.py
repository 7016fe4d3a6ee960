"""One run of one cell: set up, warm up, measure, check, print.

The traffic mix's ``kind`` picks the runner (``benchmark/runners/
<kind>.py``), which sets the program up, drives the measured window and
returns a :class:`RunData`. The metric readers named in BENCHMARK.json
turn that into numbers; the runner's checks decide ``correct``.

Output: diagnostics on stderr, then each compared number beside its
limit as the last lines of stderr, then one JSON object as the last
line of stdout.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from dataclasses import dataclass, field

from benchmark import spec


def note(**fields) -> None:
    """A diagnostic line on stderr."""
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


class CompileClock:
    """Counts JAX backend compiles and their seconds (a persistent-cache
    hit counts its read)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class RunData:
    """What a runner hands the metric readers and the result line."""

    kind: str
    seconds: float                 # the measured window, as run
    setup_s: float
    requests: list = field(default_factory=list)   # admission rows
    passes: list = field(default_factory=list)     # scan passes
    program: dict = field(default_factory=dict)    # counter/span deltas
    profile: dict | None = None                    # reduced device trace
    traced_rows: list | None = None                # bodies of traced pass
    read_paths: set = field(default_factory=set)   # Library.read_paths()
    n_rules: int = 0
    peaks: dict | None = None
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    checks: list = field(default_factory=list)


@dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    t0: float
    clock: CompileClock
    device_kind: str
    scratch: str


def peaks_for(kind: str) -> dict:
    table = json.loads((spec.HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            t0: float, require_tpu: bool = True) -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    note(device=device, workload=cell.name, seed=seed, seconds=seconds,
         trace=trace, host_cpus=len(os.sched_getaffinity(0)))
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        print(f"benchmark: the cell needs {cell.chips} TPU chip(s), JAX "
              f"sees {device}; nothing was run", file=sys.stderr)
        return 2
    scratch = os.path.join(os.environ.get("TMPDIR") or "/tmp",
                           f"bench-{cell.name}-{os.getpid()}")
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace, t0=t0,
                  clock=CompileClock(), device_kind=dev.device_kind,
                  scratch=scratch)
    runner = importlib.import_module(
        f"benchmark.runners.{cell.traffic['kind']}")
    run = runner.run(ctx)
    note(compiles=ctx.clock.compiles, compile_s=ctx.clock.seconds,
         compile_cache_hits=ctx.clock.cache_hits)

    metrics = {}
    for m, read in cell.readers(trace):
        value = read(run)
        if value is None:
            note(metric=m["name"], value=None, reason="nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    out = {"correct": bool(run.checks) and all(c.ok for c in run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if trace and run.profile is not None:
        device["busy_s"] = run.profile["busy_s"]
        device["window_s"] = run.profile["window_s"]
        out["breakdown"] = {"device_ops": run.profile["ops"],
                            "idle_gaps": run.profile["gaps"]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    for c in run.checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def main(argv: list | None, t0: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.Cell(spec.load_benchmark(), args.workload)
    except (spec.SpecError, OSError, ValueError, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.COMPILE_CACHE)
    return execute(cell, args.seed, args.seconds, bool(args.trace), t0)
