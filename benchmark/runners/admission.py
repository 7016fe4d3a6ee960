"""Admission cells: open-loop AdmissionReviews to the webhook's
``/validate`` over HTTP, from a load-generator process.

Set-up starts the program's ``Controller`` over an in-memory cluster that
holds the configuration's policies, with its webhook on a localhost port,
and warms up: a ladder of one-kind bursts of the mix (so the device lane
compiles the flush shapes the window meets), then an open-loop phase of
the same mix at the same rate with other bodies (so the router's cost
model settles), then a wait until no compile is running. The window
then sends the mix's requests when they are due. Every answer is
compared with the plain reference afterwards: the ``allowed`` flag and
the set of denying (policy, rule) pairs.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

from benchmark import arrivals, harness, promtext, spec
from benchmark.stats import percentile
from benchmark.runners import common


@contextlib.contextmanager
def serving(docs: list, cfg: dict, phases: list, drain_s: float):
    """The program's Controller over an in-memory cluster holding
    ``docs``, its webhook on a localhost port, and a load-generator
    process holding ``phases``; both stopped on exit."""
    from kyverno_tpu.runtime.client import FakeCluster
    from kyverno_tpu.runtime.webhook import VALIDATING_WEBHOOK_PATH
    from kyverno_tpu.server import Controller

    controller = Controller(client=FakeCluster(docs), serve_port=0)
    controller.start(host="127.0.0.1")
    child = None
    try:
        warm_thread = getattr(controller, "_warm_thread", None)
        if warm_thread is not None:
            warm_thread.join()
        job = {"host": "127.0.0.1",
               "port": controller._httpd.server_address[1],
               "path": VALIDATING_WEBHOOK_PATH,
               "cluster": cfg["cluster"], "phases": phases,
               "drain_s": drain_s}
        child = subprocess.Popen(
            [sys.executable, str(spec.HERE / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        child.stdin.write(json.dumps(job) + "\n")
        child.stdin.flush()
        expect(child, "ready")
        yield controller, child
    finally:
        if child is not None:
            child.stdin.close()
            child.wait(timeout=120)
        controller.stop()
        common.reap_children()


def send(child, phase: str) -> list:
    """Run one phase of the load generator; its result rows."""
    child.stdin.write(json.dumps({"go": phase}) + "\n")
    child.stdin.flush()
    return expect(child, "phase")["results"]


def run(ctx: harness.Context) -> harness.RunData:
    cell, cfg, tr = ctx.cell, ctx.cell.config, ctx.cell.traffic
    docs = cell.policies()
    common.decidability(docs)
    window = {"name": "window", "rate": tr["rate_rps"],
              "seconds": ctx.seconds, "salt": f"s{ctx.seed}",
              "order_seed": ctx.seed}
    warm = {"name": "warm", "rate": tr["rate_rps"],
            "seconds": tr["warmup_s"], "salt": f"w{ctx.seed}",
            "order_seed": ctx.seed + 1}
    ladder = {"name": "ladder", "salt": f"b{ctx.seed}",
              "bursts": ladder_bursts(cfg["cluster"], tr["warm_bursts"])}
    data = harness.RunData(kind="admission", seconds=ctx.seconds,
                           setup_s=0.0)
    log_dir = None
    with serving(docs, cfg, [ladder, warm, window], tr["drain_s"]) as (
            controller, child):
        batcher = controller.admission_batcher
        stats0 = dict(batcher.stats)
        compiles0 = ctx.clock.compiles
        ladder_rows = send(child, "ladder")
        warm_rows = send(child, "warm")
        settled_s = settle(ctx.clock)
        routed = delta(stats0, batcher.stats)
        harness.note(phase="warm", ladder=len(ladder_rows),
                     requests=len(warm_rows),
                     answered=sum(r[2] is not None for r in warm_rows),
                     compiles=ctx.clock.compiles - compiles0,
                     settled_s=settled_s, routing=routed)
        if routed.get("circuit_open") or routed.get("screen_timeout"):
            # let a breaker the warm-up tripped close before the window
            time.sleep(batcher.circuit_cooldown_s + 0.2)

        before = _program_metrics()
        stats1 = dict(batcher.stats)
        compiles1 = ctx.clock.compiles
        data.setup_s = time.perf_counter() - ctx.t0
        with common.traced(ctx.trace, ctx.scratch, "window") as log_dir:
            child.stdin.write(json.dumps({"go": "window"}) + "\n")
            child.stdin.flush()
            if log_dir is not None:
                time.sleep(min(tr["trace_s"], ctx.seconds))
        rows = expect(child, "phase")["results"]
        after = _program_metrics()
        window_compiles = ctx.clock.compiles - compiles1
        data.program = {
            "stages": promtext.histogram_delta(
                before, after, "kyverno_stage_duration_seconds",
                ("stage", "kind")),
            "traces": promtext.histogram_delta(
                before, after, "kyverno_trace_duration_seconds", ("kind",)),
            "batcher": delta(stats1, batcher.stats),
        }
        data.memory_peak_bytes = common.memory_peak_bytes()
    data.profile = common.reduce_trace(log_dir)

    lateness = [r[1] - r[0] for r in rows if r[1] is not None]
    latency = [r[2] - r[0] for r in rows if r[2] is not None]
    harness.note(phase="window", compiles_in_window=window_compiles,
                 latency_ms={
                     "p50": 1e3 * percentile(latency, 0.5),
                     "p90": 1e3 * percentile(latency, 0.9),
                     "p99": 1e3 * percentile(latency, 0.99),
                     "mean": 1e3 * sum(latency) / len(latency)}
                 if latency else None,
                 generator_lateness_ms={
                     "p50": 1e3 * percentile(lateness, 0.5),
                     "p95": 1e3 * percentile(lateness, 0.95),
                     "max": 1e3 * max(lateness)} if lateness else None,
                 routing=data.program["batcher"])
    give_up = ctx.seconds + tr["drain_s"]
    data.requests = [requests_row(r, give_up) for r in rows]
    data.attempted = len(rows)
    data.failed = sum(not r["ok"] for r in data.requests)
    data.checks = check(cell.reference().Library(docs), window, cfg, rows)
    return data


def ladder_bursts(cluster: dict, sizes: list) -> list:
    """Bursts of the mix's bodies, one kind to a burst, of each size in
    ``sizes``: sent at once, a burst makes the router batch its requests
    on the device, so the flush shapes (batch, slots and dictionary size
    buckets, per kind) that open-loop traffic meets are compiled in
    set-up. Bodies are distinct; the indices are the same every seed. A
    kind too rare to fill every size gets the sizes its bodies fill."""
    by_kind: dict = {}
    i = 0
    while any(len(by_kind.get(k, ())) < sum(sizes)
              for k in by_kind or [None]) and i < 100 * sum(sizes):
        kind = arrivals.body(i, "x", cluster)["kind"]
        by_kind.setdefault(kind, []).append(i)
        i += 1
    bursts = []
    for kind, indices in sorted(by_kind.items()):
        for size in sizes:
            if len(indices) >= size:
                bursts.append(indices[:size])
                indices = indices[size:]
    return bursts


def settle(clock: harness.CompileClock, quiet_s: float = 3.0,
           limit_s: float = 90.0) -> float:
    """Wait until no compile has started for ``quiet_s`` (compiles the
    warm-up set off in the background finish before the window)."""
    t0 = time.perf_counter()
    seen = clock.compiles
    quiet_since = t0
    while time.perf_counter() - t0 < limit_s:
        time.sleep(0.5)
        if clock.compiles != seen:
            seen, quiet_since = clock.compiles, time.perf_counter()
        elif time.perf_counter() - quiet_since >= quiet_s:
            break
    return time.perf_counter() - t0


def requests_row(r: list, give_up: float) -> dict:
    """One load-generator result as the metric readers see it."""
    ok = r[3] == 200 and isinstance(r[4], bool)
    return {"due": r[0], "sent": r[1], "done": r[2], "ok": ok,
            "latency_s": (r[2] - r[0]) if ok else None,
            "give_up_s": give_up - r[0]}


def check(library, window: dict, cfg: dict, rows: list) -> list:
    """Every answer against the reference ``library``: ``allowed`` and
    the denying (policy, rule) set must equal it; a request with no
    answer fails."""
    wrong = unanswered = 0
    reqs = arrivals.requests(window, cfg["cluster"])
    for (_, review), row in zip(reqs, rows):
        if row[3] != 200 or not isinstance(row[4], bool):
            unanswered += 1
            continue
        want = {f"{p}/{r}" for p, r in library.denials(review["request"])}
        if row[4] != (not want) or set(row[5]) != want:
            wrong += 1
    if len(rows) != len(reqs):
        unanswered += abs(len(reqs) - len(rows))
    return [harness.Check("wrong_decisions", wrong, 0),
            harness.Check("unanswered", unanswered, 0)]


def _program_metrics() -> dict:
    """The program's process-wide metrics registry, in the exposition
    format ``/metrics`` serves. The stage-span and trace histograms are
    fed to this registry; the webhook port's ``/metrics`` exposes the
    controller's own registry, which does not hold them."""
    from kyverno_tpu.runtime import metrics, tracing

    tracing.recorder().feed_metrics()
    return promtext.parse(metrics.registry().expose())


def expect(child, key: str) -> dict:
    """The load generator's next line, which must carry ``key``."""
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"load generator ended before '{key}' "
                           f"(exit {child.poll()})")
    msg = json.loads(line)
    if key not in msg:
        raise RuntimeError(f"load generator said {msg!r}, not '{key}'")
    return msg


def delta(before: dict, after: dict) -> dict:
    """Numeric counters that moved, after minus before."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float)) and v != before.get(k, 0)}
