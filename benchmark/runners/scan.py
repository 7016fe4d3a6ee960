"""Scan cells: full background-scan passes, back to back.

Set-up compiles the configuration's policies into a ``PolicyCache``,
fills an in-memory cluster from the configuration's generator and runs
one warm pass (compiling the scan's chunk shapes) through
``BackgroundScanner.scan()``, the call the controller's background loop
makes, here without the report pipeline. Before every timed pass the
cluster is filled anew with bodies no earlier pass of the run has seen,
outside the pass's time, so no memo answers a pass. Passes run until
their summed time reaches the window (or the mix's ``max_passes``, which
only a pass that does no work reaches). After each pass a sample of rows
drawn from the seed is kept, and once the window has closed every
sampled row's verdicts are compared with the plain reference on every
rule, host-resolved cells included.

What the runner keeps across passes (the samples, the traced pass's
bodies) is kept pickled, as bytes the garbage collector does not track:
kept as objects, it grew the heap pass by pass, and with it the
threshold of the interpreter's full collections, so that early passes
paid them and later ones did not, and a run's rate rested on how many
passes fit the window.
"""

from __future__ import annotations

import gc
import pickle
import random
import time

from benchmark import clusters, harness
from benchmark.runners import common


def _key(resource: dict) -> tuple:
    meta = resource.get("metadata") or {}
    return (resource.get("kind", ""), meta.get("namespace", ""),
            meta.get("name", ""))


def run(ctx: harness.Context) -> harness.RunData:
    from kyverno_tpu.api.load import load_policy
    from kyverno_tpu.models import Verdict
    from kyverno_tpu.policy.autogen import mutate_policy_for_autogen
    from kyverno_tpu.runtime.background import BackgroundScanner
    from kyverno_tpu.runtime.client import FakeCluster
    from kyverno_tpu.runtime.hostlane import resolver
    from kyverno_tpu.runtime.policycache import PolicyCache

    cell, cfg, tr = ctx.cell, ctx.cell.config, ctx.cell.traffic
    docs = cell.policies()
    common.decidability(docs)
    params = cfg["cluster"]
    gen = clusters.generator(params["generator"])
    n = gen.size(params)
    cache = PolicyCache()
    for doc in docs:
        cache.add(mutate_policy_for_autogen(load_policy(doc)))

    def fill(salt: str):
        bodies = [gen.resource(i, salt, params) for i in range(n)]
        expected = {_key(b) for b in bodies}
        return FakeCluster(bodies), expected

    t = time.perf_counter()
    cluster, _ = fill(f"w{ctx.seed}")
    fill_s = time.perf_counter() - t
    scanner = BackgroundScanner(cache.all_policies(), client=cluster)
    c0 = ctx.clock.compiles
    t = time.perf_counter()
    warm = scanner.scan()
    harness.note(phase="warm", rows=warm.resources_scanned,
                 fill_s=fill_s, pass_s=time.perf_counter() - t,
                 stage_s=warm.stage_s, compiles=ctx.clock.compiles - c0,
                 rules=len(warm.rule_refs), host_only_rules=int(
                     scanner.cps.tensors.rule_host_only[
                         :scanner.cps.tensors.n_rules_live].sum()))
    del warm

    names = {int(v): (None if v == Verdict.NOT_APPLICABLE
                      else v.name.lower()) for v in Verdict}
    rng = random.Random(ctx.seed)
    data = harness.RunData(kind="scan", seconds=0.0, setup_s=0.0)
    samples: list = []          # pickled (body, verdicts) pairs
    traced_rows = None          # pickled bodies of the traced pass
    missing = 0
    spent = 0.0
    log_dir = None
    host0 = dict(resolver().stats)
    gc_clock = _GcClock()
    while spent < ctx.seconds and len(data.passes) < tr["max_passes"]:
        p = len(data.passes) + 1
        scanner.client, expected = fill(f"s{ctx.seed}p{p}")
        gc.collect()
        if p == 1:
            data.setup_s = time.perf_counter() - ctx.t0
        c0 = ctx.clock.compiles
        gc0 = gc_clock.seconds
        with common.traced(ctx.trace and p == 1, ctx.scratch,
                           "scan_pass") as traced_dir:
            t = time.perf_counter()
            result = scanner.scan()
            dt = time.perf_counter() - t
        spent += dt
        data.passes.append({"rows": result.resources_scanned,
                            "seconds": dt, "stage_s": dict(result.stage_s),
                            "compiles": ctx.clock.compiles - c0,
                            "gc_s": gc_clock.seconds - gc0})
        if traced_dir is not None:
            log_dir = traced_dir
            traced_rows = _keep(result.resources)
        keys = [_key(r) for r in result.resources]
        got = set(keys)
        missing += (len(expected - got) + len(got - expected)
                    + len(keys) - len(got))
        pick = rng.sample(range(len(result.resources)),
                          min(tr["check_rows_per_pass"],
                              len(result.resources)))
        for b in pick:
            kind, ns, name = keys[b]
            truth = scanner.client.get_resource("", kind, ns, name)
            samples.append(_keep((truth, {
                (ref.policy.name, ref.rule.name):
                    names.get(int(result.verdicts[b, ref.rule_index]),
                              "unknown")
                for ref in result.rule_refs})))
        del result
    gc_clock.close()
    data.seconds = spent
    data.memory_peak_bytes = common.memory_peak_bytes()
    host = {k: v - host0.get(k, 0) for k, v in resolver().stats.items()}
    harness.note(phase="window", passes=[
        {k: v for k, v in ps.items()} for ps in data.passes],
        compiles_in_window=sum(ps["compiles"] for ps in data.passes),
        host_lane=host)
    common.reap_children()
    data.profile = common.reduce_trace(log_dir)
    if traced_rows is not None:
        data.traced_rows = pickle.loads(traced_rows)

    library = cell.reference().Library(docs)
    data.read_paths = library.read_paths()
    data.n_rules = len(library.rules)
    data.peaks = _peaks(ctx.device_kind)
    data.attempted = sum(ps["rows"] for ps in data.passes)
    data.failed = missing
    data.checks = check(library, [pickle.loads(s) for s in samples],
                        missing)
    return data


def _keep(obj) -> bytes:
    return pickle.dumps(obj, pickle.HIGHEST_PROTOCOL)


class _GcClock:
    """Seconds the interpreter spent collecting garbage: a diagnostic
    beside each pass's time."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def _peaks(kind: str):
    try:
        return harness.peaks_for(kind)
    except KeyError:
        return None


def check(library, samples: list, missing: int) -> list:
    """Sampled rows against the reference ``library`` on every rule;
    rows the pass left out, added or doubled; rules one side has and the
    other not."""
    t = time.perf_counter()
    want_keys = set(library.index)
    wrong = 0
    rules_missing = 0
    examples: list = []
    for body, got in samples:
        if body is None:
            wrong += len(want_keys)
            continue
        want = dict(zip(((r.policy, r.name) for r in library.rules),
                        library.row(body)))
        rules_missing = max(rules_missing, len(want_keys ^ set(got)))
        bad = [k for k in want_keys & set(got) if got[k] != want[k]]
        wrong += len(bad)
        if bad and len(examples) < 5:
            examples.append({"resource": _key(body), "rules": [
                [k[0], k[1], got[k], want[k]] for k in sorted(bad)[:4]]})
    harness.note(reference={"rows": len(samples),
                            "seconds": time.perf_counter() - t},
                 mismatches=examples)
    return [harness.Check("wrong_cells", wrong, 0),
            harness.Check("rows_missing", missing, 0),
            harness.Check("rules_missing", rules_missing, 0)]
