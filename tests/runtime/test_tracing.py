"""Span recorder, flight-recorder ring, and real histogram buckets.

Covers the PR-6 observability layer: runtime/tracing.py (trace/span
recording, ring + K-slowest eviction, Chrome export, KTPU_TRACE kill
switch), the MetricsRegistry bucket histograms + label escaping +
build_info/reset gauges, and runtime/obs_http.py routing.
"""

import gc
import json
import threading
import time

import numpy as np

from kyverno_tpu.api.load import load_policy
from kyverno_tpu.runtime import metrics as metrics_mod
from kyverno_tpu.runtime import obs_http, tracing
from kyverno_tpu.runtime.background import BackgroundScanner
from kyverno_tpu.runtime.batch import CLEAN, AdmissionBatcher
from kyverno_tpu.runtime.client import FakeCluster
from kyverno_tpu.runtime.metrics import MetricsRegistry
from kyverno_tpu.runtime.policycache import PolicyCache, PolicyType

ENFORCE = {
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "disallow-latest-tag"},
    "spec": {
        "validationFailureAction": "enforce",
        "rules": [{
            "name": "validate-image-tag",
            "match": {"resources": {"kinds": ["Pod"]}},
            "validate": {"message": "latest tag not allowed",
                         "pattern": {"spec": {"containers": [
                             {"image": "!*:latest"}]}}},
        }],
    },
}


def pod(image, name="p"):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{"name": "c", "image": image}]}}


def make_batcher(**kw):
    kw.setdefault("dispatch_cost_init_s", 0.0)
    kw.setdefault("oracle_cost_init_s", 1.0)
    kw.setdefault("cold_flush_fallback", False)
    kw.setdefault("result_cache_ttl_s", 0.0)
    cache = PolicyCache()
    cache.add(load_policy(ENFORCE))
    return AdmissionBatcher(cache, window_s=0.002, burst_threshold=1,
                            **kw), cache


# --------------------------------------------------------------- recorder


class TestRecorder:
    def test_span_recording_and_export(self):
        rec = tracing.TraceRecorder(ring_size=8)
        t = rec.start("admission", path="/validate")
        with rec.span(t, "flatten", lane="memo"):
            pass
        rec.add_span(t, "scatter", t.t_start, t.t_start + 0.001, row=0)
        rec.finish(t, allowed="True")
        [got] = rec.traces(1)
        assert got is t and t._finished
        d = t.to_dict()
        assert d["labels"]["allowed"] == "True"
        assert {s["name"] for s in d["spans"]} == {"flatten", "scatter"}
        # spans are reported relative to trace start, in t0 order
        assert [s["t0_us"] for s in d["spans"]] == sorted(
            s["t0_us"] for s in d["spans"])

    def test_lane_provenance_stamped_at_start(self, monkeypatch):
        rec = tracing.TraceRecorder()
        t = rec.start("flush")
        assert t.labels["lanes"] == "all-on"
        monkeypatch.setenv("KTPU_HOST_PREFETCH", "0")
        t2 = rec.start("flush")
        assert "host_prefetch=off" in t2.labels["lanes"]

    def test_kill_switch_disables_recording(self, monkeypatch):
        monkeypatch.setenv("KTPU_TRACE", "0")
        rec = tracing.TraceRecorder()
        assert rec.start("admission") is None
        # every instrumentation idiom tolerates the None trace
        with rec.span(None, "flatten") as s:
            assert s is None
        assert rec.add_span(None, "x", 0.0, 1.0) is None
        rec.finish(None)
        assert rec.traces() == []

    def test_ring_keeps_last_n(self):
        rec = tracing.TraceRecorder(ring_size=4, keep_slowest=2)
        for i in range(10):
            t = rec.start("admission", i=i)
            rec.finish(t)
        ring = rec.traces(10)
        assert len(ring) == 4
        # newest first
        assert [t.labels["i"] for t in ring] == [9, 8, 7, 6]

    def test_slowest_heap_keeps_k_slowest(self):
        rec = tracing.TraceRecorder(ring_size=2, keep_slowest=3)
        durations = [0.004, 0.001, 0.010, 0.002, 0.006, 0.003]
        for i, d in enumerate(durations):
            t = rec.start("admission", i=i)
            # synthesize the duration instead of sleeping
            t.t_start = time.perf_counter() - d
            rec.finish(t)
        kept = {t.labels["i"] for t in rec.slowest(10)}
        # the three slowest survive even though the ring holds only 2
        assert kept == {2, 4, 0}

    def test_max_spans_cap_counts_drops(self):
        rec = tracing.TraceRecorder(max_spans=4)
        t = rec.start("flush")
        for i in range(10):
            rec.add_span(t, f"s{i}", 0.0, 1.0)
        assert len(t.spans) == 4
        assert t.spans_dropped == 6

    def test_chrome_export_round_trips(self):
        rec = tracing.TraceRecorder()
        for i in range(3):
            t = rec.start("admission", i=i)
            with rec.span(t, "flatten"):
                pass
            with rec.span(t, "scatter"):
                pass
            rec.finish(t)
        blob = json.dumps(rec.chrome_trace(10))
        doc = json.loads(blob)
        events = doc["traceEvents"]
        assert len(events) == 3 * 3       # one trace event + two spans each
        for e in events:
            assert e["ph"] == "X"
            assert e["ts"] >= 0 and e["dur"] >= 0
        # per-trace (pid) the span timestamps are monotonic in emit order
        by_pid: dict = {}
        for e in events:
            if e["tid"] != 0:
                by_pid.setdefault(e["pid"], []).append(e["ts"])
        for ts in by_pid.values():
            assert ts == sorted(ts)

    def test_contextvar_binding(self):
        rec = tracing.TraceRecorder()
        t = rec.start("admission")
        assert tracing.current() is None or tracing.current() is not t
        with tracing.active(t):
            assert tracing.current() is t
            tok = tracing.bind(None)
            assert tracing.current() is None
            tracing.unbind(tok)
            assert tracing.current() is t

    def test_adopted_spans_counted_once(self):
        """A flush span adopted into many waiter traces must observe the
        stage histogram exactly once."""
        from kyverno_tpu.runtime import metrics as metrics_mod

        reg = metrics_mod.registry()
        rec = tracing.TraceRecorder()
        flush = rec.start("flush")
        rec.add_span(flush, "flatten", 0.0, 0.25)
        rec.finish(flush)
        rec.feed_metrics()

        key = frozenset({"stage": "flatten", "kind": "admission"}.items())

        def count():
            h = reg._histograms.get(
                "kyverno_stage_duration_seconds", {}).get(key)
            return h[0] if h else 0

        before = count()
        for _ in range(3):
            w = rec.start("admission")
            w.adopt_spans(flush.spans)
            rec.finish(w)
        rec.feed_metrics()
        # the flush already counted it under kind="flush"; the waiters
        # must not re-count the shared span at all
        assert count() == before


# ----------------------------------------------------- pipeline tracing


class TestPipelineTraces:
    def test_single_admission_trace_covers_stages(self):
        """Acceptance: one screened admission yields a retrievable trace
        covering flatten -> coalesce -> dispatch -> host-lane -> scatter
        with lane/cache provenance."""
        rec = tracing.recorder()
        rec.clear()
        batcher, _ = make_batcher()
        try:
            t = rec.start("admission", path="/validate")
            with tracing.active(t):
                status, _ = batcher.screen(
                    PolicyType.VALIDATE_ENFORCE, "Pod", "default",
                    pod("nginx:1.21"))
            rec.finish(t)
            assert status == CLEAN
            names = t.stage_names()
            assert {"coalesce_wait", "flatten",
                    "host_resolve", "scatter"} <= names
            assert ("device_dispatch" in names) or ("xla_compile" in names)
            by_name = {s.name: s for s in t.spans}
            assert by_name["coalesce_wait"].labels["lane"] in (
                "device", "fallback")
            assert by_name["flatten"].labels["lane"] in (
                "memo", "kill_switch")
            assert t.labels["lanes"] == "all-on"
        finally:
            batcher.stop()

    def test_concurrent_flushes_well_nested_spans(self):
        """Concurrent screens produce, per trace and per thread lane,
        well-nested spans: any two either disjoint or contained — never
        partially overlapping."""
        rec = tracing.recorder()
        rec.clear()
        batcher, _ = make_batcher()
        try:
            # warm the shape bucket so the burst takes the async path
            batcher.screen(PolicyType.VALIDATE_ENFORCE, "Pod", "default",
                           pod("warm:1"))
            traces = []
            lock = threading.Lock()

            def one(i):
                t = rec.start("admission", i=i)
                with tracing.active(t):
                    batcher.screen(PolicyType.VALIDATE_ENFORCE, "Pod",
                                   "default", pod(f"img:{i}", name=f"n{i}"))
                rec.finish(t)
                with lock:
                    traces.append(t)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(8)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            assert len(traces) == 8
            for t in traces:
                assert t.spans, f"trace {t.trace_id} recorded no spans"
                by_tid: dict = {}
                for s in t.spans:
                    by_tid.setdefault(s.tid, []).append(s)
                for spans in by_tid.values():
                    spans.sort(key=lambda s: (s.t0, -s.t1))
                    for a in range(len(spans)):
                        for b in range(a + 1, len(spans)):
                            sa, sb = spans[a], spans[b]
                            disjoint = sb.t0 >= sa.t1
                            nested = sb.t1 <= sa.t1
                            assert disjoint or nested, (
                                f"partial overlap {sa.name}/{sb.name}")
                # no orphan spans: every span inside the trace window
                for s in t.spans:
                    assert s.t0 >= t.t_start - 1e-6
                    assert s.t1 <= t.t_end + 1e-6
        finally:
            batcher.stop()

    def test_trace_off_bit_identical_verdicts(self, monkeypatch):
        resources = [pod(f"nginx:{i}", name=f"r{i}") for i in range(6)]
        resources += [pod("bad:latest", name="bad")]

        def run():
            batcher, _ = make_batcher()
            try:
                return [batcher.screen(PolicyType.VALIDATE_ENFORCE, "Pod",
                                       "default", r) for r in resources]
            finally:
                batcher.stop()

        on = run()
        monkeypatch.setenv("KTPU_TRACE", "0")
        off = run()
        assert on == off
        # and with tracing off, nothing new entered the global recorder
        rec = tracing.recorder()
        rec.clear()
        run()
        assert rec.traces(100) == []


# ---------------------------------------------------------- metrics/http


class TestHistogramBuckets:
    def test_cumulative_buckets_and_inf(self):
        reg = MetricsRegistry()
        reg.set_buckets("d", (0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            reg.observe("d", {"stage": "s"}, v)
        exp = reg.expose()
        assert '# TYPE d histogram' in exp
        assert 'd_bucket{stage="s",le="0.1"} 1' in exp
        assert 'd_bucket{stage="s",le="1"} 3' in exp
        assert 'd_bucket{stage="s",le="10"} 4' in exp
        assert 'd_bucket{stage="s",le="+Inf"} 5' in exp
        assert 'd_count{stage="s"} 5' in exp
        assert 'd_sum{stage="s"}' in exp

    def test_value_on_bound_lands_in_that_bucket(self):
        reg = MetricsRegistry()
        reg.set_buckets("d", (1.0, 2.0))
        reg.observe("d", None, 1.0)
        assert 'd_bucket{le="1"} 1' in reg.expose()

    def test_count_sum_callers_unchanged(self):
        reg = MetricsRegistry()
        reg.observe("kyverno_admission_review_duration_seconds",
                    {"operation": "CREATE"}, 0.25)
        exp = reg.expose()
        assert ('kyverno_admission_review_duration_seconds_count'
                '{operation="CREATE"} 1') in exp
        assert ('kyverno_admission_review_duration_seconds_sum'
                '{operation="CREATE"} 0.25') in exp

    def test_quantile_from_buckets(self):
        reg = MetricsRegistry()
        reg.set_buckets("d", (1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.5, 3.0):
            reg.observe("d", None, v)
        q = reg.histogram_quantile("d", 0.5)
        assert 1.0 <= q <= 2.0

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.inc_counter("c", {"policy": 'we"ird\npol\\icy'})
        exp = reg.expose()
        assert r'policy="we\"ird\npol\\icy"' in exp
        # the rendered line must survive a strict line-format parse
        line = next(l for l in exp.splitlines() if l.startswith("c{"))
        assert line.endswith("} 1")

    def test_build_info_and_reset_gauges(self):
        reg = MetricsRegistry()
        exp = reg.expose()
        assert "kyverno_tpu_build_info{" in exp
        assert 'engine="jax"' in exp
        assert "kyverno_metrics_last_reset_timestamp_seconds" in exp
        reg.inc_counter("c", {})
        reg.reset()
        exp2 = reg.expose()
        assert "kyverno_tpu_build_info{" in exp2     # survives reset
        assert "kyverno_metrics_last_reset_timestamp_seconds" in exp2


class TestObsHttp:
    def test_routing(self):
        status, body, ctype = obs_http.handle_obs_get("/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        assert b"kyverno_tpu_build_info" in body
        status, body, ctype = obs_http.handle_obs_get("/healthz")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok" and "lanes" in doc
        assert obs_http.handle_obs_get("/nope") is None

    def test_route_normalization(self):
        # duplicate and trailing slashes (reverse-proxy artifacts) must
        # land on the same route as the canonical path
        for path in ("//healthz", "/healthz/", "//healthz//",
                     "///healthz"):
            out = obs_http.handle_obs_get(path)
            assert out is not None, path
            status, body, _ = out
            assert status == 200 and json.loads(body)["status"] == "ok"
        for path in ("//metrics", "/metrics/", "//metrics//"):
            status, body, ctype = obs_http.handle_obs_get(path)
            assert status == 200 and ctype.startswith("text/plain")
        out = obs_http.handle_obs_get("//debug//traces?n=1")
        assert out is not None and out[0] == 200
        # normalization must not invent routes
        assert obs_http.handle_obs_get("/healthz/x") is None
        assert obs_http.handle_obs_get("/health//z") is None

    def test_lane_switches_include_stream_and_donate(self):
        lanes = tracing.killswitch_lanes()
        assert lanes.get("stream") == "on"
        assert lanes.get("donate") == "on"

    def test_debug_traces_params(self):
        rec = tracing.recorder()
        rec.clear()
        for i in range(5):
            t = rec.start("admission", i=i)
            rec.finish(t)
        _, body, _ = obs_http.handle_obs_get("/debug/traces?n=2")
        doc = json.loads(body)
        assert len(doc["traces"]) == 2
        _, body, _ = obs_http.handle_obs_get(
            "/debug/traces?n=3&format=chrome")
        doc = json.loads(body)
        assert "traceEvents" in doc
        _, body, _ = obs_http.handle_obs_get("/debug/traces?n=bogus")
        assert len(json.loads(body)["traces"]) == 5   # bad n -> default


# ----------------------------------------------------- scan stage spans

SCAN_STAGES = ("scan_snapshot", "flatten", "device_dispatch",
               "host_resolve", "scan_persist", "scan_tally")


def scanner(n=12):
    cluster = FakeCluster([pod("nginx:latest" if i % 3 == 0 else f"nginx:{i}",
                               name=f"s{i}") for i in range(n)])
    return BackgroundScanner([load_policy(ENFORCE)], client=cluster)


def fake_annotation(enabled: bool):
    """A stand-in for jax.profiler.TraceAnnotation that logs enter/exit
    and answers ``is_enabled()`` as told."""

    class Fake:
        on = enabled
        events: list = []

        def __init__(self, name):
            self.name = name

        @classmethod
        def is_enabled(cls):
            return cls.on

        def __enter__(self):
            self.events.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            self.events.append(("exit", self.name))
            return False

    return Fake


class TestScanStageSpans:
    def test_full_scan_records_each_stage_once(self):
        rec = tracing.recorder()
        rec.clear()
        result = scanner().scan()
        [t] = [t for t in rec.traces(8) if t.kind == "scan"]
        names = [s.name for s in t.spans]
        for stage in SCAN_STAGES:
            assert names.count(stage) == 1, (stage, names)
        by = {s.name: s for s in t.spans}
        assert by["scan_snapshot"].labels["rows"] == 12
        # the six are disjoint, in pass order, inside the trace
        six = [by[n] for n in ("scan_snapshot", "flatten", "device_dispatch",
                               "host_resolve", "scan_tally", "scan_persist")]
        for a, b in zip(six, six[1:]):
            assert a.t1 <= b.t0
        assert t.t_start <= six[0].t0 and six[-1].t1 <= t.t_end
        # the split rows are timed inside the persist stage
        assert by["scan_persist"].t0 <= by["row_split"].t0
        assert by["row_split"].t1 <= by["scan_persist"].t1
        # ScanResult.stage_s times the same blocks, inside the spans
        for stage, key in (("flatten", "flatten"),
                           ("device_dispatch", "device"),
                           ("host_resolve", "host_resolve")):
            assert 0 < result.stage_s[key] <= by[stage].duration_s

    def test_gc_tally_charged_to_the_bound_trace(self):
        rec = tracing.TraceRecorder()
        t = rec.start("scan")
        assert (t.gc_s, t.gc_collections) == (0.0, 0)
        with tracing.active(t):
            gc.collect()
        assert t.gc_collections >= 1 and t.gc_s > 0
        charged = t.gc_collections
        gc.collect()                  # no trace bound: charged to nobody
        assert t.gc_collections == charged
        rec.finish(t)
        d = t.to_dict()
        assert d["gc_collections"] == charged
        assert d["gc_us"] == int(t.gc_s * 1e6)

    def test_feed_metrics_observes_gc_once_per_trace(self):
        reg = metrics_mod.registry()
        labels = {"stage": "gc", "kind": "gc_probe"}
        before = reg.histogram_count("kyverno_stage_duration_seconds",
                                     labels)
        rec = tracing.TraceRecorder()
        for i in range(3):
            t = rec.start("gc_probe")
            if i:
                with tracing.active(t):
                    gc.collect()
            rec.finish(t)
        rec.feed_metrics()
        rec.feed_metrics()
        assert reg.histogram_count("kyverno_stage_duration_seconds",
                                   labels) == before + 3

    def test_span_annotates_only_under_a_profiler_session(self,
                                                          monkeypatch):
        fake = fake_annotation(enabled=False)
        monkeypatch.setattr(tracing, "_annotation_cls", fake)
        rec = tracing.TraceRecorder()
        t = rec.start("scan")
        with rec.span(t, "flatten"):
            pass
        with tracing.active(t):
            gc.collect()
        assert fake.events == []
        fake.on = True
        with rec.span(t, "flatten"):
            assert fake.events == [("enter", "ktpu:flatten")]
        assert fake.events[-1] == ("exit", "ktpu:flatten")
        with tracing.active(t):
            gc.collect()
        assert ("enter", "ktpu:gc") in fake.events
        assert fake.events[-1] == ("exit", "ktpu:gc")
        # explicit-timestamp spans stay off the profiler timeline
        n = len(fake.events)
        rec.add_span(t, "scatter", t.t_start, time.perf_counter())
        assert len(fake.events) == n
        assert [s.name for s in t.spans] == ["flatten", "flatten", "scatter"]

    def test_kill_switch_records_and_annotates_nothing(self, monkeypatch):
        fake = fake_annotation(enabled=True)
        monkeypatch.setattr(tracing, "_annotation_cls", fake)
        rec = tracing.recorder()
        rec.clear()
        on = scanner().scan()
        assert ("enter", "ktpu:scan_snapshot") in fake.events
        fake.events.clear()
        rec.clear()
        monkeypatch.setenv("KTPU_TRACE", "0")
        off = scanner().scan()
        with tracing.active(None):
            gc.collect()
        assert rec.traces(100) == [] and fake.events == []
        np.testing.assert_array_equal(on.verdicts, off.verdicts)
        assert (on.violations, on.rules_evaluated) == \
            (off.violations, off.rules_evaluated) and on.violations == 4
