"""Fleet observability plane: attribution, propagation, SLO, profiling.

Covers the PR-8 layer: bounded per-policy attribution
(runtime/metrics.py record_policy_verdicts / record_policy_verdict_matrix
/ attribution_snapshot), W3C-style trace propagation
(runtime/tracing.py make_traceparent / parse_traceparent /
adopt_remote_id + the stream-frame carriage), the SLO watchdog
(runtime/slo.py), the /debug/policies and /debug/profile endpoints, the
report/event metric wiring, and the concurrent-scrape race against the
recorder's deferred settle.
"""

import json
import os
import threading

import numpy as np
import pytest

from kyverno_tpu.runtime import metrics as metrics_mod
from kyverno_tpu.runtime import obs_http, tracing
from kyverno_tpu.runtime.metrics import MetricsRegistry
from kyverno_tpu.runtime.slo import SLOWatchdog, watchdog


@pytest.fixture(autouse=True)
def _fresh_attrib_state():
    metrics_mod.attrib_state().reset()
    yield
    metrics_mod.attrib_state().reset()


class _Ref:
    def __init__(self, policy, rule):
        self.policy = type("P", (), {"name": policy})()
        self.rule = type("R", (), {"name": rule})()


# ------------------------------------------------------------ attribution


class TestAttribution:
    def test_topk_overflow_folds_to_other(self):
        os.environ["KTPU_ATTRIB_TOP_K"] = "2"
        try:
            reg = MetricsRegistry()
            for p in ("pa", "pb", "pc"):
                metrics_mod.record_policy_verdicts(
                    reg, [(p, "r", "FAIL", 2)], lane="flush")
            assert reg.counter_value(
                "kyverno_policy_verdicts_total",
                {"policy": "pa", "rule": "r", "verdict": "FAIL",
                 "lane": "flush"}) == 2
            assert reg.counter_value(
                "kyverno_policy_verdicts_total",
                {"policy": "__other__", "rule": "__other__",
                 "verdict": "FAIL", "lane": "flush"}) == 2
            snap = metrics_mod.attribution_snapshot()
            assert snap["labelled_pairs"] == 2
            assert snap["tracked_pairs"] == 3
            assert snap["other_cells"] == 2
            # exact totals survive for the suppressed pair
            assert snap["overflow"] == [
                {"policy": "pc", "rule": "r", "total": 2}]
        finally:
            os.environ.pop("KTPU_ATTRIB_TOP_K", None)

    def test_killswitch_noops(self):
        os.environ["KTPU_ATTRIB"] = "0"
        try:
            reg = MetricsRegistry()
            metrics_mod.record_policy_verdicts(
                reg, [("p", "r", "PASS", 1)], lane="flush")
            metrics_mod.record_policy_flush_latency(reg, {"p"}, 0.01)
            assert reg.series_count("kyverno_policy_verdicts_total") == 0
            assert metrics_mod.attribution_snapshot()["tracked_pairs"] == 0
        finally:
            os.environ.pop("KTPU_ATTRIB", None)

    def test_matrix_feed_vectorized(self):
        reg = MetricsRegistry()
        refs = [_Ref("p0", "r0"), _Ref("p1", "r1")]
        from kyverno_tpu.models.engine import Verdict

        v = np.array([[Verdict.PASS, Verdict.FAIL],
                      [Verdict.PASS, Verdict.PASS],
                      [Verdict.NOT_APPLICABLE, Verdict.FAIL]], dtype=np.int32)
        metrics_mod.record_policy_verdict_matrix(reg, refs, v, lane="scan")
        assert reg.counter_value(
            "kyverno_policy_verdicts_total",
            {"policy": "p0", "rule": "r0", "verdict": "PASS",
             "lane": "scan"}) == 2
        assert reg.counter_value(
            "kyverno_policy_verdicts_total",
            {"policy": "p1", "rule": "r1", "verdict": "FAIL",
             "lane": "scan"}) == 2

    def test_tenant_rollup_bounded(self):
        reg = MetricsRegistry()
        st = metrics_mod.attrib_state()
        for i in range(metrics_mod._MAX_TENANTS + 5):
            metrics_mod.record_policy_verdicts(
                reg, [("p", "r", "PASS", 1)], lane="flush",
                namespace=f"ns-{i}")
        assert len(st.tenants) <= metrics_mod._MAX_TENANTS + 1
        assert st.tenants[metrics_mod.ATTRIB_OTHER]["PASS"] == 5

    def test_flush_latency_histogram(self):
        reg = MetricsRegistry()
        metrics_mod.record_policy_verdicts(
            reg, [("p", "r", "PASS", 1)], lane="flush")
        for _ in range(10):
            metrics_mod.record_policy_flush_latency(reg, {"p"}, 0.002)
        q = reg.histogram_quantile("kyverno_policy_latency_seconds", 0.99,
                                   {"policy": "p"})
        assert q is not None and 0.0 < q <= 0.01

    def test_debug_policies_endpoint(self):
        reg = metrics_mod.registry()
        metrics_mod.record_policy_verdicts(
            reg, [("ep", "er", "PASS", 4)], lane="flush", namespace="nsx")
        status, body, ctype = obs_http.handle_obs_get("/debug/policies?n=5")
        assert status == 200 and ctype == "application/json"
        payload = json.loads(body)
        assert payload["attrib_enabled"] is True
        rows = {(r["policy"], r["rule"]): r for r in payload["policies"]}
        assert rows[("ep", "er")]["verdicts"]["PASS"] == 4
        assert payload["tenants"]["nsx"]["PASS"] == 4


# ------------------------------------------------------------ propagation


class TestPropagation:
    def test_roundtrip_native_id(self):
        rec = tracing.TraceRecorder(ring_size=8)
        t = rec.start("admission")
        tp = tracing.make_traceparent(t)
        assert tp is not None and tp.startswith("00-")
        assert tracing.parse_traceparent(tp) == t.trace_id
        rec.finish(t)

    def test_parse_rejects_malformed(self):
        assert tracing.parse_traceparent(None) is None
        assert tracing.parse_traceparent("") is None
        assert tracing.parse_traceparent("garbage") is None
        assert tracing.parse_traceparent("00-zz-11-01") is None
        assert tracing.parse_traceparent("00-" + "0" * 32
                                         + "-0000000000000000-01") is None

    def test_foreign_w3c_id_passthrough(self):
        foreign = "00-" + "ab" * 16 + "-00f067aa0ba902b7-01"
        assert tracing.parse_traceparent(foreign) == "ab" * 16

    def test_adopt_remote_id(self):
        rec = tracing.TraceRecorder(ring_size=8)
        a = rec.start("client")
        b = rec.start("server")
        assert tracing.adopt_remote_id(
            b, tracing.parse_traceparent(tracing.make_traceparent(a)))
        assert b.trace_id == a.trace_id
        assert b.labels.get("remote") == "1"
        rec.finish(a)
        rec.finish(b)

    def test_propagate_killswitch(self):
        rec = tracing.TraceRecorder(ring_size=8)
        t = rec.start("admission")
        os.environ["KTPU_PROPAGATE"] = "0"
        try:
            assert tracing.make_traceparent(t) is None
            assert not tracing.adopt_remote_id(t, "deadbeef")
        finally:
            os.environ.pop("KTPU_PROPAGATE", None)
        rec.finish(t)

    def test_frame_carriage(self):
        from kyverno_tpu.runtime import stream_server as ss

        tp = "00-" + "cd" * 16 + "-0000000000000007-01"
        p = ss.encode_payload(ss.F_ADMIT_JSON, 42, b"{}", traceparent=tp)
        ftype, req_id, body, got = ss.decode_payload_ex(p)
        assert (ftype, req_id, body, got) == (ss.F_ADMIT_JSON, 42, b"{}",
                                              tp)
        # legacy 3-tuple decode strips the context
        assert ss.decode_payload(p) == (ss.F_ADMIT_JSON, 42, b"{}")
        # frames without the bit decode unchanged; response/error frames
        # never grow a prefix even when a traceparent is passed
        plain = ss.encode_payload(ss.F_ADMIT_ROW, 7, b"x")
        assert ss.decode_payload_ex(plain) == (ss.F_ADMIT_ROW, 7, b"x",
                                               None)
        verdict = ss.encode_payload(ss.F_VERDICT, 9, b"v", traceparent=tp)
        assert ss.decode_payload_ex(verdict) == (ss.F_VERDICT, 9, b"v",
                                                 None)
        err = ss.encode_payload(ss.F_ERROR, 3, b"e")
        assert ss.decode_payload_ex(err) == (ss.F_ERROR, 3, b"e", None)


# -------------------------------------------------------------------- SLO


class TestSLOWatchdog:
    def test_degraded_needs_both_windows_and_min_samples(self):
        w = SLOWatchdog()
        os.environ["KTPU_SLO_BUDGET_S"] = "0.01"
        try:
            for _ in range(4):                # below min samples (8)
                w.observe(0.05)
            assert not w.snapshot()["degraded"]
            for _ in range(8):
                w.observe(0.05)
            snap = w.snapshot()
            assert snap["degraded"]
            assert snap["burn_rate"]["short"] >= 1.0
            assert snap["burn_rate"]["long"] >= 1.0
        finally:
            os.environ.pop("KTPU_SLO_BUDGET_S", None)

    def test_fast_admissions_stay_ok(self):
        w = SLOWatchdog()
        for _ in range(64):
            w.observe(0.001)
        snap = w.snapshot()
        assert not snap["degraded"]
        assert snap["burn_rate"]["short"] < 0.01

    def test_killswitch(self):
        w = SLOWatchdog()
        os.environ["KTPU_SLO"] = "0"
        try:
            w.observe(100.0)
            assert w.snapshot() == {"enabled": False, "degraded": False}
            assert w.stats["observed"] == 0
        finally:
            os.environ.pop("KTPU_SLO", None)

    def test_annotation_and_cache(self):
        w = SLOWatchdog()
        assert w.annotation() is None
        os.environ["KTPU_SLO_BUDGET_S"] = "0.001"
        try:
            for _ in range(16):
                w.observe(0.05)
            ann = w.annotation()
            assert ann is not None and ann["slo"] == "degraded"
            first = w.cached_snapshot(max_age_s=60.0)
            assert w.cached_snapshot(max_age_s=60.0) is first
        finally:
            os.environ.pop("KTPU_SLO_BUDGET_S", None)

    def test_gauges_exported(self):
        w = watchdog()
        w.clear()
        for _ in range(16):
            w.observe(0.002)
        w.snapshot()
        reg = metrics_mod.registry()
        assert reg.gauge_value("kyverno_slo_admission_p99_seconds",
                               {"window": "short"}) is not None
        assert reg.gauge_value("kyverno_slo_degraded") == 0.0
        assert reg.gauge_value("kyverno_slo_budget_seconds") == 10.0
        w.clear()

    def test_healthz_degraded_verdict(self):
        w = watchdog()
        w.clear()
        for _ in range(16):
            w.observe(0.05)
        os.environ["KTPU_SLO_BUDGET_S"] = "0.001"
        try:
            status, body, _ = obs_http.handle_obs_get("/healthz")
            health = json.loads(body)
            assert health["status"] == "degraded"
            assert health["slo"]["degraded"] is True
            assert "streams" in health and \
                "open_streams" in health["streams"]
        finally:
            os.environ.pop("KTPU_SLO_BUDGET_S", None)
            w.clear()


# -------------------------------------------------------------- profiling


class TestProfiling:
    def test_capture_single_flight(self):
        from kyverno_tpu.runtime.profiling import ProfileCaptureService

        svc = ProfileCaptureService()
        out = svc.start(0.05)
        assert out["status"] == "capturing"
        busy = svc.start(0.05)
        assert busy["status"] == "busy"
        # wait for the window to close
        import time

        deadline = time.monotonic() + 10.0
        while svc.status()["capturing"] and time.monotonic() < deadline:
            time.sleep(0.02)
        st = svc.status()
        assert not st["capturing"]
        assert st["last"]["log_dir"].startswith("/")

    def test_endpoint_routing(self):
        status, body, _ = obs_http.handle_obs_get("/debug/profile")
        assert status == 200
        payload = json.loads(body)
        assert payload["status"] == "idle"
        assert "device_memory" in payload
        status, body, _ = obs_http.handle_obs_get(
            "/debug/profile?seconds=abc")
        assert status == 400

    def test_device_memory_snapshot_never_raises(self):
        from kyverno_tpu.runtime.profiling import device_memory_snapshot

        out = device_memory_snapshot(update_metrics=False)
        assert isinstance(out, dict)


# ----------------------------------------------------- report/event wiring


class TestPipelineWiring:
    def test_report_queue_depth_gauges(self):
        from kyverno_tpu.runtime.reports import ReportGenerator

        gen = ReportGenerator(client=None)
        gen.add_change_request({"apiVersion": "kyverno.io/v1alpha2",
                                "kind": "ReportChangeRequest",
                                "metadata": {"name": "x"}, "results": []})
        reg = metrics_mod.registry()
        assert reg.gauge_value("kyverno_report_pending_results") >= 1
        assert reg.gauge_value("kyverno_report_queue_depth") == 0

    def test_event_counters(self):
        from kyverno_tpu.runtime.client import FakeCluster
        from kyverno_tpu.runtime.events import EventGenerator, EventInfo

        reg = metrics_mod.registry()
        before = reg.counter_total("kyverno_events_emitted_total")
        gen = EventGenerator(FakeCluster())
        gen.run()
        try:
            gen.add(EventInfo(kind="Pod", name="p", namespace="default",
                              reason="PolicyApplied", message="m"))
            gen.drain(5.0)
        finally:
            gen.stop()
        assert reg.counter_total("kyverno_events_emitted_total") \
            == before + 1


# -------------------------------------------------- concurrent scrape race


class TestScrapeRace:
    def test_concurrent_scrapes_vs_settle_and_admissions(self):
        """/metrics scrapes racing feed_metrics() and span production:
        counters stay monotone, no scrape errors, no lost spans, and
        adopted (shared flush) spans histogram exactly once."""
        rec = tracing.TraceRecorder(ring_size=4096)
        reg = metrics_mod.registry()       # feed_metrics settles here
        n_threads, n_traces = 4, 50
        before_flat = reg.histogram_count(
            "kyverno_stage_duration_seconds", {"stage": "flatten"})
        before_scat = reg.histogram_count(
            "kyverno_stage_duration_seconds", {"stage": "scatter"})
        errors: list = []

        def produce(k):
            try:
                for i in range(n_traces):
                    t = rec.start("admission", worker=str(k))
                    rec.add_span(t, "flatten", 0.0, 0.001)
                    sp = rec.add_span(t, "scatter", 0.001, 0.002)
                    # adopted spans (the shared-flush-span shape) must
                    # histogram once even when two traces carry them
                    t2 = rec.start("admission", worker=f"{k}-adopt")
                    if t2 is not None and sp is not None:
                        t2.adopt_spans([sp])
                    rec.finish(t)
                    rec.finish(t2)
            except Exception as exc:   # pragma: no cover
                errors.append(exc)

        def scrape():
            try:
                last = 0.0
                for _ in range(40):
                    rec.feed_metrics()
                    cur = reg.histogram_count(
                        "kyverno_stage_duration_seconds")
                    assert cur >= last, "counter went backwards"
                    last = cur
            except Exception as exc:   # pragma: no cover
                errors.append(exc)

        producers = [threading.Thread(target=produce, args=(k,))
                     for k in range(n_threads)]
        scrapers = [threading.Thread(target=scrape) for _ in range(2)]
        for th in producers + scrapers:
            th.start()
        for th in producers + scrapers:
            th.join()
        assert not errors
        rec.feed_metrics()
        # no lost spans: every started trace settled
        assert rec.stats["started"] == 2 * n_threads * n_traces
        assert rec.stats["finished"] == 2 * n_threads * n_traces
        # no double-count of adopted flush spans: one flatten + one
        # scatter observation per primary trace, exactly once each
        flat = reg.histogram_count(
            "kyverno_stage_duration_seconds", {"stage": "flatten"})
        scat = reg.histogram_count(
            "kyverno_stage_duration_seconds", {"stage": "scatter"})
        assert flat - before_flat == n_threads * n_traces
        assert scat - before_scat == n_threads * n_traces


class TestWebhookMetrics:
    def test_webhook_port_serves_stage_histograms(self):
        """The webhook's /metrics serves its own registry and the
        process-wide one the recorder feeds, each family once."""
        import http.client

        from kyverno_tpu.api.load import load_policy
        from kyverno_tpu.runtime.client import FakeCluster
        from kyverno_tpu.runtime.policycache import PolicyCache
        from kyverno_tpu.runtime.webhook import (VALIDATING_WEBHOOK_PATH,
                                                 WebhookServer)

        cache = PolicyCache()
        cache.add(load_policy({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": "disallow-latest-tag"},
            "spec": {"validationFailureAction": "enforce", "rules": [{
                "name": "validate-image-tag",
                "match": {"resources": {"kinds": ["Pod"]}},
                "validate": {"message": "latest tag not allowed",
                             "pattern": {"spec": {"containers": [
                                 {"image": "!*:latest"}]}}}}]}}))
        review = json.dumps({
            "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {"uid": "m-1", "kind": {"kind": "Pod"},
                        "namespace": "default", "operation": "CREATE",
                        "object": {"apiVersion": "v1", "kind": "Pod",
                                   "metadata": {"name": "p",
                                                "namespace": "default"},
                                   "spec": {"containers": [
                                       {"name": "c",
                                        "image": "nginx:latest"}]}}}})
        own = MetricsRegistry()
        server = WebhookServer(policy_cache=cache, client=FakeCluster(),
                               registry=own)
        port = server.run(host="127.0.0.1", port=0).server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", VALIDATING_WEBHOOK_PATH, review,
                         {"Content-Type": "application/json"})
            assert conn.getresponse().read()
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            assert resp.status == 200
            text = resp.read().decode()
        finally:
            conn.close()
            server.stop()
        assert own is not metrics_mod.registry()
        assert "kyverno_admission_requests_total{" in text
        assert 'kyverno_stage_duration_seconds_bucket{' in text
        assert 'kyverno_trace_duration_seconds_count{kind="admission"}' \
            in text
        families = [line.split()[2] for line in text.splitlines()
                    if line.startswith("# TYPE ")]
        assert len(families) == len(set(families))
        assert "kyverno_tpu_build_info" in families
