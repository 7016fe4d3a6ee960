"""The arithmetic of the end-to-end and per-layer metrics, the arrivals,
the least-bytes count and the trace reduction, on small inputs."""

import json
from pathlib import Path

import pytest

from benchmark import arrivals, devtrace, roofline, spec, stats
from benchmark.harness import RunData

DATA = Path(__file__).resolve().parent / "data"


def _reader(name):
    return spec.load_reader(name)


def _req(latency, ok=True, due=0.0, give_up=100.0):
    return {"due": due, "ok": ok, "latency_s": latency if ok else None,
            "give_up_s": give_up}


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 0.95) == 95
    assert stats.percentile(values, 0.5) == 50
    assert stats.percentile([7.0], 0.95) == 7.0


def test_p95_counts_failures_as_misses():
    ok = [_req(0.1) for _ in range(94)]
    read = _reader("admission_p95_ms")
    run = RunData(kind="admission", seconds=10, setup_s=1,
                  requests=ok + [_req(0.2)] * 6)
    assert read(run) == pytest.approx(200.0)
    # six failures out of 100: the 95th percentile is a miss, counted as
    # the longest wait the run allowed it
    run.requests = ok + [_req(None, ok=False, give_up=61.0)] * 6
    assert read(run) == pytest.approx(61000.0)
    # five failures: the tail still lands on an answered request
    run.requests = ok + [_req(0.3)] + [_req(None, ok=False)] * 5
    assert read(run) == pytest.approx(300.0)


def test_scan_rate_and_per_krow_arithmetic():
    passes = [{"rows": 20000, "seconds": 10.0,
               "stage_s": {"flatten": 1.0, "device": 0.5,
                           "host_resolve": 6.0}},
              {"rows": 20000, "seconds": 14.0,
               "stage_s": {"flatten": 1.0, "device": 0.5,
                           "host_resolve": 8.0}}]
    run = RunData(kind="scan", seconds=24.0, setup_s=1, passes=passes)
    assert _reader("scan_rows_per_s")(run) == pytest.approx(40000 / 24.0)
    # 40 krows: flatten 2 s -> 50 ms/krow, host 14 s -> 350 ms/krow,
    # glue 24 - 2 - 1 - 14 = 7 s -> 175 ms/krow
    assert _reader("scan.flatten_ms_per_krow")(run) == pytest.approx(50.0)
    assert _reader("scan.device_stage_ms_per_krow")(run) == \
        pytest.approx(25.0)
    assert _reader("scan.host_ms_per_krow")(run) == pytest.approx(350.0)
    assert _reader("scan.scanner_ms_per_krow")(run) == pytest.approx(175.0)
    # admission metrics read nothing in a scan cell, and back
    assert _reader("admission_p95_ms")(run) is None
    assert _reader("scan_rows_per_s")(
        RunData(kind="admission", seconds=1, setup_s=1)) is None


def test_stage_spans_per_admission():
    program = {"traces": {("admission",): [20.0, 400.0]},
               "stages": {("flatten", "flush"): [0.8, 50.0],
                          ("flatten", "admission"): [0.4, 10.0],
                          ("flatten", "scan"): [9.0, 3.0],
                          ("coalesce_wait", "admission"): [4.0, 120.0]},
               "batcher": {"device_decided": 100}}
    run = RunData(kind="admission", seconds=40, setup_s=1, program=program)
    assert _reader("admit.webhook_ms")(run) == pytest.approx(50.0)
    assert _reader("admit.flatten_ms")(run) == pytest.approx(3.0)
    assert _reader("admit.coalesce_wait_ms")(run) == pytest.approx(10.0)
    assert _reader("admit.device_decided_share")(run) == pytest.approx(25.0)
    assert _reader("admit.host_resolve_ms")(run) == 0.0


def test_roofline_bytes_on_a_tiny_corpus():
    patterns = [
        {"spec": {"containers": [{"image": "!*:latest"}]}},
        {"metadata": {"name": "{{request.object.metadata.name}}"}},
        {"spec": {"=(volumes)": [{"X(hostPath)": "null"}]}},
        {"spec": {"containers": [{"livenessProbe": {"periodSeconds": ">0"}}]}},
    ]
    assert roofline.read_paths(patterns) == {
        ("spec", "containers", "[]", "image"),
        ("metadata", "name"),
        ("spec", "volumes", "[]", "hostPath"),
        ("spec", "containers", "[]", "livenessProbe", "periodSeconds")}
    rows = [
        {"metadata": {"name": "abc"},
         "spec": {"containers": [
             {"image": "nginx:1", "livenessProbe": {"periodSeconds": 5}},
             {"image": "x:y"}],
             "volumes": [{"hostPath": {"path": "/"}}, {"emptyDir": {}}]}},
        {"metadata": {}, "spec": {}},
    ]
    # row 1: images 7 + 3, name 3, one hostPath map 1, one number 8 = 22;
    # row 2: nothing; plus one verdict byte per (row, rule): 2 x 5
    assert roofline.least_bytes(rows, roofline.read_paths(patterns), 5) \
        == 22 + 10


def test_roofline_reader_uses_the_named_program():
    read = _reader("scan.kernel_roofline")
    rows = [{"metadata": {"name": "a" * 100}}]
    patterns = [{"metadata": {"name": "?*"}}]
    run = RunData(kind="scan", seconds=1, setup_s=1, traced_rows=rows,
                  read_paths=roofline.read_paths(patterns), n_rules=10,
                  peaks={"hbm_bytes_per_s": 1e9},
                  profile={"programs": {"jit_evaluate_blob": 1e-6,
                                        "jit_other": 5.0},
                           "busy_s": 1, "window_s": 2})
    # 110 bytes at 1 GB/s = 110 ns of a 1 us kernel
    assert read(run) == pytest.approx(11.0)
    run.profile["programs"] = {"jit_other": 5.0}
    assert read(run) is None


def test_arrivals_give_every_seed_the_same_work():
    a = arrivals.schedule(13.0, 40.0, order_seed=1)
    b = arrivals.schedule(13.0, 40.0, order_seed=2 ** 31 + 5)
    assert len(a) == len(b) == 520
    assert sorted(i for _, i in a) == sorted(i for _, i in b)
    gaps = [y[0] - x[0] for x, y in zip(a, a[1:])]
    gaps_b = [y[0] - x[0] for x, y in zip(b, b[1:])]
    assert a[0][0] == 0.0 and a[-1][0] < 40.0
    assert sum(gaps) / len(gaps) == pytest.approx(40.0 / 520, rel=0.05)
    assert a != b and sorted(gaps) != gaps
    assert sorted(round(g, 9) for g in gaps + [40.0 - a[-1][0]]) == \
        sorted(round(g, 9) for g in gaps_b + [40.0 - b[-1][0]])


def _synthetic_planes():
    ms = 1_000_000
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_evaluate_blob(17)", 10 * ms, 30 * ms),
                            ("jit_evaluate_blob(18)", 60 * ms, 10 * ms)],
            "XLA Ops": [("fusion.1", 10 * ms, 20 * ms),
                        ("fusion.2", 25 * ms, 15 * ms),
                        ("while.3", 60 * ms, 10 * ms),
                        ("fusion.1", 95 * ms, 20 * ms)],
        },
        "/host:CPU": {"python3": [("bench:traced", 0, 100 * ms),
                                  ("bench:scan_pass", 0, 100 * ms),
                                  ("bench:fill", 40 * ms, 15 * ms)]},
    }


def test_trace_reduction_on_a_synthesized_trace():
    r = devtrace.reduce(_synthetic_planes())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 40] + [60, 70] + [95, 100] clipped = 45 ms
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["programs"] == {"jit_evaluate_blob": pytest.approx(0.04)}
    assert r["ops"][0] == ("fusion.1", pytest.approx(0.025))
    assert r["gaps"] == [["scan_pass", pytest.approx(0.025)],
                         ["fill", pytest.approx(0.02)],
                         ["scan_pass", pytest.approx(0.01)]]
    assert devtrace.reduce({"/host:CPU": {}}) is None


def test_trace_reduction_on_a_recorded_tpu_trace():
    """A cut of a trace taken on a v5e during a 2,000-row scan."""
    planes = json.loads((DATA / "tpu_scan_trace.json").read_text())
    r = devtrace.reduce(planes)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.281895309)
    assert r["programs"] == {"jit_evaluate_blob": pytest.approx(0.015337722)}
    assert 0 < r["busy_s"] <= r["programs"]["jit_evaluate_blob"]
    assert r["gaps"][0][0] == "scan_pass"
    assert r["gaps"][0][1] > 0.2
