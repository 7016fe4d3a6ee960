"""BENCHMARK.json keeps to its contract, and every name resolves to its
files; a cell, configuration, traffic mix or metric added as files and
entries alone is found by name."""

import json
import re
import shutil

import pytest

from benchmark import spec
from benchmark.tests.cells import TOY_REFERENCE

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) <= 64 * 1024


def test_names_units_and_bounds(bench):
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert all(NAME.match(n) for n in names)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        group = [x["name"] for x in bench[key]]
        assert len(group) == len(set(group))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and m["layer"]


def test_every_cell_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        assert cell.chips == 1
        assert cell.policies()
        assert cell.traffic["kind"] in ("admission", "scan")
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
        for trace in (False, True):
            assert all(callable(read) for _, read in cell.readers(trace))


def test_each_config_is_used_and_its_file_lies_under_paths(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_added_files_are_found_by_name(tmp_path, bench):
    """A later change adds a cell with its own configuration, plain
    reference, traffic mix and metric reader: new files and entries, no
    edits."""
    here = tmp_path / "benchmark"
    shutil.copytree(spec.HERE / "configs", here / "configs")
    shutil.copytree(spec.HERE / "traffic", here / "traffic")
    shutil.copytree(spec.HERE / "metrics", here / "metrics")
    (here / "references").mkdir()
    (here / "references" / "toy-ref.py").write_text(TOY_REFERENCE)
    cfg = json.loads((here / "configs" / "best-practices.json").read_text())
    cfg["name"] = "tenant-copy"
    cfg["reference"] = "toy-ref"
    (here / "configs" / "tenant-copy.json").write_text(json.dumps(cfg))
    (here / "traffic" / "admit-slow.json").write_text(json.dumps(
        {"kind": "admission", "rate_rps": 2.0, "warmup_s": 5,
         "drain_s": 30, "trace_s": 5}))
    (here / "metrics" / "admit.new_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    added = json.loads(json.dumps(bench))
    added["workloads"].append({"name": "copy-admit-slow",
                               "config": "tenant-copy",
                               "traffic": "admit-slow", "chips": 1,
                               "why": "test"})
    added["per_layer"].append({"name": "admit.new_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "webhook",
                               "moves": "admission_p95_ms",
                               "workloads": ["copy-admit-slow"]})
    added["end_to_end"].append({"name": "admission_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["copy-admit-slow"]})
    cell = spec.Cell(added, "copy-admit-slow", here=here)
    assert cell.config["name"] == "tenant-copy"
    assert cell.reference().Library.TOY
    assert cell.reference().__file__ == str(here / "references" / "toy-ref.py")
    assert cell.traffic["rate_rps"] == 2.0
    readers = dict((m["name"], r) for m, r in cell.readers(trace=True))
    assert readers["admit.new_ms"](None) == 1.5
    assert [m["name"] for m, _ in cell.readers(trace=False)] == [
        "setup_s", "admission_p95_ms"]


def test_missing_files_are_refused(tmp_path, bench):
    with pytest.raises(spec.SpecError):
        spec.Cell(bench, bench["workloads"][0]["name"], here=tmp_path)
    with pytest.raises(spec.SpecError):
        spec.Cell(bench, "no-such-cell")
