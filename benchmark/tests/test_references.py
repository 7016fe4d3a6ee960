"""A configuration's plain reference, found by name: ``benchmark/
reference.py`` for a configuration without a ``reference`` key, the file
``benchmark/references/<name>.py`` it names otherwise, refused when the
cell is built if it lacks the interface; the scan check and the roofline
take what that library says."""

import json
import shutil
from types import SimpleNamespace

import pytest

from benchmark import clusters, reference, roofline, spec
from benchmark.runners import scan
from benchmark.tests.cells import TOY_REFERENCE, bench


@pytest.mark.parametrize("name", [w["name"] for w in bench()["workloads"]])
def test_a_config_without_the_key_is_judged_by_reference_py(name):
    cell = spec.Cell(bench(), name)
    assert "reference" not in cell.config
    assert cell.reference() is reference
    assert reference.__file__ == str(spec.HERE / "reference.py")


@pytest.mark.parametrize("source, control", [
    (None, "first_container_only"),                       # no such file
    (TOY_REFERENCE.replace("def read_paths", "def paths"),
     "first_container_only"),
    (TOY_REFERENCE.replace("CONTROLS = ", "KNOWN = "),
     "first_container_only"),
    (TOY_REFERENCE.replace("class Unsupported", "class Other"),
     "first_container_only"),
    (TOY_REFERENCE.replace("class Library", "class Judge"),
     "first_container_only"),
    (TOY_REFERENCE, "every_other_row"),                   # not in CONTROLS
], ids=["missing", "no-read_paths", "no-CONTROLS", "no-Unsupported",
        "no-Library", "unknown-control"])
def test_a_bad_reference_is_refused_when_the_cell_is_built(
        tmp_path, source, control):
    here = tmp_path / "benchmark"
    shutil.copytree(spec.HERE / "configs", here / "configs")
    shutil.copytree(spec.HERE / "traffic", here / "traffic")
    (here / "references").mkdir()
    if source is not None:
        (here / "references" / "toy.py").write_text(source)
    path = here / "configs" / "best-practices.json"
    cfg = json.loads(path.read_text())
    cfg.update(reference="toy", control=control)
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError):
        spec.Cell(spec.load_benchmark(), "bp-scan-fresh", here=here)


def test_scan_check_judges_rows_by_the_library_it_is_given():
    class Toy:
        rules = [SimpleNamespace(policy="p", name="a"),
                 SimpleNamespace(policy="p", name="b")]
        index = {("p", "a"): 0, ("p", "b"): 1}

        def row(self, body, request=None):
            return [body["say"], None]

    pod = {"kind": "Pod", "metadata": {"name": "x", "namespace": "n"}}
    fail, ok = dict(pod, say="fail"), dict(pod, say="pass")
    samples = [(fail, {("p", "a"): "fail", ("p", "b"): None}),
               (ok, {("p", "a"): "fail", ("p", "b"): None}),
               (ok, {("p", "a"): "pass"})]
    checks = {c.name: c.value for c in scan.check(Toy(), samples, 2)}
    assert checks == {"wrong_cells": 1, "rows_missing": 2,
                      "rules_missing": 1}


def test_least_bytes_through_read_paths_equals_the_pattern_count():
    """One namespace of the best-practices cluster: the count the
    roofline reads through ``Library.read_paths()`` is the count the
    pattern walk gave before references named their paths."""
    cell = spec.Cell(bench(), "bp-scan-fresh")
    params = dict(cell.config["cluster"], namespaces=1)
    gen = clusters.generator(params["generator"])
    rows = [gen.resource(i, "s2600000001p1", params)
            for i in range(gen.size(params))]
    library = cell.reference().Library(cell.policies())
    n = len(library.rules)
    least = roofline.least_bytes(rows, library.read_paths(), n)
    assert len(rows) == 3248 and n == 34
    assert least == roofline.least_bytes(
        rows, roofline.read_paths([r.pattern for r in library.rules]), n)
    assert least == 487818 and isinstance(least, int)
