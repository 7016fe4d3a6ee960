"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; each metric names a reader. They resolve to files in fixed places:

- configuration ``<c>``: ``benchmark/configs/<c>.json``, whose
  ``policies`` list files beside it;
- traffic mix ``<t>``: ``benchmark/traffic/<t>.json``;
- metric ``<m>``: ``benchmark/metrics/<m>.py``, defining ``read(run)``;
- plain reference ``<r>``, which decides ``correct``: the configuration's
  optional ``"reference": "<r>"`` names ``benchmark/references/<r>.py``;
  a configuration without the key is judged by ``benchmark/reference.py``.

A reference module imports nothing of the program (it may import helpers
of ``benchmark/reference.py``, such as autogen expansion) and defines:

- ``Library(docs, control=None)``: the rules of a configuration's policy
  documents, with one of ``CONTROLS`` broken where ``control`` names it;
  an instance has
  - ``.rules``: items with ``.policy`` and ``.name``, in row order;
  - ``.index``: ``(policy, rule) -> column`` of a row;
  - ``.row(body, request=None)``: one status (``"pass"``, ``"fail"``,
    ``"skip"``, ``"error"``) or ``None`` per rule;
  - ``.denials(request)``: the ``(policy, rule)`` pairs that block one
    admission request;
  - ``.read_paths()``: the paths (tuples of keys, ``"[]"`` for a list's
    elements) its rules read, which the roofline's least bytes count;
- ``CONTROLS``: the controls it implements; the configuration's
  ``control`` must be one of them;
- ``Unsupported``: the exception it raises on a policy or value it does
  not implement.

Adding a cell, configuration, traffic mix, metric or reference is adding
those files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache for the benchmark's runs: a fixed
#: place in the checkout, apart from the one the program keeps by default
COMPILE_CACHE = ROOT / ".benchmark_jax_cache"


class SpecError(ValueError):
    pass


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def config_path(name: str, here: Path = HERE) -> Path:
    return here / "configs" / f"{name}.json"


def traffic_path(name: str, here: Path = HERE) -> Path:
    return here / "traffic" / f"{name}.json"


def metric_path(name: str, here: Path = HERE) -> Path:
    return here / "metrics" / f"{name}.py"


def reference_path(name: str, here: Path = HERE) -> Path:
    return here / "references" / f"{name}.py"


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    return json.loads(path.read_text())


def load_policies(config: dict, here: Path = HERE) -> list[dict]:
    """The configuration's ClusterPolicy documents, in file order, each
    with the configuration's ``validationFailureAction``."""
    import yaml

    docs: list[dict] = []
    for rel in config["policies"]:
        path = here / "configs" / rel
        if not path.is_file():
            raise SpecError(f"missing policy file {path}")
        if path.suffix == ".json":
            loaded = json.loads(path.read_text())
            docs += loaded if isinstance(loaded, list) else [loaded]
        else:
            docs += [d for d in yaml.safe_load_all(path.read_text()) if d]
    for doc in docs:
        doc["spec"]["validationFailureAction"] = \
            config["validationFailureAction"]
    return docs


def _load_file(path: Path, module_name: str, what: str):
    if not path.is_file():
        raise SpecError(f"missing {what} {path}")
    mod_spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(mod_spec)
    # registered, so that dataclasses and pickling find the module
    sys.modules[module_name] = module
    mod_spec.loader.exec_module(module)
    return module


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def load_reader(name: str, here: Path = HERE):
    """The ``read`` function of metric ``name``'s reader file."""
    return _load_file(metric_path(name, here),
                      f"benchmark_metric_{_ident(name)}",
                      "metric reader").read


def load_reference(config: dict, here: Path = HERE):
    """The reference module that judges ``config``: the file its
    ``reference`` key names, else ``benchmark/reference.py``. Refused
    unless it defines the interface in this module's docstring and
    implements the configuration's ``control``."""
    if "reference" in config:
        name = config["reference"]
        module = _load_file(reference_path(name, here),
                            f"benchmark_reference_{_ident(name)}",
                            "reference")
    else:
        from benchmark import reference as module
    where = getattr(module, "__file__", module.__name__)
    library = getattr(module, "Library", None)
    lacks = [m for m in ("row", "denials", "read_paths")
             if not callable(getattr(library, m, None))] \
        if isinstance(library, type) else ["Library"]
    if not isinstance(getattr(module, "CONTROLS", None), (tuple, list)):
        lacks.append("CONTROLS")
    unsupported = getattr(module, "Unsupported", None)
    if not (isinstance(unsupported, type)
            and issubclass(unsupported, Exception)):
        lacks.append("Unsupported")
    if lacks:
        raise SpecError(f"reference {where} lacks {lacks}")
    if "control" in config and config["control"] not in module.CONTROLS:
        raise SpecError(f"reference {where} has no control "
                        f"{config['control']!r}; it has {module.CONTROLS}")
    return module


class Cell:
    """One workload of BENCHMARK.json with everything it names."""

    def __init__(self, bench: dict, name: str, here: Path = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _load_json(config_path(self.entry["config"], here))
        self.traffic = _load_json(traffic_path(self.entry["traffic"], here))
        self.here = here
        self._reference = load_reference(self.config, here)

        def applies(metric: dict) -> bool:
            return "workloads" not in metric or name in metric["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]
        # a per-layer metric without a cell list is read wherever the
        # end-to-end metric it moves is reported
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.per_layer
                          if "workloads" in m or m["moves"] in e2e]

    def readers(self, trace: bool) -> list[tuple[dict, object]]:
        metrics = self.per_layer if trace else self.end_to_end
        return [(m, load_reader(m["name"], self.here)) for m in metrics]

    def policies(self) -> list[dict]:
        return load_policies(self.config, self.here)

    def reference(self):
        """The reference module that decides this cell's ``correct``."""
        return self._reference
