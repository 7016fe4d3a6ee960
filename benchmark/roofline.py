"""The least bytes a policy scan has to move, counted from the inputs.

A scan must at least read, for every row, the leaf values at the paths
its policy set reads, and write one verdict byte per (row, rule). The
configuration's reference says which paths its rules read
(``Library.read_paths()``); for validate patterns :func:`read_paths`
walks them (pattern leaves, and the paths that pattern variables name).
Counting from the corpus and the policy set, not from the program's
tensors, keeps the number the same whatever a later change does to the
packing. A string leaf counts its UTF-8 bytes, a number 8, a boolean 1,
and a map or list standing at a read path (a presence check) 1.
"""

from __future__ import annotations

import json
import re

_ANCHOR = re.compile(r"^(?:\(|=\(|X\()(.+)\)$")
_VAR = re.compile(r"\{\{\s*request\.object\.([^{}\s]+)\s*\}\}")


def read_paths(patterns) -> set:
    """Paths (tuples of keys, ``[]`` for list elements) that a set of
    validate patterns reads."""
    paths: set = set()

    def walk(p, prefix: tuple) -> None:
        if isinstance(p, dict):
            for key, sub in p.items():
                m = _ANCHOR.match(key)
                walk(sub, prefix + (m.group(1) if m else key,))
        elif isinstance(p, list):
            for sub in p:
                walk(sub, prefix + ("[]",))
        else:
            paths.add(prefix)
    for pattern in patterns:
        walk(pattern, ())
        for var in _VAR.findall(json.dumps(pattern)):
            paths.add(tuple(var.split(".")))
    return paths


def _leaf(value) -> int:
    if isinstance(value, str):
        return len(value.encode())
    if isinstance(value, bool):
        return 1
    if isinstance(value, (int, float)):
        return 8
    if isinstance(value, (dict, list)):
        return 1
    return 0


def _at(node, path: tuple) -> int:
    if not path:
        return _leaf(node)
    head, rest = path[0], path[1:]
    if head == "[]":
        return sum(_at(x, rest) for x in node) if isinstance(node, list) \
            else 0
    if isinstance(node, dict) and head in node:
        return _at(node[head], rest)
    return 0


def least_bytes(rows: list, paths, n_rules: int) -> int:
    """Bytes read at ``paths`` of every row, plus a verdict byte per
    (row, rule)."""
    return sum(_at(row, p) for row in rows for p in paths) \
        + len(rows) * n_rules
