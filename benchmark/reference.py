"""Plain reference: Kyverno validate-pattern semantics, written out alone.

This module decides what ``correct`` means for every cell whose
configuration names no reference of its own (``benchmark/spec.py`` gives
the interface a reference keeps). It imports nothing of the program
under test and takes nothing the program made: it reads the policy
documents of a configuration and the resource bodies the benchmark
generated, and answers with Kyverno's rule statuses.

It covers what the configurations under ``benchmark/configs`` use, as
Kyverno release-1.5 defines it, and refuses anything else loudly
(:class:`Unsupported`), so a configuration that needs more cannot pass
unchecked:

- policy defaults and pod-controller autogen (``autogen-<rule>`` for
  DaemonSet/Deployment/Job/StatefulSet under ``spec.template``,
  ``autogen-cronjob-<rule>`` for CronJob under
  ``spec.jobTemplate.spec.template``; variables under
  ``request.object.spec``/``request.object.metadata`` shift with them;
  the ``pod-policies.kyverno.io/autogen-controllers`` annotation);
- ``match.resources.kinds``;
- ``validate.pattern`` with maps, arrays of maps (every element must
  match), arrays of scalars (index by index), the anchors ``(key)``
  (condition: a failing element is skipped, a rule with no applicable
  element is SKIP), ``=(key)`` (equality: checked where present) and
  ``X(key)`` (negation: the key must be absent), the wildcard ``*``
  value (key must be present), and scalar patterns with ``|`` and ``&``,
  the operators ``!``, ``>``, ``>=``, ``<``, ``<=`` on numbers, and the
  wildcards ``*`` and ``?``;
- ``{{request.object.<path>}}`` variables in patterns; a path that does
  not resolve makes the rule ERROR.

Statuses are the strings ``pass``, ``fail``, ``skip``, ``error``, or None
where the rule does not apply to the resource.
"""

from __future__ import annotations

import copy
import json
import re

from benchmark import roofline

PASS, FAIL, SKIP, ERROR = "pass", "fail", "skip", "error"

POD_CONTROLLERS = ("DaemonSet", "Deployment", "Job", "StatefulSet")
AUTOGEN_ANNOTATION = "pod-policies.kyverno.io/autogen-controllers"

#: controls: the reference with one of the configuration's guarantees
#: broken, the shortcut a later change could be tempted to take
CONTROLS = ("first_container_only",)


class Unsupported(ValueError):
    """A policy or value outside what this reference implements."""


class _Mismatch(Exception):
    pass


class _Skip(Exception):
    pass


class _Unresolved(Exception):
    pass


# ------------------------------------------------------------ policies


class Rule:
    __slots__ = ("policy", "name", "kinds", "pattern", "enforce")

    def __init__(self, policy: str, name: str, kinds: tuple, pattern,
                 enforce: bool):
        self.policy = policy
        self.name = name
        self.kinds = kinds
        self.pattern = pattern
        self.enforce = enforce


def _kinds(rule: dict) -> list:
    match = rule.get("match") or {}
    if set(match) - {"resources"}:
        raise Unsupported(f"match block {sorted(match)}")
    res = match.get("resources") or {}
    if set(res) - {"kinds"}:
        raise Unsupported(f"match.resources {sorted(res)}")
    kinds = list(res.get("kinds") or [])
    if not kinds or any("/" in k or "*" in k for k in kinds):
        raise Unsupported(f"kinds {kinds}")
    return kinds


def _shift(pattern, spec_to: str, meta_to: str):
    raw = json.dumps(pattern)
    raw = raw.replace("request.object.spec", spec_to)
    raw = raw.replace("request.object.metadata", meta_to)
    return json.loads(raw)


def expand_policy(doc: dict) -> list[Rule]:
    """One ClusterPolicy document -> its rules as Kyverno runs them:
    defaults applied and the pod-controller rules generated."""
    if doc.get("kind") != "ClusterPolicy":
        raise Unsupported(f"kind {doc.get('kind')}")
    meta = doc.get("metadata") or {}
    spec = doc.get("spec") or {}
    name = meta["name"]
    enforce = (spec.get("validationFailureAction") or "audit").lower() \
        == "enforce"
    rules = spec.get("rules") or []
    controllers = (meta.get("annotations") or {}).get(
        AUTOGEN_ANNOTATION, "DaemonSet,Deployment,Job,StatefulSet,CronJob")
    # Pod mixed with another kind in one rule stops autogen for the policy
    if any("Pod" in _kinds(r) and len(_kinds(r)) > 1 for r in rules):
        controllers = "none"
    ctl = [c for c in controllers.split(",") if c] \
        if controllers != "none" else []
    names = {r["name"] for r in rules}
    out: list[Rule] = []
    generated: list[Rule] = []
    for r in rules:
        extra = set(r) - {"name", "match", "validate"}
        if extra:
            raise Unsupported(f"rule keys {sorted(extra)}")
        validate = r.get("validate") or {}
        if set(validate) - {"message", "pattern"}:
            raise Unsupported(f"validate keys {sorted(validate)}")
        if "pattern" not in validate:
            raise Unsupported("validate without pattern")
        kinds = _kinds(r)
        pattern = validate["pattern"]
        out.append(Rule(name, r["name"], tuple(kinds), pattern, enforce))
        if kinds != ["Pod"] or r["name"].startswith("autogen-"):
            continue
        plain = [c for c in ctl if c in POD_CONTROLLERS]
        gname = f"autogen-{r['name']}"[:63]
        if plain and gname not in names:
            generated.append(Rule(
                name, gname, tuple(plain),
                _shift({"spec": {"template": pattern}},
                       "request.object.spec.template.spec",
                       "request.object.spec.template.metadata"),
                enforce))
        cname = f"autogen-cronjob-{r['name']}"[:63]
        if "CronJob" in ctl and cname not in names:
            generated.append(Rule(
                name, cname, ("CronJob",),
                _shift({"spec": {"jobTemplate": {"spec": {
                    "template": pattern}}}},
                    "request.object.spec.jobTemplate.spec.template.spec",
                    "request.object.spec.jobTemplate.spec.template."
                    "metadata"),
                enforce))
    return out + generated


# ------------------------------------------------------------ matching


_ANCHOR = re.compile(r"^(\(|=\(|X\()(.+)\)$")


def _anchor(key: str):
    m = _ANCHOR.match(key)
    if not m:
        if key[:2] in ("^(", "+(", "<(") or key.startswith("$("):
            raise Unsupported(f"anchor {key}")
        return None, key
    return {"(": "condition", "=(": "equality", "X(": "negation"}[
        m.group(1)], m.group(2)


def _element(value, pattern) -> None:
    if isinstance(pattern, dict):
        if not isinstance(value, dict):
            raise _Mismatch
        _map(value, pattern)
    elif isinstance(pattern, list):
        if not isinstance(value, list):
            raise _Mismatch
        _array(value, pattern)
    elif not _scalar(value, pattern):
        raise _Mismatch


def _map(value: dict, pattern: dict) -> None:
    plain = []
    for key, sub in pattern.items():
        kind, bare = _anchor(key)
        if kind is None:
            plain.append((bare, sub))
        elif kind == "condition":
            if bare in value:
                try:
                    _element(value[bare], sub)
                except (_Mismatch, _Skip):
                    raise _Skip from None
        elif kind == "equality":
            if bare in value:
                _element(value[bare], sub)
        elif bare in value:          # negation
            raise _Mismatch
    for key, sub in sorted(plain, key=lambda kv: kv[0]):
        if sub == "*":
            if value.get(key) is None:
                raise _Mismatch
            continue
        _element(value.get(key), sub)


def _array(value: list, pattern: list) -> None:
    if not pattern:
        raise _Mismatch
    if isinstance(pattern[0], dict):
        applied = skipped = 0
        for item in value:
            try:
                _element(item, pattern[0])
            except _Skip:
                skipped += 1
                continue
            applied += 1
        if applied == 0 and skipped:
            raise _Skip
        return
    if len(value) < len(pattern):
        raise _Mismatch
    for item, sub in zip(value, pattern):
        _element(item, sub)


def _wild(pattern: str, text: str) -> bool:
    regex = "".join(".*" if c == "*" else "." if c == "?" else re.escape(c)
                    for c in pattern)
    return re.fullmatch(regex, text, re.S) is not None


def _number(v):
    if isinstance(v, bool):
        return None
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, str) and re.fullmatch(r"-?\d+(\.\d+)?", v):
        return float(v)
    return None


def _scalar(value, pattern) -> bool:
    if isinstance(pattern, bool):
        return isinstance(value, bool) and value == pattern
    if isinstance(pattern, (int, float)):
        n = _number(value)
        return n is not None and n == float(pattern)
    if pattern is None:
        return value in (None, "", False, 0)
    if not isinstance(pattern, str):
        raise Unsupported(f"pattern {pattern!r}")
    if value == pattern:
        return True
    return any(all(_one(value, part.strip(" ")) for part in alt.split("&"))
               for alt in (a.strip(" ") for a in pattern.split("|")))


_OPS = (">=", "<=", ">", "<", "!")


def _one(value, pattern: str) -> bool:
    op = next((o for o in _OPS if pattern.startswith(o)), "")
    rest = pattern[len(op):].strip()
    if re.fullmatch(r"\d+(\.\d+)?", rest):
        n = _number(value)
        if n is None:
            if value is None or isinstance(value, (dict, list)):
                return False
            raise Unsupported(f"number pattern {pattern!r} on {value!r}")
        want = float(rest)
        return {"": n == want, "!": n != want, ">": n > want,
                ">=": n >= want, "<": n < want, "<=": n <= want}[op]
    if re.match(r"\d", rest):
        raise Unsupported(f"quantity or duration pattern {pattern!r}")
    if op not in ("", "!"):
        return False
    if isinstance(value, bool):
        text = "true" if value else "false"
    elif isinstance(value, int):
        text = str(value)
    elif isinstance(value, str):
        text = value
    else:
        return False
    hit = _wild(rest, text)
    return not hit if op == "!" else hit


# ------------------------------------------------------------ variables


_VAR = re.compile(r"\{\{\s*([^{}]*?)\s*\}\}")


def _resolve(expr: str, request: dict):
    if not re.fullmatch(r"request(\.[A-Za-z0-9_-]+)+", expr):
        raise Unsupported(f"variable {expr!r}")
    node = {"request": request}
    for part in expr.split("."):
        if not isinstance(node, dict) or node.get(part) is None:
            raise _Unresolved(expr)
        node = node[part]
    return node


def _substitute(pattern, request: dict):
    if isinstance(pattern, dict):
        return {k: _substitute(v, request) for k, v in pattern.items()}
    if isinstance(pattern, list):
        return [_substitute(v, request) for v in pattern]
    if not isinstance(pattern, str) or "{{" not in pattern:
        return pattern
    whole = _VAR.fullmatch(pattern)
    if whole:
        return _resolve(whole.group(1), request)

    def text(m):
        v = _resolve(m.group(1), request)
        if not isinstance(v, str):
            raise Unsupported(f"non-string {m.group(1)} inside a string")
        return v
    return _VAR.sub(text, pattern)


# ------------------------------------------------------------ library


def _first_container_only(resource: dict) -> dict:
    out = copy.deepcopy(resource)
    spec = out.get("spec") or {}
    for pod in (spec, (spec.get("template") or {}).get("spec") or {}):
        if isinstance(pod, dict) and isinstance(pod.get("containers"), list):
            pod["containers"] = pod["containers"][:1]
    return out


class Library:
    """The rules of one configuration's policies. Rules with the same
    kinds and pattern are decided once per resource."""

    def __init__(self, docs: list[dict], control: str | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        self.rules: list[Rule] = []
        for doc in docs:
            self.rules.extend(expand_policy(doc))
        groups: dict[str, list[int]] = {}
        for i, r in enumerate(self.rules):
            key = json.dumps([sorted(r.kinds), r.pattern], sort_keys=True)
            groups.setdefault(key, []).append(i)
        self._groups = [(self.rules[ix[0]], ix) for ix in groups.values()]
        self.index = {(r.policy, r.name): i for i, r in enumerate(self.rules)}

    def status(self, rule: Rule, resource: dict, request: dict) -> str:
        try:
            pattern = _substitute(rule.pattern, request)
        except _Unresolved:
            return ERROR
        try:
            _element(resource, pattern)
        except _Mismatch:
            return FAIL
        except _Skip:
            return SKIP
        return PASS

    def row(self, resource: dict, request: dict | None = None) -> list:
        """Status of every rule (None where it does not apply)."""
        if request is None:
            request = {"object": resource}
        kind = resource.get("kind")
        seen = resource
        if self.control == "first_container_only":
            seen = _first_container_only(resource)
        out: list = [None] * len(self.rules)
        for rule, members in self._groups:
            if kind not in rule.kinds:
                continue
            s = self.status(rule, seen, request)
            for i in members:
                out[i] = s
        return out

    def read_paths(self) -> set:
        """The paths the rules' patterns read (leaves and variables)."""
        return roofline.read_paths([r.pattern for r in self.rules])

    def denials(self, request: dict) -> set:
        """(policy, rule) pairs of enforce policies that block one
        admission request: every rule that fails or errs."""
        row = self.row(request["object"], request)
        return {(r.policy, r.name) for r, s in zip(self.rules, row)
                if r.enforce and s in (FAIL, ERROR)}
