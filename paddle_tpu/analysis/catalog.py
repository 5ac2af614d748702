"""Catalog extraction: metric/fault-point names from code and from docs.

One parser, two consumers: TPL003/TPL004 (static parity, both
directions) and ``tools/metrics_dump.py --check-docs`` (runtime parity:
the live registry from a ``--demo`` run diffed against the same doc
table). Keeping the doc grammar in one place is the point — the moment
the parser and the prose drift, BOTH checks fail on the same line.

Doc grammar (docs/OBSERVABILITY.md, docs/RESILIENCE.md):

- Only **table rows** count (lines starting with ``|``) and only
  outside fenced code blocks — prose and quick-start examples can
  mention any name without registering it in the catalog.
- A metric is a backtick span that IS a metric token:
  ``` `paddle_tpu_foo_total{label,label}` ``` (the ``{...}`` label hint
  is stripped; spans with placeholders like ``<name>`` are skipped —
  they document dynamically-named families).
- A fault point is a backtick span in the row's FIRST cell matching
  ``subsystem.point`` (lowercase dotted), the RESILIENCE.md fault-point
  table shape.

Code grammar:

- A metric registration is ``<registry>.counter|gauge|histogram(name,
  ...)`` where ``<registry>`` looks like a registry (``reg`` / ``_REG``
  / ``registry`` / ``metrics.get_registry()`` / ``get_registry()``).
  ``profiler.record_counter("a.b", v)`` also registers: its bridged
  gauge lands at ``sanitize_metric_name("a.b")``.
- A fault site is a literal first argument to ``faults.point`` /
  ``faults.declare_point`` / ``faults.inject`` (or those names imported
  bare). Non-literal names (``faults.point(point_name)``) are skipped —
  the literal appears at the caller that chose the name.
- A trace emit site (TPL010) is ``<tracer>.emit("name", ...)`` — or a
  span site, ``.begin("name", ...)`` / ``.next("name", ...)`` — with a
  literal name where ``<tracer>`` looks like a tracer (``trace`` /
  ``_trace`` / ``tracer`` / ``_tracer`` tail, or a ``get_tracer()``
  call) — the receiver shape is the discriminator that keeps
  unrelated ``.emit(...)`` APIs (the ONNX node builder) out of the
  catalog. Doc side: a backtick span in the FIRST cell of an
  OBSERVABILITY.md table row matching ``req.name`` / ``step.name``, or
  one of the bare span names ``step`` / ``sweep``.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from .scopes import dotted_name

__all__ = [
    "FaultSite", "MetricRegistration", "TraceEmit",
    "collect_fault_sites", "collect_label_uses",
    "collect_metric_registrations", "collect_trace_emits",
    "parse_event_doc", "parse_fault_doc", "parse_metric_doc",
    "sanitize_metric_name",
]

_METRIC_TOKEN_RE = re.compile(
    r"^(paddle_tpu_[a-zA-Z0-9_]+)(\{([a-zA-Z0-9_,\s]*)\})?$")
_FAULT_TOKEN_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
# trace events are namespaced req./step./brownout. — disjoint from
# fault tokens only by convention, so the event catalog lives in
# OBSERVABILITY.md (TPL010) while fault points live in RESILIENCE.md
# (TPL004)
_EVENT_TOKEN_RE = re.compile(
    r"^((req|step|brownout)\.[a-z][a-z0-9_]*|step|sweep)$")
_TRACE_SITE_ATTRS = ("emit", "begin", "next")
_TRACER_RECEIVER_RE = re.compile(r"^_?tracer?$")
_BACKTICK_RE = re.compile(r"`([^`]+)`")
_REGISTRY_RECEIVER_RE = re.compile(r"^_?reg(istry)?$", re.IGNORECASE)

# the registry's naming funnel, duplicated in miniature so the linter
# never imports paddle_tpu (see paddle_tpu/metrics/registry.py
# sanitize_metric_name — the two are pinned equal by tests)
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def sanitize_metric_name(raw: str) -> str:
    s = re.sub(r"[^a-zA-Z0-9_:]", "_", str(raw))
    if not s or not _NAME_RE.match(s):
        s = "_" + s
    if not s.startswith("paddle_tpu_"):
        s = "paddle_tpu_" + s
    return s


# ------------------------------------------------------------------ doc side
def _table_rows(text: str):
    """(lineno, line) for markdown table rows outside fenced code."""
    fenced = False
    for i, line in enumerate(text.splitlines(), 1):
        stripped = line.strip()
        if stripped.startswith("```"):
            fenced = not fenced
            continue
        if fenced or not stripped.startswith("|"):
            continue
        if set(stripped) <= {"|", "-", " ", ":"}:
            continue                     # separator row
        yield i, stripped


def parse_metric_doc(path: str) -> Dict[str, Tuple[int, Tuple[str, ...]]]:
    """{metric_name: (lineno, declared label hint)} from the FIRST cell
    of catalog table rows — a prose cross-reference in another row's
    meaning cell must not satisfy parity after the real row is deleted.
    ``{eng}`` is the docs' shorthand for the per-engine
    ``{engine_id, model_id}`` pair and expands accordingly."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    out: Dict[str, Tuple[int, Tuple[str, ...]]] = {}
    for lineno, row in _table_rows(text):
        cells = [c.strip() for c in row.strip("|").split("|")]
        if not cells:
            continue
        for span in _BACKTICK_RE.findall(cells[0]):
            m = _METRIC_TOKEN_RE.match(span.strip())
            if not m:
                continue
            labels: List[str] = []
            for lab in (m.group(3) or "").split(","):
                lab = lab.strip()
                if lab == "eng":
                    labels.extend(("engine_id", "model_id"))
                elif lab:
                    labels.append(lab)
            out.setdefault(m.group(1), (lineno, tuple(labels)))
    return out


def parse_fault_doc(path: str) -> Dict[str, int]:
    """{fault_point: lineno} from the first cell of catalog table rows."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    out: Dict[str, int] = {}
    for lineno, row in _table_rows(text):
        cells = [c.strip() for c in row.strip("|").split("|")]
        if not cells:
            continue
        for span in _BACKTICK_RE.findall(cells[0]):
            if _FAULT_TOKEN_RE.match(span.strip()):
                out.setdefault(span.strip(), lineno)
    return out


def parse_event_doc(path: str) -> Dict[str, int]:
    """{trace_event_name: lineno} from the first cell of catalog table
    rows — the docs/OBSERVABILITY.md event-name table (TPL010)."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    out: Dict[str, int] = {}
    for lineno, row in _table_rows(text):
        cells = [c.strip() for c in row.strip("|").split("|")]
        if not cells:
            continue
        for span in _BACKTICK_RE.findall(cells[0]):
            if _EVENT_TOKEN_RE.match(span.strip()):
                out.setdefault(span.strip(), lineno)
    return out


# ----------------------------------------------------------------- code side
@dataclass(frozen=True)
class MetricRegistration:
    name: Optional[str]        # None when the name isn't a literal
    kind: str                  # counter / gauge / histogram / bridge-gauge
    labels: Optional[Tuple[str, ...]]   # None when not statically known
    relpath: str
    line: int


@dataclass(frozen=True)
class FaultSite:
    name: str
    kind: str                  # point / declare_point / inject
    relpath: str
    line: int


@dataclass(frozen=True)
class TraceEmit:
    name: str
    relpath: str
    line: int


def _is_registry_receiver(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return bool(_REGISTRY_RECEIVER_RE.match(node.id))
    if isinstance(node, ast.Attribute):
        # self._registry / metrics.registry style
        return bool(_REGISTRY_RECEIVER_RE.match(node.attr))
    if isinstance(node, ast.Call):
        tail = dotted_name(node.func)
        return bool(tail and tail.split(".")[-1] == "get_registry")
    return False


def _literal_labels(call: ast.Call) -> Optional[Tuple[str, ...]]:
    """The ``labels=`` keyword as a tuple of strings, () when absent,
    None when present but not a literal (e.g. ``labels=_eng``)."""
    for kw in call.keywords:
        if kw.arg == "labels":
            v = kw.value
            if isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, str)
                    for e in v.elts):
                return tuple(e.value for e in v.elts)
            return None
    return ()


def registration_of(call: ast.Call, relpath: str) -> \
        Optional[MetricRegistration]:
    """The MetricRegistration described by ``call``, or None when the
    call isn't a registry declaration."""
    func = call.func
    if isinstance(func, ast.Attribute) and func.attr in (
            "counter", "gauge", "histogram"):
        if not _is_registry_receiver(func.value) or not call.args:
            return None
        first = call.args[0]
        name = (first.value if isinstance(first, ast.Constant)
                and isinstance(first.value, str) else None)
        return MetricRegistration(name=name, kind=func.attr,
                                  labels=_literal_labels(call),
                                  relpath=relpath, line=call.lineno)
    tail = dotted_name(func)
    if tail and tail.split(".")[-1] == "record_counter" and call.args:
        first = call.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            return MetricRegistration(
                name=sanitize_metric_name(first.value), kind="bridge-gauge",
                labels=(), relpath=relpath, line=call.lineno)
    return None


def collect_metric_registrations(tree: ast.Module,
                                 relpath: str) -> List[MetricRegistration]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            reg = registration_of(node, relpath)
            if reg is not None:
                out.append(reg)
    return out


def collect_label_uses(tree: ast.Module) -> List[Tuple[ast.Call,
                                                       Optional[str]]]:
    """Every ``<receiver>.labels(...)`` call with the receiver's dotted
    name — TPL003 cross-checks the keywords against the declaration the
    receiver was assigned from. A Call receiver (the chained
    ``reg.counter(...).labels(...)`` one-liner) has no dotted name and
    is yielded with recv=None; the rule resolves it directly from the
    chained registration."""
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "labels"):
            recv = dotted_name(node.func.value)
            if recv is not None or isinstance(node.func.value, ast.Call):
                out.append((node, recv))
    return out


_FAULT_FUNCS = {"point", "declare_point", "inject"}


def collect_fault_sites(tree: ast.Module, relpath: str) -> List[FaultSite]:
    """Literal fault-point names at ``faults.point/declare_point/inject``
    call sites. Bare names (``point(...)``) count only when the module
    imported them from the faults package — a module defining its own
    ``point()`` is not a fault site."""
    bare_ok = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
                node.module.endswith("faults")
                or node.module.endswith("injection")
                or node.module == "faults"):
            for alias in node.names:
                if alias.name in _FAULT_FUNCS:
                    bare_ok.add(alias.asname or alias.name)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = node.func
        kind = None
        if (isinstance(func, ast.Attribute) and func.attr in _FAULT_FUNCS
                and isinstance(func.value, ast.Name)
                and func.value.id == "faults"):
            kind = func.attr
        elif isinstance(func, ast.Name) and func.id in bare_ok:
            kind = func.id
        if kind is None:
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            out.append(FaultSite(name=first.value, kind=kind,
                                 relpath=relpath, line=node.lineno))
    return out


def _is_tracer_receiver(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return bool(_TRACER_RECEIVER_RE.match(node.id))
    if isinstance(node, ast.Attribute):
        # self._trace / tracing_module.tracer style — the TAIL decides,
        # so a bare ``self.emit(...)`` (the ONNX builder) never matches
        return bool(_TRACER_RECEIVER_RE.match(node.attr))
    if isinstance(node, ast.Call):
        tail = dotted_name(node.func)
        return bool(tail and tail.split(".")[-1] == "get_tracer")
    return False


def collect_trace_emits(tree: ast.Module, relpath: str) -> List[TraceEmit]:
    """Literal trace-event names at tracer ``.emit(...)`` / ``.begin(...)``
    / ``.next(...)`` call sites (see the module docstring's trace-emit
    grammar)."""
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _TRACE_SITE_ATTRS
                and node.args
                and _is_tracer_receiver(node.func.value)):
            first = node.args[0]
            if isinstance(first, ast.Constant) \
                    and isinstance(first.value, str):
                out.append(TraceEmit(name=first.value, relpath=relpath,
                                     line=node.lineno))
    return out
