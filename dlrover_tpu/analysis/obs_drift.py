"""Obs-catalog drift checker (GL6xx): docs ↔ code, both directions.

``docs/observability.md`` is the operator contract: its metric catalog,
span catalog and flight-event catalog tables claim what the fleet
emits, and ``obs/tsdb.DASHBOARD_SERIES`` claims what ``tools/top.py``
can render. PR 11's sixth review pass caught a ``DASHBOARD_SERIES``
entry that nothing fed; this checker makes that a lint failure instead:

GL601  a documented metric/span/flight-event that no code registers,
       ingests or emits (the code lost it, or the docs invented it).
GL602  an emitted metric/span/flight-event with no catalog row.
GL603  a ``DASHBOARD_SERIES`` entry no metric registration or tsdb
       ingest backs — the dashboard column renders empty forever.

Like the protocol pass this is cross-artifact: the per-file half
(:func:`extract_obs_facts`) records every constant-name emission site —
``registry.counter/gauge/histogram("name", …)``, ``store.ingest("name",
…)``, ``obs.span("name", …)`` / ``record_span("name", …)``,
``record_event("name", …)`` and the ``DASHBOARD_SERIES`` tuple — and is
cached by the runner; the project half (:func:`check_obs_catalog`)
parses the markdown tables and diffs. Dynamic names (a variable first
argument) are invisible by design: the replay paths
(``registry.counter(sample.name)``) re-emit names some original
constant site already declared.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.analysis.findings import Finding

TSDB_SUFFIX = "obs/tsdb.py"

_METRIC_METHODS = {"counter", "gauge", "histogram"}
_SPAN_FUNCS = {"span", "record_span"}
_EVENT_METHODS = {"record_event"}

# markdown section headings → catalog kinds (case-insensitive substring)
_SECTIONS = (
    ("metric catalog", "metric"),
    ("span catalog", "span"),
    ("flight-event catalog", "event"),
)
_ROW_RE = re.compile(r"^\|\s*`([A-Za-z0-9_.:*-]+)`")


def _first_str_arg(node: ast.Call) -> Optional[str]:
    if node.args and isinstance(node.args[0], ast.Constant) and \
            isinstance(node.args[0].value, str):
        return node.args[0].value
    for kw in node.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def _src(source_lines: Sequence[str], lineno: int) -> str:
    if 1 <= lineno <= len(source_lines):
        return source_lines[lineno - 1]
    return ""


def extract_obs_facts(relpath: str, tree: ast.Module,
                      source_lines: Sequence[str]) -> Dict:
    """Constant-name observability emission sites in one module:
    ``{"metric"|"span"|"event"|"dashboard": [[name, line, srcline]…]}``."""
    out: Dict[str, List[List]] = {
        "metric": [], "span": [], "event": [], "dashboard": []}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            func = node.func
            name = _first_str_arg(node)
            if name is None:
                continue
            if isinstance(func, ast.Attribute):
                if func.attr in _METRIC_METHODS or func.attr == "ingest":
                    out["metric"].append(
                        [name, node.lineno, _src(source_lines,
                                                 node.lineno)])
                elif func.attr in _SPAN_FUNCS:
                    out["span"].append(
                        [name, node.lineno, _src(source_lines,
                                                 node.lineno)])
                elif func.attr in _EVENT_METHODS:
                    out["event"].append(
                        [name, node.lineno, _src(source_lines,
                                                 node.lineno)])
            elif isinstance(func, ast.Name) and func.id in _SPAN_FUNCS:
                out["span"].append(
                    [name, node.lineno, _src(source_lines,
                                             node.lineno)])
        elif isinstance(node, ast.Assign) and relpath.endswith(
                TSDB_SUFFIX):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and \
                        tgt.id == "DASHBOARD_SERIES" and isinstance(
                        node.value, (ast.Tuple, ast.List)):
                    for el in node.value.elts:
                        if isinstance(el, ast.Constant) and isinstance(
                                el.value, str):
                            out["dashboard"].append(
                                [el.value, el.lineno,
                                 _src(source_lines, el.lineno)])
    return {k: v for k, v in out.items() if v}


def parse_catalog(doc_text: str) -> Dict[str, Dict[str, Tuple[int, str]]]:
    """Markdown catalogs: kind → {name: (line, row_text)}. A section is
    a ``##`` heading containing one of the known titles; rows are table
    lines whose first cell is a backticked name."""
    catalogs: Dict[str, Dict[str, Tuple[int, str]]] = {
        kind: {} for _, kind in _SECTIONS}
    current: Optional[str] = None
    for i, line in enumerate(doc_text.splitlines(), start=1):
        if line.startswith("##"):
            lowered = line.lower()
            current = None
            for title, kind in _SECTIONS:
                if title in lowered:
                    current = kind
                    break
            continue
        if current is None:
            continue
        m = _ROW_RE.match(line.strip())
        if m:
            catalogs[current].setdefault(m.group(1),
                                         (i, line.strip()))
    return catalogs


def check_obs_catalog(
        doc_relpath: str, doc_text: str,
        facts_by_path: Dict[str, Dict]
) -> List[Tuple[Finding, str]]:
    """Diff the doc catalogs against the pooled emission facts. Returns
    (finding, source_line) pairs like the protocol checker."""
    catalogs = parse_catalog(doc_text)
    emitted: Dict[str, Dict[str, Tuple[str, int, str]]] = {
        "metric": {}, "span": {}, "event": {}}
    dashboard: List[Tuple[str, str, int, str]] = []
    for path in sorted(facts_by_path):
        obs = facts_by_path[path].get("obs") or {}
        for kind in emitted:
            for name, line, srcline in obs.get(kind, ()):
                emitted[kind].setdefault(name, (path, line, srcline))
        for name, line, srcline in obs.get("dashboard", ()):
            dashboard.append((name, path, line, srcline))

    out: List[Tuple[Finding, str]] = []
    # -- GL601: documented, never emitted -------------------------------
    for kind in ("metric", "span", "event"):
        for name, (line, row) in sorted(catalogs[kind].items()):
            if name in emitted[kind]:
                continue
            out.append((Finding(
                "GL601", doc_relpath, line, 0,
                f"documented {kind} `{name}` is not emitted anywhere "
                f"in the package", symbol=name), row))
    # -- GL602: emitted, never documented -------------------------------
    for kind in ("metric", "span", "event"):
        for name, (path, line, srcline) in sorted(
                emitted[kind].items()):
            if name in catalogs[kind]:
                continue
            out.append((Finding(
                "GL602", path, line, 0,
                f"{kind} `{name}` is emitted here but has no "
                f"{doc_relpath} catalog row", symbol=name), srcline))
    # -- GL603: dashboard series without a feed -------------------------
    for name, path, line, srcline in sorted(dashboard):
        if name in emitted["metric"]:
            continue
        out.append((Finding(
            "GL603", path, line, 0,
            f"DASHBOARD_SERIES entry `{name}` has no metric "
            f"registration or tsdb ingest backing it", symbol=name),
            srcline))
    return out
