"""Metric-name consistency: instrumentation sites vs
``metrics.WELL_KNOWN_HISTOGRAMS`` vs the ``tools/counter_diff.py``
report sections vs docs/observability.md.

Codes:

- ``hist-unregistered`` — ``metrics.observe(name)`` / ``timer(name)``
  with a literal name missing from WELL_KNOWN_HISTOGRAMS (it records
  fine at runtime but is invisible to /metrics consumers that iterate
  the well-known list and to counter_diff's sections).
- ``hist-unused`` — a WELL_KNOWN_HISTOGRAMS entry whose name appears
  nowhere else in the package.
- ``hist-undocumented`` — WELL_KNOWN entry not in docs/observability.md.
- ``diff-stale-hist`` — a ``*_HISTS`` section tuple in
  tools/counter_diff.py naming a histogram that is not well-known.
- ``gauge-undocumented`` — a literal ``set_gauge`` name missing from
  docs/observability.md (dynamic f-string gauges are out of scope).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from tez_tpu.analysis.core import Checker, Context, Finding

_METRICS_SUFFIX = "common/metrics.py"
_DIFF_SUFFIX = "tools/counter_diff.py"


def _well_known(ctx: Context) -> Tuple[Dict[str, int], str]:
    sf = ctx.find_file(_METRICS_SUFFIX)
    if sf is None or sf.tree is None:
        return {}, ""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and node.targets and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "WELL_KNOWN_HISTOGRAMS" and \
                isinstance(node.value, (ast.Tuple, ast.List)):
            return {e.value: e.lineno for e in node.value.elts
                    if isinstance(e, ast.Constant)}, sf.rel
    return {}, sf.rel


def _literal_arg(node: ast.Call) -> str:
    if node.args and isinstance(node.args[0], ast.Constant) and \
            isinstance(node.args[0].value, str):
        return node.args[0].value
    return ""


def run(ctx: Context) -> List[Finding]:
    well_known, metrics_rel = _well_known(ctx)
    findings: List[Finding] = []
    if not well_known:
        return findings

    observed: Dict[str, Tuple[str, int]] = {}
    gauges: Dict[str, Tuple[str, int]] = {}
    mentioned: Dict[str, Tuple[str, int]] = {}
    for sf in ctx.files:
        if sf.tree is None or sf.rel.endswith(_METRICS_SUFFIX):
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute):
                name = _literal_arg(node)
                if name:
                    if node.func.attr in ("observe", "timer"):
                        observed.setdefault(name, (sf.rel, node.lineno))
                    elif node.func.attr == "set_gauge":
                        gauges.setdefault(name, (sf.rel, node.lineno))
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    node.value in well_known:
                mentioned.setdefault(node.value, (sf.rel, node.lineno))

    doc = ctx.doc_text("observability.md")

    for name, (rel, line) in sorted(observed.items()):
        if name not in well_known:
            findings.append(Finding(
                "metric_names", "hist-unregistered", rel, line, name,
                f"histogram {name!r} observed here but missing from "
                f"metrics.WELL_KNOWN_HISTOGRAMS"))
    for name, line in sorted(well_known.items()):
        if name not in mentioned:
            findings.append(Finding(
                "metric_names", "hist-unused", metrics_rel, line, name,
                f"WELL_KNOWN_HISTOGRAMS entry {name!r} never referenced "
                f"outside common/metrics.py"))
        if doc and f"`{name}`" not in doc:
            findings.append(Finding(
                "metric_names", "hist-undocumented", metrics_rel, line,
                name,
                f"well-known histogram {name!r} missing from "
                f"docs/observability.md"))

    diff_sf = ctx.find_file(_DIFF_SUFFIX)
    if diff_sf is not None and diff_sf.tree is not None:
        for node in ast.walk(diff_sf.tree):
            if isinstance(node, ast.Assign) and node.targets and \
                    isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id.endswith("_HISTS") and \
                    isinstance(node.value, (ast.Tuple, ast.List)):
                for e in node.value.elts:
                    if isinstance(e, ast.Constant) and \
                            e.value not in well_known:
                        findings.append(Finding(
                            "metric_names", "diff-stale-hist",
                            diff_sf.rel, e.lineno, str(e.value),
                            f"counter_diff section lists histogram "
                            f"{e.value!r} which is not in "
                            f"WELL_KNOWN_HISTOGRAMS"))

    for name, (rel, line) in sorted(gauges.items()):
        if doc and f"`{name}`" not in doc:
            findings.append(Finding(
                "metric_names", "gauge-undocumented", rel, line, name,
                f"gauge {name!r} set here but missing from "
                f"docs/observability.md"))
    return findings


CHECKER = Checker(
    "metric_names",
    "histogram/gauge names at instrumentation sites vs metrics.py vs "
    "counter_diff sections vs docs/observability.md",
    run)
