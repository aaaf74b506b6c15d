"""Per-layer metrics read from the program's own spans.

The span plane (tez_tpu/common/tracing.py, armed by the configuration's
``trace_conf`` in the traced run) keeps its buffer after the session stops;
the readers under ``layer_metrics/`` that are ``.py`` files call in here.

window_spans    the spans of the window's DAGs, found by the ``trace_id`` of
                each DAG's root span; None where the buffer evicted a span
                (``tracing.dropped() > 0``): a sum over a window that lost
                spans is no number.
self_seconds    self time by span name with ``trace_reduce.self_intervals``
                (a span less what its children on the same thread cover),
                summed over threads.
untasked_max_s  the longest stretch inside one DAG's root span with no
                ``task.attempt`` span open: the witness of a host stall.

A program that lacks a span (the parent of the PR that brought it) gives no
self time under that name, and the reader returns None: the result line
leaves the metric out.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import trace_reduce

#: (name, start, end, thread, trace_id); epoch seconds
SpanRow = Tuple[str, float, float, str, str]

ROOT_CAT = "dag"
ATTEMPT = "task.attempt"


def span_name(name: str, cat: str) -> str:
    """The name ``trace_reduce.program_spans`` gives a span:
    ``attempt:<id>`` of category ``task`` is ``task.attempt``."""
    name = name.split(":", 1)[0]
    return f"{cat}.{name}" if "." not in name and cat else name


def completed(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [d for d in obs["dags"] if d["state"] == "SUCCEEDED"]


def window_spans(obs: Dict[str, Any]) -> Optional[List[SpanRow]]:
    from tez_tpu.common import tracing
    if getattr(tracing, "dropped", lambda: 0)() > 0:
        return None
    ids = {d["dag_id"] for d in completed(obs)}
    spans = [s for s in tracing.snapshot() if s.end is not None]
    traces = {s.trace_id for s in spans
              if s.cat == ROOT_CAT and s.args.get("dag_id") in ids}
    return [(span_name(s.name, s.cat), s.start, s.end, s.thread, s.trace_id)
            for s in spans if s.trace_id in traces]


def self_seconds(spans: Iterable[SpanRow], names: Iterable[str]) -> float:
    wanted = set(names)
    selfs = trace_reduce.self_intervals(
        [(n, a, b, t) for n, a, b, t, _trace in spans])
    return sum(b - a for n, a, b in selfs if n in wanted)


def untasked_max_s(spans: Iterable[SpanRow]) -> Optional[float]:
    by_trace: Dict[str, List[SpanRow]] = {}
    for row in spans:
        by_trace.setdefault(row[4], []).append(row)
    longest = None
    for rows in by_trace.values():
        roots = [r for r in rows if r[0].startswith(ROOT_CAT + ".")]
        if not roots:
            continue
        lo, hi = roots[0][1], roots[0][2]
        tasked = trace_reduce.union(trace_reduce.clip(
            [(a, b) for n, a, b, _t, _i in rows if n == ATTEMPT], lo, hi))
        for a, b in trace_reduce.gaps(tasked, lo, hi):
            longest = b - a if longest is None else max(longest, b - a)
    return longest


def self_s_per_dag(obs: Dict[str, Any], names: Iterable[str]
                   ) -> Optional[float]:
    """What a ``.py`` reader returns: self time under `names` a DAG."""
    dags = completed(obs)
    spans = window_spans(obs)
    if not dags or spans is None:
        return None
    total = self_seconds(spans, names)
    return total / len(dags) if total > 0 else None


def dag_untasked_max_s(obs: Dict[str, Any]) -> Optional[float]:
    spans = window_spans(obs)
    return None if spans is None else untasked_max_s(spans)


def histograms_s_per_dag(obs: Dict[str, Any], names: Iterable[str]
                         ) -> Optional[float]:
    """Sum of several histograms' window totals (ms) a DAG, in seconds."""
    dags = completed(obs)
    total = sum(obs["histogram_ms"].get(n, 0.0) for n in names)
    return total / 1000.0 / len(dags) if dags and total > 0 else None
