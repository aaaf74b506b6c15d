"""Per-layer metrics read from the critical path of the window's DAG periods.

The program's spans carry ``after=<span_id>`` links (who ended a wait, who
handed the work over: tez_tpu/common/tracing.py ``here``), and
``tez_tpu.tools.trace_export.critical_path`` walks each period backwards
through them.  A *period* is one turn of the harness's closed loop: a DAG's
client-side submit (``t_submit``) to the next DAG's, the last one to its
``t_done`` -- so the periods add up to the window's elapsed time and the
path's seconds a DAG to the traced ``dag_wall_s``.

path_s_per_dag      the path's seconds a DAG in one class (``host work``,
                    ``device wait``, ``control``, ``stall``, ``unnamed``).
                    The five partition the path.  None where the buffer
                    evicted a span, where the program has no links (the
                    parent of the PR that brought them), and where the walk
                    left more than 2 % of a period unwalked: a metric left
                    out, never a number that does not add up.
dag_turnaround_s    mean, over consecutive DAGs, of the last ``task.attempt``
                    end of one to the first ``task.attempt`` start of the
                    next: what lies between two root spans, where
                    ``dag_untasked_max_s`` cannot look.
dag_head_s          mean of a DAG's client-side submit to its first launch
                    on a chip (its first ``kernel.*`` or ``exchange.launch``
                    span).

Nothing here raises on a program that lacks a span or a link: the reader
returns None and the result line leaves the metric out.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import span_metrics

MISS_LIMIT = 0.02
CLIENT_SUBMIT = "submit_dag"


def periods(obs: Dict[str, Any]) -> List[Tuple[float, float]]:
    dags = obs["dags"]
    return [(d["t_submit"], nxt["t_submit"])
            for d, nxt in zip(dags, dags[1:])] + \
        [(dags[-1]["t_submit"], dags[-1]["t_done"])] if dags else []


def window_path(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The walk over the window's periods, whatever it missed (kept on
    `obs`: five readers and tools/trace_window_check.py ask for it), or
    None where there is none to give."""
    if "_window_path" not in obs:
        obs["_window_path"] = _walk(obs)
    return obs["_window_path"]


def _walk(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    from tez_tpu.common import tracing
    from tez_tpu.tools import trace_export
    if not hasattr(tracing, "here") or tracing.dropped() > 0 or \
            not span_metrics.completed(obs):
        return None
    spans = [s for s in tracing.snapshot() if s.end is not None]
    lo, hi = obs["dags"][0]["t_submit"], obs["dags"][-1]["t_done"]
    client = [s for s in spans if s.cat == "client"
              and s.name == CLIENT_SUBMIT and lo <= s.start <= hi]
    if not client:
        return None
    try:
        return trace_export.critical_path(spans, periods(obs),
                                          thread=client[-1].thread)
    except Exception:      # noqa: BLE001 - a later PR's traced run must live
        return None


def path_s_per_dag(obs: Dict[str, Any], cls: str) -> Optional[float]:
    path = window_path(obs)
    if path is None or path["miss"] > MISS_LIMIT:
        return None
    return path["by_class"][cls] / len(span_metrics.completed(obs))


def _dag_spans(obs: Dict[str, Any]) -> Optional[List[Tuple[Dict[str, Any],
                                                            List[Any]]]]:
    """(the harness's DAG, the spans of its trace), in the window's order,
    for the DAGs whose root span the buffer holds; None where it lost any."""
    from tez_tpu.common import tracing
    if getattr(tracing, "dropped", lambda: 0)() > 0:
        return None
    spans = [s for s in tracing.snapshot() if s.end is not None]
    trace_of = {s.args.get("dag_id"): s.trace_id for s in spans
                if s.cat == span_metrics.ROOT_CAT}
    by_trace: Dict[str, List[Any]] = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    return [(d, by_trace[trace_of[d["dag_id"]]]) for d in obs["dags"]
            if d["dag_id"] in trace_of]


def dag_turnaround_s(obs: Dict[str, Any]) -> Optional[float]:
    dags = _dag_spans(obs)
    if not dags:
        return None
    tasked = [[s for s in spans if s.name.startswith("attempt:")]
              for _d, spans in dags]
    turns = [min(s.start for s in nxt) - max(s.end for s in prev)
             for prev, nxt in zip(tasked, tasked[1:]) if prev and nxt]
    return sum(turns) / len(turns) if turns else None


def dag_head_s(obs: Dict[str, Any]) -> Optional[float]:
    dags = _dag_spans(obs)
    if not dags:
        return None
    heads = []
    for d, spans in dags:
        launches = [s.start for s in spans if s.name == "exchange.launch" or
                    (s.cat == "kernel" and s.name != "kernel.compile")]
        if launches:
            heads.append(min(launches) - d["t_submit"])
    return sum(heads) / len(heads) if heads else None
