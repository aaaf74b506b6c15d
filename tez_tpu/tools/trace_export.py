"""Export tez_tpu traces as Chrome/Perfetto ``trace_event`` JSON.

Two sources, one output format (load either in https://ui.perfetto.dev or
chrome://tracing):

1. **Live span buffer** (`tez_tpu.common.tracing`): causally-linked spans
   recorded while a DAG ran with ``tez.trace.enabled`` — per-fetch, per-phase
   timing with trace-id/parent-span-id links in the args.
2. **History journals** (post-mortem): any JSONL history/recovery journal
   parses into DagInfo (tools/history_parser.py) and renders as DAG/vertex/
   attempt spans — this works even after an AM crash, since the recovery
   journal doubles as history.

Also home of the span-based critical-path computation used by the
``span_critical_path`` analyzer: the longest causal chain through the span
graph, reported with per-span self time so the dominant vertex/fetch/commit
is named, not guessed.

CLI:
  python -m tez_tpu.tools.trace_export history1.jsonl [...] -o trace.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import zlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

from tez_tpu.common.tracing import Span

_PID = os.getpid()


def _tid(name: str) -> int:
    """Stable small-ish int for a thread (or lane) name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _us(t: float) -> int:
    return int(t * 1_000_000)


def spans_to_events(spans: Iterable[Span]) -> List[Dict[str, Any]]:
    """Span objects -> trace_event dicts ("X" complete events; span point
    events and zero-duration instant spans -> "i" instants)."""
    events: List[Dict[str, Any]] = []
    tid_names: Dict[int, str] = {}
    for sp in spans:
        end = sp.end if sp.end is not None else sp.start
        # Span.thread is "<name>#<ident>": the lane is the thread (two
        # threads of one name get two lanes), its label the readable part
        tid = _tid(sp.thread)
        tid_names.setdefault(tid, sp.thread.split("#", 1)[0])
        args = dict(sp.args)
        args["trace_id"] = sp.trace_id
        args["span_id"] = sp.span_id
        if sp.parent_id:
            args["parent_span_id"] = sp.parent_id
        if sp.cat == "instant" or end <= sp.start:
            events.append({"name": sp.name, "cat": sp.cat or "span",
                           "ph": "i", "s": "t", "ts": _us(sp.start),
                           "pid": _PID, "tid": tid, "args": args})
        else:
            events.append({"name": sp.name, "cat": sp.cat or "span",
                           "ph": "X", "ts": _us(sp.start),
                           "dur": max(1, _us(end) - _us(sp.start)),
                           "pid": _PID, "tid": tid, "args": args})
        for ts, ename, attrs in sp.events:
            events.append({"name": ename, "cat": "event", "ph": "i",
                           "s": "t", "ts": _us(ts), "pid": _PID, "tid": tid,
                           "args": dict(attrs, span_id=sp.span_id,
                                        trace_id=sp.trace_id)})
    for tid, tname in tid_names.items():
        events.append({"name": "thread_name", "ph": "M", "pid": _PID,
                       "tid": tid, "args": {"name": tname}})
    return events


def spans_to_trace(spans: Iterable[Span]) -> Dict[str, Any]:
    return {"traceEvents": spans_to_events(spans), "displayTimeUnit": "ms"}


def history_to_events(dag: "Any") -> List[Dict[str, Any]]:
    """DagInfo (tools/history_parser) -> trace_event dicts.  Lanes (tids)
    are containers, like the swimlane; vertices and the DAG itself render
    on their own lanes so the phase structure reads at a glance."""
    events: List[Dict[str, Any]] = []

    def lane(name: str) -> int:
        tid = _tid(name)
        events.append({"name": "thread_name", "ph": "M", "pid": _PID,
                       "tid": tid, "args": {"name": name}})
        return tid

    if dag.start_time and dag.finish_time > dag.start_time:
        events.append({"name": f"dag:{dag.name}", "cat": "dag", "ph": "X",
                       "ts": _us(dag.start_time),
                       "dur": max(1, _us(dag.finish_time) -
                                  _us(dag.start_time)),
                       "pid": _PID, "tid": lane("dag"),
                       "args": {"dag_id": dag.dag_id, "state": dag.state}})
    for v in dag.vertices.values():
        if v.start_time and v.finish_time > v.start_time:
            events.append({"name": f"vertex:{v.name}", "cat": "vertex",
                           "ph": "X", "ts": _us(v.start_time),
                           "dur": max(1, _us(v.finish_time) -
                                      _us(v.start_time)),
                           "pid": _PID, "tid": lane(f"vertex:{v.name}"),
                           "args": {"state": v.state,
                                    "num_tasks": v.num_tasks}})
    for a in dag.all_attempts():
        if not a.start_time or a.finish_time <= a.start_time:
            continue
        events.append({"name": f"attempt:{a.attempt_id}", "cat": "task",
                       "ph": "X", "ts": _us(a.start_time),
                       "dur": max(1, _us(a.finish_time) - _us(a.start_time)),
                       "pid": _PID,
                       "tid": lane(a.container_id or a.node_id or "task"),
                       "args": {"vertex": a.vertex_name, "state": a.state,
                                "node": a.node_id}})
    # admission plane (post-PR-11): the queue-wait window between submit
    # and start, plus the session's QUEUED/SHED verdict stream — without
    # this lane a parked DAG's wait was silently absent from the export
    if dag.submit_time and dag.start_time > dag.submit_time:
        events.append({"name": "admission:queue-wait", "cat": "admission",
                       "ph": "X", "ts": _us(dag.submit_time),
                       "dur": max(1, _us(dag.start_time) -
                                  _us(dag.submit_time)),
                       "pid": _PID, "tid": lane("admission"),
                       "args": {"dag_id": dag.dag_id,
                                "tenant": dag.tenant}})
    for ev in dag.admission_events:
        t = ev.get("time", 0.0)
        if not t:
            continue
        events.append({"name": f"admission:{ev.get('event', '?')}",
                       "cat": "admission", "ph": "i", "s": "t",
                       "ts": _us(t), "pid": _PID, "tid": lane("admission"),
                       "args": {k: v for k, v in ev.items() if k != "time"}})
    return events


def history_to_trace(dag: "Any") -> Dict[str, Any]:
    return {"traceEvents": history_to_events(dag), "displayTimeUnit": "ms"}


# --------------------------------------------------------------------------
# Flight-recorder tracks (planes with no span coverage: store, push,
# exchange, admission verdicts, breaker/watchdog, SLO)
# --------------------------------------------------------------------------

def flight_to_events(snap: "Any") -> List[Dict[str, Any]]:
    """FlightSnapshot -> trace_event dicts, one lane per plane.

    Span edges re-render as complete events (useful when the dump is the
    only artifact — no live span buffer post-mortem); every histogram
    observation becomes a complete event on a per-name counter lane (the
    store publish/fetch/demote, push rtt, exchange round, and admission
    queue-wait tracks); typed plane events render as instants on their
    plane's lane.  Timestamps project onto the wall clock through the
    anchor embedded in the snapshot, so these tracks line up with
    history/span tracks from the same process."""
    from tez_tpu.common import clock
    from tez_tpu.obs import flight as fl
    events: List[Dict[str, Any]] = []
    lanes: Dict[int, str] = {}

    def lane(name: str) -> int:
        tid = _tid(name)
        if tid not in lanes:
            lanes[tid] = name
            events.append({"name": "thread_name", "ph": "M", "pid": _PID,
                           "tid": tid, "args": {"name": name}})
        return tid

    anchor = snap.anchor
    for e in snap.events:
        wall = clock.mono_to_wall(e.t_ns, anchor)
        if e.kind == fl.SPAN:
            start = clock.mono_to_wall(e.a, anchor)
            events.append({"name": e.name, "cat": e.scope or "span",
                           "ph": "X", "ts": _us(start),
                           "dur": max(1, e.b // 1000), "pid": _PID,
                           "tid": lane(f"flight:span:{e.scope or 'span'}"),
                           "args": {"seq": e.seq}})
        elif e.kind == fl.COUNTER:
            dur = max(1, e.a)          # a = observed microseconds
            events.append({"name": e.name, "cat": "counter", "ph": "X",
                           "ts": _us(wall) - dur, "dur": dur, "pid": _PID,
                           "tid": lane(f"flight:counter:{e.name}"),
                           "args": {"seq": e.seq, "observed_us": e.a}})
        else:
            events.append({"name": e.name or e.kind_name,
                           "cat": e.kind_name, "ph": "i", "s": "t",
                           "ts": _us(wall), "pid": _PID,
                           "tid": lane(f"flight:{e.kind_name}"),
                           "args": {"seq": e.seq, "scope": e.scope,
                                    "a": e.a, "b": e.b}})
    return events


def flight_to_trace(snap: "Any") -> Dict[str, Any]:
    return {"traceEvents": flight_to_events(snap), "displayTimeUnit": "ms"}


def write_trace(trace: Dict[str, Any], path: str) -> str:
    with open(path, "w") as fh:
        json.dump(trace, fh, default=str)
    return path


# --------------------------------------------------------------------------
# Span-based critical path
# --------------------------------------------------------------------------

def critical_path(spans: List[Span]) -> List[Span]:
    """The longest causal chain: starting from each root span, follow the
    child whose end time is latest (what actually gated the parent's end),
    and return the root->leaf path of the trace that finished last.  Spans
    still open (end is None) participate with their start as end.  The AM's
    own spans (cat ``am``: queue, task.done, commit) and its instants
    (vertex boundaries, the last vertex's end) stand around the tasks' work
    — they end after the last attempt by construction — so the chain
    follows them only where a span has no other child."""
    by_parent: Dict[Optional[str], List[Span]] = {}
    roots: List[Span] = []
    ids = {sp.span_id for sp in spans}
    for sp in spans:
        if sp.parent_id and sp.parent_id in ids:
            by_parent.setdefault(sp.parent_id, []).append(sp)
        else:
            roots.append(sp)
    if not roots:
        return []

    def end_of(sp: Span) -> float:
        return sp.end if sp.end is not None else sp.start

    root = max(roots, key=end_of)
    path = [root]
    cur = root
    while True:
        kids = by_parent.get(cur.span_id)
        if not kids:
            return path
        cur = max([k for k in kids if k.cat not in ("am", "instant")]
                  or kids, key=end_of)
        path.append(cur)


def dominant_span(path: List[Span]) -> Optional[Span]:
    """The path member with the largest SELF time (own duration minus the
    duration of its on-path child) — the span a perf PR should attack."""
    if not path:
        return None
    best, best_self = None, -1.0
    for i, sp in enumerate(path):
        child_dur = path[i + 1].duration if i + 1 < len(path) else 0.0
        self_t = max(0.0, sp.duration - child_dur)
        if self_t > best_self:
            best, best_self = sp, self_t
    return best


def critical_path_report(spans: List[Span]) -> Dict[str, Any]:
    path = critical_path(spans)
    dom = dominant_span(path)
    def self_ms(i: int) -> float:
        child = path[i + 1].duration if i + 1 < len(path) else 0.0
        return round(max(0.0, path[i].duration - child) * 1000, 3)

    return {
        "chain": [{"name": sp.name, "cat": sp.cat,
                   "duration_ms": round(sp.duration * 1000, 3),
                   "self_ms": self_ms(i),
                   "vertex": sp.args.get("vertex", ""),
                   "span_id": sp.span_id} for i, sp in enumerate(path)],
        "dominant": None if dom is None else {
            "name": dom.name, "cat": dom.cat,
            "vertex": dom.args.get("vertex", ""),
            "span_id": dom.span_id,
            "duration_ms": round(dom.duration * 1000, 3)},
    }


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Export Chrome/Perfetto trace JSON from history "
                    "journals (or the live span buffer via --live).")
    ap.add_argument("journals", nargs="*",
                    help="history/recovery JSONL files")
    ap.add_argument("-o", "--out", default="trace.json")
    ap.add_argument("--dag", default="",
                    help="dag_id to export (default: last one seen)")
    ap.add_argument("--live", action="store_true",
                    help="export the in-process span buffer instead of "
                         "history files")
    ap.add_argument("--flight", nargs="*", default=[], metavar="DUMP",
                    help="flight_*.json dumps whose per-plane tracks "
                         "(store/push/exchange/admission/breaker) are "
                         "merged into the export")
    args = ap.parse_args(argv)
    if args.live:
        from tez_tpu.common import tracing
        trace = spans_to_trace(tracing.snapshot())
    elif args.journals:
        from tez_tpu.tools.history_parser import parse_jsonl_files
        dags = parse_jsonl_files(args.journals)
        if not dags:
            print("no DAGs found in journals", file=sys.stderr)
            return 1
        dag_id = args.dag or sorted(dags)[-1]
        if dag_id not in dags:
            print(f"dag {dag_id} not in {sorted(dags)}", file=sys.stderr)
            return 1
        trace = history_to_trace(dags[dag_id])
    elif args.flight:
        trace = {"traceEvents": [], "displayTimeUnit": "ms"}
    else:
        ap.error("journal files, --flight dumps, or --live required")
    if args.flight:
        from tez_tpu.obs import flight as fl
        for path in args.flight:
            trace["traceEvents"].extend(
                flight_to_events(fl.load_dump(path)))
    write_trace(trace, args.out)
    print(f"wrote {len(trace['traceEvents'])} events to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
