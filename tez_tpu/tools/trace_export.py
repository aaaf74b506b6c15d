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

Also home of the span-based critical path (``critical_path``): one walk
backwards through a DAG's period, across threads by the ``after`` links the
spans carry, that puts every second of the period under one span name and
one class.  The ``span_critical_path`` analyzer, the benchmark's ``path_*``
metrics and ``tools/trace_window_check.py`` all print this one walk.

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
#
# One walk, backwards in time, through the spans of one *period* (in a
# closed loop: a DAG's client-side submit to the next one's, so that the
# path's length IS the wall time the loop's user paid for that DAG).  At any
# instant the path is on one thread, in the innermost span open there; it
# changes thread only through a link a span carries, ``after=<span_id>``
# (tracing.here() of whoever ended a wait or handed the work over):
#
# * inside a span whose link target was still at work after the span began
#   (a wait, and who ended it): step to the target, at once;
# * at the start of a span before which its thread was idle: step to the
#   link target's end (the stretch between is the hand-over, the beginning
#   span's), or, where the thread's own previous span ended later than
#   that, stay on the thread;
# * with no link: to the span, on any thread, that ended last before that
#   instant -- a *guess*, counted as one unless it is the thread's own
#   previous span.
#
# Every second of the period lands in exactly one span name (or in
# ``(no span)``) and one class, so the classes add up to the period.

#: spans in which a host thread is blocked for the device, or for a launch
#: queued ahead of its own (``join.match`` and ``agg.fold`` only with
#: ``stage="readback"``)
DEVICE_WAITS = frozenset({"device.d2h", "merge.readback",
                          "exchange.readback"})
STAGED_DEVICE_WAITS = frozenset({"join.match", "agg.fold"})
#: envelopes: their self time is time no span names
ENVELOPES = frozenset({"attempt", "initialize", "run", "close"})
CLASSES = ("host work", "device wait", "control", "stall", "unnamed")
STALL = "host.stall"
NO_SPAN = "(no span)"
_EPS = 1e-9
_MAX_STEPS = 200_000


def path_name(sp: Span) -> str:
    """``attempt:<id>`` of cat ``task`` -> ``task.attempt``: the names the
    benchmark's reducer gives spans, so the tables line up."""
    name = sp.name.split(":", 1)[0]
    return f"{sp.cat}.{name}" if "." not in name and sp.cat else name


def path_class(sp: Span) -> str:
    name = sp.name.split(":", 1)[0]
    if name in DEVICE_WAITS or (name in STAGED_DEVICE_WAITS and
                                sp.args.get("stage") == "readback"):
        return "device wait"
    if sp.cat in ("client", "am"):
        return "control"
    if sp.cat == "dag" or (sp.cat == "task" and name in ENVELOPES):
        return "unnamed"
    return "host work"


def _row_key(sp: Span) -> str:
    """The row a span stands on: its thread (``<name>#<ident>``), or, for
    a span on a lane, a row of its own -- lane spans overlap freely."""
    ident = sp.thread.rsplit("#", 1)[-1]
    return sp.thread if ident.isdigit() and "#" in sp.thread \
        else f"{sp.thread}|{sp.span_id}"


def _segments(spans: List[Span]) -> Dict[str, List[Tuple[float, float, Span]]]:
    """Per row, the disjoint stretches (a, b, span) in which `span` is the
    innermost one open there, sorted by time."""
    rows: Dict[str, List[Span]] = {}
    for sp in spans:
        rows.setdefault(_row_key(sp), []).append(sp)
    out: Dict[str, List[Tuple[float, float, Span]]] = {}
    for key, items in rows.items():
        items.sort(key=lambda sp: (sp.start, -sp.end))
        segs: List[Tuple[float, float, Span]] = []
        stack: List[List[Any]] = []          # [span, its open stretch's start]

        def pop() -> None:
            top, since = stack.pop()
            if top.end > since:
                segs.append((since, top.end, top))
            if stack:
                stack[-1][1] = max(stack[-1][1], top.end)

        for sp in items:
            while stack and stack[-1][0].end <= sp.start:
                pop()
            if stack and sp.start > stack[-1][1]:
                segs.append((stack[-1][1], sp.start, stack[-1][0]))
            stack.append([sp, sp.start])
        while stack:
            pop()
        segs.sort(key=lambda seg: seg[0])
        out[key] = segs
    return out


def critical_path(spans: List[Span],
                  periods: Optional[List[Tuple[float, float]]] = None,
                  thread: Optional[str] = None) -> Dict[str, Any]:
    """Walk each period's critical path (see above) and add them up.

    `periods`: (start, end) in epoch seconds; one period from the first
    span's start to the last one's end where none is given.  `thread`: the
    ``Span.thread`` each period ends on (the client's); the thread of the
    span that ends last inside a period where none is given.

    Returns seconds by span name (``by_name``) and by class (``by_class``:
    all of CLASSES, summing to ``seconds``), the hand-over stretches by the
    span that began after them (``handoff_s``, part of ``by_name``),
    ``steps`` between threads by kind (``link``, ``thread``: a thread's
    own previous span, ``guess``), the ``stalls`` met as [start, seconds],
    ``miss`` (the largest share of a period the walk left unwalked: 0
    unless it gave up) and ``chain``: the last period's path as it ran,
    [{name, cat, vertex, span_id, thread, start, seconds}], oldest first."""
    import bisect
    done = [sp for sp in spans if sp.end is not None and sp.end > sp.start
            and sp.cat != "instant"]
    stalls = sorted((sp.start, sp.end) for sp in done if sp.name == STALL)
    work = [sp for sp in done if sp.name != STALL]
    by_id = {sp.span_id: sp for sp in work}
    segs = _segments(work)
    starts = {key: [seg[0] for seg in rows] for key, rows in segs.items()}
    # for a guess: every stretch's end, roots and lanes of waiting rows out
    ends = sorted((seg[1], key, i) for key, rows in segs.items()
                  for i, seg in enumerate(rows)
                  if seg[2].cat != "dag" and "|" not in key)
    end_times = [e[0] for e in ends]
    if periods is None:
        periods = [(min(sp.start for sp in work), max(sp.end for sp in work))
                   ] if work else []
    res: Dict[str, Any] = {
        "periods": len(periods), "seconds": 0.0, "by_name": {},
        "by_class": {c: 0.0 for c in CLASSES}, "handoff_s": {},
        "steps": {"link": 0, "thread": 0, "guess": 0}, "stalls": [],
        "miss": 0.0, "chain": []}
    by_name, by_class, steps = res["by_name"], res["by_class"], res["steps"]
    met: set = set()                      # the stalls the path ran into

    def bill(sp: Optional[Span], lo: float, hi: float, chain: List) -> None:
        """[lo, hi] of the path goes to `sp` (None: to no span), less what
        a stall covers of it."""
        if hi <= lo:
            return
        stalled = 0.0
        i = bisect.bisect_left(stalls, (lo,)) - 1
        for a, b in stalls[max(i, 0):]:
            if a >= hi:
                break
            over = min(b, hi) - max(a, lo)
            if over > 0:
                stalled += over
                met.add((a, b))
        name = path_name(sp) if sp is not None else NO_SPAN
        cls = path_class(sp) if sp is not None else "unnamed"
        by_name[name] = by_name.get(name, 0.0) + hi - lo - stalled
        by_class[cls] += hi - lo - stalled
        if stalled:
            by_name[STALL] = by_name.get(STALL, 0.0) + stalled
            by_class["stall"] += stalled
        if chain and sp is not None and chain[-1]["span_id"] == sp.span_id:
            chain[-1]["start"] = lo
            chain[-1]["seconds"] += hi - lo
        else:
            chain.append({
                "name": name, "cat": sp.cat if sp is not None else "",
                "vertex": sp.args.get("vertex", "") if sp is not None
                else "", "span_id": sp.span_id if sp is not None else "",
                "thread": sp.thread if sp is not None else "",
                "start": lo, "seconds": hi - lo})

    def seg_at(key: str, t: float) -> Optional[int]:
        """Index of the stretch of row `key` with a < t <= b."""
        i = bisect.bisect_left(starts.get(key, ()), t) - 1
        return i if i >= 0 and segs[key][i][1] >= t - _EPS else None

    def before(key: str, t: float) -> Optional[Tuple[float, float, Span]]:
        """The last stretch of row `key` that ends at or before t."""
        i = bisect.bisect_left(starts.get(key, ()), t) - 1
        while i >= 0 and segs[key][i][1] > t + _EPS:
            i -= 1
        return segs[key][i] if i >= 0 else None

    for ps, pe in periods:
        chain: List[Dict[str, Any]] = []
        key = None
        if thread is not None and thread in segs:
            key = thread
        t = pe
        if key is None:
            i = bisect.bisect_right(end_times, pe + _EPS) - 1
            if i < 0 or ends[i][0] <= ps:
                bill(None, ps, pe, chain)
                res["seconds"] += pe - ps
                continue
            key = ends[i][1]
        n = 0
        while t > ps + _EPS:
            n += 1
            if n > _MAX_STEPS:
                break
            i = seg_at(key, t)
            if i is None:
                # idle on this row: back to its previous span, if the
                # period still holds one; else to whoever ended last
                prev = before(key, t)
                if prev is not None and prev[1] > ps:
                    bill(None, prev[1], t, chain)
                    t = prev[1]
                    steps["thread"] += 1
                    continue
                j = bisect.bisect_right(end_times, t + _EPS) - 1
                if j < 0 or ends[j][0] <= ps:
                    bill(None, ps, t, chain)
                    t = ps
                    break
                bill(None, ends[j][0], t, chain)
                t, key = ends[j][0], ends[j][1]
                steps["guess"] += 1
                continue
            a, _b, sp = segs[key][i]
            link = by_id.get(sp.args.get("after") or "")
            if link is not None and (link.start >= t or
                                     _row_key(link) == key):
                link = None               # not yet begun, or this very row
            if link is not None and min(t, link.end) > max(a, sp.start) + _EPS:
                # a wait, and the work that ended it
                at = min(t, link.end)
                bill(sp, at, t, chain)
                t, key = at, _row_key(link)
                steps["link"] += 1
                continue
            lo = max(a, ps)
            bill(sp, lo, t, chain)
            t = lo
            if t <= ps + _EPS:
                break
            prev = before(key, t)
            if prev is not None and prev[1] >= t - _EPS:
                continue                  # its parent, or a child before it
            # the span began with its row idle
            t_prev = prev[1] if prev is not None and prev[1] > ps else None
            if link is not None:
                at = min(t, link.end)
                if t_prev is None or at >= t_prev:
                    bill(sp, max(at, ps), t, chain)
                    if t > max(at, ps):
                        res["handoff_s"][path_name(sp)] = \
                            res["handoff_s"].get(path_name(sp), 0.0) + \
                            t - max(at, ps)
                    t, key = at, _row_key(link)
                    steps["link"] += 1
                    continue
            j = bisect.bisect_right(end_times, t + _EPS) - 1
            if t_prev is not None and (link is not None or j < 0 or
                                       ends[j][0] <= t_prev + 1e-6):
                bill(None, t_prev, t, chain)
                t = t_prev
                steps["thread"] += 1
                continue
            if j < 0 or ends[j][0] <= ps:
                bill(None, ps, t, chain)
                t = ps
                break
            bill(None, ends[j][0], t, chain)
            t, key = ends[j][0], ends[j][1]
            steps["guess"] += 1
        if pe > ps:
            res["miss"] = max(res["miss"], max(0.0, t - ps) / (pe - ps))
        res["seconds"] += pe - ps
        chain.reverse()
        res["chain"] = chain
    res["stalls"] = [[a, b - a] for a, b in sorted(met)]
    return res


def dag_period(spans: List[Span], dag_id: str
               ) -> Optional[Tuple[float, float, Optional[str]]]:
    """(start, end, client thread) of one DAG's period in a buffer: from
    the ``client.submit_dag`` that holds its root's start to the end of its
    ``client.status``; the root span's own where the client's are not
    there.  None where the buffer has no root span of that DAG."""
    root = next((sp for sp in spans if sp.cat == "dag" and sp.end is not None
                 and sp.args.get("dag_id") == dag_id), None)
    if root is None:
        return None
    lo, hi, thread = root.start, root.end, None
    for sp in spans:
        if sp.cat != "client" or sp.end is None:
            continue
        if sp.name == "submit_dag" and sp.start <= root.start <= sp.end:
            lo, thread = sp.start, sp.thread
        elif sp.name == "status" and sp.args.get("dag_id") == dag_id:
            hi = max(hi, sp.end)
    return lo, hi, thread


def dominant_span(path: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The member of a walked path's chain with the most seconds on it --
    the span a perf PR should attack."""
    named = [c for c in path["chain"] if c["span_id"]]
    return max(named, key=lambda c: c["seconds"]) if named else None


def critical_path_report(spans: List[Span], dag_id: Optional[str] = None
                         ) -> Dict[str, Any]:
    """The walk of one DAG's period (the whole buffer as one period where
    no DAG is named), for the analyzer: the chain oldest first, each member
    with its seconds on the path, and the dominant one."""
    period = dag_period(spans, dag_id) if dag_id is not None else None
    if period is not None:
        path = critical_path(spans, [period[:2]], thread=period[2])
    else:
        path = critical_path(spans)
    dom = dominant_span(path)

    def row(c: Dict[str, Any]) -> Dict[str, Any]:
        return {"name": c["name"], "cat": c["cat"], "vertex": c["vertex"],
                "span_id": c["span_id"],
                "self_ms": round(c["seconds"] * 1000, 3),
                "duration_ms": round(c["seconds"] * 1000, 3)}

    return {"chain": [row(c) for c in path["chain"]],
            "dominant": None if dom is None else row(dom),
            "by_class_ms": {k: round(v * 1000, 3)
                            for k, v in path["by_class"].items()},
            "steps": path["steps"]}


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
