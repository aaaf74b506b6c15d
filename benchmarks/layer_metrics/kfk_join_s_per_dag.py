"""kfk_join_s_per_dag: see kfk_join_s_per_dag.json."""
import span_metrics
import trace_reduce

#: the join spans that say which join they serve in their ``how``
STAGES = ("join.build", "join.probe", "join.match")


def read(obs):
    from tez_tpu.common import tracing
    dags = span_metrics.completed(obs)
    if not dags or tracing.dropped() > 0:
        return None
    ids = {d["dag_id"] for d in dags}
    spans = [s for s in tracing.snapshot() if s.end is not None]
    traces = {s.trace_id for s in spans if s.cat == span_metrics.ROOT_CAT
              and s.args.get("dag_id") in ids}
    rows = []
    for s in spans:
        if s.trace_id not in traces:
            continue
        name = span_metrics.span_name(s.name, s.cat)
        if name in STAGES and s.args.get("how") == "inner":
            name += "[inner]"
        rows.append((name, s.start, s.end, s.thread))
    wanted = {f"{n}[inner]" for n in STAGES} | {"join.carry"}
    total = sum(b - a for n, a, b in trace_reduce.self_intervals(rows)
                if n in wanted)
    return total / len(dags) if total > 0 else None
