"""partition_sample_s_per_dag: see partition_sample_s_per_dag.json.

The client samples before the DAG exists, so the span stands under no DAG's
root: it is found by the window's clock, on the thread that opened it."""
import span_metrics

NAME = "partition.sample"


def read(obs):
    from tez_tpu.common import tracing
    dags = span_metrics.completed(obs)
    if not dags or getattr(tracing, "dropped", lambda: 0)() > 0:
        return None
    lo, hi = obs["dags"][0]["t_submit"], dags[-1]["t_done"]
    spans = [s for s in tracing.snapshot()
             if s.end is not None and lo <= s.start and s.end <= hi]
    threads = {s.thread for s in spans if s.name == NAME}
    rows = [(span_metrics.span_name(s.name, s.cat), s.start, s.end, s.thread,
             s.trace_id) for s in spans if s.thread in threads]
    total = span_metrics.self_seconds(rows, (NAME,))
    return total / len(dags) if total > 0 else None
