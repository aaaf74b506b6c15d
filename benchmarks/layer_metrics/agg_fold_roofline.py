"""agg_fold_roofline: see agg_fold_roofline.json."""
import span_metrics
import trace_reduce

PROGRAM = "_group_sum_impl"


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    busy_s = dict(trace["breakdown"]["device_ops"]).get(PROGRAM)
    if not busy_s:
        return None
    rows = sum(group.get("AGG_FOLD_ROWS", 0)
               for dag in span_metrics.completed(obs)
               for group in dag["counters"].values())
    return trace_reduce.hbm_roofline_pct(
        rows, obs["config"]["key_lanes"] + 2, busy_s, obs["device_kind"])
