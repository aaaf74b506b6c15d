"""hashjoin_probe_roofline: see hashjoin_probe_roofline.json."""
import span_metrics
import trace_reduce

PROGRAM = "_join_probe_impl"


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    busy_s = dict(trace["breakdown"]["device_ops"]).get(PROGRAM)
    if not busy_s:
        return None
    rows = sum(group.get("JOIN_MATCH_ROWS", 0)
               for dag in span_metrics.completed(obs)
               for group in dag["counters"].values())
    return trace_reduce.hbm_roofline_pct(
        rows, obs["config"]["key_lanes"] + 1, busy_s, obs["device_kind"])
