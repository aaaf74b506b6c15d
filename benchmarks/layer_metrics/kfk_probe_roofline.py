"""kfk_probe_roofline: see kfk_probe_roofline.json."""
import json
import os

import span_metrics
import trace_reduce

PROGRAM = "_kfk_probe_impl"


def _total(obs, name):
    return sum(group.get(name, 0) for dag in span_metrics.completed(obs)
               for group in dag["counters"].values())


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    busy_s = dict(trace["breakdown"]["device_ops"]).get(PROGRAM)
    rows = _total(obs, "KFK_PROBE_ROWS")
    if not busy_s or not rows:
        return None
    least = trace_reduce.least_hbm_bytes(
        rows, obs["config"]["key_lanes"] + 1) + \
        4 * _total(obs, "KFK_PROBE_STREAM_ROWS")
    with open(os.path.join(trace_reduce.HERE, "peaks.json")) as fh:
        peak = json.load(fh)[obs["device_kind"]]["hbm_gb_per_s"] * 1e9
    pct = 100.0 * least / peak / busy_s
    if pct > 105.0:
        raise ValueError(
            f"{PROGRAM}: roofline share {pct:.1f} % > 105 %: {least} bytes "
            f"need {least / peak:.6f}s of HBM time but the device was busy "
            f"{busy_s:.6f}s; the rows are counted too high or the busy time "
            f"leaves out part of the work")
    return pct
