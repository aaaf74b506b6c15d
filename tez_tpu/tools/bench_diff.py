"""Compare two bench runs metric-by-metric and fail on regressions.

Usage:
  python -m tez_tpu.tools.bench_diff OLD NEW [--threshold 0.20]

OLD/NEW are either the driver's ``BENCH_*.json`` wrappers
(``{"tail": ..., "parsed": ...}``: every JSON metric line is recovered
from the captured stdout tail) or raw ``bench.py`` stdout saved to a file.
Metrics are matched across runs by the text up to the first ``(`` —
parenthesised qualifiers (record counts, corpus sizes) change between
revisions, the headline name does not.

All bench metrics are throughputs (higher is better): a metric REGRESSES
when NEW's value drops more than ``--threshold`` (default 20%) below
OLD's, and any regression makes the exit status nonzero — wire this into
CI as ``make bench-diff OLD=... NEW=...``.  A 0.0 value is the bench's
"stage unavailable" sentinel and is reported but never compared.  When
both runs carry the device pipeline's ``stage_ms`` breakdown the
per-stage deltas are printed too (informational: stage attribution shifts
between backends; the gate is the end-to-end value).

``--armed-overhead FRAC`` switches to the flight-recorder overhead gate:
OLD is a disarmed run, NEW the identical run with
``tez.obs.flight.enabled``, and any shared metric more than FRAC worse
(slower for s/ms-unit records, lower for throughputs) fails the diff —
CI uses 0.03 to hold the recorder to its 3% tier-1 budget
(docs/doctor.md).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

DEFAULT_THRESHOLD = 0.20


def normalize(metric: str) -> str:
    """Match key: the metric text up to the first parenthesis."""
    return metric.split("(", 1)[0].strip()


def _metric_lines(text: str) -> List[Dict]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec and "value" in rec:
            out.append(rec)
    return out


def load_metrics(path: str) -> Dict[str, Dict]:
    """{normalized_name: metric_record} from a wrapper or raw stdout file.
    Later lines win on a normalized-name collision (the bench prints the
    headline last)."""
    with open(path) as f:
        text = f.read()
    recs: List[Dict] = []
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:
        recs = _metric_lines(doc.get("tail") or "")
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed and \
                not any(r["metric"] == parsed["metric"] for r in recs):
            recs.append(parsed)
    elif isinstance(doc, dict) and "metric" in doc:
        recs = [doc]
    elif isinstance(doc, list):
        recs = [r for r in doc
                if isinstance(r, dict) and "metric" in r and "value" in r]
    else:
        recs = _metric_lines(text)
    return {normalize(r["metric"]): r for r in recs}


def _stage_diff(old: Dict, new: Dict) -> List[str]:
    so, sn = old.get("stage_ms"), new.get("stage_ms")
    if not (isinstance(so, dict) and isinstance(sn, dict)):
        return []
    lines = []
    for stage in sorted(set(so) | set(sn)):
        a, b = float(so.get(stage, 0.0)), float(sn.get(stage, 0.0))
        lines.append(f"    stage {stage:14} {a:10.1f} {b:10.1f} "
                     f"{b - a:+10.1f} ms")
    return lines


#: units where LOWER is better (wall/latency records, e.g. a tier-1 suite
#: wall measured armed vs disarmed); everything else is a throughput
LOWER_IS_BETTER_UNITS = frozenset({"s", "sec", "seconds", "ms"})


def diff(old_path: str, new_path: str,
         threshold: float = DEFAULT_THRESHOLD,
         armed_overhead: Optional[float] = None) -> int:
    old, new = load_metrics(old_path), load_metrics(new_path)
    if not old or not new:
        print(f"no metrics parsed from "
              f"{old_path if not old else new_path}", file=sys.stderr)
        return 2
    shared = [k for k in old if k in new]
    regressions = 0
    print(f"{'metric':52} {'OLD':>10} {'NEW':>10} {'ratio':>7}")
    for key in shared:
        a, b = old[key], new[key]
        va, vb = float(a["value"]), float(b["value"])
        unit = b.get("unit", a.get("unit", ""))
        if va <= 0.0 or vb <= 0.0:
            print(f"{key:52} {va:10.2f} {vb:10.2f}    skip "
                  f"(unavailable sentinel)")
            continue
        ratio = vb / va
        flag = ""
        if armed_overhead is not None:
            # armed-vs-disarmed gate (OLD = disarmed, NEW = armed): the
            # flight recorder buys its always-on ring by promising a
            # bounded cost — flag any metric that pays more than the
            # declared overhead, in the unit's own "worse" direction
            worse = ratio > 1.0 + armed_overhead \
                if unit in LOWER_IS_BETTER_UNITS \
                else ratio < 1.0 - armed_overhead
            if worse:
                flag = (f"  << ARMED OVERHEAD "
                        f"(>{armed_overhead:.0%} vs disarmed)")
                regressions += 1
        elif ratio < 1.0 - threshold:
            flag = f"  << REGRESSION (>{threshold:.0%} drop)"
            regressions += 1
        print(f"{key:52} {va:10.2f} {vb:10.2f} {ratio:6.2f}x "
              f"{unit}{flag}")
        for line in _stage_diff(a, b):
            print(line)
    for key in sorted(set(old) - set(new)):
        print(f"{key:52} {float(old[key]['value']):10.2f} "
              f"{'-':>10}    (metric dropped)")
    for key in sorted(set(new) - set(old)):
        print(f"{key:52} {'-':>10} {float(new[key]['value']):10.2f}"
              f"    (metric added)")
    # absolute ratio floors: a metric that declares min_vs_baseline must
    # hold that vs_baseline ratio in NEW regardless of what OLD recorded
    # (so the gate bites even on the first run that ships the metric).
    # A vs_baseline <= 0 is the "stage unavailable" sentinel and is
    # reported but never gated.
    for key in sorted(new):
        rec = new[key]
        floor, vs = rec.get("min_vs_baseline"), rec.get("vs_baseline")
        if floor is None or vs is None or float(vs) <= 0.0:
            continue
        if float(vs) < float(floor):
            print(f"{key:52} vs_baseline {float(vs):.2f}x below floor "
                  f"{float(floor):.2f}x  << REGRESSION (ratio floor)")
            regressions += 1
    bound = armed_overhead if armed_overhead is not None else threshold
    what = "armed overhead" if armed_overhead is not None else "regression"
    if regressions:
        print(f"\n{regressions} metric(s) over the {bound:.0%} "
              f"{what} bound")
        return 1
    print(f"\nno {what} beyond {bound:.0%} across "
          f"{len(shared)} shared metric(s)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tez_tpu.tools.bench_diff", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("old", help="baseline run (BENCH_*.json or raw stdout)")
    ap.add_argument("new", help="candidate run")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative drop that counts as a regression "
                         "(default 0.20 = 20%%)")
    ap.add_argument("--armed-overhead", type=float, default=None,
                    metavar="FRAC",
                    help="flight-recorder gate: OLD is a disarmed run, "
                         "NEW the same run with tez.obs.flight.enabled; "
                         "fail when any shared metric is worse than FRAC "
                         "(use 0.03 for the 3%% tier-1 budget) — seconds/"
                         "ms units gate on slowdown, throughputs on drop")
    args = ap.parse_args(argv)
    return diff(args.old, args.new, threshold=args.threshold,
                armed_overhead=args.armed_overhead)


if __name__ == "__main__":
    sys.exit(main())
