"""From a profiler trace and the program's spans to device busy time, idle
gaps and a roofline share.  The benchmark's yardstick: no PR that claims a
gain may change it.

Tracer          jax.profiler around the measured window, with a marker event
                that puts the trace's clock and time.time() on one axis.
load_xplane     .xplane.pb -> {"planes": [{"name", "lines": [{"name",
                "events": [[name, start_ns, duration_ns], ...]}]}]}; the form
                the recorded fixture under tests/ is kept in.
reduce_planes   that form -> busy union per device, window, per-program
                sums, idle gaps labelled by the program span that covers most
                of each.
hbm_roofline_pct least HBM time for the rows sorted and merged over the time
                the device was busy; over 105 % raises.

What a v5e trace looks like (TPU v5 lite, jax 0.9.0): one plane
``/device:TPU:<n>`` a chip; its line ``XLA Modules`` has one event for every
execution of a compiled program, named ``jit_<function>(<fingerprint>)``; its
line ``XLA Ops`` has the operations inside, nested under ``while`` and
``conditional``.  Busy time is the union of the ``XLA Ops`` events; the
program names come from ``XLA Modules``.
"""
from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
MARK = "bench.window.mark"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Tracer:
    """The profiler around the window.  No Python tracer: with four runner
    threads it would be most of what the host does."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        #: time.time() at "start", "mark" (inside the marker event) and "stop"
        self.marks: Dict[str, float] = {}

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.marks["start"] = time.time()
        with jax.profiler.TraceAnnotation(MARK):
            self.marks["mark"] = time.time()

    def stop(self) -> None:
        import jax
        self.marks["stop"] = time.time()
        jax.profiler.stop_trace()

    def xplane_path(self) -> str:
        found = glob.glob(os.path.join(self.log_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under "
                               f"{self.log_dir}, found {found}")
        return found[0]


def program_spans() -> List[Tuple[str, float, float, str]]:
    """The program's own spans (tez_tpu.common.tracing, armed by the
    configuration's ``trace_conf``) as (name, start, end, thread), epoch
    seconds."""
    from tez_tpu.common import tracing
    out = []
    for sp in tracing.snapshot():
        if sp.end is None:
            continue
        name = sp.name.split(":", 1)[0]
        if "." not in name and sp.cat:
            name = f"{sp.cat}.{name}"
        out.append((name, sp.start, sp.end, sp.thread))
    return out


def load_xplane(path: str) -> Dict[str, Any]:
    """The device planes' kept lines and the marker, as plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            elif not is_device:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name == MARK]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The complement of a disjoint sorted cover inside [lo, hi]."""
    out = []
    at = lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_intervals(spans: List[Tuple[str, float, float, str]]
                   ) -> List[Tuple[str, float, float]]:
    """Each span less what its children on the same thread cover: at any
    instant a thread is in its innermost open span."""
    by_thread: Dict[str, List[Tuple[str, float, float]]] = {}
    for name, start, end, thread in spans:
        by_thread.setdefault(thread, []).append((name, start, end))
    out = []
    for items in by_thread.values():
        items.sort(key=lambda s: (s[1], -s[2]))
        for i, (name, start, end) in enumerate(items):
            children = []
            for other, s2, e2 in items[i + 1:]:
                if s2 >= end:
                    break
                children.append((s2, min(e2, end)))
            at = start
            for a, b in union(children):
                if a > at:
                    out.append((name, at, a))
                at = max(at, b)
            if end > at:
                out.append((name, at, end))
    return out


def label_gap(lo: float, hi: float, selfs: List[Tuple[str, float, float]]
              ) -> str:
    """The span name that covers most of [lo, hi], summed over threads, with
    how many threads' worth of the gap it covers."""
    cover: Dict[str, float] = {}
    for name, a, b in selfs:
        over = min(b, hi) - max(a, lo)
        if over > 0:
            cover[name] = cover.get(name, 0.0) + over
    if not cover:
        return "no_program_span"
    name = max(cover, key=cover.get)
    return f"{name}_x{cover[name] / (hi - lo):.1f}_threads"


def program_name(event_name: str) -> str:
    """``jit_merge_path_pair(1234)`` -> ``merge_path_pair``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes: Dict[str, Any], n_devices: int,
                  spans: List[Tuple[str, float, float, str]],
                  marks: Dict[str, float]) -> Optional[Dict[str, Any]]:
    """Busy union, idle gaps and per-program sums of the first `n_devices`
    device planes, inside the window the marks give.  None where no
    operation ran on a device."""
    mark_ns = [e[1] for p in planes["planes"]
               if not DEVICE_PLANE.match(p["name"])
               for line in p["lines"] for e in line["events"]
               if e[0] == MARK]
    if not mark_ns:
        raise RuntimeError(f"the trace has no {MARK} event: the trace's "
                           f"clock cannot be put beside the host's")
    # trace seconds = epoch seconds + offset
    offset = min(mark_ns) / 1e9 - marks["mark"]
    lo, hi = marks["start"] + offset, marks["stop"] + offset
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in planes["planes"]
                     if DEVICE_PLANE.match(p["name"]))[:n_devices]
    busy_by_device = {}
    program_s: Dict[str, float] = {}
    for dev_id, plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy_by_device[dev_id] = union(clip(
            [(s / 1e9, (s + d) / 1e9) for _n, s, d in events], lo, hi))
        for name, s, d in lines.get(MODULES_LINE, []):
            for a, b in clip([(s / 1e9, (s + d) / 1e9)], lo, hi):
                key = program_name(name)
                program_s[key] = program_s.get(key, 0.0) + (b - a)
    busy_s = {d: total(iv) for d, iv in busy_by_device.items()}
    if not busy_s or max(busy_s.values()) <= 0:
        return None
    fullest = max(busy_s, key=busy_s.get)
    selfs = self_intervals([(n, a + offset, b + offset, t)
                            for n, a, b, t in spans])
    idle = sorted(gaps(busy_by_device[fullest], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    suffix = f"_chip_{fullest}"
    return {
        "window_s": hi - lo,
        "busy_s_mean": sum(busy_s.values()) / len(busy_s),
        "busy_s_fullest": busy_s[fullest],
        "busy_s_by_device": busy_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                program_s.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[label_gap(a, b, selfs) + suffix, b - a]
                          for a, b in idle]}}


def reduce_trace(path: str, n_devices: int,
                 spans: List[Tuple[str, float, float, str]],
                 marks: Dict[str, float]) -> Optional[Dict[str, Any]]:
    return reduce_planes(load_xplane(path), n_devices, spans, marks)


def least_hbm_bytes(rows: int, lanes: int) -> int:
    """The least a sort or a merge of `rows` rows of `lanes` 4-byte lanes
    (key lanes and the index lane) moves through HBM: each row read once
    and written once."""
    return rows * lanes * 4 * 2


def hbm_roofline_pct(rows: int, lanes: int, busy_s: float,
                     device_kind: str) -> Optional[float]:
    """Memory-bound share of the roofline: least HBM time over busy time.
    Nothing to read where no row was counted; an unknown device or a share
    over 105 % is an error, never a clamp."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(has: {sorted(peaks)})")
    if rows <= 0 or busy_s <= 0:
        return None
    least_s = least_hbm_bytes(rows, lanes) / (
        peaks[device_kind]["hbm_gb_per_s"] * 1e9)
    pct = 100.0 * least_s / busy_s
    if pct > 105.0:
        raise ValueError(
            f"roofline share {pct:.1f} % > 105 %: {rows} rows x {lanes} "
            f"lanes need {least_s:.6f}s of HBM time but the device was busy "
            f"{busy_s:.6f}s; the rows are counted too high or the busy time "
            f"leaves out part of the work")
    return pct
