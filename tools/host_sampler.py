#!/usr/bin/env python3
"""A witness outside the program: run beside a benchmark run, in a process of
its own, to tell a machine that stood still from a program that did.

    python3 tools/host_sampler.py OUT.jsonl [PERIOD_S] [CMDLINE_PART] &

Every PERIOD_S (0.5) it writes one JSON line: its own clock, /proc/meminfo's
MemFree / Cached / AnonPages, and, for the first process whose command line
holds CMDLINE_PART ("benchmarks/run.py"), utime / stime / threads from
/proc/<pid>/stat and the byte counts of /proc/<pid>/io.  That is what the chip
tool's machine (a gVisor sandbox: no /proc/vmstat, /proc/stat all zero) lets a
process read.  A gap between two lines much longer than the period is the
whole machine freezing: this process only sleeps and reads (PERF.md §6 PR 28:
3.0 s in the very seconds of a 5.51 s DAG).  `--gaps OUT.jsonl` lists them.
"""
from __future__ import annotations

import glob
import json
import sys
import time


def read(path: str) -> str:
    try:
        with open(path) as fh:
            return fh.read()
    except OSError:
        return ""


def find_pid(part: str):
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        cmd = read(path)
        if part in cmd and "host_sampler" not in cmd:
            return int(path.split("/")[2])
    return None


def sample(part: str) -> dict:
    row = {"t": time.time()}
    mem = {line.split(":")[0]: int(line.split()[1])
           for line in read("/proc/meminfo").splitlines() if line}
    row["mem_kb"] = {k: mem[k] for k in ("MemFree", "Cached", "AnonPages")
                     if k in mem}
    pid = find_pid(part)
    if pid:
        stat = read(f"/proc/{pid}/stat").rsplit(")", 1)[-1].split()
        if len(stat) > 17:
            row["proc"] = {"utime_ticks": int(stat[11]),
                           "stime_ticks": int(stat[12]),
                           "threads": int(stat[17])}
        row["io"] = {line.split(":")[0]: int(line.split()[1])
                     for line in read(f"/proc/{pid}/io").splitlines() if line}
    return row


def gaps(path: str, factor: float = 1.6) -> int:
    times = [json.loads(line)["t"] for line in open(path)]
    steps = sorted(b - a for a, b in zip(times, times[1:]))
    period = steps[len(steps) // 2]
    for a, b in zip(times, times[1:]):
        if b - a > factor * period:
            print(f"stood still {b - a - period:.2f} s at {a:.2f}")
    return 0


def main() -> int:
    if sys.argv[1] == "--gaps":
        return gaps(sys.argv[2])
    out = sys.argv[1]
    period = float(sys.argv[2]) if len(sys.argv) > 2 else 0.5
    part = sys.argv[3] if len(sys.argv) > 3 else "benchmarks/run.py"
    with open(out, "w") as fh:
        while True:
            t0 = time.time()
            fh.write(json.dumps(sample(part)) + "\n")
            fh.flush()
            time.sleep(max(0.0, period - (time.time() - t0)))


if __name__ == "__main__":
    sys.exit(main())
