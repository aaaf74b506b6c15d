"""Query-plane bench: join-strategy legs + the adaptive replan loop.

Entered via ``make bench-query`` (``TEZ_BENCH_QUERY_ONLY=1 bench.py``).
Three measurements over the deterministic TPC-H-style corpus
(tools/query_corpus.py), every output verified bit-exact against the
numpy oracle before any number is reported:

1. broadcast vs repartition on the UNIFORM corpus (info line): the
   strategy-sensitive ``nation_revenue`` join forced both ways through
   ``tez.query.join.strategy``; vs_baseline = repartition wall /
   broadcast wall (no floor — which side wins is data-dependent, the
   line exists so regressions in either lowering are visible).
2. the same pair on the ZIPF corpus (info line).
3. the ADAPTIVE REPLAN leg (the floored headline): one QuerySession,
   journaling to a real JSONL history store, runs an exchange-bound
   query twice — the build side is a selective filter the file-size
   estimator cannot see through, so run 1 lowers to a repartition
   sort-merge join; the session observes the true post-filter build
   bytes, PlanFeedback flips the node to broadcast, and run 2 re-plans.
   The leg asserts the QUERY_REPLANNED summary event hit the journal
   AND that ``graft doctor`` renders it for run 2's DAG, then reports
   ``vs_baseline = run1 wall / run2 wall`` with ``min_vs_baseline: 1.0``
   — bench_diff.py fails the bench if the replanned run ever stops
   beating the naive first run.  The result cache stays OFF for this
   leg so the speedup is purely the plan flip, never lineage reuse.
"""
from __future__ import annotations

import contextlib
import io
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

SCALE = float(os.environ.get("TEZ_BENCH_QUERY_SCALE", "2.0"))
TIMEOUT = float(os.environ.get("TEZ_BENCH_QUERY_TIMEOUT", "300"))


def _conf(workdir: str, name: str, extra: Optional[Dict] = None) -> Dict:
    conf: Dict[str, Any] = {
        "tez.staging-dir": os.path.join(workdir, name, "staging"),
        "tez.am.local.num-containers": 4,
    }
    conf.update(extra or {})
    return conf


def _run_forced(workdir: str, corpus, query, strategy: str
                ) -> Tuple[float, List[Tuple[str, str]]]:
    """One fresh session, one corpus query with the join lowering forced;
    returns (wall seconds, sorted output records)."""
    from tez_tpu.query import QuerySession
    from tez_tpu.store import reset_store
    reset_store()
    name = f"{query.name}_{strategy}"
    out = os.path.join(workdir, name, "out")
    with QuerySession(name, _conf(workdir, name, {
            "tez.query.join.strategy": strategy,
            "tez.query.replan.enabled": False})) as s:
        r = s.run(query.build(corpus), out, query_name=query.name,
                  sink=query.sink, timeout=TIMEOUT)
    assert r.state == "SUCCEEDED", f"{name} failed ({r.state})"
    got = r.read_output()
    want = query.oracle(corpus)
    assert got == want and got, (
        f"{name}: output diverges from oracle "
        f"({len(got)} vs {len(want)} records)")
    return r.wall_s, got


def _strategy_leg(workdir: str, corpus, flavor: str) -> dict:
    """Info line: the strategy-sensitive corpus join forced both ways."""
    from tez_tpu.tools.query_corpus import CORPUS_QUERIES
    query = next(q for q in CORPUS_QUERIES if q.strategy_sensitive)
    bc_wall, bc_out = _run_forced(workdir, corpus, query, "broadcast")
    rp_wall, rp_out = _run_forced(workdir, corpus, query, "repartition")
    assert bc_out == rp_out, \
        f"{query.name}: strategies disagree on the {flavor} corpus"
    return {
        "metric": (f"query broadcast vs repartition join, {flavor} "
                   f"corpus (info line; '{query.name}', scale {SCALE}, "
                   f"both outputs bit-exact vs numpy oracle; "
                   f"repartition {rp_wall:.2f}s)"),
        "value": round(bc_wall, 3), "unit": "s",
        "vs_baseline": round(rp_wall / bc_wall, 3),
    }


def _exchange_bound_query(corpus):
    """The replan scenario: a selective numeric filter guards the build
    side, so the file-size estimator over-states it and run 1 pays a
    full repartition of the (large) lineitem side."""
    small = corpus.scan("orders").filter("o_total", "ge", "95000",
                                         numeric=True)
    return (corpus.scan("lineitem")
            .join(small, "l_orderkey", "o_orderkey")
            .aggregate(["l_flag"], [("n", "count", "l_flag"),
                                    ("rev", "sum", "l_price")]))


def _doctor_render(history_dir: str, dag_id: str) -> str:
    """Run the real doctor CLI over the bench's JSONL history store and
    return its rendered text for one DAG."""
    from tez_tpu.tools import doctor
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = doctor.main([history_dir, "--dag", dag_id])
    assert rc == 0, f"doctor exited {rc} for {dag_id}"
    return buf.getvalue()


def _replan_leg(workdir: str, corpus) -> dict:
    """The floored headline: run 1 repartitions by estimate, the session
    observes, run 2 is replanned to broadcast and must win."""
    from tez_tpu.query import QuerySession
    from tez_tpu.store import reset_store
    reset_store()
    history_dir = os.path.join(workdir, "history")
    conf = _conf(workdir, "replan", {
        "tez.query.broadcast.max-mb": 0.02,
        "tez.history.logging.service.class":
            "tez_tpu.am.history:JsonlHistoryLoggingService",
        "tez.history.logging.log-dir": history_dir,
    })
    with QuerySession("replan", conf) as s:
        # warmup: a different query pays the one-time session costs
        # (library load, first-DAG scheduling) so run 1 vs run 2 compares
        # plans, not process warmth
        from tez_tpu.tools.query_corpus import CORPUS_QUERIES
        warm = next(q for q in CORPUS_QUERIES
                    if q.name == "pricing_summary")
        w = s.run(warm.build(corpus), os.path.join(workdir, "warm"),
                  query_name=warm.name, sink=warm.sink, timeout=TIMEOUT)
        assert w.state == "SUCCEEDED", f"warmup failed ({w.state})"

        r1 = s.run(_exchange_bound_query(corpus),
                   os.path.join(workdir, "replan1"),
                   query_name="exchange_bound", timeout=TIMEOUT)
        r2 = s.run(_exchange_bound_query(corpus),
                   os.path.join(workdir, "replan2"),
                   query_name="exchange_bound", timeout=TIMEOUT)
    assert r1.state == "SUCCEEDED" and r2.state == "SUCCEEDED", \
        f"replan legs failed ({r1.state}/{r2.state})"
    d1 = next(d for d in r1.decisions if d["kind"] == "join_strategy")
    d2 = next(d for d in r2.decisions if d["kind"] == "join_strategy")
    assert (d1["choice"], d1["basis"]) == ("repartition", "estimate"), d1
    assert (d2["choice"], d2["basis"]) == ("broadcast", "replan"), d2
    assert r2.replans, "run 2 replanned silently — nothing journaled"
    assert r1.read_output() == r2.read_output() != [], \
        "replanned run changed the answer"

    # the acceptance gate: the typed QUERY_REPLANNED event must be in the
    # durable journal AND visible in doctor's rendering of run 2's DAG
    report = _doctor_render(history_dir, r2.dag_id)
    assert "REPLANNED" in report and "repartition -> broadcast" in report, \
        f"doctor did not surface the replan:\n{report}"
    sys.stderr.write(report + "\n")

    flip = r2.replans[0]
    return {
        "metric": (f"adaptive replan: exchange-bound join, run 1 "
                   f"{d1['choice']} by {d1['basis']} ({r1.wall_s:.2f}s) "
                   f"-> run 2 {d2['choice']} by {d2['basis']}, "
                   f"{flip['from']} -> {flip['to']} journaled as "
                   f"QUERY_REPLANNED + rendered by doctor, outputs "
                   f"bit-exact, result cache OFF (zipf corpus, scale "
                   f"{SCALE})"),
        "value": round(r2.wall_s, 3), "unit": "s",
        "vs_baseline": round(r1.wall_s / r2.wall_s, 3),
        "min_vs_baseline": 1.0,
    }


def bench_query() -> List[dict]:
    """The query-plane records for bench.py's JSON stream (headline =
    the floored replan leg, printed last)."""
    import tempfile
    from tez_tpu.tools.query_corpus import generate
    workdir = tempfile.mkdtemp(prefix="tez-querybench-")
    try:
        t0 = time.time()
        uniform = generate(os.path.join(workdir, "uniform"),
                           scale=SCALE, skew=0.0, seed=11)
        zipf = generate(os.path.join(workdir, "zipf"),
                        scale=SCALE, skew=1.1, seed=12)
        sys.stderr.write(f"corpus generated in {time.time() - t0:.1f}s "
                         f"(scale {SCALE})\n")
        # process warmup: one throwaway query pays the one-time library /
        # first-DAG costs so the FIRST timed leg isn't the slow one
        from tez_tpu.tools.query_corpus import CORPUS_QUERIES
        warm = next(q for q in CORPUS_QUERIES
                    if q.name == "pricing_summary")
        _run_forced(os.path.join(workdir, "warm"), uniform, warm, "auto")
        records = [
            _strategy_leg(os.path.join(workdir, "uni"), uniform, "uniform"),
            _strategy_leg(os.path.join(workdir, "zipf"), zipf, "zipf"),
            _replan_leg(os.path.join(workdir, "replan"), zipf),
        ]
        return records
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
