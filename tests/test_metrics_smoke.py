"""End-to-end scrape smoke (``make metrics-smoke``): boot a real session
AM with the web UI on, run one DAG, then validate every exposition
surface against its strict contract — /metrics through the golden
parser, /metrics.json structurally, /doctor/live through ``graft top``'s
pure renderer.  Fast and non-slow: this is the tier-1 guard that the
live ops plane actually serves.
"""
import json
import urllib.request

from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.payload import ProcessorDescriptor
from tez_tpu.dag.dag import DAG, Vertex
from tez_tpu.obs.exposition import parse_exposition
from tez_tpu.tools import top


def _get(url):
    # generous: the AM web thread competes with the whole suite's
    # threads under full-suite load
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.read().decode("utf-8")


def test_metrics_smoke(tmp_path):
    c = TezClient.create("metricsmoke", {
        "tez.staging-dir": str(tmp_path / "s"),
        "tez.am.web.enabled": True,
        # a sampler tick lands between submit and scrape without sleeps
        "tez.am.metrics.sample-period-ms": 25.0,
    }).start()
    try:
        dag = DAG.create("smokedag").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 1}), 2))
        st = c.submit_dag(dag).wait_for_completion(timeout=180)
        assert st.state.name == "SUCCEEDED"
        am = c.framework_client.am
        url = am.web_ui.url

        # -- GET /metrics: strict Prometheus 0.0.4 ------------------------
        text = _get(url + "metrics")
        fams = parse_exposition(text)
        assert "tez_latency_am_heartbeat_rtt_ms" in fams
        assert any(info["type"] == "histogram" for info in fams.values())
        assert "tez_counter" in fams

        # -- GET /metrics.json: rows, windows, accounting -----------------
        # the 25ms sampler thread can be starved under full-suite load on
        # a small box: wait (bounded) for its first tick, then assert
        import time
        deadline = time.time() + 60
        body = json.loads(_get(url + "metrics.json?window=30"))
        while body["accounting"]["samples"] < 1 and time.time() < deadline:
            time.sleep(0.05)
            body = json.loads(_get(url + "metrics.json?window=30"))
        assert body["window_s"] == 30.0
        assert body["histograms"] and body["gauges"]
        series = {r["series"] for r in body["histograms"]}
        assert "am.heartbeat.rtt" in series
        acct = body["accounting"]
        assert acct["samples"] >= 1
        assert acct["scrape_errors"] == 0
        assert acct["collector_errors"] == 0
        # the sampler has ticked, so windowed aggregates are attached
        assert any("window" in r for r in body["histograms"])

        # -- drill-down: stream filter keeps only labeled series ----------
        empty = json.loads(_get(url + "metrics.json?stream=nosuch"))
        assert empty["histograms"] == [] and empty["gauges"] == []

        # -- GET /doctor/live + graft top ---------------------------------
        live = json.loads(_get(url + "doctor/live?window=30"))
        assert live["sampler"]["enabled"]
        assert live["sampler"]["ticks"] >= 1
        assert set(live["planes"]["busy_ms"]) >= {"admission", "store"}
        assert "queue_depth" in live
        frame = top.render(live)
        assert "graft top" in frame
        assert "rings:" in frame.splitlines()[-1]
        # the scraping path agrees with the pure renderer's input
        # (but for the ticks: the sampler may tick between the two reads)
        import re
        def head(text):
            return re.sub(r"\d+ ticks", "N ticks", text.splitlines()[0])
        assert head(top.render(top.fetch(url, window_s=30))) == head(frame)
    finally:
        c.stop()
    # the scrapes themselves must not have dirtied scrape accounting
    from tez_tpu.obs import timeseries
    assert timeseries.registry().accounting()["scrape_errors"] == 0
