"""Standalone AM + remote client: the full cross-process control plane
(client -> AM over DAGClientServer, AM -> runners over the umbilical)."""
import os
import subprocess
import sys
import time

import pytest

from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.payload import ProcessorDescriptor
from tez_tpu.common.security import JobTokenSecretManager
from tez_tpu.dag.dag import DAG, Vertex


def spawn_am(tmp_path, *extra_args):
    """Launch a standalone AM process; returns (proc, port, token)."""
    token = JobTokenSecretManager().secret.hex()
    env = dict(os.environ)
    env["TEZ_TPU_JOB_TOKEN"] = token
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tez_tpu.am.client_server",
         "--staging-dir", str(tmp_path / "stg"), *extra_args],
        env=env, stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline().strip()
    assert line.startswith("READY "), line
    return proc, int(line.split()[1]), token


@pytest.fixture()
def standalone_am(tmp_path):
    proc, port, token = spawn_am(tmp_path, "--num-containers", "2")
    yield port, token
    proc.terminate()
    proc.wait(timeout=10)


def test_remote_client_runs_dag_on_standalone_am(standalone_am):
    port, token = standalone_am
    client = TezClient.create("remote", {
        "tez.framework.mode": "remote",
        "tez.am.address": f"127.0.0.1:{port}",
        "tez.job.token": token,
    }).start()
    try:
        dag = DAG.create("remote-dag").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 1}), 3))
        status = client.submit_dag(dag).wait_for_completion(timeout=60)
        assert status.state is DAGStatusState.SUCCEEDED
        assert status.vertex_status["v"].progress.succeeded_task_count == 3
    finally:
        client.stop()


def test_minicluster_full_stack(tmp_path):
    """MiniTezCluster analog (SURVEY.md §4 tier 3): standalone AM process,
    runner PROCESSES under it (socket umbilical), per-runner TCP shuffle
    servers with HMAC auth, remote client over the DAGClientServer — a real
    ordered-shuffle wordcount through the full multi-process stack, output
    validated against a host golden."""
    import collections
    from tez_tpu.examples import ordered_wordcount
    corpus = tmp_path / "in.txt"
    corpus.write_text("apple banana cherry apple banana apple\n" * 120)
    out = str(tmp_path / "out")

    proc, port, token = spawn_am(tmp_path, "--runner-mode", "subprocess",
                                 "--num-containers", "3")
    try:
        client = TezClient.create("mini", {
            "tez.framework.mode": "remote",
            "tez.am.address": f"127.0.0.1:{port}",
            "tez.job.token": token,
        }).start()
        try:
            dag = ordered_wordcount.build_dag(
                [str(corpus)], out, tokenizer_parallelism=2,
                summation_parallelism=2)
            status = client.submit_dag(dag).wait_for_completion(timeout=120)
            assert status.state is DAGStatusState.SUCCEEDED
        finally:
            client.stop()
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    got = {}
    for f in sorted(os.listdir(out)):
        if f.startswith("part-"):
            for line in open(os.path.join(out, f), "rb"):
                w, c = line.rstrip(b"\n").rsplit(b"\t", 1)
                got[w.decode()] = int(c)
    golden = collections.Counter(
        w for l in open(corpus) for w in l.split())
    assert got == dict(golden)


def test_remote_client_bad_token_rejected(standalone_am):
    port, _ = standalone_am
    bad = TezClient.create("bad", {
        "tez.framework.mode": "remote",
        "tez.am.address": f"127.0.0.1:{port}",
        "tez.job.token": JobTokenSecretManager().secret.hex(),
    })
    with pytest.raises(PermissionError):
        bad.start()


def test_remote_kill(standalone_am):
    port, token = standalone_am
    client = TezClient.create("remote", {
        "tez.framework.mode": "remote",
        "tez.am.address": f"127.0.0.1:{port}",
        "tez.job.token": token,
    }).start()
    try:
        dag = DAG.create("tokill").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 30_000}), 2))
        dc = client.submit_dag(dag)
        time.sleep(0.5)
        dc.try_kill_dag("remote kill")
        status = dc.wait_for_completion(timeout=30)
        assert status.state is DAGStatusState.KILLED
    finally:
        client.stop()


def test_remote_stop_synchronous_reaches_close():
    """Synchronous stop() (tez.client.asynchronous-stop=False) must poll
    the (host, port) captured at start() — not re-read tez.am.address,
    which may be cleared or portless by then — and must reach am.close()
    even when no address is available at all."""
    import socket

    from tez_tpu.client.remote import RemoteFrameworkClient
    from tez_tpu.common import config as C

    class FakeAM:
        def __init__(self):
            self.closed = False
            self.shutdowns = 0

        def shutdown_session(self):
            self.shutdowns += 1

        def close(self):
            self.closed = True

    # grab a port with nothing listening: the liveness poll must exit on
    # the first refused connect, not wait out the 15s default
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    conf = C.TezConfiguration({
        "tez.session.mode": True,
        "tez.client.asynchronous-stop": False,
        "tez.am.address": "cleared-no-port",   # unparseable at stop time
    })
    c = RemoteFrameworkClient(conf)
    am = FakeAM()
    c.am = am
    c._am_addr = ("127.0.0.1", port)   # as captured by start()
    t0 = time.time()
    c.stop()
    assert time.time() - t0 < 5.0
    assert am.shutdowns == 1 and am.closed and c.am is None

    # never start()ed AND the conf address is portless: the guarded
    # re-parse degrades to skipping the poll — close() still runs
    c2 = RemoteFrameworkClient(conf)
    am2 = FakeAM()
    c2.am = am2
    c2.stop()
    assert am2.shutdowns == 1 and am2.closed and c2.am is None
