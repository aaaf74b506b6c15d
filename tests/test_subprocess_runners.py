"""Out-of-process runner tests: the multi-process execution mode with the
socket umbilical and cross-process shuffle (the MiniCluster-style tier:
real processes, real sockets — SURVEY.md §4 tier 3)."""
import collections
import os
import random

import pytest

from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient


@pytest.fixture()
def proc_client(tmp_staging):
    c = TezClient.create("proc", {
        "tez.staging-dir": tmp_staging,
        "tez.runner.mode": "subprocess",
        "tez.am.local.num-containers": 2,
        # force runner processes onto CPU (tests must not touch real TPU)
        "tez.am.runner.env": {"JAX_PLATFORMS": "cpu"},
    }).start()
    yield c
    c.stop()


def write_corpus(path, num_lines=300, seed=0):
    rng = random.Random(seed)
    words = [f"w{i:02d}" for i in range(25)]
    counts = collections.Counter()
    with open(path, "w") as fh:
        for _ in range(num_lines):
            line = [rng.choice(words) for _ in range(6)]
            counts.update(line)
            fh.write(" ".join(line) + "\n")
    return counts


def test_ordered_wordcount_across_processes(proc_client, tmp_path):
    """Full OrderedWordCount with producer and consumer tasks in SEPARATE
    runner processes: task specs over the socket umbilical, shuffle data
    over the TCP shuffle servers with HMAC auth."""
    from tez_tpu.examples import ordered_wordcount
    corpus = tmp_path / "in.txt"
    golden = write_corpus(str(corpus))
    out = str(tmp_path / "out")
    dag = ordered_wordcount.build_dag([str(corpus)], out,
                                      tokenizer_parallelism=2,
                                      summation_parallelism=2)
    status = proc_client.submit_dag(dag).wait_for_completion(timeout=120)
    assert status.state is DAGStatusState.SUCCEEDED
    rows = {}
    for f in sorted(os.listdir(out)):
        if f.startswith("part-"):
            for line in open(os.path.join(out, f), "rb"):
                w, c = line.rstrip(b"\n").split(b"\t")
                rows[w.decode()] = int(c)
    assert rows == dict(golden)
    # cross-process fetches actually happened (DCN counter nonzero) unless
    # both vertices landed in one runner — with 2 runners and 4+ tasks at
    # least some fetches cross processes
    counters = status.counters.to_dict().get("TaskCounter", {})
    assert counters.get("SHUFFLE_BYTES", 0) > 0


def test_failing_task_retries_across_processes(proc_client):
    from tez_tpu.common.payload import ProcessorDescriptor
    from tez_tpu.dag.dag import DAG, Vertex
    v = Vertex.create("v", ProcessorDescriptor.create(
        "tez_tpu.library.test_components:TestProcessor",
        payload={"do_fail": True, "failing_task_indices": [0],
                 "failing_upto_attempt": 0}), 2)
    status = proc_client.submit_dag(
        DAG.create("retry").add_vertex(v)).wait_for_completion(timeout=120)
    assert status.state is DAGStatusState.SUCCEEDED


def test_runner_process_killed_midtask_recovers(tmp_staging):
    """SIGKILL a runner process while its task runs: the heartbeat monitor
    times the attempt out, the pool respawns a runner, the task retries and
    the DAG completes (container-loss recovery, reference:
    ContainerHeartbeatHandler + container reallocation)."""
    import signal
    import time
    from tez_tpu.common.payload import ProcessorDescriptor
    from tez_tpu.dag.dag import DAG, Vertex
    c = TezClient.create("killer", {
        "tez.staging-dir": tmp_staging,
        "tez.runner.mode": "subprocess",
        "tez.am.local.num-containers": 2,
        "tez.task.heartbeat.timeout-ms": 1000,
        "tez.am.runner.env": {"JAX_PLATFORMS": "cpu"},
    }).start()
    try:
        am = c.framework_client.am
        am.heartbeat_monitor.check_interval = 0.2
        dag = DAG.create("killdag").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 4000}), 2))
        dc = c.submit_dag(dag)
        # Deterministic victim selection: wait until some attempt is
        # actually RUNNING in a live runner process, then kill THAT
        # process (a fixed sleep races child startup on a loaded box).
        from tez_tpu.am.task_impl import TaskAttemptState
        deadline = time.time() + 30
        victim = None
        while time.time() < deadline and victim is None:
            running_cids = set()
            d = am.current_dag
            for v in (d.vertices.values() if d else ()):
                for t in v.tasks.values():
                    for a in t.attempts.values():
                        if a.state is TaskAttemptState.RUNNING and \
                                a.container_id is not None:
                            running_cids.add(str(a.container_id))
            if running_cids:
                with am.runner_pool._lock:
                    for p, cid in am.runner_pool._procs.values():
                        if str(cid) in running_cids and p.poll() is None:
                            victim = p
                            break
            if victim is None:
                time.sleep(0.1)
        assert victim is not None, "no attempt started in a runner process"
        os.kill(victim.pid, signal.SIGKILL)
        status = dc.wait_for_completion(timeout=60)
        assert status.state is DAGStatusState.SUCCEEDED
        d = am.dag_counters.to_dict().get("DAGCounter", {})
        # 2 original tasks + at least one retry after the kill
        assert d.get("TOTAL_LAUNCHED_TASKS", 0) >= 3
    finally:
        c.stop()
