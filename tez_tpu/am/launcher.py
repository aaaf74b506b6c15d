"""Runner pool: the container launcher + TezChild loop, in-process.

Reference parity: tez-dag ContainerLauncherManager.java:62 +
LocalContainerLauncher.java:87 (tasks as threads, uber-style) + the TezChild
run loop (tez-runtime-internals TezChild.java:214).  A "container" here is a
worker thread with an object registry (so kernel caches survive across tasks
— the TPU analog of JVM container reuse); on a real pod each would be a
runner process on a TPU host.
"""
from __future__ import annotations

import functools
import itertools
import logging
import threading
from typing import Any, Dict, Optional

from tez_tpu.am.history import HistoryEvent, HistoryEventType
from tez_tpu.api.runtime import ObjectRegistry
from tez_tpu.common import clock, faults
from tez_tpu.common.counters import DAGCounter
from tez_tpu.common.ids import ContainerId

log = logging.getLogger(__name__)


class RunnerPool:
    def __init__(self, ctx: Any, max_runners: int,
                 idle_timeout: Optional[float] = None):
        self.ctx = ctx
        self.max_runners = max_runners
        conf = getattr(ctx, "conf", None)
        if idle_timeout is None:
            ms = conf.get("tez.am.container.idle.release-timeout-min.millis") \
                if conf is not None else None
            idle_timeout = (5000 if ms is None else ms) / 1000.0
        self.idle_timeout = idle_timeout
        #: session mode holds this many runners even when idle (reference:
        #: tez.am.session.min.held-containers)
        self.min_held = int(conf.get("tez.am.session.min.held-containers")
                            or 0) if conf is not None else 0
        #: reuse off = one task per container, fresh ObjectRegistry/caches
        #: every time (reference: tez.am.container.reuse.enabled)
        from tez_tpu.common import config as C
        reuse = conf.get(C.AM_CONTAINER_REUSE_ENABLED) \
            if conf is not None else None
        self.reuse_enabled = True if reuse is None else bool(reuse)
        self._runners: Dict[ContainerId, threading.Thread] = {}
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._stopped = False

    def ensure_runners(self, backlog: int) -> None:
        """Spin up runner threads while there is queued work and capacity."""
        with self._lock:
            if self._stopped:
                return
            want = min(self.max_runners, len(self._runners) + max(0, backlog))
            while len(self._runners) < want:
                cid = ContainerId(self.ctx.app_id, next(self._seq))
                t = threading.Thread(target=self._runner_loop, args=(cid,),
                                     name=str(cid), daemon=True)
                self._runners[cid] = t
                t.start()

    def _runner_loop(self, container_id: ContainerId) -> None:
        """The TezChild loop: pull task, run, repeat until idle."""
        from tez_tpu.runtime.task_runner import TaskRunner
        self.ctx.history(HistoryEvent(
            HistoryEventType.CONTAINER_LAUNCHED,
            container_id=str(container_id)))
        registry = ObjectRegistry()
        tasks_run = 0
        try:
            try:
                faults.fire("am.container.launch", detail=str(container_id))
            except Exception as e:  # noqa: BLE001 — injected launch failure
                # dies like a container that crashed at startup: the finally
                # emits CONTAINER_STOPPED and the watchdog respawns while
                # backlog remains
                log.warning("container %s launch failed: %s", container_id, e)
                return
            while not self._stopped:
                spec = self.ctx.task_comm.get_task(container_id,
                                                   timeout=self.idle_timeout)
                if spec is None:
                    tracker = getattr(self.ctx, "node_tracker", None)
                    if tracker is not None and \
                            not tracker.is_usable(self.ctx.node_id):
                        break   # blacklisted node must not hold-and-spin
                    # idle release — but session mode keeps min.held runners
                    # warm (container reuse across DAGs; kernel caches
                    # live).  Decision and table removal are ATOMIC so
                    # several simultaneously-idle runners can't all leave.
                    with self._lock:
                        if len(self._runners) > self.min_held:
                            self._runners.pop(container_id, None)
                            break
                    continue
                if tasks_run > 0:
                    self.ctx.dag_counters.increment(
                        DAGCounter.TOTAL_CONTAINER_REUSE_COUNT)
                tasks_run += 1
                runner = TaskRunner(spec, self.ctx.task_comm, registry,
                                    work_dir=self.ctx.work_dir,
                                    node_id=self.ctx.node_id)
                runner.run()
                registry.clear_scope(ObjectRegistry.VERTEX)
                if not self.reuse_enabled:
                    # one task per container: exit; ensure_runners spawns a
                    # fresh one (fresh registry) while backlog remains
                    with self._lock:
                        self._runners.pop(container_id, None)
                    break
        finally:
            with self._lock:
                self._runners.pop(container_id, None)
            self.ctx.history(HistoryEvent(
                HistoryEventType.CONTAINER_STOPPED,
                container_id=str(container_id),
                data={"tasks_run": tasks_run}))

    def live_count(self) -> int:
        with self._lock:
            return len(self._runners)

    def shutdown(self, wait: bool = True) -> None:
        self._stopped = True
        if wait:
            deadline = clock.wall_s() + 10
            for t in list(self._runners.values()):
                t.join(timeout=max(0.1, deadline - clock.wall_s()))


#: env var naming the chip a runner process was given; the runner claims it
#: at start-up and exits non-zero if it cannot (runtime/remote_runner.py)
RUNNER_CHIP_ENV = "TEZ_TPU_RUNNER_CHIP"
#: exit status of a runner that could not claim its chip
CHIP_CLAIM_FAILED_RC = 3


@functools.lru_cache(maxsize=1)
def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted on the PCI bus the way
    libtpu's own start-up does — WITHOUT initialising a JAX backend: the AM
    process must never claim a chip its runners need."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def chip_env(chip: int) -> Dict[str, str]:
    """The environment libtpu reads to give ONE process exactly one local
    chip as its own 1x1x1 slice (a chip belongs to one process at a time;
    without this the first runner claims every chip and the rest cannot
    start their backend)."""
    port = 8476 + chip
    return {
        RUNNER_CHIP_ENV: str(chip),
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


def wants_device(env: Dict[str, str]) -> bool:
    """A runner launched with JAX_PLATFORMS=cpu (tests, host-only
    deployments) runs the host engine by request and needs no chip."""
    first = env.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return first != "cpu"


class SubprocessRunnerPool:
    """Launches runner PROCESSES (the TezChild-as-JVM analog) instead of
    threads.  Reference: ContainerLauncherManager + TezContainerLauncherImpl
    launching containers on NodeManagers; here runners are subprocesses of
    the AM host (a multi-host deployment execs the same module on each
    worker pointed at the AM's umbilical address).

    One process for each chip: on a host with TPU chips every runner that
    may use the device engine is handed exactly one chip through its
    environment (:func:`chip_env`), and the pool never holds more such
    runners than there are chips."""

    def __init__(self, ctx: Any, max_runners: int,
                 idle_timeout: float = 5.0):
        self.ctx = ctx
        self.max_runners = max_runners
        self.idle_timeout = idle_timeout
        self._procs: Dict[int, Any] = {}
        self._chips: Dict[int, int] = {}     # runner seq -> chip it owns
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._stopped = False

    def ensure_runners(self, backlog: int) -> None:
        import os
        import socket
        import subprocess
        import sys
        # node id = HOST, not process: failure accounting must accumulate
        # across respawns on the same machine (a multi-host deployment
        # passes each host's own stable --node-id)
        node = f"{socket.gethostname()}-{self.ctx.app_id}"
        base_env = dict(os.environ)
        # conf-supplied runner environment (reference: container launch
        # context env); empty value = unset the variable
        for k, v in (self.ctx.conf.get("tez.am.runner.env") or {}).items():
            if v == "":
                base_env.pop(k, None)
            else:
                base_env[k] = str(v)
        chips = local_tpu_chips() if wants_device(base_env) else 0
        with self._lock:
            if self._stopped:
                return
            self._reap()
            want = min(self.max_runners, len(self._procs) + max(0, backlog))
            if chips:
                want = min(want, chips)
            while len(self._procs) < want:
                n = next(self._seq)
                env = dict(base_env)
                if chips:
                    chip = min(set(range(chips)) -
                               set(self._chips.values()))
                    env.update(chip_env(chip))
                    self._chips[n] = chip
                env["TEZ_TPU_JOB_TOKEN"] = self.ctx.secrets.secret.hex()
                from tez_tpu.common.tls import export_env
                env.update(export_env(self.ctx.conf))
                repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
                existing = env.get("PYTHONPATH", "")
                env["PYTHONPATH"] = repo_root + (
                    os.pathsep + existing if existing else "")
                cid = f"container_proc_{self.ctx.app_id}_{n:06d}"
                try:
                    faults.fire("am.container.launch", detail=cid)
                except Exception as e:  # noqa: BLE001 — injected failure
                    log.warning("container %s launch failed: %s", cid, e)
                    self._chips.pop(n, None)
                    break   # retried on the watchdog's next ensure_runners
                from tez_tpu.common import config as C
                reuse = self.ctx.conf.get(C.AM_CONTAINER_REUSE_ENABLED)
                cmd = [sys.executable, "-m",
                       "tez_tpu.runtime.remote_runner",
                       "--am-port", str(self.ctx.umbilical_server.port),
                       "--node-id", node,
                       "--container-id", cid,
                       "--idle-timeout", str(self.idle_timeout)]
                if reuse is not None and not reuse:
                    cmd += ["--max-tasks", "1"]
                proc = subprocess.Popen(cmd, env=env)
                self._procs[n] = (proc, cid)
                data = {"pid": proc.pid}
                if n in self._chips:
                    data["tpu_chip"] = self._chips[n]
                self.ctx.history(HistoryEvent(
                    HistoryEventType.CONTAINER_LAUNCHED,
                    container_id=cid, data=data))

    def _reap(self) -> None:
        for n, (proc, cid) in list(self._procs.items()):
            if proc.poll() is not None:
                del self._procs[n]
                chip = self._chips.pop(n, None)
                if chip is not None and \
                        proc.returncode == CHIP_CLAIM_FAILED_RC:
                    # remote_runner's claim_chip failed: say so here too —
                    # the pool respawns while backlog remains, and a chip
                    # held by another process fails every respawn
                    log.error("runner %s could not claim TPU chip %d and "
                              "exited", cid, chip)
                self.ctx.history(HistoryEvent(
                    HistoryEventType.CONTAINER_STOPPED,
                    container_id=cid,
                    data={"returncode": proc.returncode}))

    def live_count(self) -> int:
        with self._lock:
            self._reap()
            return len(self._procs)

    def shutdown(self, wait: bool = True) -> None:
        with self._lock:
            self._stopped = True
            procs = [p for p, _ in self._procs.values()]
        for p in procs:
            p.terminate()
        if wait:
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    p.kill()
        with self._lock:
            self._reap()
