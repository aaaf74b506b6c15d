"""TezClient: session & non-session DAG submission.

Reference parity: tez-api/.../client/TezClient.java:228 (builder, start:384,
submitDAG:613, stop:727, preWarm:897) + FrameworkClient SPI (YARN vs
LocalClient).  Here the stock framework client is local/in-process (the
reference's LocalClient path); a cluster deployment would swap a gRPC
FrameworkClient behind the same surface.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

from tez_tpu.am.app_master import DAGAppMaster
from tez_tpu.client.dag_client import DAGClient
from tez_tpu.client.errors import DAGRejectedError
from tez_tpu.common import config as C
from tez_tpu.common.ids import new_app_id
from tez_tpu.dag.dag import DAG
from tez_tpu.utils.backoff import ExponentialBackoff, retry_call

log = logging.getLogger(__name__)

__all__ = ["TezClient", "FrameworkClient", "LocalFrameworkClient",
           "DAGRejectedError"]


class _RetryAfterBackoff:
    """Backoff policy flooring each delay at the AM's RETRY-AFTER hint.

    The server's hint is a floor, not the whole story: sleeping exactly
    retry-after re-synchronizes every shed client into the same resubmit
    instant, so full-jittered exponential delay rides on top (the same
    decorrelation argument as utils/backoff.py)."""

    def __init__(self, inner: ExponentialBackoff):
        self.inner = inner
        self.hint = 0.0

    def delay(self, attempt: int) -> float:
        return self.hint + self.inner.delay(attempt)

    def sleep(self, attempt: int) -> None:
        time.sleep(self.delay(attempt))


class FrameworkClient:
    """SPI: how to reach/launch an AM (reference: FrameworkClient.java:58)."""

    def start(self) -> None: ...

    def stop(self) -> None: ...

    def submit_dag(self, plan: Any) -> Any:
        raise NotImplementedError


class LocalFrameworkClient(FrameworkClient):
    """In-process AM (reference: LocalClient.java:80)."""

    def __init__(self, conf: C.TezConfiguration):
        self.conf = conf
        self.app_id = new_app_id()
        self.am: Optional[DAGAppMaster] = None
        self._attempt = 0

    def start(self) -> None:
        self._attempt = 1
        self.am = DAGAppMaster(self.app_id, self.conf,
                               attempt=self._attempt)
        self.am.start()

    def stop(self) -> None:
        if self.am is not None:
            self.am.stop()
            self.am = None

    def submit_dag(self, plan: Any) -> Any:
        return self.am.submit_dag(plan)

    def reattach(self) -> Any:
        """Successor incarnation of a crashed in-process AM: attempt+1
        (which zombie-fences the dead incarnation's attempts via the epoch
        registry), journal replay, admission-queue rebuild — the local
        analog of reconnecting to a supervisor-restarted AM."""
        self._attempt += 1
        self.am = DAGAppMaster(self.app_id, self.conf,
                               attempt=self._attempt)
        self.am.start()
        self.am.recover_and_resume()
        return self.am


class TezClient:
    def __init__(self, name: str, conf: Optional[Dict[str, Any]] = None,
                 session: bool = False):
        self.name = name
        self.conf = C.TezConfiguration(conf or {})
        self.session_mode = session or self.conf.get(C.SESSION_MODE)
        self.framework_client: Optional[FrameworkClient] = None
        self._started = False
        #: weakrefs to every DAGClient this client issued — reattach()
        #: re-binds the live ones against the recovered AM registry
        self._handles: list = []

    @staticmethod
    def create(name: str, conf: Optional[Dict[str, Any]] = None,
               session: bool = False) -> "TezClient":
        return TezClient(name, conf, session)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "TezClient":
        assert not self._started
        if self.conf.get("tez.framework.mode") == "remote":
            from tez_tpu.client.remote import RemoteFrameworkClient
            self.framework_client = RemoteFrameworkClient(self.conf)
        else:
            self.framework_client = LocalFrameworkClient(self.conf)
        self.framework_client.start()
        # a traced session's client phases (building a DAG: TeraSort samples
        # its split points there) are spans too; the AM arms per DAG
        from tez_tpu.common import tracing
        tracing.install_from_conf(self.conf, scope=f"client:{self.name}")
        self._started = True
        return self

    #: client-side-only keys never shipped into DAG plans (the job token
    #: must not leak into the plan -> history journal on disk)
    _CLIENT_ONLY_KEYS = ("tez.job.token", "tez.am.address",
                         "tez.framework.mode")

    def submit_dag(self, dag: DAG) -> DAGClient:
        assert self._started, "client not started"
        from tez_tpu.common import tracing
        # the client's side of a DAG's head: the plan made, and the AM's
        # admission up to its return (a root of its own: the DAG's root
        # span opens inside, in the AM)
        with tracing.span("submit_dag", cat="client", dag=dag.name):
            conf = {k: v for k, v in self.conf.items()
                    if k not in self._CLIENT_ONLY_KEYS}
            plan = dag.create_dag_plan(conf)
            dag_id = self.framework_client.submit_dag(plan)
        return self._track(DAGClient(self.framework_client.am, dag_id))

    def _track(self, handle: DAGClient) -> DAGClient:
        import weakref
        self._handles = [r for r in self._handles if r() is not None]
        self._handles.append(weakref.ref(handle))
        return handle

    def submit_dag_with_retry(self, dag: DAG, retries: int = 5,
                              backoff: Optional[ExponentialBackoff] = None,
                              rng: Any = None) -> DAGClient:
        """submit_dag that honors load shedding: a typed
        :class:`DAGRejectedError` (the AM's SHED verdict) is resubmitted
        after sleeping at least its RETRY-AFTER hint plus full-jitter
        exponential backoff.  Any other failure — and the final rejection
        after ``retries`` attempts — propagates unchanged."""
        policy = _RetryAfterBackoff(
            backoff or ExponentialBackoff(base=0.2, cap=10.0, jitter=True,
                                          rng=rng))

        def once() -> DAGClient:
            try:
                return self.submit_dag(dag)
            except DAGRejectedError as e:
                policy.hint = max(0.0, float(e.retry_after_s))
                log.info("dag %s shed by AM (%s); retry after >= %.3fs",
                         dag.name, e.reason, policy.hint)
                raise

        return retry_call(once, retries, retryable=(DAGRejectedError,),
                          backoff=policy)

    def queue_status(self) -> Dict[str, Any]:
        """The AM's admission/queue snapshot (works for local and remote
        framework clients — the remote proxy has the same method)."""
        return self.framework_client.am.queue_status()

    # -- AM crash survival (docs/recovery.md) --------------------------------
    def reattach(self) -> "TezClient":
        """Recover from an AM crash: rediscover/restart the AM and re-bind
        every live DAGClient handle against the recovered registry.

        Local framework client: constructs the successor incarnation
        (attempt+1) and runs journal replay inline.  Remote: bounded
        full-jitter reconnect to the captured AM address — the supervisor
        restarts the process, the successor replays before serving.
        Handles whose dag_id the recovered registry cannot resolve raise a
        typed :class:`DAGLostError` — by then the journal has been replayed,
        so an unknown dag_id is proof the DAG never reached a replayable
        state."""
        assert self._started, "client not started"
        am = self.framework_client.reattach()
        from tez_tpu.client.errors import DAGLostError
        lost = []
        for ref in list(self._handles):
            handle = ref()
            if handle is None:
                continue
            handle._am = am
            # registry validation is local-AM only: a remote proxy answers
            # per-call (an unknown dag_id reports state UNKNOWN instead)
            find = getattr(am, "find_dag", None)
            if find is None:
                continue
            dag_id = str(handle.dag_id)
            if find(handle.dag_id, include_retired=True) is None and \
                    dag_id not in am.completed_dags:
                lost.append(dag_id)
        if lost:
            raise DAGLostError(
                ", ".join(lost),
                reason="no journal record reached a replayable state "
                       "(not recovered, not requeued, not completed)")
        return self

    def attach_dag(self, name: str, timeout: float = 60.0,
                   poll: float = 0.05) -> DAGClient:
        """Re-bind to a DAG by NAME after reattach() — the handle for a
        submission whose original submitter observed AMCrashedError.

        dag ids are AM-assigned, so a submission that died parked in the
        admission queue never had one; its journaled DAG_QUEUED record
        replays under the successor AM and eventually promotes to a real
        dag_id, which this polls for.  Raises :class:`DAGLostError` once
        the name is provably absent everywhere — not running, not retired,
        not parked in the recovered queue."""
        assert self._started, "client not started"
        from tez_tpu.client.errors import DAGLostError
        am = self.framework_client.am
        deadline = time.time() + timeout
        missing_since: Optional[float] = None
        while True:
            dag_id = am.find_dag_id_by_name(name)
            if dag_id is not None:
                return self._track(DAGClient(am, dag_id))
            if name in (am.queued_dag_names() or []):
                missing_since = None   # parked: promotion is coming
            elif missing_since is None:
                missing_since = time.time()
            elif time.time() - missing_since > 0.5:
                # absent from registry AND queue across multiple probes —
                # the replayed journal holds no trace of this name
                raise DAGLostError(
                    name, reason="recovered AM has no queued or submitted "
                                 "record under this name")
            if time.time() > deadline:
                raise TimeoutError(
                    f"DAG {name} not re-attachable within {timeout}s")
            time.sleep(poll)

    def pre_warm(self) -> None:
        """Spin runners up before the first DAG (reference: preWarm:897).
        Works for both local and remote framework clients."""
        self.framework_client.am.prewarm()

    def stop(self) -> None:
        if self._started:
            self.framework_client.stop()
            from tez_tpu.common import tracing
            tracing.clear(f"client:{self.name}")
            self._started = False

    def __enter__(self) -> "TezClient":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()
