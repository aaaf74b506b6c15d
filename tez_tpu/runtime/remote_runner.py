"""Out-of-process runner: the TezChild-as-a-process analog.

Reference parity: tez-runtime-internals TezChild.java:214 — a separate
process that connects back to the AM's umbilical, loops getTask, runs tasks,
and dies when told.  Each runner also hosts a ShuffleServer so its outputs
are fetchable across process/host boundaries (the NM-resident ShuffleHandler
role collapses onto the runner host here).

Launch: python -m tez_tpu.runtime.remote_runner
            --am-host H --am-port P --node-id NAME
  with the job token in the TEZ_TPU_JOB_TOKEN env var (hex), mirroring the
  reference's credential handoff via the container environment.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import tempfile

log = logging.getLogger(__name__)


def run_loop(am_host: str, am_port: int, node_id: str, token_hex: str,
             idle_timeout: float = 5.0, work_dir: str = "",
             container_id: str = "", advertise_host: str = "127.0.0.1",
             max_tasks: int = 0) -> int:
    from tez_tpu.am.umbilical_server import RemoteUmbilical
    from tez_tpu.api.runtime import ObjectRegistry
    from tez_tpu.common.ids import ContainerId
    from tez_tpu.common.security import JobTokenSecretManager
    from tez_tpu.runtime.task_runner import TaskRunner
    from tez_tpu.shuffle.server import ShuffleServer
    from tez_tpu.shuffle.service import local_shuffle_service

    secrets = JobTokenSecretManager(bytes.fromhex(token_hex))
    umbilical = RemoteUmbilical(am_host, am_port, secrets)
    # consumers on OTHER hosts dial advertise_host: a non-loopback
    # advertisement requires a non-loopback bind (both server flavors)
    bind_host = "127.0.0.1" if advertise_host in ("127.0.0.1", "localhost") \
        else "0.0.0.0"
    from tez_tpu.common.tls import server_context
    shuffle_ssl = server_context(None)   # TEZ_TPU_SSL_* from the launch env
    native_dir = os.environ.get("TEZ_TPU_NATIVE_SHUFFLE_DIR", "")
    shuffle_server = None
    if native_dir and shuffle_ssl is not None:
        # the C++ sendfile server has no TLS; silently serving plaintext
        # when the operator asked for encrypted shuffle would be a
        # downgrade attack on ourselves — refuse loudly, use the TLS
        # Python server
        log.warning("TEZ_TPU_NATIVE_SHUFFLE_DIR ignored: shuffle TLS is "
                    "enabled and the native server speaks plaintext; "
                    "serving via the Python TLS server instead")
        native_dir = ""
    if native_dir:
        # native sendfile data server (ShuffleHandler analog): registered
        # runs are write-through serialized to disk; remote fetches never
        # enter Python.  Falls back to the Python server if the native lib
        # is unavailable on this host.
        try:
            from tez_tpu.shuffle.native_server import (FileShuffleStore,
                                                       NativeShuffleServer)
            store_dir = os.path.join(native_dir, f"runner-{os.getpid()}")
            shuffle_server = NativeShuffleServer(
                secrets, store_dir, host=bind_host).start()
            # attach only after the server is up: a failed native start
            # must not leave every spill double-written for nothing
            local_shuffle_service().attach_store(FileShuffleStore(store_dir))
        except Exception:  # noqa: BLE001
            log.exception("native shuffle server unavailable; "
                          "using the Python server")
            shuffle_server = None
    if shuffle_server is None:
        shuffle_server = ShuffleServer(secrets, local_shuffle_service(),
                                       host=bind_host,
                                       ssl_context=shuffle_ssl).start()
    if not container_id:
        container_id = str(ContainerId(f"app_proc_{node_id}", os.getpid()))
    registry = ObjectRegistry()
    work_dir = work_dir or tempfile.mkdtemp(prefix=f"tez-runner-{node_id}-")
    # advertise_host is what consumers dial for shuffle fetches; on a
    # multi-host deployment pass this worker's reachable address
    shuffle_meta = {"host": advertise_host, "port": shuffle_server.port,
                    "secret": secrets}
    log.info("runner %s up: shuffle port %d, am %s:%d", node_id,
             shuffle_server.port, am_host, am_port)
    tasks_run = 0
    try:
        while True:
            try:
                spec = umbilical.get_task(container_id, timeout=idle_timeout,
                                          node_id=node_id)
            except ConnectionError:
                log.info("umbilical gone; runner exiting")
                break
            if spec is None:
                break  # idle: release this runner (container release)
            runner = TaskRunner(spec, umbilical, registry,
                                work_dir=work_dir, node_id=node_id,
                                service_metadata={"shuffle": shuffle_meta})
            runner.run()
            registry.clear_scope(ObjectRegistry.VERTEX)
            tasks_run += 1
            if max_tasks and tasks_run >= max_tasks:
                # container reuse disabled: one fresh process per task
                # (tez.am.container.reuse.enabled=False; the pool respawns
                # while backlog remains)
                break
    finally:
        shuffle_server.stop()
        umbilical.close()
        log.info("runner %s done after %d tasks", node_id, tasks_run)
    return 0


def claim_chip(chip: str) -> None:
    """The launcher gave this process one TPU chip (am/launcher.py
    chip_env): initialise the backend NOW and check it is that one chip.
    Raises when the chip cannot be claimed — a runner without its chip must
    die, not sort on the host."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != 1:
        raise RuntimeError(
            f"runner was assigned TPU chip {chip} but JAX reports "
            f"{len(devices)} {devices[0].platform} device(s)")
    log.info("runner owns TPU chip %s: %s", chip, devices[0])


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--am-host", default="127.0.0.1")
    parser.add_argument("--am-port", type=int, required=True)
    parser.add_argument("--node-id", default=f"proc-{os.getpid()}")
    parser.add_argument("--container-id", default="")
    parser.add_argument("--advertise-host", default="127.0.0.1")
    parser.add_argument("--idle-timeout", type=float, default=5.0)
    parser.add_argument("--max-tasks", type=int, default=0,
                        help="exit after N tasks; 0 = loop until idle "
                             "(tez.am.container.reuse.enabled=False -> 1)")
    args = parser.parse_args()
    token = os.environ.get("TEZ_TPU_JOB_TOKEN", "")
    if not token:
        print("TEZ_TPU_JOB_TOKEN env var required", file=sys.stderr)
        return 2
    logging.basicConfig(level=os.environ.get("TEZ_TPU_LOG", "INFO"))
    from tez_tpu.common import ndc
    ndc.install()   # every task log line carries its attempt id (%(ndc)s)
    from tez_tpu.am.launcher import CHIP_CLAIM_FAILED_RC, RUNNER_CHIP_ENV
    chip = os.environ.get(RUNNER_CHIP_ENV, "")
    if chip:
        try:
            claim_chip(chip)
        except Exception as e:  # noqa: BLE001 — any failure is fatal here
            print(f"runner cannot claim TPU chip {chip}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return CHIP_CLAIM_FAILED_RC
    return run_loop(args.am_host, args.am_port, args.node_id, token,
                    idle_timeout=args.idle_timeout,
                    container_id=args.container_id,
                    advertise_host=args.advertise_host,
                    max_tasks=args.max_tasks)


if __name__ == "__main__":
    sys.exit(main())
