"""Test harness config: force an 8-device virtual CPU mesh so multi-chip
sharding paths compile and execute without TPU hardware (the driver's
dryrun_multichip does the same)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The config update outranks the env var: tests run on the virtual CPU mesh
# even when the shell exported another JAX_PLATFORMS before pytest started.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lockorder_witness_session():
    """Arm the runtime lock-order witness for the whole suite (graftlint's
    dynamic half — docs/static_analysis.md): every lock the tests create
    inside tez_tpu is wrapped, nested acquisitions are recorded, and the
    session fails if any order inversion was observed or if a witnessed
    edge is missing from the static lock graph.  TEZ_LOCKORDER_WITNESS=0
    opts out (e.g. when bisecting an unrelated failure)."""
    if os.environ.get("TEZ_LOCKORDER_WITNESS", "1") == "0":
        yield
        return
    from tez_tpu.common import lockorder
    lockorder.arm("pytest-session")
    yield
    lockorder.disarm("pytest-session")
    from tez_tpu.analysis import lockorder as static_lockorder
    from tez_tpu.analysis.core import Context
    import tez_tpu
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(tez_tpu.__file__)))
    edges, locks = static_lockorder.build_graph(Context(root))
    problems = lockorder.check(set(edges), locks)
    assert not problems, \
        "lock-order witness: " + "\n".join(problems)


@pytest.fixture()
def tmp_staging(tmp_path):
    return str(tmp_path / "staging")


@pytest.fixture(autouse=True)
def _disarm_fault_plane():
    """The fault plane is process-global; a test that leaks armed rules
    would poison every later test in the session."""
    yield
    from tez_tpu.common import faults
    faults.clear_all()


@pytest.fixture(autouse=True)
def _disarm_trace_plane():
    """The tracing plane and metrics registry are process-global; spans or
    gauges leaked by one test must not bleed into the next one's exports."""
    yield
    from tez_tpu.common import metrics, tracing
    tracing.clear_all()
    metrics.registry().reset()


@pytest.fixture(autouse=True)
def _reset_timeseries_plane():
    """The live time-series registry is process-global like the metrics
    registry; sampled rings and registered collectors leaked by one
    test's AM must not feed the next test's windows."""
    yield
    from tez_tpu.obs import timeseries
    reg = timeseries.registry()
    reg.reset()
    for name in reg.collectors():
        reg.unregister_collector(name)
    reg.capacity = timeseries.DEFAULT_CAPACITY


@pytest.fixture(autouse=True)
def _reset_device_breaker():
    """The device circuit breaker is a sticky process singleton; a test
    that tripped it (injected device faults) must not leave the device
    engine short-circuited to host for every later test."""
    yield
    from tez_tpu.ops.async_stage import reset_process_breaker
    reset_process_breaker()


@pytest.fixture(autouse=True)
def _reset_epoch_registry():
    """The AM-epoch registry is process-global; a test that restarted an AM
    (attempt 2+) would otherwise fence the next test's attempt-1 AMs if an
    app_id collided."""
    yield
    from tez_tpu.common import epoch
    epoch.reset()


@pytest.fixture(autouse=True)
def _reset_buffer_store():
    """The tiered buffer store is a process singleton attached to the
    shuffle service; a test that enabled it (store conf knobs) must not
    leave its tiny tiers — or its sealed lineage cache — behind for
    later tests."""
    yield
    from tez_tpu.store import reset_store
    reset_store()
