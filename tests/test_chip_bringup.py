"""What PR 21's chip bring-up can pin without a chip: where the compile
cache goes, that a backend which cannot initialise is an error and not "no
accelerator", that chip_smoke.py refuses a CPU unless told otherwise, and
that runner processes are handed one chip each."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra=None, env_drop=(), cwd=REPO, timeout=300,
         fsize=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    for k in env_drop:
        env.pop(k, None)
    env.update(env_extra or {})
    args = [sys.executable, "-c", code_or_args] \
        if isinstance(code_or_args, str) else [sys.executable, *code_or_args]
    if fsize is not None:
        # RLIMIT_FSIZE for the child only, set by the child (no preexec_fn:
        # this process has threads)
        args = [sys.executable, "-c",
                "import os, resource, sys; "
                f"resource.setrlimit(resource.RLIMIT_FSIZE, ({fsize},) * 2); "
                "os.execv(sys.argv[1], sys.argv[1:])", *args]
    return subprocess.run(args, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


# ---------------------------------------------------------------- cache dir
_CACHE_PROBE = """
import jax
updates = []
_orig = jax.config.update
def _spy(name, value):
    updates.append(name)
    return _orig(name, value)
jax.config.update = _spy
import tez_tpu.ops.device, tez_tpu.parallel.exchange
from tez_tpu.ops import compile_cache
import json
print(json.dumps({"dir": compile_cache.cache_dir(),
                  "jax": jax.config.jax_compilation_cache_dir,
                  "code_set_dir": "jax_compilation_cache_dir" in updates}))
"""


def test_cache_dir_env_set_is_left_alone(tmp_path):
    want = str(tmp_path / "cc")
    r = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": want})
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"dir": want, "jax": want, "code_set_dir": False}


def test_cache_dir_unset_is_checkout_and_stable():
    want = os.path.join(REPO, ".jax_cache")
    outs = [_run(_CACHE_PROBE, env_drop=("JAX_COMPILATION_CACHE_DIR",))
            for _ in range(2)]
    for r in outs:
        assert r.returncode == 0, r.stderr
        got = json.loads(r.stdout.strip().splitlines()[-1])
        assert got == {"dir": want, "jax": want, "code_set_dir": True}
    from tez_tpu.ops import compile_cache
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert compile_cache.cache_dir() == compile_cache.cache_dir() == want


def test_cache_entries_land_where_the_env_says(tmp_path):
    want = str(tmp_path / "cc")
    r = _run("""
import jax, numpy as np
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from tez_tpu.ops import compile_cache, device
device.hash_partition(np.zeros((300, 8), np.uint8), np.full(300, 8), 4)
print(compile_cache.entry_count())
""", {"JAX_COMPILATION_CACHE_DIR": want})
    assert r.returncode == 0, r.stderr
    assert int(r.stdout.strip().splitlines()[-1]) >= 1
    assert any(n.endswith("-cache") for n in os.listdir(want))


# ------------------------------------------------- no silent "no accelerator"
@pytest.fixture()
def fresh_backend_query():
    from tez_tpu.ops import device
    device.backend_platform.cache_clear()
    yield device
    device.backend_platform.cache_clear()


def test_backend_init_failure_propagates(fresh_backend_query, monkeypatch):
    device = fresh_backend_query
    from tez_tpu.ops import sorter

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                           "libtpu multi-process lockfile")
    monkeypatch.setattr(device.jax, "default_backend", boom)
    for query in (device.accelerator_present,
                  lambda: sorter.resolve_engine("auto"),
                  lambda: sorter.DeviceSorter(num_partitions=2,
                                              engine="auto")):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            query()


def test_cpu_nobody_asked_for_is_an_error(fresh_backend_query, monkeypatch):
    device = fresh_backend_query
    from tez_tpu.ops import compile_cache
    config = types.SimpleNamespace(jax_platforms=None)
    monkeypatch.setattr(compile_cache, "jax",
                        types.SimpleNamespace(config=config))
    monkeypatch.setattr(device.jax, "default_backend", lambda: "cpu")
    with pytest.raises(RuntimeError, match="without being asked"):
        device.accelerator_present()
    # a chip machine's "tpu,cpu" that still came out as cpu is no request
    config.jax_platforms = "tpu,cpu"
    with pytest.raises(RuntimeError, match="without being asked"):
        device.accelerator_present()
    config.jax_platforms = "cpu"
    assert device.accelerator_present() is False


# ------------------------------------------------------------- chip_smoke.py
def test_chip_smoke_refuses_cpu_without_the_flag():
    r = _run(["chip_smoke.py", "--mb", "32", "--sort-mb", "4",
              "--vocab-size", "200000"])
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py", "--allow-cpu"], {"PYTHONPATH": ""},
             cwd=str(tmp_path))
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_chip_smoke_tiny_dry_run_passes_with_the_flag():
    r = _run(["chip_smoke.py", "--mb", "32", "--sort-mb", "4",
              "--vocab-size", "200000", "--mesh-mb", "8", "--allow-cpu"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": last["device"]["count"]}}
    assert "DRY RUN on a CPU backend" in r.stdout
    assert "built from source in this run" in r.stdout
    assert "every DeviceFailover counter is zero" in r.stdout
    assert "mesh leg: skipped" in r.stdout or "mesh: rows landed" in r.stdout


def test_chip_smoke_passes_under_a_file_size_limit_below_the_corpus():
    """The driver's chip machine caps file sizes (EFBIG on a one-file 1 GB
    corpus): the corpus is parts, and corpus and span size are cut, out loud,
    to what a tokenizer's spills and final run can fit."""
    r = _run(["chip_smoke.py", "--mb", "32", "--sort-mb", "8",
              "--vocab-size", "200000", "--mesh-mb", "8", "--allow-cpu"],
             fsize=16 << 20)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert ("17 MB corpus (cut from 32 MB, io.sort.mb=8: "
            "RLIMIT_FSIZE=16777216") in r.stdout
    assert "io.sort.mb=7, 4x4x1" in r.stdout
    assert "main: corpus 25 MB" in r.stdout      # > the 16.8 MB limit
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"] is True


# ------------------------------------------------- one process for each chip
class _FakeProc:
    def __init__(self, env):
        self.env = env
        self.pid = 4242
        self.returncode = None

    def poll(self):
        return self.returncode


class _Ctx:
    app_id = "app_t"

    def __init__(self, runner_env):
        self.conf = {"tez.am.runner.env": runner_env}
        self.secrets = types.SimpleNamespace(secret=b"\x01\x02")
        self.umbilical_server = types.SimpleNamespace(port=1)
        self.events = []

    def history(self, ev):
        self.events.append(ev)


def _pool(monkeypatch, runner_env, chips, max_runners=4):
    from tez_tpu.am import launcher
    launched = []

    def fake_popen(cmd, env):
        launched.append(_FakeProc(env))
        return launched[-1]
    monkeypatch.setattr(launcher, "local_tpu_chips", lambda: chips)
    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    return launcher.SubprocessRunnerPool(_Ctx(runner_env), max_runners), \
        launched


def test_runners_get_one_chip_each_and_never_outnumber_chips(monkeypatch):
    pool, launched = _pool(monkeypatch, {"JAX_PLATFORMS": ""}, chips=2)
    pool.ensure_runners(backlog=9)
    assert len(launched) == 2                      # 4 asked, 2 chips
    assert sorted(p.env["TPU_VISIBLE_CHIPS"] for p in launched) == ["0", "1"]
    for p in launched:
        assert p.env["TEZ_TPU_RUNNER_CHIP"] == p.env["TPU_VISIBLE_CHIPS"]
        assert p.env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert p.env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert len({p.env["TPU_PROCESS_PORT"] for p in launched}) == 2
    # the runner that owned chip 0 dies: its chip, and only it, is re-issued
    dead = next(p for p in launched if p.env["TPU_VISIBLE_CHIPS"] == "0")
    dead.returncode = 3
    pool.ensure_runners(backlog=9)
    assert len(launched) == 3
    assert launched[-1].env["TPU_VISIBLE_CHIPS"] == "0"
    chips = [e.data.get("tpu_chip") for e in pool.ctx.events
             if e.event_type.name == "CONTAINER_LAUNCHED"]
    assert chips == [0, 1, 0] or chips == [1, 0, 0]


def test_host_only_runners_take_no_chip(monkeypatch):
    pool, launched = _pool(monkeypatch, {"JAX_PLATFORMS": "cpu"}, chips=2)
    pool.ensure_runners(backlog=9)
    assert len(launched) == 4
    assert not any("TPU_VISIBLE_CHIPS" in p.env or
                   "TEZ_TPU_RUNNER_CHIP" in p.env for p in launched)


def test_runner_that_cannot_claim_its_chip_exits_nonzero():
    r = _run(["-m", "tez_tpu.runtime.remote_runner", "--am-port", "1"],
             {"TEZ_TPU_RUNNER_CHIP": "0", "TEZ_TPU_JOB_TOKEN": "00"})
    assert r.returncode == 3
    assert "cannot claim TPU chip 0" in r.stderr


def test_am_with_subprocess_runners_never_initialises_a_backend(tmp_path):
    """The AM process hands chips to its runners, so it must not hold one:
    after a whole DAG in subprocess-runner mode no JAX backend exists in
    the client/AM process."""
    corpus = tmp_path / "in.txt"
    corpus.write_text("a b a c b a\n" * 50)
    r = _run(f"""
from tez_tpu.client.tez_client import TezClient
from tez_tpu.examples import ordered_wordcount
conf = {{"tez.staging-dir": {str(tmp_path / 'stg')!r},
        "tez.runner.mode": "subprocess", "tez.am.local.num-containers": 2,
        "tez.am.runner.env": {{"JAX_PLATFORMS": "cpu"}}}}
with TezClient.create("noinit", conf) as c:
    dag = ordered_wordcount.build_dag([{str(corpus)!r}],
        {str(tmp_path / 'out')!r}, tokenizer_parallelism=2,
        summation_parallelism=2)
    print(c.submit_dag(dag).wait_for_completion(timeout=120).state.name)
from jax._src import xla_bridge
print("BACKENDS", xla_bridge.backends_are_initialized())
""")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SUCCEEDED" in r.stdout
    assert "BACKENDS False" in r.stdout
