"""Native sendfile shuffle server: protocol compatibility with the Python
client, auth, ranges, deletion, and an E2E DAG run over subprocess runners.

Reference role: tez-plugins/tez-aux-services ShuffleHandler.java:159 (native
data server + job-token HMAC + zero-copy file regions + keep-alive).
"""
import os

import numpy as np
import pytest

from tez_tpu.common.security import JobTokenSecretManager
from tez_tpu.ops.runformat import KVBatch, Run
from tez_tpu.shuffle import native_server
from tez_tpu.shuffle.server import FetchSession, ShuffleFetcher
from tez_tpu.shuffle.service import ShuffleDataNotFound

pytestmark = pytest.mark.skipif(
    not native_server.native_available(),
    reason="libtezhost.so unavailable (no C++ toolchain)")


def _make_run(num_partitions=3, rows_per=4, seed=0):
    rng = np.random.default_rng(seed)
    n = num_partitions * rows_per
    keys = [f"k{seed}_{i:03d}".encode() for i in range(n)]
    vals = [rng.integers(0, 256, 8, dtype=np.int64).astype(np.uint8)
            .tobytes() for i in range(n)]
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8)
    ko = np.cumsum([0] + [len(k) for k in keys]).astype(np.int64)
    vb = np.frombuffer(b"".join(vals), dtype=np.uint8)
    vo = np.cumsum([0] + [len(v) for v in vals]).astype(np.int64)
    row_index = (np.arange(num_partitions + 1) * rows_per).astype(np.int64)
    return Run(KVBatch(kb, ko, vb, vo), row_index)


@pytest.fixture()
def server(tmp_path):
    secrets = JobTokenSecretManager()
    store = native_server.FileShuffleStore(str(tmp_path / "store"))
    srv = native_server.NativeShuffleServer(secrets, str(tmp_path / "store"))
    yield secrets, store, srv
    srv.stop()


def _batches_equal(a: KVBatch, b: KVBatch) -> bool:
    return (a.num_records == b.num_records and
            np.array_equal(a.key_bytes, b.key_bytes) and
            np.array_equal(a.key_offsets, b.key_offsets) and
            np.array_equal(a.val_bytes, b.val_bytes) and
            np.array_equal(a.val_offsets, b.val_offsets))


def test_fetch_parity_with_python_client(server):
    secrets, store, srv = server
    run = _make_run()
    store.register("v/task0/out", 0, run)
    fetcher = ShuffleFetcher(secrets)
    for p in range(3):
        got = fetcher.fetch("127.0.0.1", srv.port, "v/task0/out", 0, p)
        assert len(got) == 1
        assert _batches_equal(got[0], run.partition(p))
    assert srv.bytes_served > 0


def test_range_fetch_and_keepalive(server):
    secrets, store, srv = server
    store.register("v/t/out", 2, _make_run(seed=1))
    sess = FetchSession(secrets, "127.0.0.1", srv.port)
    try:
        got = sess.fetch_range("v/t/out", 2, 0, 3)   # one request, 3 blobs
        assert [b.num_records for b in got] == [4, 4, 4]
        # keep-alive: same connection serves another fetch
        again = sess.fetch_range("v/t/out", 2, 1, 2)
        assert _batches_equal(again[0], got[1])
    finally:
        sess.close()


def test_auth_rejected(server):
    secrets, store, srv = server
    store.register("v/x/out", 0, _make_run(seed=2))
    wrong = ShuffleFetcher(JobTokenSecretManager())   # different token
    with pytest.raises(PermissionError):
        wrong.fetch("127.0.0.1", srv.port, "v/x/out", 0, 0)
    assert srv.auth_failures >= 1


def test_missing_and_out_of_range(server):
    secrets, store, srv = server
    store.register("v/y/out", 0, _make_run(seed=3))
    fetcher = ShuffleFetcher(secrets)
    with pytest.raises(ShuffleDataNotFound):
        fetcher.fetch("127.0.0.1", srv.port, "v/NOPE/out", 0, 0)
    with pytest.raises(ShuffleDataNotFound):
        fetcher.fetch("127.0.0.1", srv.port, "v/y/out", 0, 7)


def test_store_deletion_tracker(tmp_path):
    store = native_server.FileShuffleStore(str(tmp_path))
    store.register("dagA/v1/t0", 0, _make_run())
    store.register("dagA/v2/t0", 0, _make_run())
    store.register("dagB/v1/t0", 0, _make_run())
    assert store.unregister_prefix("dagA/") == 2
    names = os.listdir(str(tmp_path))
    assert len([n for n in names if n.endswith(".data")]) == 1


def test_e2e_dag_over_native_shuffle(tmp_path, tmp_staging):
    """OrderedWordCount through subprocess runners serving via the native
    server (TEZ_TPU_NATIVE_SHUFFLE_DIR), output verified."""
    import collections
    from tez_tpu.client.dag_client import DAGStatusState
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples import ordered_wordcount

    words = [f"w{i % 40:02d}" for i in range(4000)]
    corpus = tmp_path / "c.txt"
    corpus.write_text(" ".join(words))
    out_dir = str(tmp_path / "out")
    conf = {
        "tez.staging-dir": tmp_staging,
        "tez.runner.mode": "subprocess",
        "tez.am.local.num-containers": 2,
        "tez.am.runner.env": {
            "JAX_PLATFORMS": "cpu",
            "TEZ_TPU_NATIVE_SHUFFLE_DIR": str(tmp_path / "native"),
        },
    }
    with TezClient.create("native-e2e", conf) as client:
        dag = ordered_wordcount.build_dag(
            [str(corpus)], out_dir, tokenizer_parallelism=2,
            summation_parallelism=2, sorter_parallelism=1)
        status = client.submit_dag(dag).wait_for_completion(timeout=120)
        assert status.state is DAGStatusState.SUCCEEDED
    got = {}
    for name in sorted(os.listdir(out_dir)):
        with open(os.path.join(out_dir, name)) as fh:
            for line in fh.read().splitlines():
                if line.strip():
                    w, c = line.rsplit(None, 1)
                    got[w] = int(c)
    assert got == dict(collections.Counter(words))
    # the native store actually served: data files were written
    native_files = []
    for root, _dirs, files in os.walk(str(tmp_path / "native")):
        native_files += [f for f in files if f.endswith(".data")]
    assert native_files, "native store was never written"
