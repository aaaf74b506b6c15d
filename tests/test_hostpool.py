"""ops/hostpool.py: large host arrays whose memory is found again, and never
while anything can still reach it."""
import threading
import time

import numpy as np
import pytest

from tez_tpu.ops import hostpool
from tez_tpu.ops.hostpool import HostPool, MIN_BYTES, size_class

MIB = 1 << 20


@pytest.mark.parametrize("nbytes", [
    MIN_BYTES, MIN_BYTES + 1, 3 * MIB - 5000, 23_592_960, 39_636_180,
    54_735_660, 94_371_840, (1 << 30) + 1])
def test_size_class_holds_the_request_with_an_eighth_to_spare(nbytes):
    cap = size_class(nbytes)
    assert nbytes <= cap <= nbytes + nbytes // 8 + 4096
    assert size_class(cap) == cap


def test_small_requests_are_plain_arrays():
    pool = HostPool(64 * MIB)
    a = pool.empty(MIN_BYTES - 1)
    assert a.base is None and (pool.made, pool.reused) == (0, 0)


@pytest.mark.parametrize("dtype", [np.uint8, np.int64, np.uint32])
def test_empty_has_the_count_and_dtype_asked_for(dtype):
    pool = HostPool(64 * MIB)
    count = 2 * MIB // np.dtype(dtype).itemsize + 3
    a = pool.empty(count, dtype)
    assert a.shape == (count,) and a.dtype == np.dtype(dtype)
    assert a.flags.writeable and a.flags.aligned and a.flags.c_contiguous
    a[:] = 5
    assert int(a.sum()) == 5 * count


def test_block_comes_back_when_the_last_view_is_gone_and_not_before():
    pool = HostPool(64 * MIB)
    a = pool.empty(3 * MIB)
    a[:] = 7
    view = a[100:200].reshape(10, 10)
    through_buffer = np.frombuffer(memoryview(a[:64]), dtype=np.uint8)
    del a
    assert pool.idle_bytes == 0
    b = pool.empty(3 * MIB)            # a second block: the first is held
    b[:] = 9
    assert pool.made == 2 and int(view.max()) == 7
    del view
    assert pool.idle_bytes == 0
    assert int(through_buffer.min()) == 7
    del through_buffer
    assert pool.idle_bytes == size_class(3 * MIB)


def test_a_freed_block_serves_the_next_request_of_its_class():
    pool = HostPool(64 * MIB)
    a = pool.empty(3 * MIB)
    where = a.ctypes.data
    del a
    b = pool.empty(3 * MIB - 5000)     # the same eighth
    assert b.ctypes.data == where and (pool.made, pool.reused) == (1, 1)
    assert pool.idle_bytes == 0
    c = pool.empty(5 * MIB)            # another class: a new block
    assert pool.made == 2 and c.ctypes.data != where


def test_past_the_limit_the_block_idle_longest_goes():
    pool = HostPool(7 * MIB)
    first, second, third = (pool.empty(n * MIB) for n in (2, 3, 4))
    kept = third.ctypes.data
    del first
    del second
    assert pool.idle_bytes == 5 * MIB
    del third                          # 9 MiB idle: the 2 MiB block goes
    assert pool.idle_bytes == 7 * MIB
    assert pool.empty(4 * MIB).ctypes.data == kept and pool.reused == 1
    pool.empty(2 * MIB)
    assert pool.made == 4              # the dropped one is made again
    pool.clear()
    assert pool.idle_bytes == 0


def test_concatenate_equals_numpys():
    rng = np.random.default_rng(3)
    parts = [rng.integers(0, 256, n, dtype=np.uint8)
             for n in (MIB, 0, 2 * MIB + 17)]
    out = hostpool.concatenate(parts)
    assert out.base is not None
    np.testing.assert_array_equal(out, np.concatenate(parts))
    offsets = hostpool.concatenate([np.arange(5, dtype=np.int64),
                                    np.arange(3, dtype=np.int64)])
    assert offsets.dtype == np.int64 and offsets.tolist() == \
        [0, 1, 2, 3, 4, 0, 1, 2]


def test_no_two_live_arrays_share_memory_across_threads():
    pool = HostPool(256 * MIB)
    wrong = []

    def worker(ident: int) -> None:
        for _ in range(30):
            a = pool.empty(2 * MIB + ident)
            a[:] = ident
            time.sleep(0.001)
            if int(a.min()) != ident or int(a.max()) != ident:
                wrong.append(ident)
            del a

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not wrong
    assert pool.reused > 0 and pool.made <= 8 * 30


def test_a_gather_of_a_gather_outlives_its_source():
    """The native gathers write into pooled blocks: the second's source is
    the first's output, freed right after, and the third request takes that
    block while the second's output is still read."""
    from tez_tpu.ops.runformat import gather_ragged
    rng = np.random.default_rng(5)
    n, w = 40_000, 90
    data = rng.integers(0, 256, n * w, dtype=np.uint8)
    offsets = np.arange(n + 1, dtype=np.int64) * w
    p1, p2 = rng.permutation(n), rng.permutation(n)
    once, once_offsets = gather_ragged(data, offsets, p1)
    twice, twice_offsets = gather_ragged(once, once_offsets, p2)
    del once, once_offsets
    again, _ = gather_ragged(data, offsets, p1)     # may reuse once's block
    rows = data.reshape(n, w)
    np.testing.assert_array_equal(twice.reshape(n, w), rows[p1][p2])
    np.testing.assert_array_equal(again.reshape(n, w), rows[p1])
    assert twice_offsets.tolist() == offsets.tolist()
