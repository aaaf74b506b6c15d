"""Group boundaries of a sorted block (`runformat.group_starts`, the
`input.group` span): keys of one width compare a word of the row at a time,
ragged keys byte by byte where lengths agree.  Both are held to a plain
Python reference (row i's bytes against row i-1's) and to each other."""
import numpy as np
import pytest

from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.library import inputs
from tez_tpu.library.inputs import GroupedKVReader, StreamingGroupedKVReader
from tez_tpu.ops.runformat import (MAX_FIXED_WIDTH, KVBatch, fixed_key_width,
                                   group_starts)
from tez_tpu.ops.serde import BytesSerde

BLOCK = 65536


def reference(keys):
    """Row indices whose key differs from the row before."""
    return [i for i, k in enumerate(keys) if i == 0 or k != keys[i - 1]]


def batch_of(keys):
    """A batch of these keys and zero-width values."""
    ko = np.zeros(len(keys) + 1, np.int64)
    np.cumsum(np.fromiter(map(len, keys), np.int64, len(keys)), out=ko[1:])
    kb = np.frombuffer(b"".join(keys), np.uint8).copy()
    return KVBatch(kb, ko, np.zeros(0, np.uint8), np.zeros_like(ko))


def fixed_keys(width, n, pattern, seed=0):
    """`n` sorted keys of `width` bytes: all one key, every row its own,
    or runs of a zipf's lengths with one tie run across row 65,536."""
    if pattern == "equal":
        ids = np.zeros(n, np.uint64)
    elif pattern == "distinct":
        ids = np.arange(n, dtype=np.uint64)
    else:
        ids = np.sort(np.random.default_rng(seed).zipf(1.3, n)
                      .astype(np.uint64) % 997)
        if n > BLOCK + 100:
            ids[BLOCK - 100:BLOCK + 100] = ids[BLOCK - 100]
            ids = np.sort(ids)
    # the low `width` bytes of each id, big-endian: adjacent ids that
    # differ give keys that differ wherever the width can tell them apart
    be = ids.astype(">u8").view(np.uint8).reshape(n, 8)
    rows = np.zeros((n, width), np.uint8)
    take = min(width, 8)
    rows[:, width - take:] = be[:, 8 - take:]
    if width > 8:
        rows[:, :width - 8] = 0x61   # a constant head: the tail decides
    return [r.tobytes() for r in rows]


def arrays(keys):
    b = batch_of(keys)
    return b.key_bytes, b.key_offsets


@pytest.mark.parametrize("pattern", ["equal", "distinct", "runs"])
@pytest.mark.parametrize("n", [0, 1, 2, BLOCK, 2 * BLOCK + 1])
@pytest.mark.parametrize("width", [0, 1, 3, 4, 8, 12, 16])
def test_fixed_width_matches_reference_and_ragged_path(width, n, pattern):
    keys = fixed_keys(width, n, pattern)
    kb, ko = arrays(keys)
    assert fixed_key_width(ko) == (width if n else 0)
    got = group_starts(kb, ko)
    assert got.dtype == np.int64
    assert got.tolist() == reference(keys)
    # the path the block took before: the same boundaries, byte for byte
    assert np.array_equal(got, group_starts(kb, ko, width=-1))
    assert np.array_equal(
        GroupedKVReader._compute_groups(batch_of(keys)), got)


@pytest.mark.parametrize("width", [1, 3, 5, 7, 8, 9, 13, 24, 31, 32])
def test_every_byte_of_the_row_is_compared(width):
    """Rows that differ in one byte only, at each position: the words laid
    over the row (the last one overlapping) miss none of them."""
    base = bytes(range(1, width + 1))
    keys = [base]
    for pos in range(width):
        row = bytearray(base)
        row[pos] ^= 0xFF
        keys += [bytes(row), bytes(row), base]
    kb, ko = arrays(keys)
    assert fixed_key_width(ko) == width
    assert group_starts(kb, ko).tolist() == reference(keys)


@pytest.mark.parametrize("start", [1, 3, 5, 7, 1000, BLOCK - 3])
@pytest.mark.parametrize("width", [3, 8, 12])
def test_a_block_sliced_from_the_middle_of_a_batch(width, start):
    """`slice_rows` leaves the key bytes at an unaligned address; the
    offsets of a block not rebased start past 0."""
    keys = fixed_keys(width, 2 * BLOCK, "runs", seed=start)
    whole = batch_of(keys)
    block = whole.slice_rows(start, start + BLOCK // 2)
    want = reference(keys[start:start + BLOCK // 2])
    assert GroupedKVReader._compute_groups(block).tolist() == want
    ko = whole.key_offsets[start:start + BLOCK // 2 + 1]
    assert int(ko[0]) == start * width
    assert group_starts(whole.key_bytes, ko).tolist() == want


@pytest.mark.parametrize("keys", [
    [b"a", b"ab", b"ab", b"abc", b"abc", b"abd", b"b"],
    [b"", b"", b"x", b"x", b"xx", b"xy"],
    [b"k%d" % i for i in sorted([5, 5, 50, 50, 500, 5000, 5000])],
    [b"w" * 40, b"w" * 40, b"x" * 40],            # one width, past the cap
    [b"aaaaaaaa", b"bbbbbbb", b"ccccccccc", b"dddddddd"],  # 4 x 8 B in all
], ids=["mixed", "empty-then-mixed", "numbers", "wide", "same-total"])
def test_ragged_keys_take_the_ragged_path(keys):
    kb, ko = arrays(keys)
    assert fixed_key_width(ko) == -1
    assert group_starts(kb, ko).tolist() == reference(keys)


def test_the_span_names_the_width_the_block_was_compared_at():
    tracing.arm(scope="t")
    GroupedKVReader._compute_groups(batch_of(fixed_keys(8, 100, "runs")))
    GroupedKVReader._compute_groups(batch_of([b"a", b"bb", b"bb"]))
    GroupedKVReader._compute_groups(batch_of([b"x" * (MAX_FIXED_WIDTH + 1)]))
    spans = [s for s in tracing.snapshot() if s.name == "input.group"]
    assert [(s.args["rows"], s.args["width"]) for s in spans] == [
        (100, 8), (3, -1), (1, -1)]


@pytest.mark.parametrize("keys,width", [
    ([b"ab", b"AB", b"Ab", b"ac", b"AC"], 2),      # one width after upper()
    ([b"a ", b"A", b"a", b"b", b"B  "], 1),        # made one width by strip()
    ([b"a", b"A ", b"b", b"bb"], -1),              # still ragged
])
def test_a_key_normalizer_groups_the_normalized_keys(keys, width):
    def norm(k):
        return k.upper().strip()
    kb, ko = arrays([norm(k) for k in keys])
    assert fixed_key_width(ko) == width
    got = GroupedKVReader._compute_groups(batch_of(keys), norm)
    assert got.tolist() == reference([norm(k) for k in keys])


class _Ctx:
    def __init__(self):
        self.counters = TezCounters()

    def notify_progress(self):
        pass


class _Plan:
    def __init__(self, blocks):
        self.blocks = blocks

    def iter_batches(self):
        return iter(self.blocks)


def _stream(blocks):
    ctx = _Ctx()
    reader = StreamingGroupedKVReader(_Plan(blocks), BytesSerde(),
                                      BytesSerde(), ctx)
    out = [(b.key_bytes.tobytes(), b.key_offsets.tolist(), starts.tolist())
           for b, starts in reader.grouped_blocks()]
    return out, {c: ctx.counters.find_counter(c).value for c in (
        TaskCounter.REDUCE_INPUT_GROUPS, TaskCounter.REDUCE_INPUT_RECORDS)}


@pytest.mark.parametrize("block", [7, 1000, 4096])
@pytest.mark.parametrize("width", [1, 8, 12])
def test_streamed_hot_key_over_many_blocks_as_before(width, block,
                                                     monkeypatch):
    """A hot key's run spans several blocks (as `drain_equal` cuts them):
    the same (batch, starts) sequence and counters as the ragged path."""
    keys = fixed_keys(width, 9000, "runs", seed=width)
    hot = keys[len(keys) // 2]
    keys = sorted(keys + [hot] * (3 * block + 5))
    blocks = [batch_of(keys[i:i + block]) for i in range(0, len(keys), block)]
    got, counts = _stream(blocks)
    monkeypatch.setattr(inputs, "fixed_key_width", lambda ko: -1)
    assert (got, counts) == _stream(blocks)
    assert counts[TaskCounter.REDUCE_INPUT_GROUPS] == len(reference(keys))
    assert counts[TaskCounter.REDUCE_INPUT_RECORDS] == len(keys)
    # every group closed within its block: no key on both sides of a yield
    ends = [(kb[ko[s[0]]:ko[s[0] + 1]], kb[ko[s[-1]]:ko[s[-1] + 1]])
            for kb, ko, s in got]
    assert all(a[1] != b[0] for a, b in zip(ends, ends[1:]))
