"""Skew/straggler exchange plane: round planning, the folded-in
splitter, engine resolution, and coded r2 — all bit-exact against the
legacy padded formulation (itself kernel-verified against the host
reference in test_distributed_exchange.py)."""
import numpy as np
import pytest

import jax

from tez_tpu.common import faults
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.parallel.coordinator import (MeshExchangeCoordinator,
                                          plan_rounds)

KEY_BYTES = 6
VAL_BYTES = 5


@pytest.fixture(scope="module", autouse=True)
def _devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


@pytest.fixture(autouse=True)
def _no_faults():
    yield
    faults.install("test", [])


def _corpus(rows, producers, consumers, hot_frac, hot_part, seed=0):
    """Producer spans with ``hot_frac`` of rows in consumer partition
    ``hot_part`` — classified by the real FNV partitioner, so the skew is
    exact by construction."""
    from tez_tpu.ops.host_sort import fnv_rows_host
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 256, size=(4096, KEY_BYTES), dtype=np.uint8)
    part = fnv_rows_host(pool, np.full(pool.shape[0], KEY_BYTES,
                                       dtype=np.int64)) % consumers
    hot, cold = pool[part == hot_part], pool[part != hot_part]
    n_hot = int(rows * hot_frac)
    keys = np.concatenate([
        hot[rng.integers(0, hot.shape[0], n_hot)],
        cold[rng.integers(0, cold.shape[0], rows - n_hot)]])
    keys = keys[rng.permutation(rows)]
    vals = rng.integers(0, 256, size=(rows, VAL_BYTES), dtype=np.uint8)
    spans = []
    for i in range(producers):
        k, v = keys[i::producers], vals[i::producers]
        n = k.shape[0]
        spans.append(KVBatch(
            k.reshape(-1), np.arange(n + 1, dtype=np.int64) * KEY_BYTES,
            v.reshape(-1), np.arange(n + 1, dtype=np.int64) * VAL_BYTES))
    return spans


def _run(coord, spans, edge, consumers, **kw):
    for i, b in enumerate(spans):
        coord.register_producer(edge, i, len(spans), consumers, b,
                                KEY_BYTES, VAL_BYTES, **kw)
    return [coord.wait_consumer(edge, c, len(spans), consumers, timeout=120)
            for c in range(consumers)]


def _sig(res):
    return [(np.asarray(b.key_bytes).tobytes(),
             np.asarray(b.val_bytes).tobytes()) for b in res]


def _golden(spans, consumers):
    out = _run(MeshExchangeCoordinator(legacy_sizing=True), spans,
               "golden/a->b", consumers, engine="padded")
    return _sig(out)


# ---------------------------------------------------------------- planning

def test_plan_rounds_budget_invariants():
    """Every round's quota fits the device budget, quotas sum exactly to
    the histogram, and the balanced cap never exceeds per_round."""
    rng = np.random.default_rng(7)
    for _ in range(50):
        D = int(rng.integers(1, 9))
        per_round = int(rng.integers(1, 200))
        counts = rng.integers(0, per_round * 4, D).astype(np.int64)
        for legacy in (False, True):
            plan = plan_rounds(counts, per_round, D, legacy=legacy)
            total = np.zeros(D, dtype=np.int64)
            for quota, cap in plan:
                assert quota.max() <= per_round
                assert 1 <= cap <= per_round
                assert quota.sum() > 0          # no empty rounds
                total += quota
            np.testing.assert_array_equal(total, counts)
    assert plan_rounds(np.zeros(4, dtype=np.int64), 16, 4) == []


def test_plan_rounds_balanced_cap_beats_legacy():
    """One hot destination: legacy pads every pair to the hot partition,
    balanced splits its quota over D senders — a D-fold smaller cap."""
    counts = np.array([1000, 10, 10, 10], dtype=np.int64)
    [(_, legacy_cap)] = plan_rounds(counts, 1 << 20, 4, legacy=True)
    [(_, cap)] = plan_rounds(counts, 1 << 20, 4, legacy=False)
    assert legacy_cap >= 1000
    assert cap < legacy_cap
    assert cap >= -(-1000 // 4)      # still holds the hot dest's chunks


# ------------------------------------------------------- property matrix

@pytest.mark.parametrize("consumers", [8, 16])
@pytest.mark.parametrize("hot_frac", [0.0, 0.45])
@pytest.mark.parametrize("coded", ["off", "r2"])
def test_exchange_matrix_bit_exact(consumers, hot_frac, coded):
    """(W, skew, engine=auto, coded) matrix: every cell bit-identical to
    the legacy padded run of the same corpus — including W=16 on 8
    devices (two consumer partitions per device, host recombine)."""
    spans = _corpus(6_000, 4, consumers, hot_frac, hot_part=1,
                    seed=consumers * 10 + int(hot_frac * 100))
    golden = _golden(spans, consumers)
    coord = MeshExchangeCoordinator(max_rows_per_round=2_000, split_after=1)
    out = _run(coord, spans, f"cell-{coded}/a->b", consumers,
               engine="auto", coded=coded)
    assert _sig(out) == golden
    if hot_frac > 0.0:
        # 45% in one of >=8 partitions always busts the 2k budget
        assert coord.partition_splits >= 1
        assert coord.multi_round_exchanges == 0
    from tez_tpu.parallel.exchange import probe_ragged_support
    ok, _ = probe_ragged_support(coord.mesh_for(coord.devices_for(consumers)))
    assert coord.last_engine == ("ragged" if ok else "padded")


def test_splitter_recombine_preserves_key_order():
    """Equal hot keys split across sub-partitions must recombine in their
    original arrival order — values of one repeated key come back exactly
    as the no-split exchange delivers them."""
    consumers = 8
    spans = _corpus(4_000, 4, consumers, hot_frac=0.5, hot_part=3, seed=2)
    golden = _golden(spans, consumers)
    coord = MeshExchangeCoordinator(max_rows_per_round=600, split_after=1)
    out = _run(coord, spans, "recombine/a->b", consumers, engine="auto")
    assert coord.partition_splits >= 1
    assert _sig(out) == golden      # byte-exact => value order preserved


def test_splitter_disabled_falls_back_to_rounds():
    """split_after=0 turns the splitter off: the same hot corpus instead
    pays extra rounds, and stays bit-exact."""
    consumers = 8
    spans = _corpus(4_000, 4, consumers, hot_frac=0.5, hot_part=3, seed=2)
    golden = _golden(spans, consumers)
    coord = MeshExchangeCoordinator(max_rows_per_round=600, split_after=0)
    out = _run(coord, spans, "nosplit/a->b", consumers, engine="auto")
    assert coord.partition_splits == 0
    assert coord.multi_round_exchanges >= 1
    assert _sig(out) == golden


# ------------------------------------------------------------------ coded

def test_coded_r2_masks_delayed_chip():
    """With one chip's readback delayed, the coded exchange returns from
    the buddy copy without waiting out the delay — and stays bit-exact."""
    consumers = 8
    spans = _corpus(3_000, 4, consumers, hot_frac=0.0, hot_part=0, seed=4)
    golden = _golden(spans, consumers)
    coord = MeshExchangeCoordinator()
    # warm run compiles the coded program fault-free
    _run(coord, spans, "warm-coded/a->b", consumers, coded="r2")
    faults.install("test", faults.parse_spec(
        "mesh.exchange.delay:delay:ms=1500,n=1,match=device=5"))
    import time
    t0 = time.perf_counter()
    out = _run(coord, spans, "delayed-coded/a->b", consumers, coded="r2")
    wall = time.perf_counter() - t0
    assert _sig(out) == golden
    assert coord.coded_buddy_wins >= 1
    assert wall < 1.5, f"coded exchange waited out the delay ({wall:.2f}s)"


def test_coded_r2_both_copies_failed_raises():
    """fail-mode on BOTH holders of one partition (primary chip and its
    buddy) must surface an error, not silently drop the partition."""
    consumers = 8
    spans = _corpus(2_000, 4, consumers, hot_frac=0.0, hot_part=0, seed=6)
    coord = MeshExchangeCoordinator()
    _run(coord, spans, "warm-fail/a->b", consumers, coded="r2")
    # partition 2's primary is device 2; its buddy copy lives on device 3
    # ((2+1) % 8) — failing both readbacks kills every recovery path
    faults.install("test", faults.parse_spec(
        "mesh.exchange.delay:fail:n=1,match=device=2;"
        "mesh.exchange.delay:fail:n=1,match=device=3"))
    with pytest.raises(Exception, match="copies"):
        _run(coord, spans, "bothfail/a->b", consumers, coded="r2")


# --------------------------------------------------------- routing parity

MAX_KEY = 256     # tez.runtime.tpu.mesh.max.key.bytes: the edge's maximum


def _ragged_keys(seed):
    """Keys of every length the lane layout cares about — empty, under a
    lane, one lane, a lane and a byte, two lanes, the edge's maximum — of
    random bytes (half of them >= 0x80), in random order."""
    rng = np.random.default_rng(seed)
    keys = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (0, 1, 3, 4, 5, 8, MAX_KEY) for _ in range(24)]
    keys += [b"\x80", b"\xff" * 8, b"\x00" * 5, b"\xfe" * MAX_KEY]
    return [keys[i] for i in rng.permutation(len(keys))]


@pytest.mark.parametrize("consumers", [1, 4, 7, 8, 16])
def test_producer_routing_equals_every_partitioner(consumers):
    """The routing a producer stores (native FNV over its raw ragged key
    bytes, % W) is the numpy host partitioner's over the padded matrix,
    the device kernel's over the lanes and the scalar reference's; % D it
    is the destination the parent's plan computed from the lanes."""
    import jax.numpy as jnp
    from tez_tpu.ops.host_sort import fnv_rows_host
    from tez_tpu.ops.keycodec import lanes_to_matrix
    from tez_tpu.parallel.exchange import _fnv_lanes, fnv_bytes_host
    keys = _ragged_keys(consumers)
    batch = KVBatch.from_pairs([(k, b"v") for k in keys])
    coord = MeshExchangeCoordinator()
    # one of two producers: the exchange does not run, the span is kept
    coord.register_producer("parity/a->b", 0, 2, consumers, batch, 16, 4)
    lanes, klens, _, part = coord.edges["parity/a->b"].spans[0]
    W, D = consumers, coord.devices_for(consumers)
    assert part.dtype == np.uint8 and part.shape == (len(keys),)
    assert lanes.shape[1] * 4 == MAX_KEY
    host = fnv_rows_host(lanes_to_matrix(lanes), klens.astype(np.int64))
    np.testing.assert_array_equal(part, host % np.uint32(W))
    device = np.asarray(_fnv_lanes(jnp.asarray(lanes), jnp.asarray(klens)))
    np.testing.assert_array_equal(part, device % np.uint32(W))
    np.testing.assert_array_equal(
        part, [fnv_bytes_host(k) % W for k in keys])
    # the parent's plan: hashes % D as int64, from the rebuilt byte matrix
    parent_rdest = (host % np.uint32(D)).astype(np.int64)
    np.testing.assert_array_equal(part % D, parent_rdest)


def test_routing_dtype_follows_the_consumer_count():
    """The stored routing is the narrowest unsigned dtype holding W."""
    from tez_tpu.parallel.exchange import fnv_bytes_host
    batch = KVBatch.from_pairs([(b"k%d" % i, b"v") for i in range(50)])
    coord = MeshExchangeCoordinator()
    for W, dtype in ((255, np.uint8), (256, np.uint16), (70_000, np.uint32)):
        coord.register_producer(f"w{W}/a->b", 0, 2, W, batch, 8, 4)
        part = coord.edges[f"w{W}/a->b"].spans[0][3]
        assert part.dtype == dtype
        np.testing.assert_array_equal(
            part, [fnv_bytes_host(b"k%d" % i) % W for i in range(50)])
    with pytest.raises(ValueError, match="consumers"):
        coord.register_producer("w255/a->b", 1, 2, 4, batch, 8, 4)


def _wide_ranks(group, groups):
    """The parent's rank within a group: a stable argsort of int64 keys
    (a merge sort of whole keys) and a scatter."""
    group = group.astype(np.int64)
    counts = np.bincount(group, minlength=groups)
    order = np.argsort(group, kind="stable")
    ranks = np.empty(group.size, dtype=np.int64)
    starts = np.zeros(groups + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    ranks[order] = np.arange(group.size, dtype=np.int64) - \
        np.repeat(starts[:-1], counts)
    return ranks


def _parent_split(rdest, counts, per_round, D):
    """The parent's hot-key splitter, kept as the plain reference: every
    destination over the round budget keeps one budget's worth; the rest
    is re-homed into the others' headroom, least loaded first, in
    arrival-contiguous blocks in ascending device index."""
    hot = np.flatnonzero(counts > per_round)
    load = counts.astype(np.int64).copy()
    load[hot] = per_round
    orig = rdest.copy()
    for d in hot[np.argsort(-counts[hot], kind="stable")]:
        amounts = np.zeros(D, dtype=np.int64)
        amounts[d] = per_round
        remaining = int(counts[d]) - per_round
        for t in np.argsort(load, kind="stable"):
            if remaining == 0:
                break
            if t == d or load[t] >= per_round:
                continue
            take = min(int(per_round - load[t]), remaining)
            amounts[t] += take
            load[t] += take
            remaining -= take
        if remaining:
            base, extra = divmod(remaining, D)
            add = np.full(D, base, dtype=np.int64)
            add[:extra] += 1
            amounts += add
            load += add
        rdest[np.flatnonzero(orig == d)] = np.repeat(np.arange(D), amounts)
    return rdest


def _parent_device_inputs(spans, D, per_round, coded, split=False):
    """The parent's plan and pack, kept as the plain reference: narrow
    spans zero-padded to the widest, routing from the byte matrix rebuilt
    out of the lanes, int64 destinations (re-homed by the splitter where
    ``split``), a stable argsort for the ranks and two more a round
    (``qorder``, ``place``), five fresh zeroed arrays filled by fancy
    index.  Returns what each round hands the device program."""
    from tez_tpu.ops.device import _bucket
    from tez_tpu.ops.host_sort import fnv_rows_host
    from tez_tpu.ops.keycodec import lanes_to_matrix

    def _rows(arrays):
        width = max(a.shape[1] for a in arrays)
        return np.concatenate([
            np.pad(a, ((0, 0), (0, width - a.shape[1]))) for a in arrays])

    lanes = _rows([s[0] for s in spans])
    klens = np.concatenate([s[1] for s in spans])
    vwords = _rows([s[2] for s in spans])
    value_words = vwords.shape[1]
    hashes = fnv_rows_host(lanes_to_matrix(lanes), klens.astype(np.int64))
    rdest = (hashes % np.uint32(D)).astype(np.int64)
    counts = np.bincount(rdest, minlength=D)
    if split:
        rdest = _parent_split(rdest, counts, per_round, D)
        counts = np.bincount(rdest, minlength=D)
    ranks = _wide_ranks(rdest, D)
    rounds = []
    for r, _ in enumerate(plan_rounds(counts, per_round, D)):
        lo = r * per_round
        sel = np.flatnonzero((ranks >= lo) & (ranks < lo + per_round))
        rows_idx, dests_all, rtag = sel, rdest[sel], None
        if coded:
            rows_idx = np.concatenate([sel, sel])
            rtag = np.concatenate([dests_all, dests_all]).astype(np.uint32)
            dests_all = np.concatenate([dests_all, (dests_all + 1) % D])
        qc = np.bincount(dests_all, minlength=D)
        lrank = _wide_ranks(dests_all, D)                  # ``qorder``
        senders = lrank // np.maximum(1, -(-qc // D))[dests_all]
        N = _bucket(int(np.bincount(senders, minlength=D).max()))
        pos = senders * N + _wide_ranks(senders, D)        # ``place``
        vw = value_words + (1 if coded else 0)
        r_lanes = np.zeros((D * N, lanes.shape[1]), np.uint32)
        r_klens = np.zeros(D * N, np.uint32)
        r_vwords = np.zeros((D * N, vw), np.uint32)
        r_valid = np.zeros(D * N, bool)
        r_dests = np.zeros(D * N, np.uint32)
        r_lanes[pos] = lanes[rows_idx]
        r_klens[pos] = klens[rows_idx]
        r_vwords[pos, :value_words] = vwords[rows_idx]
        if coded:
            r_vwords[pos, value_words] = rtag
        r_valid[pos] = True
        r_dests[pos] = dests_all.astype(np.uint32)
        rounds.append((r_lanes, r_klens, r_vwords, r_valid, r_dests))
    return rounds


def _mixed_width_spans(seed):
    """Four producers whose keys and values differ in width: 6-byte keys
    with 5-byte values, then ragged keys up to 13 bytes with values up to
    11, so the spans come with 2 and 4 key lanes, 2 and 3 value words."""
    rng = np.random.default_rng(seed)
    spans = _corpus(1_200, 2, 8, hot_frac=0.3, hot_part=2, seed=seed)
    for _ in range(2):
        spans.append(KVBatch.from_pairs([
            (rng.integers(0, 256, rng.integers(0, 14), dtype=np.uint8)
             .tobytes(),
             rng.integers(0, 256, rng.integers(0, 12), dtype=np.uint8)
             .tobytes()) for _ in range(700)]))
    return spans


#: case -> (consumers, devices forced (None: all eight), per_round,
#: coded, split_after, exchanges on the edge's suffix, spans)
_INPUT_CASES = {
    # a skewed exchange of several rounds, plain and coded
    "off": (8, None, 500, "off", 0, 1, None),
    "r2": (8, None, 500, "r2", 0, 1, None),
    # the splitter engages on the suffix's second exchange: one round of
    # re-homed rows
    "splitter": (8, None, 1_000, "off", 2, 2, None),
    "w4_single_round": (4, None, 1 << 20, "off", 0, 1, None),
    # two consumer partitions a device
    "w8_over_d4": (8, 4, 700, "off", 0, 1, None),
    "mixed_widths": (8, None, 150, "off", 0, 1, _mixed_width_spans),
}


@pytest.mark.parametrize("case", list(_INPUT_CASES))
def test_device_inputs_equal_the_parents_plan(case, monkeypatch):
    """Every array handed to the device program — so every rank, round
    rank and position behind it, and every zero around the rows — is what
    the parent's formulas give for the same spans: over several rounds,
    behind the splitter, in a single round on four devices, with two
    consumers a device, and for producers of different widths."""
    consumers, devices, per_round, coded, split_after, exchanges, make = \
        _INPUT_CASES[case]
    spans = make(9) if make else \
        _corpus(5_000, 4, consumers, hot_frac=0.45, hot_part=5 % consumers,
                seed=9)
    coord = MeshExchangeCoordinator(max_rows_per_round=per_round,
                                    split_after=split_after)
    if devices:
        monkeypatch.setattr(coord, "devices_for", lambda w: devices)
    D = coord.devices_for(consumers)
    handed = []
    compiled_fn = coord._compiled_fn

    def _spy(*args, **kw):
        fn = compiled_fn(*args, **kw)

        def _call(*arrays):
            handed.append(tuple(np.array(a) for a in arrays))
            return fn(*arrays)
        return _call

    monkeypatch.setattr(coord, "_compiled_fn", _spy)
    golden = _golden(spans, consumers)
    for x in range(exchanges):
        del handed[:]
        edge = f"dag{x}/inputs-{case}/a->b"
        out = _run(coord, spans, edge, consumers, engine="auto", coded=coded)
        assert _sig(out) == golden
        # the splitter engages once the suffix's streak reaches split_after
        split = 0 < split_after <= x + 1
        assert (coord.partition_splits > 0) == split
        stored = [coord.edges[edge].spans[i] for i in range(len(spans))]
        expected = _parent_device_inputs(stored, D, per_round, coded == "r2",
                                         split=split)
        assert len(handed) == len(expected)
        if case in ("splitter", "w4_single_round"):
            assert len(handed) == (1 if split or case != "splitter" else 3)
        else:
            assert len(handed) > 2                    # multi-round
        for got, want in zip(handed, expected):
            for g, w in zip(got, want):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(g, w)


def test_arrival_ranks_equal_the_wide_sort():
    """``arrival_ranks`` on a narrow key is the parent's int64 stable
    argsort and scatter, for one group, many groups and empty groups."""
    from tez_tpu.parallel.coordinator import arrival_ranks
    rng = np.random.default_rng(3)
    for groups, dtype in ((1, np.uint8), (8, np.uint8), (255, np.uint8),
                          (300, np.uint16)):
        group = rng.zipf(1.3, 20_000) % groups
        group[group == groups // 2] = 0            # an empty group
        got = arrival_ranks(group.astype(dtype),
                            np.bincount(group, minlength=groups))
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, _wide_ranks(group, groups))
    assert arrival_ranks(np.zeros(0, np.uint8), np.zeros(4, np.int64)).size == 0


# ----------------------------------------------- the native row passes

def _encode_reference(batch, key_width, value_width):
    """The numpy forms the native encode replaced: ``pad_to_matrix`` +
    ``matrix_to_lanes`` for the keys; the same for the values behind a
    first word that holds the value's length (the parent's
    ``_encode_values``)."""
    from tez_tpu.ops.keycodec import matrix_to_lanes, pad_to_matrix
    kmat, klens = pad_to_matrix(batch.key_bytes, batch.key_offsets,
                                key_width)
    vmat, vlens = pad_to_matrix(batch.val_bytes, batch.val_offsets,
                                value_width)
    vwords = np.concatenate([vlens.astype(np.uint32)[:, None],
                             matrix_to_lanes(vmat).astype(np.uint32)], axis=1)
    return matrix_to_lanes(kmat), klens.astype(np.uint32), vwords


def _decode_rows(lanes, lengths, values, valid):
    """The parent's decode, kept as the plain reference: a byte matrix out
    of the lanes, an n x width boolean mask, a boolean extraction."""
    from tez_tpu.ops.keycodec import lanes_to_matrix
    sel = np.flatnonzero(valid)
    if sel.size == 0:
        return KVBatch.empty()
    lanes = lanes[sel]
    klens = lengths[sel].astype(np.int64)
    vwords = values[sel]
    n, L = lanes.shape
    kmat = lanes_to_matrix(lanes)
    key_bytes = kmat[np.arange(L * 4)[None, :] < klens[:, None]]
    key_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(klens, out=key_offsets[1:])
    vlens = vwords[:, 0].astype(np.int64)
    vmat = lanes_to_matrix(np.ascontiguousarray(vwords[:, 1:]))
    val_bytes = vmat[np.arange(vmat.shape[1])[None, :] < vlens[:, None]]
    val_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(vlens, out=val_offsets[1:])
    return KVBatch(key_bytes, key_offsets, val_bytes, val_offsets)


def _pass_batch(case):
    """(batch, key width, value width) of a row-pass case."""
    rng = np.random.default_rng(len(case))

    def _bytes(n):
        return rng.integers(0, 256, n, dtype=np.uint8).tobytes()

    if case == "fixed8":
        # fixed 8-byte keys, 4-byte values; 1,003 rows: no multiple of any
        # thread count
        return KVBatch.from_pairs(
            [(_bytes(8), _bytes(4)) for _ in range(1_003)]), 16, 8
    if case == "ragged":
        # keys of 0-16 bytes (an empty key, keys of exactly the width),
        # values of 0-12 bytes, high bytes among them
        pairs = [(_bytes(int(rng.integers(0, 17))),
                  _bytes(int(rng.integers(0, 13)))) for _ in range(2_111)]
        pairs += [(b"", b""), (b"\xff" * 16, b"\x80" * 12), (b"k", b"")]
        return KVBatch.from_pairs(pairs), 16, 12
    if case == "odd_widths":
        # widths that are no whole words: 6-byte keys, 5-byte values
        return KVBatch.from_pairs(
            [(_bytes(6), _bytes(5)) for _ in range(257)]), 6, 5
    if case == "single_row":
        return KVBatch.from_pairs([(b"key", b"value")]), 4, 8
    assert case == "empty"
    return KVBatch.empty(), 16, 4


_PASS_CASES = ["fixed8", "ragged", "odd_widths", "single_row", "empty"]


@pytest.mark.parametrize("case", _PASS_CASES)
def test_native_encode_equals_the_numpy_forms(case):
    """``exchange_encode_native`` gives byte for byte what ``pad_to_matrix``
    + ``matrix_to_lanes`` + the parent's value encode give: lanes, true
    lengths, the length word, zero padding."""
    from tez_tpu.ops.native import exchange_encode_native
    batch, key_width, value_width = _pass_batch(case)
    got = exchange_encode_native(batch.key_bytes, batch.key_offsets,
                                 batch.val_bytes, batch.val_offsets,
                                 key_width, value_width)
    want = _encode_reference(batch, key_width, value_width)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.uint32
        assert g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("valid", ["all", "some", "none"])
@pytest.mark.parametrize("case", _PASS_CASES)
def test_native_decode_equals_decode_rows(case, valid):
    """``exchange_decode_native`` == the parent's ``_decode_rows`` on the
    same shard: all rows valid, a scattered half, none; and through the
    coordinator's ``_decode_shard`` with a further column in the value
    words (the coded edge's routing tag), which is skipped."""
    from tez_tpu.parallel.coordinator import _decode_shard
    batch, key_width, value_width = _pass_batch(case)
    lanes, klens, vwords = _encode_reference(batch, key_width, value_width)
    n = lanes.shape[0]
    keep = {"all": np.ones(n, bool), "none": np.zeros(n, bool),
            "some": np.random.default_rng(5).random(n) < 0.5}[valid]
    want = _decode_rows(lanes, klens, vwords, keep)
    tagged = np.concatenate(
        [vwords, np.full((n, 1), 0xDEADBEEF, np.uint32)], axis=1)
    for got in (_decode_shard(lanes, klens, vwords, keep),
                _decode_shard(lanes, klens, tagged, keep,
                              vwords.shape[1] - 1)):
        for name in ("key_bytes", "key_offsets", "val_bytes", "val_offsets"):
            g, w = getattr(got, name), getattr(want, name)
            assert g.dtype == w.dtype, name
            np.testing.assert_array_equal(g, w, err_msg=name)
    if valid == "all":
        # and back: the decoded batch is the producer's
        np.testing.assert_array_equal(want.key_bytes, batch.key_bytes)
        np.testing.assert_array_equal(want.val_offsets, batch.val_offsets)


@pytest.mark.parametrize("dest_dtype", [np.uint8, np.uint16])
def test_native_placement_writes_every_slot_of_pooled_memory(dest_dtype):
    """A round large enough that its five arrays come from the host pool,
    whose blocks hold whatever the last user left: the placement still
    equals five zeroed arrays filled by the parent's index formulas — the
    tails past each sender's rows and the lanes of narrower spans are
    written too."""
    from tez_tpu.ops import hostpool
    from tez_tpu.ops.device import _bucket
    from tez_tpu.parallel.coordinator import (_place_round_native,
                                              _row_chunks)
    from tez_tpu.ops.native import exchange_dest_hist_native
    rng = np.random.default_rng(11)
    D, L, VW, per_round = 4, 4, 3, 30_000
    sizes, widths = (40_001, 0, 33_333, 17), ((4, 3), (4, 3), (2, 2), (3, 1))
    spans = [(rng.integers(1, 1 << 32, (n, l), dtype=np.uint32),
              rng.integers(0, 17, n).astype(np.uint32),
              rng.integers(1, 1 << 32, (n, v), dtype=np.uint32),
              rng.choice(D, n, p=[0.55, 0.15, 0.15, 0.15]).astype(dest_dtype))
             for n, (l, v) in zip(sizes, widths)]
    rdest = np.concatenate([s[3] for s in spans])
    counts = np.bincount(rdest, minlength=D)
    # dirty blocks of the sizes the placement will ask for
    N = _bucket(per_round)
    for words in (L, VW, 1):
        dirty = hostpool.empty(D * N * words, np.uint32)
        dirty[:] = 0xFFFFFFFF
        del dirty
    chunks, bounds = _row_chunks(spans)
    assert len(chunks) > 4 and bounds[-1] == rdest.size
    hist = exchange_dest_hist_native(rdest, bounds, D)
    np.testing.assert_array_equal(hist.sum(axis=0), counts)
    lanes = np.concatenate([np.pad(s[0], ((0, 0), (0, L - s[0].shape[1])))
                            for s in spans])
    klens = np.concatenate([s[1] for s in spans])
    vwords = np.concatenate([np.pad(s[2], ((0, 0), (0, VW - s[2].shape[1])))
                             for s in spans])
    ranks = _wide_ranks(rdest, D)
    plan = plan_rounds(counts, per_round, D)
    assert len(plan) == 2
    for r, (quota, _) in enumerate(plan):
        lo = r * per_round
        got, N = _place_round_native(chunks, bounds, rdest, hist, lo,
                                     per_round, quota, L, VW)
        sel = np.flatnonzero((ranks >= lo) & (ranks < lo + per_round))
        dests = rdest[sel].astype(np.int64)
        senders = (ranks[sel] - lo) // np.maximum(1, -(-quota // D))[dests]
        assert N == _bucket(int(np.bincount(senders, minlength=D).max()))
        pos = senders * N + _wide_ranks(senders, D)
        want = (np.zeros((D * N, L), np.uint32), np.zeros(D * N, np.uint32),
                np.zeros((D * N, VW), np.uint32), np.zeros(D * N, bool),
                np.zeros(D * N, np.uint32))
        for w, rows in zip(want, (lanes[sel], klens[sel], vwords[sel], True,
                                  dests)):
            w[pos] = rows
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)


def test_native_placement_counter_follows_the_edge():
    """``exchange.rows.placed.native`` is ``exchange.rows.sent`` on a plain
    edge and 0 on a coded one and under legacy sizing, which keep the
    numpy placement."""
    from tez_tpu.common.counters import MESH_EXCHANGE_GROUP, TezCounters
    consumers = 8
    spans = _corpus(3_000, 4, consumers, hot_frac=0.3, hot_part=1, seed=12)
    golden = _golden(spans, consumers)

    def _counts(coord, edge, **kw):
        counters = TezCounters()
        out = _run(coord, spans, edge, consumers, counters=counters, **kw)
        assert _sig(out) == golden
        g = counters.group(MESH_EXCHANGE_GROUP)
        return (g.find_counter("exchange.rows.sent").value,
                g.find_counter("exchange.rows.placed.native").value)

    assert _counts(MeshExchangeCoordinator(max_rows_per_round=700),
                   "plain/a->b") == (3_000, 3_000)
    assert _counts(MeshExchangeCoordinator(max_rows_per_round=700),
                   "coded/a->b", coded="r2") == (3_000, 0)
    assert _counts(MeshExchangeCoordinator(legacy_sizing=True),
                   "legacy/a->b") == (3_000, 0)


def test_execute_wrapped_one_span_a_call_keeps_working():
    """The benchmark's ``exchange_left_out`` fault wraps ``_execute(self,
    st)``: it swaps ``st.spans`` for one producer's span a call and reads
    the result by consumer index (the wrapper below is that fault's, copied;
    the benchmark's file is not imported).  Each consumer then gets only
    its own producer's rows for it, through the native passes."""
    consumers = 4
    spans = _corpus(2_000, consumers, consumers, hot_frac=0.2, hot_part=3,
                    seed=13)
    execute = MeshExchangeCoordinator._execute

    def kept_local(self, st):
        kept = dict(st.spans)
        out = []
        try:
            for c in range(st.num_consumers):
                st.spans = {c: kept[c]} if c in kept else {}
                out.append(execute(self, st)[c])
        finally:
            st.spans = kept
        return out

    MeshExchangeCoordinator._execute = kept_local
    try:
        out = _run(MeshExchangeCoordinator(), spans, "leftout/a->b",
                   consumers)
    finally:
        MeshExchangeCoordinator._execute = execute
    for c in range(consumers):
        alone = _run(MeshExchangeCoordinator(legacy_sizing=True), [spans[c]],
                     f"alone{c}/a->b", consumers, engine="padded")
        assert _sig([out[c]]) == _sig([alone[c]])
        assert out[c].num_records > 0
    assert sum(b.num_records for b in out) < 2_000
