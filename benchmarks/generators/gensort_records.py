"""gensort-style records, their plain reference, and the comparison that
decides `correct` for TeraSort.

The deployment is sortbenchmark.org's GraySort record as Hadoop's
examples/terasort sorts it (TeraGen -> TeraSort -> TeraValidate): 100 bytes,
a 10-byte uniformly random binary key, 90 bytes of payload.  Keys are drawn
from ``numpy.random.Generator(--seed)``; the payload is laid out in
``gensort``'s manner and is NOT byte-identical to it (the configuration
lists it under ``assumed``):

    [0:10]   key            [10:12]  00 11
    [12:44]  the record's ordinal, 32 hex digits
    [44:48]  88 99 AA BB    [48:96]  12 hex digits of the key, each 4 times
    [96:100] CC DD EE FF

Part file p holds the ordinals [p*n/parts, (p+1)*n/parts), as each TeraGen
mapper writes one range of row ids.  Nothing here imports the program under
test.

The reference is numpy's own sort of the records by key: the key packed into
big-endian integer columns and ``np.lexsort`` (an ``S10`` view would strip
trailing NULs).  TeraValidate's guarantees and a little more, which the
comparison holds every committed output to, all exact, each with the limit 0:

* the same records: the multiset of 100-byte records out is the multiset in
  (``records_lost_or_invented``: ordinals missing, records that are no input
  record, copies beyond the first);
* sorted parts: no key falls inside a part file (``records_out_of_order``);
* total order: no part's first key is below the previous part's last
  (``partitions_out_of_order``); the order among equal keys is free;
* whole records: every part's size is a multiple of 100
  (``records_malformed``);
* committed once: ``_SUCCESS`` there and no temporary tree left
  (``commits_missing``).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

RECORD = 100
KEY = 10
HEX = np.frombuffer(b"0123456789ABCDEF", dtype=np.uint8)
#: each number compared, with its limit: all exact comparisons
LIMITS = {"records_lost_or_invented": 0, "records_out_of_order": 0,
          "partitions_out_of_order": 0, "records_malformed": 0,
          "commits_missing": 0}
#: guarantee broken -> what reference_output() does to the reference's output
CONTROLS = ("record_dropped", "payload_swapped", "unordered",
            "hash_partitioned", "committed_twice")


def _records(rng: np.random.Generator, n: int) -> np.ndarray:
    """(n, 100) bytes: the records of ordinals 0..n-1."""
    out = np.empty((n, RECORD), dtype=np.uint8)
    out[:, :KEY] = rng.integers(0, 256, (n, KEY), dtype=np.uint8)
    out[:, 10:12] = (0x00, 0x11)
    ordinal = np.arange(n, dtype=np.uint64)
    out[:, 12:28] = HEX[0]
    for k in range(16):
        out[:, 43 - k] = HEX[(ordinal >> np.uint64(4 * k)) & np.uint64(15)]
    out[:, 44:48] = (0x88, 0x99, 0xAA, 0xBB)
    for j in range(12):
        nibble = (out[:, j // 2] >> (4 if j % 2 == 0 else 0)) & 15
        out[:, 48 + 4 * j:52 + 4 * j] = HEX[nibble][:, None]
    out[:, 96:] = (0xCC, 0xDD, 0xEE, 0xFF)
    return out


def _key_columns(records: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The 10-byte keys as (high u64, low u16) columns that order as the
    bytes do."""
    hi = np.ascontiguousarray(records[:, :8]).view(">u8")[:, 0]
    lo = np.ascontiguousarray(records[:, 8:KEY]).view(">u2")[:, 0]
    return hi.astype(np.uint64), lo.astype(np.uint16)


def _sort_order(records: np.ndarray) -> np.ndarray:
    hi, lo = _key_columns(records)
    return np.lexsort((lo, hi))


def _count_records(params: Dict[str, Any]) -> int:
    """The traffic mix's ``records``; a rehearsal's ``corpus_mib`` takes its
    place.  A whole number to each part."""
    parts = int(params["parts"])
    if "corpus_mib" in params:
        n = (int(params["corpus_mib"]) << 20) // RECORD
    else:
        n = int(params["records"])
    return n // parts * parts


def generate(dest: str, params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Write the records as ``params["parts"]`` files under `dest`.  Returns
    inputs, input_bytes, records and the reference."""
    if int(params["record_bytes"]) != RECORD or int(params["key_bytes"]) != KEY:
        raise ValueError("gensort records are 100 bytes with a 10-byte key")
    n, parts = _count_records(params), int(params["parts"])
    if n < parts:
        raise ValueError(f"{n} records cannot fill {parts} parts")
    records = _records(np.random.default_rng(int(seed)), n)
    os.makedirs(dest)
    for p in range(parts):
        records[p * n // parts:(p + 1) * n // parts].tofile(
            os.path.join(dest, f"part-{p:05d}"))
    order = _sort_order(records)
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return {"inputs": [dest], "input_bytes": n * RECORD, "records": n,
            "reference": {"sorted": records[order], "rank": rank,
                          "partitions": int(params["partitions"])}}


def _ordinals(records: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(ordinal, valid) parsed from the 32 hex digits of each record."""
    digits = records[:, 12:44].astype(np.int64)
    value = np.where(digits >= 65, digits - 55, digits - 48)
    valid = (((digits >= 48) & (digits <= 57)) |
             ((digits >= 65) & (digits <= 70))).all(axis=1)
    valid &= (value[:, :16] == 0).all(axis=1)
    ordinal = np.zeros(len(records), dtype=np.int64)
    for k in range(16, 32):
        ordinal = ordinal * 16 + np.where(valid, value[:, k], 0)
    valid &= ordinal < n
    return np.where(valid, ordinal, 0), valid


def _lost_or_invented(out: np.ndarray, reference: Dict[str, Any]) -> int:
    """Records out that are no input record, copies beyond the first, and
    input records that never came out."""
    golden = reference["sorted"]
    if out.shape == golden.shape and np.array_equal(out, golden):
        return 0        # the common case: one pass, no gather
    # equal keys may stand in another order, or something is wrong: every
    # record out has to be the input record of the ordinal it carries
    ordinal, valid = _ordinals(out, len(golden))
    same = valid.copy()
    same[valid] = (out[valid] == golden[reference["rank"][ordinal[valid]]]
                   ).all(axis=1)
    seen = np.bincount(ordinal[same], minlength=len(golden))
    return int((~same).sum() + (seen == 0).sum() +
               np.maximum(seen - 1, 0).sum())


def compare(out_dir: str, reference: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of one committed output directory, each held to LIMITS."""
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    parts: List[np.ndarray] = []
    malformed = disorder = overlap = 0
    last: Optional[Tuple[int, int]] = None
    for name in names:
        if name.startswith(("_", ".")):
            continue
        raw = np.fromfile(os.path.join(out_dir, name), dtype=np.uint8)
        if len(raw) % RECORD:
            malformed += 1
        part = raw[:len(raw) // RECORD * RECORD].reshape(-1, RECORD)
        parts.append(part)
        if not len(part):
            continue
        hi, lo = _key_columns(part)
        disorder += int(((hi[1:] < hi[:-1]) |
                         ((hi[1:] == hi[:-1]) & (lo[1:] < lo[:-1]))).sum())
        first = (int(hi[0]), int(lo[0]))
        if last is not None and first < last:
            overlap += 1
        last = (int(hi[-1]), int(lo[-1]))
    out = np.concatenate(parts) if parts else np.zeros((0, RECORD), np.uint8)
    committed = "_SUCCESS" in names and not any(
        n.startswith("_temporary") for n in names)
    return {"records_lost_or_invented": _lost_or_invented(out, reference),
            "records_out_of_order": disorder,
            "partitions_out_of_order": overlap,
            "records_malformed": malformed,
            "commits_missing": 0 if committed else 1}


def reference_output(dest: str, reference: Dict[str, Any],
                     broken: Optional[str] = None) -> None:
    """The plain reference's own committed output: the sorted records cut
    into ``partitions`` part files of consecutive key ranges.  `broken`
    names the guarantee a control breaks:

    * ``record_dropped``: one record missing, as a lost spill block would be;
    * ``payload_swapped``: two records exchange payloads: every key right
      and in order, the gather that moves the other 90 bytes wrong;
    * ``unordered``: every part holds its own key range, first and last key
      in place, the records between them in input order: the sort left out;
    * ``hash_partitioned``: every part sorted, the ranges overlapping: what
      the hash partitioner in the total-order one's place would give;
    * ``committed_twice``: one more part file repeats the last part's
      records, as a re-run task committed beside the first attempt would.
    """
    golden = reference["sorted"]
    n, k = len(golden), reference["partitions"]
    bounds = [p * n // k for p in range(k + 1)]
    pieces = [golden[bounds[p]:bounds[p + 1]] for p in range(k)]
    if broken == "record_dropped":
        pieces[k // 2] = np.delete(pieces[k // 2], len(pieces[k // 2]) // 2,
                                   axis=0)
    elif broken == "payload_swapped":
        piece = pieces[k // 2].copy()
        a, b = len(piece) // 3, 2 * len(piece) // 3
        piece[[a, b], KEY:] = piece[[b, a], KEY:]
        pieces[k // 2] = piece
    elif broken == "unordered":
        for p, piece in enumerate(pieces):
            ordinal, _valid = _ordinals(piece[1:-1], n)
            pieces[p] = np.concatenate([
                piece[:1], piece[1:-1][np.argsort(ordinal, kind="stable")],
                piece[-1:]])
    elif broken == "hash_partitioned":
        ordinal, _valid = _ordinals(golden, n)
        pieces = [golden[ordinal % k == p] for p in range(k)]
    elif broken == "committed_twice":
        pieces.append(pieces[-1])
    elif broken is not None:
        raise ValueError(f"no control {broken!r} (has: {CONTROLS})")
    os.makedirs(dest)
    for p, piece in enumerate(pieces):
        piece.tofile(os.path.join(dest, f"part-{p:05d}"))
    with open(os.path.join(dest, "_SUCCESS"), "w"):
        pass
