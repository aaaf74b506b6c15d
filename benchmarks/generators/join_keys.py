"""JoinDataGen's keys, their plain reference, and the comparison that
decides `correct` for the sort-merge join.

The deployment is Apache Tez's tez-examples join set: JoinDataGen.java makes
two inputs and the expected result, SortMergeJoinExample.java joins them,
JoinValidate.java checks.  A key is 13 random letters, ``_``, the generating
task's index, ``_``, that task's running count: lower case where the key is
written to both sides, upper case where to one side only; every key is
unique; every second key of the smaller ("hash", here ``right``) side is
also on the larger ("stream", here ``left``) side.  With 4 generating tasks
and up to 1,125,000 keys a task a key is 17 to 23 bytes.

Task k writes ``left/part-0000k`` and ``right/part-0000k``, one key a line:

    count c in [0, left/parts)                 left; lower case and on the
                                               right too at every (left /
                                               matches)th count
    count c in [left/parts, left/parts + ...)  right only, upper case

and the right part file alternates a key of both sides with a key of its
own.  The letters are drawn from ``numpy.random.Generator(--seed)``
(upstream: java.util.Random, unseeded).  Nothing here imports the program
under test.

The reference is the set of lower-case keys, kept as they are written --
JoinDataGen's third output -- so nothing is sorted or joined to make it.
The comparison reads every part file of a committed output and holds it to
the configuration's guarantees, all exact, each with the limit 0:

* matches: the set of keys out is the expected set (``keys_missing``:
  expected and absent; ``keys_invented``: present and not expected);
* once: no key on more than one line of the whole output
  (``keys_repeated``);
* whole lines: every line is ``<key>\\t1`` (``lines_malformed``);
* committed once: ``_SUCCESS`` there and no temporary tree left
  (``commits_missing``).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

LETTERS = 13
LINE = 24        # the longest key (23 bytes) and its newline
TAIL = b"\t1"
#: each number compared, with its limit: all exact comparisons
LIMITS = {"keys_missing": 0, "keys_invented": 0, "keys_repeated": 0,
          "lines_malformed": 0, "commits_missing": 0}
#: guarantee broken -> what reference_output() does to the reference's output
CONTROLS = ("match_dropped", "left_side_only", "committed_twice")


def _lines(rng: np.random.Generator, task: int, counts: np.ndarray,
           lower: np.ndarray) -> np.ndarray:
    """(n, LINE) bytes: a key and its newline a row, left-aligned, NUL
    behind (no key holds one)."""
    n = len(counts)
    out = np.zeros((n, LINE), dtype=np.uint8)
    out[:, :LETTERS] = rng.integers(0, 26, (n, LETTERS), dtype=np.uint8) + \
        np.where(lower, ord("a"), ord("A"))[:, None].astype(np.uint8)
    mid = f"_{task}_".encode()
    at = LETTERS + len(mid)
    out[:, LETTERS:at] = np.frombuffer(mid, dtype=np.uint8)
    digits = np.ones(n, dtype=np.int64)
    for d in range(1, 8):
        digits += counts >= 10 ** d
    if at + int(digits.max(initial=1)) >= LINE:
        raise ValueError(f"task {task} counts to {int(counts.max())}: the "
                         f"key would pass {LINE - 1} bytes")
    for p in range(int(digits.max(initial=1))):
        has = p < digits
        power = 10 ** np.where(has, digits - 1 - p, 0)
        out[:, at + p] = np.where(has, ord("0") + counts // power % 10, 0)
    out[np.arange(n), at + digits] = ord("\n")
    return out


def _sizes(params: Dict[str, Any]) -> Tuple[int, int]:
    """(left keys, right keys) a part file.  A rehearsal's ``corpus_mib``
    takes the place of the traffic mix's sizes, two to one as they are."""
    parts = int(params["parts"])
    every = int(params["overlap_every"])
    if "corpus_mib" in params:
        total = (int(params["corpus_mib"]) << 20) // 22
        left, right = total * 2 // 3, total // 3
    else:
        left, right = int(params["left_keys"]), int(params["right_keys"])
    per_right = right // parts // every * every
    return left // parts, per_right


def generate(dest: str, params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Write both sides as ``params["parts"]`` files each under `dest`.
    Returns inputs, input_bytes, records (keys of both sides) and the
    reference."""
    if int(params["key_letters"]) != LETTERS:
        raise ValueError(f"JoinDataGen's keys start with {LETTERS} letters")
    parts, every = int(params["parts"]), int(params["overlap_every"])
    per_left, per_right = _sizes(params)
    per_both = per_right // every
    if not 1 <= per_both <= per_left:
        raise ValueError(f"{per_left} left and {per_right} right keys a "
                         f"part: every {every}th right key cannot be on "
                         f"the left")
    stride = per_left // per_both
    rng = np.random.default_rng(int(seed))
    dirs = {side: os.path.join(dest, side) for side in ("left", "right")}
    for d in dirs.values():
        os.makedirs(d)
    expected: List[bytes] = []
    input_bytes = 0
    for task in range(parts):
        counts = np.arange(per_left + per_right - per_both, dtype=np.int64)
        both = (counts % stride == 0) & (counts < stride * per_both)
        lines = _lines(rng, task, counts, both)
        own = np.flatnonzero(counts >= per_left)
        # a key of both sides, then every - 1 of the right's own
        right_rows = np.empty(per_right, dtype=np.int64)
        right_rows[0::every] = np.flatnonzero(both)
        for j in range(1, every):
            right_rows[j::every] = own[j - 1::every - 1]
        for side, rows in (("left", lines[:per_left]),
                           ("right", lines[right_rows])):
            data = rows[rows != 0]
            data.tofile(os.path.join(dirs[side], f"part-{task:05d}"))
            input_bytes += len(data)
        expected.extend(bytes(lines[both][lines[both] != 0]).split())
    return {"inputs": [dirs["left"], dirs["right"]],
            "input_bytes": input_bytes,
            "records": parts * (per_left + per_right),
            "reference": {"expected": frozenset(expected),
                          "left_dir": dirs["left"], "partitions": parts}}


def _part_files(out_dir: str) -> Tuple[List[str], List[str]]:
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    return names, [os.path.join(out_dir, n) for n in names
                   if not n.startswith(("_", "."))]


def compare(out_dir: str, reference: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of one committed output directory, each held to LIMITS."""
    names, files = _part_files(out_dir)
    keys: List[bytes] = []
    malformed = 0
    for path in files:
        with open(path, "rb") as fh:
            lines = fh.read().split(b"\n")
        if lines.pop():                   # bytes behind the last newline
            malformed += 1
        whole = [ln[:-len(TAIL)] for ln in lines if ln.endswith(TAIL)]
        malformed += len(lines) - len(whole)
        keys.extend(whole)
    got = set(keys)
    expected = reference["expected"]
    committed = "_SUCCESS" in names and not any(
        n.startswith("_temporary") for n in names)
    return {"keys_missing": len(expected - got),
            "keys_invented": len(got - expected),
            "keys_repeated": len(keys) - len(got),
            "lines_malformed": malformed,
            "commits_missing": 0 if committed else 1}


def reference_output(dest: str, reference: Dict[str, Any],
                     broken: Optional[str] = None) -> None:
    """The plain reference's own committed output: the expected keys as
    ``<key>\\t1`` lines over ``partitions`` part files.  `broken` names the
    guarantee a control breaks:

    * ``match_dropped``: one expected key left out, as a match lost at a
      block's edge would be;
    * ``left_side_only``: the larger side's keys as the answer: what a
      joiner that ignores one input gives;
    * ``committed_twice``: one more part file repeats the last part's
      lines, as a re-run task committed beside the first attempt would.
    """
    keys = list(reference["expected"])
    if broken == "match_dropped":
        del keys[len(keys) // 2]
    elif broken == "left_side_only":
        keys = []
        for path in _part_files(reference["left_dir"])[1]:
            with open(path, "rb") as fh:
                keys.extend(fh.read().split())
    elif broken not in (None, "committed_twice"):
        raise ValueError(f"no control {broken!r} (has: {CONTROLS})")
    k = reference["partitions"]
    pieces = [keys[p::k] for p in range(k)]
    if broken == "committed_twice":
        pieces.append(pieces[-1])
    os.makedirs(dest)
    for p, piece in enumerate(pieces):
        with open(os.path.join(dest, f"part-{p:05d}"), "wb") as fh:
            fh.write(b"".join(key + TAIL + b"\n" for key in piece))
    with open(os.path.join(dest, "_SUCCESS"), "w"):
        pass
