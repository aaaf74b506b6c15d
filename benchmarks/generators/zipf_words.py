"""Word corpus, its plain reference, and the comparison that decides `correct`.

The deployment is Apache Tez's OrderedWordCount as this repo's BASELINE.md
"100 GB protocol, stage 1" sizes it: words ``w<id>`` of one fixed width drawn
zipf(a) (or uniform) over a vocabulary, 8192 words to a line.  Everything is
made from the traffic mix's ``data_seed`` and ``--seed`` (see generate());
nothing here imports the program under test.

The reference is the generator's own ``bincount`` of the ids it drew: the
counts the word count has to produce.  OrderedWordCount's guarantees, which
the comparison holds every committed output to, each with the limit 0:

* exact: every word's count equals the reference's (``words_wrong_count``);
* once: every word on exactly one line (``words_repeated``) -- summing the
  lines of a word, as tez_tpu/tools/spill_bench.verify_output does, would pass
  an output whose exchange or final merge had been left out;
* ordered: lines in order of count, the second ordered edge's whole purpose
  (``lines_out_of_order``); the order among equal counts is free;
* committed once: ``_SUCCESS`` there and no temporary tree left
  (``commits_missing``); nothing unparseable (``lines_malformed``).

Copied and cut from tez_tpu/tools/spill_bench.py make_corpus / verify_output
(PERF.md lists the original for deletion): written as byte matrices instead
of numpy string arrays (~0.05 s/MB here instead of 0.12 s/MB), and with an
exact number of words so that every seed gives the same number of rows.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

WORDS_PER_LINE = 8192
#: each number compared, with its limit: all exact comparisons
LIMITS = {"words_wrong_count": 0, "words_repeated": 0,
          "lines_out_of_order": 0, "lines_malformed": 0,
          "commits_missing": 0}
#: guarantee broken -> what control_output() does to the reference's output
CONTROLS = ("approximate_counts", "unordered", "committed_twice")


def _width(vocab: int) -> int:
    return len(str(vocab - 1))


def _draw(rng: np.random.Generator, n: int, params: Dict[str, Any]
          ) -> np.ndarray:
    vocab = int(params["vocab"])
    if params.get("distribution", "zipf") == "uniform":
        return rng.integers(0, vocab, n, dtype=np.int64)
    return rng.zipf(float(params["zipf_a"]), n).astype(np.int64) % vocab


def _word_bytes(ids: np.ndarray, width: int, first_word: int) -> np.ndarray:
    """(n, width + 2) bytes: 'w', the id zero-filled, then a space, or a
    newline after every WORDS_PER_LINE-th word of the corpus."""
    out = np.empty((len(ids), width + 2), dtype=np.uint8)
    out[:, 0] = ord("w")
    rest = ids.copy()
    for k in range(width, 0, -1):
        rest, digit = np.divmod(rest, 10)
        out[:, k] = digit + ord("0")
    out[:, width + 1] = ord(" ")
    pos = first_word + np.arange(len(ids))
    out[pos % WORDS_PER_LINE == WORDS_PER_LINE - 1, width + 1] = ord("\n")
    return out


def generate(dest: str, params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Write the corpus as ``params["parts"]`` files of whole lines under
    `dest`.  Returns inputs, input_bytes, records and the reference.

    Which words a part file holds is drawn from the traffic mix's
    ``data_seed``; ``seed`` orders them inside the part.  The program pads
    every run it sorts and merges to a power of two, so its work is a step
    function of the partition sizes: corpora drawn afresh from three seeds
    gave DAGs of 10.5, 11.1 and 11.8 s in owc_spill_zipf, each steady to
    0.5 % (chip run, PR 25).  With the same words in another order every
    seed gives the same sizes, and so the same work."""
    vocab = int(params["vocab"])
    width = _width(vocab)
    lines = (int(params["corpus_mib"]) << 20) // ((width + 2) * WORDS_PER_LINE)
    parts = int(params["parts"])
    if lines < parts:
        raise ValueError(f"corpus of {lines} lines cannot fill {parts} parts")
    drawn = np.random.default_rng(int(params["data_seed"]))
    counts = np.zeros(vocab, dtype=np.int64)
    os.makedirs(dest)
    done = 0
    for part in range(parts):
        # part p holds lines [p*lines//parts, (p+1)*lines//parts)
        n = ((part + 1) * lines // parts - part * lines // parts) \
            * WORDS_PER_LINE
        ids = _draw(drawn, n, params)
        counts += np.bincount(ids, minlength=vocab)
        order = np.random.default_rng([int(seed), part]).permutation(n)
        with open(os.path.join(dest, f"part-{part:05d}.txt"), "wb") as fh:
            fh.write(_word_bytes(ids[order], width, done).tobytes())
        done += n
    return {"inputs": [dest], "input_bytes": done * (width + 2),
            "records": done,
            "reference": {"counts": counts, "width": width}}


def _parse(data: bytes, width: int, vocab: int):
    """ids, counts and the number of malformed lines of one output file of
    ``w<id>\\t<count>\\n`` lines."""
    a = np.frombuffer(data, dtype=np.uint8)
    if not len(a):
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 0
    ends = np.flatnonzero(a == 10)
    malformed = 0 if a[-1] == 10 else 1
    starts = np.concatenate([[0], ends[:-1] + 1]) if len(ends) else ends
    lens = ends - starts
    ok = lens >= width + 3
    starts, ends = starts[ok], ends[ok]
    good = (a[starts] == ord("w")) & (a[starts + width + 1] == 9)
    ids = np.zeros(len(starts), dtype=np.int64)
    for k in range(1, width + 1):
        d = a[starts + k].astype(np.int64) - 48
        good &= (d >= 0) & (d <= 9)
        ids = ids * 10 + d
    n_digits = ends - (starts + width + 2)
    good &= n_digits <= 18
    vals = np.zeros(len(starts), dtype=np.int64)
    for k in range(int(n_digits.max()) if len(n_digits) else 0):
        live = n_digits > k
        d = a[np.where(live, starts + width + 2 + k, 0)].astype(np.int64) - 48
        good &= ~live | ((d >= 0) & (d <= 9))
        vals = np.where(live, vals * 10 + d, vals)
    good &= ids < vocab
    malformed += int((~ok).sum()) + int((~good).sum())
    return ids[good], vals[good], malformed


def compare(out_dir: str, reference: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of one committed output directory, each held to LIMITS."""
    golden = reference["counts"]
    width = reference["width"]
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    got = np.zeros(len(golden), dtype=np.int64)
    seen = np.zeros(len(golden), dtype=np.int64)
    malformed = disorder = 0
    last: Optional[int] = None
    for name in names:
        if name.startswith(("_", ".")):
            continue
        with open(os.path.join(out_dir, name), "rb") as fh:
            ids, vals, bad = _parse(fh.read(), width, len(golden))
        malformed += bad
        if len(ids):
            seen += np.bincount(ids, minlength=len(golden))
            np.add.at(got, ids, vals)
            disorder += int((np.diff(vals) < 0).sum())
            if last is not None and vals[0] < last:
                disorder += 1
            last = int(vals[-1])
    committed = "_SUCCESS" in names and not any(
        n.startswith("_temporary") for n in names)
    return {"words_wrong_count": int((got != golden).sum()),
            "words_repeated": int((seen > 1).sum()),
            "lines_out_of_order": disorder,
            "lines_malformed": malformed,
            "commits_missing": 0 if committed else 1}


def reference_output(dest: str, reference: Dict[str, Any],
                     broken: Optional[str] = None) -> None:
    """The plain reference's own committed output: one line a word, ordered
    by (count, word).  `broken` names the guarantee a control breaks:

    * ``approximate_counts``: the commonest word's count short by 1/64, as
      a sampled or lossy count would be;
    * ``unordered``: in order of word, the sort by count left out;
    * ``committed_twice``: a second part file repeats the first's lines, as a
      re-run task committed beside the first attempt would.
    """
    counts = reference["counts"].copy()
    width = reference["width"]
    if broken == "approximate_counts":
        hot = int(np.argmax(counts))
        counts[hot] -= max(1, int(counts[hot]) // 64)
    ids = np.flatnonzero(counts)
    if broken != "unordered":
        ids = ids[np.argsort(counts[ids], kind="stable")]
    lines = [b"w%0*d\t%d\n" % (width, int(i), int(counts[i])) for i in ids]
    os.makedirs(dest)
    with open(os.path.join(dest, "part-00000"), "wb") as fh:
        fh.writelines(lines)
    if broken == "committed_twice":
        with open(os.path.join(dest, "part-00001"), "wb") as fh:
            fh.writelines(lines)
    with open(os.path.join(dest, "_SUCCESS"), "w"):
        pass
