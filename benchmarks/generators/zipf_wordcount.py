"""WordCount's corpus, its plain reference, and the comparison that decides
`correct`, for a word count over an unordered edge.

The corpus and the reference are zipf_words.py's own (``generate``: words
``w<id>`` of one fixed width drawn zipf(a) over a vocabulary, the
generator's ``bincount`` of the ids it wrote as the reference), so a cell of
this generator counts the words an OrderedWordCount cell of the same
traffic counts.  Nothing here imports the program under test.

WordCount's guarantees, which the comparison holds every committed output
to, each with the limit 0:

* exact: every word's count equals the reference's (``words_wrong_count``);
* once: every word on exactly one line of the whole output
  (``words_repeated``);
* committed once: ``_SUCCESS`` there and no temporary tree left
  (``commits_missing``); nothing unparseable (``lines_malformed``).

No order: the tokenizer-to-summation edge is unordered, and holding its
output to an order would test a guarantee it does not give, so zipf_words'
``lines_out_of_order`` is not compared.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, Optional

import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_generators_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_words = _sibling("zipf_words")
generate = _words.generate
_parse = _words._parse

#: each number compared, with its limit: all exact comparisons
LIMITS = {"words_wrong_count": 0, "words_repeated": 0,
          "lines_malformed": 0, "commits_missing": 0}
#: guarantee broken -> what reference_output() does to the reference's output
CONTROLS = ("approximate_counts", "committed_twice", "partition_left_out")
#: part files of the reference's own output: the summations of the cell
PARTS = 4


def compare(out_dir: str, reference: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of one committed output directory, each held to LIMITS."""
    golden = reference["counts"]
    width = reference["width"]
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    got = np.zeros(len(golden), dtype=np.int64)
    seen = np.zeros(len(golden), dtype=np.int64)
    malformed = 0
    for name in names:
        if name.startswith(("_", ".")):
            continue
        with open(os.path.join(out_dir, name), "rb") as fh:
            ids, vals, bad = _parse(fh.read(), width, len(golden))
        malformed += bad
        if len(ids):
            seen += np.bincount(ids, minlength=len(golden))
            np.add.at(got, ids, vals)
    committed = "_SUCCESS" in names and not any(
        n.startswith("_temporary") for n in names)
    return {"words_wrong_count": int((got != golden).sum()),
            "words_repeated": int((seen > 1).sum()),
            "lines_malformed": malformed,
            "commits_missing": 0 if committed else 1}


def reference_output(dest: str, reference: Dict[str, Any],
                     broken: Optional[str] = None) -> None:
    """The plain reference's own committed output: one line a word, in order
    of word, over PARTS part files (a word's part by its id).  `broken`
    names the guarantee a control breaks:

    * ``approximate_counts``: the commonest word's count short by 1/64, as
      a sampled or lossy count would be;
    * ``committed_twice``: a further part file repeats the first's lines, as
      a re-run task committed beside the first attempt would;
    * ``partition_left_out``: the first part's words missing, as when one
      summation's output never reached the commit.
    """
    counts = reference["counts"].copy()
    width = reference["width"]
    if broken == "approximate_counts":
        hot = int(np.argmax(counts))
        counts[hot] -= max(1, int(counts[hot]) // 64)
    ids = np.flatnonzero(counts)
    parts = [ids[ids % PARTS == p] for p in range(PARTS)]
    if broken == "committed_twice":
        parts.append(parts[0])
    if broken == "partition_left_out":
        parts[0] = parts[0][:0]
    os.makedirs(dest)
    for p, part in enumerate(parts):
        with open(os.path.join(dest, f"part-{p:05d}"), "wb") as fh:
            fh.writelines(b"w%0*d\t%d\n" % (width, int(i), int(counts[i]))
                          for i in part)
    with open(os.path.join(dest, "_SUCCESS"), "w"):
        pass
