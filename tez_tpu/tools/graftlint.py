"""graftlint CLI: run the static-analysis suite over the tez_tpu tree.

::

    python -m tez_tpu.tools.graftlint            # = make lint
    python -m tez_tpu.tools.graftlint --update-baseline
    python -m tez_tpu.tools.graftlint --checker lockorder --graph

Exit codes: 0 = clean (no findings outside the committed baseline),
1 = new findings, 2 = internal error.  Output is stable and sorted —
``path:line: code [checker] message`` — so run-to-run diffs are
reviewable.

The baseline (``tez_tpu/tools/graftlint_baseline.json``) holds triaged
known-finding identities; the gate fails only on findings *not* listed
there, so adopting a new checker never blocks unrelated PRs.  Refresh it
with ``--update-baseline`` after triage and commit the diff.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback
from typing import List

from tez_tpu.analysis import all_checkers
from tez_tpu.analysis.core import (Context, load_baseline,
                                   partition_by_baseline, run_checkers,
                                   save_baseline)

_DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "graftlint_baseline.json")


def _default_root() -> str:
    # <root>/tez_tpu/tools/graftlint.py -> <root>
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="AST-based static analysis for the tez_tpu tree "
                    "(docs/static_analysis.md)")
    ap.add_argument("--root", default=_default_root(),
                    help="repository root holding tez_tpu/ and docs/")
    ap.add_argument("--baseline", default=_DEFAULT_BASELINE,
                    help="suppression baseline JSON path")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings")
    ap.add_argument("--checker", action="append", default=None,
                    metavar="NAME",
                    help="run only this checker (repeatable)")
    ap.add_argument("--list-checkers", action="store_true",
                    help="print the checker catalog and exit")
    ap.add_argument("--graph", action="store_true",
                    help="also dump the static lock acquisition graph")
    args = ap.parse_args(argv)

    checkers = all_checkers()
    if args.list_checkers:
        for c in checkers:
            print(f"{c.name}: {c.doc}")
        return 0
    if args.checker:
        unknown = set(args.checker) - {c.name for c in checkers}
        if unknown:
            print(f"graftlint: unknown checker(s): "
                  f"{', '.join(sorted(unknown))}", file=sys.stderr)
            return 2
        checkers = [c for c in checkers if c.name in args.checker]

    try:
        ctx = Context(args.root)
        findings = run_checkers(ctx, checkers)
        if args.graph:
            from tez_tpu.analysis import lockorder
            edges, locks = lockorder.build_graph(ctx)
            print(f"# lock graph: {len(locks)} locks, {len(edges)} edges")
            for (a, b) in sorted(edges):
                where, line = edges[(a, b)]
                print(f"{a} -> {b}  [{where}:{line}]")
        if args.update_baseline:
            save_baseline(args.baseline, findings)
            print(f"graftlint: baseline rewritten with {len(findings)} "
                  f"finding(s) at {args.baseline}")
            return 0
        new, known, stale = partition_by_baseline(
            findings, load_baseline(args.baseline))
        for f in new:
            print(f.render())
        for ident in stale:
            print(f"graftlint: stale baseline entry (fixed? run "
                  f"--update-baseline): {ident}")
        print(f"graftlint: {len(checkers)} checker(s), "
              f"{len(new)} new finding(s), {len(known)} baselined, "
              f"{len(stale)} stale baseline entr(ies)")
        return 1 if new else 0
    except Exception:               # noqa: BLE001 — exit-code contract
        traceback.print_exc()
        print("graftlint: internal error", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
