"""Drive benchmarks/run.py on a TeraSort cell with the program broken
underneath: each fault has to come out as not correct.

    python3 benchmarks/tests/faulty_terasort.py <fault> --workload ... --rehearse 2

* ``half_batch``        half of the part files never reach the DAG (nor its
  sampler);
* ``hash_put_back``     the total-order partitioner's batch form is the hash
  again, as at the parent commit: every part sorted, the ranges overlapping.

The controls (the reference's own output with one guarantee broken) need no
plant: ``faulty_run.py control`` reads the generator's ``CONTROLS``.
"""
from __future__ import annotations

import os
import sys

from faulty_run import BENCH, ROOT, load_run


def plant_half_batch() -> None:
    from tez_tpu.examples import terasort
    build = terasort.build_dag

    def build_half(input_paths, output_path, **kwargs):
        files = sorted(os.path.join(d, f) for d in input_paths
                       for f in os.listdir(d))
        return build(files[::2], output_path, **kwargs)

    terasort.build_dag = build_half


def plant_hash_put_back() -> None:
    from tez_tpu.library import outputs
    form = outputs.batch_form
    outputs.batch_form = lambda p: "hash" if form(p) == "range" else form(p)


FAULTS = {"half_batch": plant_half_batch,
          "hash_put_back": plant_hash_put_back}


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
