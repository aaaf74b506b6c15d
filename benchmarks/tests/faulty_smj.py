"""Drive benchmarks/run.py on a sort-merge-join cell with the program broken
underneath: each fault has to come out as not correct.

    python3 benchmarks/tests/faulty_smj.py <fault> --workload ... --rehearse 2

* ``half_batch``               half of each side's part files never reach
  the DAG;
* ``sides_partitioned_apart``  the right edge partitions by key range where
  the left hashes: every joiner's merge and match are sound, but a key's two
  sides meet in different joiners.

The controls (the reference's own output with one guarantee broken) need no
plant: ``faulty_run.py control`` reads the generator's ``CONTROLS``.
"""
from __future__ import annotations

import os
import sys

from faulty_run import BENCH, ROOT, load_run


def plant_half_batch() -> None:
    from tez_tpu.examples import sort_merge_join
    build = sort_merge_join.build_bench_dag

    def build_half(inputs, out_dir, **kwargs):
        files = []
        for d in inputs:
            files += sorted(os.path.join(d, f) for f in os.listdir(d))[::2]
        return build(files, out_dir, **kwargs)

    sort_merge_join.build_bench_dag = build_half


def plant_sides_partitioned_apart() -> None:
    from tez_tpu.library.outputs import OrderedPartitionedKVOutput
    initialize = OrderedPartitionedKVOutput.initialize

    def initialize_apart(self):
        events = initialize(self)
        if self.context.vertex_name == "right":
            points = [b"N", b"a", b"n"][:self.num_physical_outputs - 1]
            assert len(points) == self.num_physical_outputs - 1
            self.sorter.partitioner = "range"
            self.sorter.split_points = points
        return events

    OrderedPartitionedKVOutput.initialize = initialize_apart


FAULTS = {"half_batch": plant_half_batch,
          "sides_partitioned_apart": plant_sides_partitioned_apart}


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
