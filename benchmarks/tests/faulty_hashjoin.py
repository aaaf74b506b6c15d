"""Drive benchmarks/run.py on a hash-join cell with the program broken
underneath: each fault has to come out as not correct.

    python3 benchmarks/tests/faulty_hashjoin.py <fault> --workload ... --rehearse 2

* ``half_batch``             half of each side's part files never reach the
  DAG;
* ``hash_side_partitioned``  the hash side's broadcast edge made a
  partitioned one, hashed with another seed than the stream's: every
  joiner's build and probe are sound, but a joiner holds a quarter of the
  hash side, and not the quarter its stream keys ask for.

The controls (the reference's own output with one guarantee broken) need no
plant: ``faulty_run.py control`` reads the generator's ``CONTROLS``.
"""
from __future__ import annotations

import os
import sys

from faulty_run import BENCH, ROOT, load_run


def plant_half_batch() -> None:
    from tez_tpu.examples import hash_join
    build = hash_join.build_bench_dag

    def build_half(inputs, out_dir, **kwargs):
        files = []
        for d in inputs:
            files += sorted(os.path.join(d, f) for f in os.listdir(d))[::2]
        return build(files, out_dir, **kwargs)

    hash_join.build_bench_dag = build_half


def plant_hash_side_partitioned() -> None:
    import numpy as np
    from tez_tpu.examples import hash_join
    from tez_tpu.library.conf import UnorderedPartitionedKVEdgeConfig
    from tez_tpu.library.unordered import UnorderedPartitionedKVOutput
    from tez_tpu.ops.native import (fnv32_partition_native,
                                    group_by_partition_native)
    from tez_tpu.ops.runformat import Run

    class PartitionedInBroadcastsPlace(UnorderedPartitionedKVEdgeConfig):
        @staticmethod
        def new_builder(key_serde="bytes", value_serde="bytes"):
            return PartitionedInBroadcastsPlace(key_serde, value_serde)

        def build(self):
            edge = super().build()
            edge.create_default_broadcast_edge_property = \
                edge.create_default_edge_property
            return edge

    hash_join.UnorderedKVEdgeConfig = PartitionedInBroadcastsPlace
    initialize = UnorderedPartitionedKVOutput.initialize

    def initialize_apart(self):
        events = initialize(self)
        if self.context.vertex_name == "hashside":
            count = self.num_physical_outputs
            assert count > 1

            def partition_apart(batch):
                parts = (fnv32_partition_native(
                    batch.key_bytes, batch.key_offsets, count) + 1) % count
                perm, row_index = group_by_partition_native(
                    parts.astype(np.int32), count)
                return Run(batch.take(perm), row_index)

            self.writer_impl.partition_batch = partition_apart
        return events

    UnorderedPartitionedKVOutput.initialize = initialize_apart


FAULTS = {"half_batch": plant_half_batch,
          "hash_side_partitioned": plant_hash_side_partitioned}


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
