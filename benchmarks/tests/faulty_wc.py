"""Drive benchmarks/run.py on a WordCount cell with the program broken
underneath: each fault has to come out as not correct.

    python3 benchmarks/tests/faulty_wc.py <fault> --workload ... --rehearse 2

* ``half_block``      every fold takes the first half of its block's rows;
* ``table_dropped``   the group table starts empty again before every fold
  (blocks of 8,192 rows, so that a rehearsal's summations fold several);
* ``values_ignored``  the fold counts rows instead of summing their values,
  on a corpus whose tokenizer writes 2s (and a reference that counts each
  word twice): counting rows gives the answer only while every value is 1;
* ``twos``            that corpus and reference with a sound fold: the
  control of the plant itself, which has to read correct.

The controls (the reference's own output with one guarantee broken) need no
plant: ``faulty_run.py control`` reads the generator's ``CONTROLS``.
"""
from __future__ import annotations

import functools
import sys

from faulty_run import BENCH, ROOT, load_run

#: rows a fold block, where a fault needs several blocks a summation
SMALL_BLOCK = 8192


def plant_half_block() -> None:
    from tez_tpu.library import aggregate
    fold = aggregate._DeviceTable.fold

    def fold_half(self, lanes, lens, vals, magnitude, counters):
        half = len(lens) // 2
        return fold(self, lanes[:half], lens[:half], vals[:half], magnitude,
                    counters)

    aggregate._DeviceTable.fold = fold_half


def plant_table_dropped() -> None:
    from tez_tpu.library import aggregate
    from tez_tpu.ops import device
    fold = aggregate._DeviceTable.fold

    def fold_afresh(self, lanes, lens, vals, magnitude, counters):
        self.settle()
        self.rows_in = self.min_rows + self.block_bucket
        self.table = device.empty_group_table(self.rows_in, lanes.shape[1])
        self.count = 0
        return fold(self, lanes, lens, vals, magnitude, counters)

    aggregate._DeviceTable.fold = fold_afresh
    aggregate.group_sum_blocks = functools.partial(
        aggregate.group_sum_blocks, block_rows=SMALL_BLOCK)


def _write_twos(run) -> None:
    """The tokenizer writes 2 for every word, and the reference counts each
    word twice."""
    import numpy as np
    from tez_tpu.ops import serde

    class Twos(serde.VarLongSerde):
        def to_bytes(self, obj):
            return super().to_bytes(2 * int(obj))

    serde.VarLongSerde = Twos
    load_module = run.load_module

    def load_doubling(kind, name):
        mod = load_module(kind, name)
        if kind == "generators":
            generate = mod.generate

            def generate_twice(dest, params, seed):
                made = generate(dest, params, seed)
                made["reference"]["counts"] = \
                    made["reference"]["counts"] * np.int64(2)
                return made

            mod.generate = generate_twice
        return mod

    run.load_module = load_doubling


def plant_values_ignored(run) -> None:
    import numpy as np
    from tez_tpu.library import aggregate
    _write_twos(run)
    aggregate._values = lambda block: np.ones(block.num_records, np.int64)


FAULTS = {"half_block": plant_half_block,
          "table_dropped": plant_table_dropped,
          "values_ignored": plant_values_ignored,
          "twos": _write_twos}
#: the plants that take the harness's module, to reach its generator
TAKES_RUN = ("values_ignored", "twos")


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    if which in TAKES_RUN:
        FAULTS[which](run)
    else:
        FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
