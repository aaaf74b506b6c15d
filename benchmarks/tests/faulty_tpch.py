"""Drive benchmarks/run.py on the TPC-H Q3 cell with the program broken
underneath: each fault has to come out as not correct.

    python3 benchmarks/tests/faulty_tpch.py <fault> --workload ... --rehearse 2

* ``carry_off_by_one``  the key-foreign-key join carries the date and the
  priority of the neighbouring build row (the next in arrival order), not
  of the row the line hit;
* ``int32_sum``         the grouped revenue summed in int32, as a device
  table of int32 sums would: past 214,748.3647 it wraps;
* ``half_block``        the probe answers for the first half of each block's
  lines and calls the rest misses.

The controls (the reference's own output with one guarantee broken) need no
plant: ``faulty_run.py control`` reads the generator's ``CONTROLS``.
"""
from __future__ import annotations

import sys

from faulty_run import BENCH, ROOT, load_run


def plant_carry_off_by_one() -> None:
    from tez_tpu.library import join
    carried = join._carried

    def carried_next(stream, rows, build, build_rows):
        return carried(stream, rows, build,
                       (build_rows + 1) % build.num_records)

    join._carried = carried_next


def plant_int32_sum() -> None:
    import numpy as np
    from tez_tpu.ops import device
    fold = device.group_sum_host

    def fold_int32(t_lanes, t_lens, t_sums, b_lanes, b_lens, b_vals):
        lanes, lens, sums = fold(t_lanes, t_lens, t_sums, b_lanes, b_lens,
                                 b_vals)
        return lanes, lens, sums.astype(np.int32).astype(np.int64)

    device.group_sum_host = fold_int32


def plant_half_block() -> None:
    from tez_tpu.ops import device

    def halved(probe):
        def probe_half(stream_lanes, stream_lens, *build):
            hit = probe(stream_lanes, stream_lens, *build)
            hit[len(hit) // 2:] = -1
            return hit
        return probe_half

    device.kfk_probe = halved(device.kfk_probe)
    device.kfk_probe_host = halved(device.kfk_probe_host)


FAULTS = {"carry_off_by_one": plant_carry_off_by_one,
          "int32_sum": plant_int32_sum,
          "half_block": plant_half_block}


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
