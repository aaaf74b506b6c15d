"""Stage-by-stage wall profile of the NATIVE HOST engine (no device in it).

Breaks the host engine's 2M-record span sort + merge into its stages and
sets them beside the PipelinedSorter-semantics C++ proxy (BASELINE.json).
Host code only: it pins JAX_PLATFORMS=cpu for itself, spawns nothing, and
none of its numbers is a device number.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    from bench import make_records
    from tez_tpu.ops.native import (fnv32_partition_native,
                                    sort_partition_keys_native,
                                    merge_runs_native,
                                    pipelined_sorter_proxy)
    from tez_tpu.ops.runformat import KVBatch
    from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    key_len = 12
    num_producers, num_partitions = 4, 4
    kb, ko, vb, vo = make_records(n, key_len)
    total_mb = (kb.nbytes + vb.nbytes) / 1e6
    uniq = len(np.unique(kb.reshape(n, key_len), axis=0))
    print(f"n={n} total={total_mb:.1f}MB unique_keys={uniq}")

    def t(label, fn, reps=3):
        fn()  # warm
        t0 = time.time()
        for _ in range(reps):
            out = fn()
        dt = (time.time() - t0) / reps
        print(f"{label:38s} {dt*1000:8.1f} ms")
        return out, dt

    per = n // num_producers
    kbp = kb[: per * key_len]
    kop = np.arange(per + 1, dtype=np.int64) * key_len
    vbp = vb[: per * 8]
    vop = np.arange(per + 1, dtype=np.int64) * 8

    parts, dt_part = t("fnv32_partition (1 producer span)",
                       lambda: fnv32_partition_native(kbp, kop,
                                                      num_partitions))
    perm, dt_sort = t("tz_sort_partition_keys (1 span)",
                      lambda: sort_partition_keys_native(kbp, kop, parts))

    batch = KVBatch(kbp, kop, vbp, vop)
    _, dt_take = t("batch.take(perm) (1 span)", lambda: batch.take(perm))

    def one_producer():
        s = DeviceSorter(num_partitions=num_partitions, engine="host",
                         key_width=key_len)
        s.write_batch(KVBatch(kbp, kop, vbp, vop))
        return s.flush()
    run1, dt_prod = t("DeviceSorter full producer (1 span)", one_producer)

    def all_runs():
        runs = []
        for p in range(num_producers):
            lo = p * per
            hi = (p + 1) * per if p < num_producers - 1 else n
            m = hi - lo
            s = DeviceSorter(num_partitions=num_partitions, engine="host",
                             key_width=key_len)
            s.write_batch(KVBatch(
                kb[lo * key_len:hi * key_len],
                np.arange(m + 1, dtype=np.int64) * key_len,
                vb[lo * 8:hi * 8],
                np.arange(m + 1, dtype=np.int64) * 8))
            runs.append(s.flush())
        return runs
    runs, dt_runs = t("all 4 producers", all_runs, reps=1)

    _, dt_merge = t("merge_sorted_runs (4 runs)",
                    lambda: merge_sorted_runs(runs, num_partitions, key_len,
                                              engine="host"), reps=1)

    # merge internals
    batch_c = KVBatch.concat([r.batch for r in runs])
    partitions = np.concatenate([
        np.repeat(np.arange(r.num_partitions, dtype=np.int32),
                  np.diff(r.row_index)) for r in runs])
    run_bounds = np.zeros(len(runs) + 1, dtype=np.int64)
    np.cumsum([r.batch.num_records for r in runs], out=run_bounds[1:])
    _, dt_concat = t("  merge: KVBatch.concat",
                     lambda: KVBatch.concat([r.batch for r in runs]))
    permm, dt_mr = t("  merge: tz_merge_runs",
                     lambda: merge_runs_native(batch_c.key_bytes,
                                               batch_c.key_offsets,
                                               partitions, run_bounds))
    _, dt_take2 = t("  merge: take(perm) 2M",
                    lambda: batch_c.take(permm))

    def full():
        return merge_sorted_runs(all_runs(), num_partitions, key_len,
                                 engine="host")
    _, dt_full = t("FULL native_once (sorts + merge)", full, reps=3)

    res = pipelined_sorter_proxy(kb.reshape(n, key_len), vb.reshape(n, 8),
                                 num_producers, num_partitions)
    if res is None:
        print("C++ proxy unavailable (native lib missing); no ratio")
        return
    print(f"{'C++ proxy (baseline)':38s} {res[0]*1000:8.1f} ms")
    print(f"native/proxy ratio: {res[0]/dt_full:.3f}x  "
          f"({total_mb/dt_full:.1f} MB/s vs {total_mb/res[0]:.1f} MB/s)")


if __name__ == "__main__":
    main()
