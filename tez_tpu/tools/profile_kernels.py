"""On-chip kernel timing harness for two host-vs-device questions.

Reference role: the reference tunes its hot loops by JMH-style
micro-measurement; here the measurements are (a) the XLA fori_loop FNV hash
partition and (b) whether device-side ragged->lanes encode beats the host
encode + padded upload (tez.runtime.tpu.device.encode).

Run on the target chip:  python -m tez_tpu.tools.profile_kernels [n_rows]
Prints one JSON line carrying the backend it ran on.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _make_keys(n: int, key_len: int = 12, seed: int = 0):
    rng = np.random.default_rng(seed)
    kb = rng.integers(97, 123, n * key_len, dtype=np.int64).astype(np.uint8)
    ko = (np.arange(n + 1, dtype=np.int64) * key_len)
    return kb, ko


def _time(fn, reps: int = 5) -> float:
    fn()   # warm/compile
    t0 = time.time()
    for _ in range(reps):
        fn()
    return (time.time() - t0) / reps


def main() -> int:
    import jax

    from tez_tpu.ops import device
    from tez_tpu.ops.keycodec import (encode_keys, encode_keys_device,
                                      matrix_to_lanes, pad_to_matrix)

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    width = 16
    backend = jax.default_backend()
    kb, ko = _make_keys(n)
    mat, lengths = pad_to_matrix(kb, ko, width)

    results = {}

    # -- hash: XLA fori_loop ----------------------------------------------
    results["hash_xla_s"] = _time(
        lambda: device.hash_partition(mat, lengths, 8))

    # -- encode: host pad+pack+upload vs device gather --------------------
    def host_encode():
        lanes, lens = encode_keys(kb, ko, width)
        d = jax.device_put(lanes)
        jax.block_until_ready(d)
        return d

    def device_encode():
        lanes, lens = encode_keys_device(kb, ko, width)
        jax.block_until_ready(lanes)
        return lanes

    h = np.asarray(host_encode())
    d = np.asarray(device_encode())
    assert np.array_equal(h, d), "device encode diverges from host"
    results["encode_host_s"] = _time(host_encode)
    results["encode_device_s"] = _time(device_encode)
    results["device_encode_speedup"] = round(
        results["encode_host_s"] / results["encode_device_s"], 3)

    print(json.dumps({"backend": backend, "rows": n, **results}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
