"""The FNV-1a row hash in numpy: the reference the device kernels
(ops/device.py ``_fnv_rows``), the native routing
(``fnv32_partition_native``) and the scalar HashPartitioner are held to by
the exchange tests and tools/chaos.py.  It sorts nothing: the host sorting
engine that ``tez.runtime.sorter.class`` selects is native/spansort.cpp,
driven from ops/sorter.py.
"""
from __future__ import annotations

import numpy as np


def fnv_rows_host(key_mat: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Vectorized FNV-1a over each row's first lengths[i] bytes — identical
    to the device kernel and the scalar HashPartitioner."""
    h = np.full(key_mat.shape[0], 2166136261, dtype=np.uint64)
    for j in range(key_mat.shape[1]):
        nh = ((h ^ key_mat[:, j].astype(np.uint64)) * np.uint64(16777619)) \
            & np.uint64(0xFFFFFFFF)
        h = np.where(j < lengths, nh, h)
    return h.astype(np.uint32)
