"""The shared sort body, pinned: `_lsd_passes` / `_sort_by_key` are traced by
every merge, match and probe program, and their lowered text is their
compile-cache key; the group fold's own sort is pinned beside them.
Each program's text (debug info off) at a small shape
is held to the sha256 checked in beside this file
(`shared_sort_programs.json`): a kernel that takes a sort of its own must
leave these programs byte for byte as they were, or the join cells'
six-lane programs compile again cold."""
from __future__ import annotations

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from tez_tpu.ops import device

TABLE, BLOCK = 1 << 18, 1 << 20
PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "shared_sort_programs.json")


def _programs():
    """name -> (traced function, abstract arguments)."""
    u32, i32 = jnp.uint32, jnp.int32
    s = jax.ShapeDtypeStruct
    return {
        "_merge_sort_impl": (device._merge_sort_impl, (
            s((512,), i32), s((512, 2), u32), s((512,), u32))),
        "_fused_resident_merge_impl": (device._fused_resident_merge_impl, (
            [s((256, 2), u32)] * 3, [s((256,), i32)] * 3)),
        "_join_probe_impl": (device._join_probe_impl, (
            s((512, 6), u32), s((512,), i32), s((256, 6), u32),
            s((256,), i32))),
        # WordCount's fold: a table of 2^18 groups and a block of 2^20
        # rows of two lanes (the wc_unordered_zipf cell's one program)
        "_group_sum_impl": (functools.partial(
            device._group_sum_impl, table_rows=TABLE), (
            s((TABLE + BLOCK, 2), u32), s((TABLE + BLOCK,), i32),
            s((TABLE + BLOCK,), i32), s((BLOCK, 2), u32), s((BLOCK,), i32),
            s((BLOCK,), i32))),
    }


@pytest.mark.parametrize("name", sorted(_programs()))
def test_the_shared_sort_programs_lower_as_they_were_pinned(name):
    fn, args = _programs()[name]
    text = jax.jit(fn).lower(*args).as_text(debug_info=False)
    with open(PINS) as f:
        pinned = json.load(f)[name]
    assert hashlib.sha256(text.encode()).hexdigest() == pinned
