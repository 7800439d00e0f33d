"""Generator of the `tpch_filtered_joins` suite: `datagen/tpch.py`'s tables
from the seed, unchanged (`lineitem`, `orders` and `part` are what the suite's
three templates read; no other table is made)."""

from __future__ import annotations

import ctypes
import importlib.util
import os

import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

TABLES = ("part", "orders", "lineitem")
sizes = _tpch.sizes


def generate(sf, seed, tables=TABLES):
    """`datagen/tpch.py`'s tables, and what making them left behind given
    back: `lineitem` is concatenated from 128 blocks, and the blocks' memory
    stays with Arrow's pool and the C allocator (4 GB at SF10) unless asked
    for, on a machine of 40 GiB that also holds the program's host columns,
    the reference's and the chip runtime's own 13.7 GB."""
    out = _tpch.generate(sf, seed, tables)
    pa.default_memory_pool().release_unused()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # another C library: nothing to trim
    return out
