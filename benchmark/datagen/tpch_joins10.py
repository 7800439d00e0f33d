"""Generator of the `tpch_joins10` suite: `datagen/tpch.py`'s tables from the
seed, unchanged (`lineitem`, `orders`, `part`, `customer`, `supplier`,
`nation`, `region` are what the suite's four templates read; a traffic that
leaves q14 out never makes `part`)."""

from __future__ import annotations

import ctypes
import importlib.util
import os

import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

TABLES = _tpch.TABLES
sizes = _tpch.sizes


def generate(sf, seed, tables=TABLES):
    """`datagen/tpch.py`'s tables, and what making them left behind given
    back: `lineitem` is concatenated from 128 blocks, and the blocks' memory
    stays with Arrow's pool and the C allocator (4 GB at SF10) unless asked
    for. The suite's tables at SF10 are 11.3 GB of Arrow, beside what the
    program keeps on the host for 458 batches a query, the reference's
    columns and the chip runtime's own 13.7 GB, on a machine of 40 GiB."""
    out = _tpch.generate(sf, seed, tables)
    pa.default_memory_pool().release_unused()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # another C library: nothing to trim
    return out
