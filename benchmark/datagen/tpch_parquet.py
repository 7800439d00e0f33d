"""Generator of the `tpch_parquet` suite: `datagen/tpch.py`'s tables from the
seed, written as Parquet files (`parquet_store.write`) and also returned.

The harness needs the Arrow tables for their row counts and hands them to the
plain reference after the window; the templates (`queries/tpch_parquet.py`)
read the files. The files are a lossless copy of the tables
(`tests/benchmark_harness/test_bench_parquet.py` reads them back, value for
value and type for type), so the reference on the tables is the reference on
the files.
"""

from __future__ import annotations

import importlib.util
import os

import parquet_store

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

TABLES = _tpch.TABLES
sizes = _tpch.sizes


def generate(sf: float, seed: int, tables=TABLES, rows_per_file: int = parquet_store.ROWS_PER_FILE):
    """The named tables at scale `sf` from `seed`: written as files of at most
    `rows_per_file` rows (a test at a small scale passes fewer, so that its
    scan still reads several files), and returned as Arrow tables."""
    arrow = _tpch.generate(sf, seed, tables)
    parquet_store.write(arrow, rows_per_file)
    return arrow
