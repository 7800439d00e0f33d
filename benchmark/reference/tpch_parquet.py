"""Plain reference of the `tpch_parquet` suite: `reference/tpch.py`'s, on the
Arrow tables the Parquet files were written from. The files are a lossless
copy of those tables (`tests/benchmark_harness/test_bench_parquet.py`), so it
is the same plain reference on the same data."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

answer = _tpch.answer
to_bfloat16 = _tpch.to_bfloat16
