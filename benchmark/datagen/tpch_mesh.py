"""Generator of the `tpch_mesh` suite: `datagen/tpch.py`'s tables from the
seed, unchanged."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

TABLES = _tpch.TABLES
sizes = _tpch.sizes
generate = _tpch.generate
