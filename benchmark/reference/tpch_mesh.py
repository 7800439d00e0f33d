"""Plain reference of the `tpch_mesh` suite: `reference/tpch.py`'s, on the
same Arrow tables. How the program lays the rows out over its chips is none
of the reference's business."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

answer = _tpch.answer
to_bfloat16 = _tpch.to_bfloat16
