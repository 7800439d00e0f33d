"""Generator of the `tpch_joins_mesh` suite: `datagen/tpch_joins10.py`'s, which
is `datagen/tpch.py`'s tables from the seed, unchanged (`lineitem`, `orders`,
`customer`, `supplier`, `nation`, `region` are what the suite's three
templates read), with what making `lineitem` left behind given back
(`release_unused`, `malloc_trim`): at SF30 the blocks it is concatenated from
are 12 GB that would else stay with the allocator for the whole run, on a
machine whose 140 GiB also hold 34 GB of Arrow tables, the program's host
side of 1,374 batches a query and the reference's columns."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch_joins10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch_joins10.py"))
_j10 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_j10)

TABLES = _j10.TABLES
sizes = _j10.sizes
generate = _j10.generate
