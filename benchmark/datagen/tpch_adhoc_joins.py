"""Generator of the `tpch_adhoc_joins` suite: `datagen/tpch_joins10.py`'s
tables from the seed, unchanged (it gives back what making them left behind),
and from the same seed the run's substitution parameters
(`adhoc_join_params.set_seed`): the harness gives the seed to the generator
alone."""

from __future__ import annotations

import importlib.util
import os

import adhoc_join_params

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch_joins10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch_joins10.py"))
_joins10 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_joins10)

TABLES = _joins10.TABLES
sizes = _joins10.sizes


def generate(scale_factor, seed, tables=TABLES):
    adhoc_join_params.set_seed(seed)
    return _joins10.generate(scale_factor, seed, tables)
