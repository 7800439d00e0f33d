"""Generator of the `tpch_adhoc` suite: `datagen/tpch.py`'s tables from the
seed, unchanged, and from the same seed the run's substitution parameters
(`adhoc_params.set_seed`): the harness gives the seed to the generator alone."""

from __future__ import annotations

import importlib.util
import os

import adhoc_params

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

TABLES = _tpch.TABLES
sizes = _tpch.sizes


def generate(scale_factor, seed, tables):
    adhoc_params.set_seed(seed)
    return _tpch.generate(scale_factor, seed, tables)
