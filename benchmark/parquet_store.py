"""The Parquet files of the `tpch_parquet` suite: where the last `write` put
them, for the generator that writes them and the templates that read them.

A plain module, imported by name (`run.py` and `control.py` put `benchmark/`
on `sys.path`), so `datagen/tpch_parquet.py` and `queries/tpch_parquet.py`,
which the harness loads as two separate modules, see one instance of it.

The files lie in a fresh `tempfile.mkdtemp()`, outside the checkout (the
driver copies the tree, and 0.3 GB of files left in it would go along), and
are removed when the next `write` replaces them and when the process ends.

The layout is the configuration's (`configs/tpch-sf1-parquet-1chip.json`,
`storage`): `pyarrow.parquet.write_table` at its defaults (snappy, dictionary
encoding and statistics on), each table cut in row order into files of at
most `ROWS_PER_FILE` rows, which at those defaults is one row group a file.
No sort, no partitioning by a column, and no tuning to the engine's
`scan_split_bytes` or `morsel_size_rows`.
"""

from __future__ import annotations

import atexit
import os
import shutil
import tempfile
from typing import Dict, List, Optional

import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_FILE = 1_048_576

_dir: Optional[str] = None
_files: Dict[str, List[str]] = {}


def write(tables: Dict[str, pa.Table], rows_per_file: int = ROWS_PER_FILE) -> str:
    """Write each table as `<table>-<nnn>.parquet` files of at most
    `rows_per_file` rows into a new directory, in place of what an earlier
    call wrote. Returns the directory."""
    global _dir
    discard()
    _dir = tempfile.mkdtemp(prefix="tpch_parquet_")
    for name, table in tables.items():
        _files[name] = []
        for k, start in enumerate(range(0, max(table.num_rows, 1), rows_per_file)):
            path = os.path.join(_dir, f"{name}-{k:03d}.parquet")
            pq.write_table(table.slice(start, rows_per_file), path)
            _files[name].append(path)
    return _dir


def paths(table: str) -> List[str]:
    """The files of `table` from the last `write`, in row order."""
    if table not in _files:
        raise RuntimeError(f"no Parquet files of {table!r}: the suite's generator "
                           f"(datagen/tpch_parquet.py) writes them, and has not run")
    return list(_files[table])


def directory() -> Optional[str]:
    return _dir


def discard() -> None:
    """Remove the files of the last `write`."""
    global _dir
    if _dir is not None:
        shutil.rmtree(_dir, ignore_errors=True)
    _dir = None
    _files.clear()


atexit.register(discard)
