"""Top-level user API re-exports (DataFrame, col, lit, from_*/read_* functions).

daft_tpu/__init__.py lazily forwards attribute access here.
Reference parity: daft/__init__.py + daft/convert.py + daft/io/__init__.py:19-37.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

from .config import execution_config, execution_config_ctx, set_execution_config
from .core.micropartition import MicroPartition
from .dataframe import DataFrame, GroupedDataFrame
from .expressions import Expression, col, lit
from .checkpoint import CheckpointStore, FileCheckpointStore, MemoryCheckpointStore
from .io.io_config import HTTPConfig, IOConfig, S3Config, io_config, set_io_config
from .io.sink import DataSink, WriteResult
from .io.source import DataSource, DataSourceTask
from .plan.builder import LogicalPlanBuilder
from .schema import Schema
from .udf import Func, cls, func, method, udf
from .window import Window
from . import functions

__all__ = [
    "DataFrame", "GroupedDataFrame", "Expression", "col", "lit", "element", "func",
    "from_pydict", "from_pylist", "from_arrow", "from_pandas",
    "read_parquet", "read_csv", "read_json", "from_glob_path", "sql", "sql_expr",
    "cls", "method", "udf", "Func",
    "launch_dashboard", "enable_event_log", "serving_session",
]


# ---- observability conveniences ------------------------------------------------------


def launch_dashboard(host: str = "127.0.0.1", port: int = 0):
    """Start the embedded dashboard (query history UI, /api/* JSON, a
    Prometheus /metrics exposition, and per-query Chrome-trace downloads at
    /api/query/<id>/trace); returns the Dashboard (``.url``, ``.shutdown()``).
    Reference: daft.subscribers.dashboard.launch."""
    from .observability.dashboard import launch

    return launch(host, port)


def enable_event_log(path: str):
    """Append one JSON line per query lifecycle event to `path` (see
    observability/event_log.py, schema_version documented there); returns the
    subscriber for observability.event_log.disable_event_log."""
    from .observability.event_log import enable_event_log as _enable

    return _enable(path)


def serving_session(max_concurrent: Optional[int] = None, runner=None,
                    prepared_cap: int = 64):
    """Open a ServingSession: N concurrent queries with fair per-tenant
    admission, an HBM admission controller, and a prepared-query cache
    (daft_tpu/serving/). Use as a context manager:

        with daft_tpu.serving_session(max_concurrent=4) as sess:
            fut = sess.submit(df.groupby("k").agg(...), tenant="acme")
            rows = fut.to_pydict()
    """
    from .serving import ServingSession

    return ServingSession(max_concurrent=max_concurrent, runner=runner,
                          prepared_cap=prepared_cap)


def element() -> Expression:
    """Placeholder for the current list element in list.map-style expressions."""
    return col("")


# ---- in-memory constructors ----------------------------------------------------------


def from_pydict(data: Dict[str, Any]) -> DataFrame:
    part = MicroPartition.from_pydict(data)
    return DataFrame(LogicalPlanBuilder.from_in_memory(part.schema, [part]))


def from_pylist(rows: List[dict]) -> DataFrame:
    keys: List[str] = []
    for r in rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    return from_pydict({k: [r.get(k) for r in rows] for k in keys})


def from_arrow(tables) -> DataFrame:
    from .observability.runtime_stats import profile_span

    if not isinstance(tables, (list, tuple)):
        tables = [tables]
    # a table's load: chunked columns are combined and strings widened here,
    # outside any query (benchmark/coldreport.py reads the span)
    with profile_span("load.from_arrow", "host") as sp:
        parts = [MicroPartition.from_arrow(t) for t in tables]
        if sp is not None:
            sp.args.update(rows=sum(t.num_rows for t in tables),
                           bytes=sum(t.nbytes for t in tables))
    return DataFrame(LogicalPlanBuilder.from_in_memory(parts[0].schema, list(parts)))


def from_pandas(dfs) -> DataFrame:
    import pyarrow as pa

    if not isinstance(dfs, (list, tuple)):
        dfs = [dfs]
    return from_arrow([pa.Table.from_pandas(d) for d in dfs])


def _from_partitions(parts: List[MicroPartition], schema: Schema) -> DataFrame:
    return DataFrame(LogicalPlanBuilder.from_in_memory(schema, parts))


# ---- file readers --------------------------------------------------------------------


def read_parquet(path: Union[str, List[str]], **options) -> DataFrame:
    from .io.parquet import ParquetScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(ParquetScanOperator(path, **options)))


def read_csv(path: Union[str, List[str]], **options) -> DataFrame:
    from .io.csv import CsvScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(CsvScanOperator(path, **options)))


def read_json(path: Union[str, List[str]], **options) -> DataFrame:
    from .io.json import JsonScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(JsonScanOperator(path, **options)))


def read_text(path: Union[str, List[str]], **options) -> DataFrame:
    """Line-oriented text files (one string column 'text'; .gz supported)."""
    from .io.text import TextScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(TextScanOperator(path, **options)))


def read_warc(path: Union[str, List[str]], **options) -> DataFrame:
    """WARC (Common Crawl) archives: one row per record (.gz supported)."""
    from .io.warc import WarcScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(WarcScanOperator(path, **options)))


def read_iceberg(table_path: str, snapshot_id: "Optional[int]" = None) -> DataFrame:
    """Read an Apache Iceberg table (v1/v2 metadata; Avro manifests parsed
    natively — io/iceberg.py). Identity partition pruning and parquet
    predicate/column pushdowns apply through the optimizer."""
    from .io.iceberg import IcebergScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(
        IcebergScanOperator(table_path, snapshot_id=snapshot_id)))


def read_deltalake(table_path: str) -> DataFrame:
    """Read a Delta Lake table (_delta_log JSON replay + parquet checkpoints —
    io/delta.py). Partition/stats pruning applies through the optimizer;
    partition columns are reconstructed from the log."""
    from .io.delta import DeltaScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(DeltaScanOperator(table_path)))


read_delta_lake = read_deltalake


def read_sql(sql_query: str, connection, partition_col=None,
             num_partitions: int = 1) -> DataFrame:
    """Read the result of a SQL query over a DB-API connection (reference:
    daft.read_sql); stdlib sqlite3 works out of the box."""
    from .io.sql_writer import read_sql as _read

    return _read(sql_query, connection, partition_col, num_partitions)


def read_hudi(table_path: str) -> DataFrame:
    """Read an Apache Hudi copy-on-write table (timeline replay + latest
    file slices per file group — io/hudi.py; reference: daft/io/hudi)."""
    from .io.hudi import HudiScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(HudiScanOperator(table_path)))


def from_glob_path(path: str) -> DataFrame:
    from .io.glob_files import GlobPathScanOperator

    return DataFrame(LogicalPlanBuilder.from_scan(GlobPathScanOperator(path)))


# ---- SQL -----------------------------------------------------------------------------


def sql(query: str, **bindings) -> DataFrame:
    from .sql import sql as _sql

    return _sql(query, **bindings)


def sql_expr(text: str) -> Expression:
    from .sql import sql_expr as _sql_expr

    return _sql_expr(text)


def load_extension(path: str):
    """Load a native extension module (stable C ABI over the Arrow C Data
    Interface — see native/include/daft_tpu_ext.h) and register its scalar
    functions (reference: daft-ext module loading)."""
    from .ext import load_extension as _load

    return _load(path)


def call_function(name: str, *args, **kwargs) -> Expression:
    """Call a registered scalar function (built-in or extension-provided) as
    an expression."""
    from .expressions.expressions import Function
    from .plan.builder import _to_expr

    return Function(name, [_to_expr(a) for a in args], kwargs or None)


def file(path_expr, io_config=None) -> Expression:
    """Build a lazy File column from path/URL strings (reference:
    daft.functions.file)."""
    from .plan.builder import _to_expr

    return _to_expr(path_expr)._fn("file", io_config=io_config)


def from_files(path: str, io_config=None) -> DataFrame:
    """List files matching a glob into a DataFrame with lazy File references
    (reference: daft.from_files — path/size columns + a file handle column).
    Columns: path (string), size (int64), file (File)."""
    from .expressions import col as _col

    df = from_glob_path(path)
    return df.with_columns({
        "file": file(_col("path"), io_config=io_config),
    })


def read_lance(uri: str, **kwargs) -> DataFrame:
    """Read a Lance dataset (requires the `lance` package, like the
    reference's daft.read_lance)."""
    try:
        import lance
    except ImportError as e:
        raise ImportError("read_lance requires the 'lance' package "
                          "(pip install pylance)") from e
    ds = lance.dataset(uri, **kwargs)
    return from_arrow(ds.to_table())
