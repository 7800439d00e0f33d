"""DataFrame: the lazy user-facing API.

Reference parity: daft/dataframe/dataframe.py:115 (~150 methods). Every method
appends to a LogicalPlanBuilder; collect() optimizes, translates and executes,
caching result partitions so downstream queries reuse them (reference's
PartitionSetCache behavior).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Union

from ..core.micropartition import MicroPartition
from ..expressions import AggExpr, Expression, col, lit
from ..plan.builder import ColumnInput, LogicalPlanBuilder, _to_expr, _to_exprs
from ..schema import Schema


class DataFrame:
    def __init__(self, builder: LogicalPlanBuilder):
        self._builder = builder
        self._result: Optional[List[MicroPartition]] = None

    # ---- metadata ----------------------------------------------------------------
    @property
    def schema(self) -> Schema:
        return self._builder.schema()

    @property
    def column_names(self) -> List[str]:
        return self.schema.column_names()

    def __repr__(self) -> str:
        if self._result is not None:
            return self._preview_string()
        return f"DataFrame(schema={self.schema}, not materialized)"

    @property
    def columns(self) -> List[Expression]:
        """Columns as a list of Expressions (reference: DataFrame.columns)."""
        return [col(f.name) for f in self.schema]

    def metrics(self):
        """Per-operator execution metrics of the materialized plan as a
        RecordBatch (reference: DataFrame.metrics). Runs the plan under a
        stats collector if it has not been materialized with one."""
        from ..core.recordbatch import RecordBatch
        from ..observability.runtime_stats import (StatsCollector,
                                                   current_collector,
                                                   set_collector)
        from ..runners import get_or_create_runner

        collector = StatsCollector()
        prev = current_collector()
        set_collector(collector)
        try:
            for _ in get_or_create_runner().run_iter(self._builder):
                pass
        finally:
            set_collector(prev)
        rows: Dict[str, list] = {"operator": [], "rows_out": [], "batches": [],
                                 "self_time_s": []}
        for s in collector.finish():
            rows["operator"].append(s.name)
            rows["rows_out"].append(s.rows_out)
            rows["batches"].append(s.batches_out)
            rows["self_time_s"].append(s.seconds)
        return RecordBatch.from_pydict(rows)

    def explain(self, show_all: bool = False) -> str:
        s = "== Unoptimized Logical Plan ==\n" + self._builder.plan.display()
        if show_all:
            opt = self._builder.optimize()
            s += "\n\n== Optimized Logical Plan ==\n" + opt.plan.display()
            from ..plan.physical import translate

            s += "\n\n== Physical Plan ==\n" + translate(opt.plan).display()
        return s

    def explain_analyze(self, profile: Optional[str] = None) -> str:
        """Execute the plan through the configured runner collecting
        per-operator runtime stats; returns the plans plus an operator table
        (rows out / batches / self time split into compute / starve /
        blocked) — reference: EXPLAIN ANALYZE over runtime_stats. On a
        distributed runner the report additionally renders the stage DAG
        rollup (per-stage task counts, min/median/max task time skew, queue
        wait, shuffle volumes, straggler flags, per-worker attribution) from
        the run's QueryTrace, plus the per-query metrics-registry deltas
        (device batches, shuffle bytes) so engine-path attribution is in the
        report.

        `profile="trace.json"` additionally writes the query's timeline as
        Chrome trace-event JSON (QueryTrace.to_chrome_trace) — open it in
        Perfetto (ui.perfetto.dev) or chrome://tracing. Works on both the
        native runner (driver lanes only) and the distributed runner (plus
        per-worker task lanes and device/io spans)."""
        import json
        import time

        from ..observability.metrics import registry
        from ..observability.runtime_stats import (SpanRecorder, StatsCollector,
                                                   current_collector,
                                                   current_spans, format_stats,
                                                   set_collector, set_spans)
        from ..plan.physical import translate
        from ..runners import get_or_create_runner

        optimized = self._builder.optimize()
        phys = translate(optimized.plan)
        collector = StatsCollector()
        prev = current_collector()
        runner = get_or_create_runner()
        reg_before = registry().snapshot()
        set_collector(collector)
        span_rec = prev_spans = None
        if profile:
            # capture real wall-clock device/io spans for the timeline
            span_rec = SpanRecorder()
            prev_spans = current_spans()
            set_spans(span_rec)
        t_wall0 = time.time()
        t0 = time.perf_counter()
        try:
            for _ in runner.run_iter(self._builder):
                pass
        finally:
            set_collector(prev)
            if profile:
                set_spans(prev_spans)
        total = time.perf_counter() - t0
        stats = collector.finish()
        report = ("== Physical Plan ==\n" + phys.display()
                  + "\n\n== Runtime Stats ==\n"
                  + format_stats(stats, total))
        trace = getattr(runner, "last_trace", None)
        if trace is not None and trace.tasks:
            report += "\n\n== Distributed Stages ==\n" + trace.render()
        deltas = registry().diff(reg_before)
        if deltas:
            report += "\n\n== Engine Counters ==\n" + "\n".join(
                f"{k:<32} {v:>12g}" for k, v in sorted(deltas.items()))
        if profile:
            if trace is None:
                # native runner: synthesize an empty trace for driver lanes
                from ..distributed.trace import QueryTrace

                trace = QueryTrace("")
                trace.started_wall = t_wall0
            data = trace.to_chrome_trace(driver_ops=stats,
                                         driver_spans=span_rec.drain(),
                                         total_seconds=total)
            with open(profile, "w") as f:
                json.dump(data, f)
        return report

    def explain_placement(self) -> str:
        """Execute the plan and report every device-placement decision the
        cost model made: chosen tier, per-term cost tables for every priced
        tier (rtt / h2d / compute / d2h / ici / factorize, residency
        credit), the what-if margin (how close the losing tier was), cache-
        hit vs fresh verdicts, and — for dispatched device stages — the
        observed seconds and per-row model error next to the prediction.
        The raw records also ride QueryEnd.placements (event log schema v9)
        and the process ledger behind the dashboard's /api/placement."""
        from ..observability import placement
        from ..runners import get_or_create_runner

        with placement.query_scope() as scope:
            for _ in get_or_create_runner().run_iter(self._builder):
                pass
        return placement.render(scope.records())

    def _next(self, builder: LogicalPlanBuilder) -> "DataFrame":
        return DataFrame(builder)

    # ---- transforms --------------------------------------------------------------
    def select(self, *columns: ColumnInput) -> "DataFrame":
        exprs = _to_exprs(columns)
        # expand unnest() markers into one column per struct field
        from ..expressions.expressions import Unnest

        if any(isinstance(e, Unnest) for e in exprs):
            schema = self.schema
            expanded = []
            for e in exprs:
                if isinstance(e, Unnest):
                    dt = e.child.to_field(schema).dtype
                    if not dt.is_struct():
                        raise ValueError(f"unnest() requires a struct column, got {dt}")
                    for fname, _ft in dt.struct_fields:
                        expanded.append(e.child.struct.get(fname).alias(fname))
                else:
                    expanded.append(e)
            exprs = expanded
        return self._next(self._builder.select(exprs))

    def with_column(self, name: str, expr: ColumnInput) -> "DataFrame":
        return self.with_columns({name: expr})

    def with_columns(self, columns: Dict[str, ColumnInput]) -> "DataFrame":
        exprs = [_to_expr(e).alias(n) for n, e in columns.items()]
        return self._next(self._builder.with_columns(exprs))

    def with_column_renamed(self, existing: str, new: str) -> "DataFrame":
        return self._next(self._builder.rename({existing: new}))

    def with_columns_renamed(self, mapping: Dict[str, str]) -> "DataFrame":
        return self._next(self._builder.rename(mapping))

    def exclude(self, *names: str) -> "DataFrame":
        return self._next(self._builder.exclude(list(names)))

    def where(self, predicate: ColumnInput) -> "DataFrame":
        if isinstance(predicate, str):
            from ..sql import sql_expr

            predicate = sql_expr(predicate)
        return self._next(self._builder.filter(_to_expr(predicate)))

    filter = where

    def limit(self, n: int) -> "DataFrame":
        return self._next(self._builder.limit(n))

    def offset(self, n: int) -> "DataFrame":
        return self._next(self._builder.offset(n))

    def sample(self, fraction: float, with_replacement: bool = False,
               seed: Optional[int] = None) -> "DataFrame":
        return self._next(self._builder.sample(fraction, with_replacement, seed))

    def explode(self, *columns: ColumnInput) -> "DataFrame":
        return self._next(self._builder.explode(_to_exprs(columns)))

    def unpivot(self, ids: Sequence[ColumnInput], values: Sequence[ColumnInput] = (),
                variable_name: str = "variable", value_name: str = "value") -> "DataFrame":
        ids_ex = _to_exprs(ids if isinstance(ids, (list, tuple)) else [ids])
        if not values:
            id_names = {e.name() for e in ids_ex}
            values = [c for c in self.column_names if c not in id_names]
        vals_ex = _to_exprs(values if isinstance(values, (list, tuple)) else [values])
        return self._next(self._builder.unpivot(ids_ex, vals_ex, variable_name, value_name))

    melt = unpivot

    def distinct(self, *on: ColumnInput) -> "DataFrame":
        return self._next(self._builder.distinct(_to_exprs(on) if on else None))

    unique = distinct
    drop_duplicates = distinct

    def sort(self, by: Union[ColumnInput, List[ColumnInput]],
             desc: Union[bool, List[bool]] = False,
             nulls_first: Optional[Union[bool, List[bool]]] = None) -> "DataFrame":
        by_list = by if isinstance(by, list) else [by]
        return self._next(self._builder.sort(by_list, desc, nulls_first))

    def add_monotonically_increasing_id(self, column_name: str = "id") -> "DataFrame":
        return self._next(self._builder.add_monotonically_increasing_id(column_name))

    _add_monotonically_increasing_id = add_monotonically_increasing_id

    def repartition(self, num: Optional[int], *partition_by: ColumnInput) -> "DataFrame":
        if partition_by:
            return self._next(self._builder.repartition(num, "hash", _to_exprs(partition_by)))
        return self._next(self._builder.repartition(num, "random"))

    def into_partitions(self, num: int) -> "DataFrame":
        return self._next(self._builder.into_partitions(num))

    def into_batches(self, batch_size: int) -> "DataFrame":
        return self._next(self._builder.into_batches(batch_size))

    # ---- joins -------------------------------------------------------------------
    def join(self, other: "DataFrame",
             on: Optional[Union[ColumnInput, List[ColumnInput]]] = None,
             left_on: Optional[Union[ColumnInput, List[ColumnInput]]] = None,
             right_on: Optional[Union[ColumnInput, List[ColumnInput]]] = None,
             how: str = "inner", prefix: Optional[str] = None, suffix: Optional[str] = None,
             strategy: Optional[str] = None, null_equals_null: bool = False) -> "DataFrame":
        if on is not None:
            left_on = right_on = on
        if how == "cross":
            return self._next(self._builder.cross_join(other._builder, prefix, suffix))
        if left_on is None or right_on is None:
            raise ValueError("join requires `on` or both `left_on` and `right_on`")
        lo = left_on if isinstance(left_on, list) else [left_on]
        ro = right_on if isinstance(right_on, list) else [right_on]
        return self._next(self._builder.join(other._builder, lo, ro, how, prefix, suffix,
                                             strategy, null_equals_null))

    def concat(self, other: "DataFrame") -> "DataFrame":
        return self._next(self._builder.concat(other._builder))

    union_all = concat

    def union(self, other: "DataFrame") -> "DataFrame":
        return self.concat(other).distinct()

    def intersect(self, other: "DataFrame") -> "DataFrame":
        # semi join on all columns + distinct (reference: ops/intersect.rs
        # semantics); SQL set ops treat NULL keys as equal
        names = self.column_names
        return self.join(other, left_on=[col(n) for n in names],
                         right_on=[col(n) for n in names], how="semi",
                         null_equals_null=True).distinct()

    def union_by_name(self, other: "DataFrame") -> "DataFrame":
        """Distinct union with columns matched by name (reference:
        DataFrame.union_by_name); columns absent on one side fill with nulls."""
        return self.union_all_by_name(other).distinct()

    def union_all_by_name(self, other: "DataFrame") -> "DataFrame":
        """Union keeping duplicates, columns matched by name; missing columns
        become nulls (reference: DataFrame.union_all_by_name)."""
        from ..expressions import lit

        all_names = list(self.column_names)
        for n in other.column_names:
            if n not in all_names:
                all_names.append(n)

        def conform(df: "DataFrame") -> "DataFrame":
            have = set(df.column_names)
            exprs = []
            for n in all_names:
                if n in have:
                    exprs.append(col(n))
                else:
                    dtype = (other if df is self else self).schema[n].dtype
                    exprs.append(lit(None).cast(dtype).alias(n))
            return df.select(*exprs)

        return conform(self).concat(conform(other))

    def intersect_all(self, other: "DataFrame") -> "DataFrame":
        """INTERSECT ALL: multiset intersection — each row kept min(l, r)
        times. Row-numbering each duplicate within its key group turns the
        multiset op into a plain semi join on (columns..., occurrence#)."""
        return self._multiset_setop(other, "semi")

    def except_all(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT ALL: multiset difference — each row kept max(l - r, 0) times."""
        return self._multiset_setop(other, "anti")

    def _multiset_setop(self, other: "DataFrame", how: str) -> "DataFrame":
        from ..functions import row_number
        from ..window import Window

        names = list(self.column_names)
        w = Window().partition_by(*names).order_by(names[0])
        rn = "__occurrence__"
        left = self.with_column(rn, row_number().over(w))
        right = other.with_column(rn, row_number().over(w))
        keys = [col(n) for n in names] + [col(rn)]
        return left.join(right, left_on=keys, right_on=keys, how=how,
                         null_equals_null=True).select(*[col(n) for n in names])

    def shuffle(self, seed: Optional[int] = None) -> "DataFrame":
        """Randomly reorder rows (reference: DataFrame.shuffle — a global sort
        on a random key)."""
        import random as _random

        from ..expressions import lit

        rng_seed = seed if seed is not None else _random.randrange(2 ** 31)
        tmp = "__shuffle_key__"
        keyed = self.with_column(tmp, (col(self.column_names[0]).hash(seed=rng_seed)
                                       if self.column_names else lit(0)))
        return keyed.sort(tmp).exclude(tmp)

    def except_distinct(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT DISTINCT: rows of self absent from other (NULLs match NULLs,
        per SQL set-op semantics)."""
        names = self.column_names
        return self.join(other, left_on=[col(n) for n in names],
                         right_on=[col(n) for n in names], how="anti",
                         null_equals_null=True).distinct()

    except_ = except_distinct

    # ---- aggregation -------------------------------------------------------------
    def groupby(self, *group_by: ColumnInput) -> "GroupedDataFrame":
        return GroupedDataFrame(self, _to_exprs(group_by))

    group_by = groupby

    def agg(self, *aggs: Expression) -> "DataFrame":
        return self._next(self._builder.aggregate(_flatten_aggs(aggs), []))

    def sum(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).sum() for c in cols])

    def mean(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).mean() for c in cols])

    def min(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).min() for c in cols])

    def max(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).max() for c in cols])

    def count(self, *cols: ColumnInput) -> "DataFrame":
        if not cols:
            return self.agg(lit(1).count("all").alias("count"))
        return self.agg(*[_to_expr(c).count() for c in cols])

    def count_rows(self) -> int:
        return self.count().to_pydict()["count"][0]

    def stddev(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).stddev() for c in cols])

    def var(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).var() for c in cols])

    def skew(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).skew() for c in cols])

    def any_value(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[_to_expr(c).any_value() for c in cols])

    def agg_list(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[AggExpr("list", _to_expr(c)) for c in cols])

    list_agg = agg_list

    def agg_set(self, *cols: ColumnInput) -> "DataFrame":
        """Distinct values per column as lists (reference: DataFrame.agg_set)."""
        return self.agg(*[AggExpr("set", _to_expr(c)) for c in cols])

    list_agg_distinct = agg_set

    def agg_concat(self, *cols: ColumnInput) -> "DataFrame":
        return self.agg(*[AggExpr("concat", _to_expr(c)) for c in cols])

    def string_agg(self, *cols: ColumnInput, delimiter: str = "") -> "DataFrame":
        """Concatenate string values into one string per column (reference:
        DataFrame.string_agg); implemented as list-agg + list.join."""
        names = [_to_expr(c).name() for c in cols]
        out = self.agg(*[AggExpr("list", _to_expr(c)).alias(n)
                         for c, n in zip(cols, names)])
        return out.select(*[col(n).list.join(delimiter).alias(n) for n in names])

    def __len__(self) -> int:
        return self.count_rows()

    def pivot(self, group_by: Union[ColumnInput, List[ColumnInput]], pivot_col: ColumnInput,
              value_col: ColumnInput, agg_fn: str,
              names: Optional[List[str]] = None) -> "DataFrame":
        gb = group_by if isinstance(group_by, list) else [group_by]
        if names is None:
            pc_expr = _to_expr(pivot_col)
            vals = (self.select(pc_expr).distinct().sort(pc_expr.name()).to_pydict())[pc_expr.name()]
            names = [str(v) for v in vals if v is not None]
        return self._next(self._builder.pivot(gb, pivot_col, value_col, agg_fn, names))

    # ---- materialization ---------------------------------------------------------
    def _materialize(self) -> List[MicroPartition]:
        if self._result is None:
            from ..runners import get_or_create_runner

            self._result = get_or_create_runner().run(self._builder)
        return self._result

    def collect(self) -> "DataFrame":
        parts = self._materialize()
        # pin results into the plan so downstream ops read from memory
        new = DataFrame(LogicalPlanBuilder.from_in_memory(self.schema, parts))
        new._result = parts
        return new

    def iter_partitions(self) -> Iterator[MicroPartition]:
        if self._result is not None:
            yield from self._result
            return
        from ..runners import get_or_create_runner

        yield from get_or_create_runner().run_iter(self._builder)

    def iter_rows(self) -> Iterator[dict]:
        for part in self.iter_partitions():
            for b in part.batches:
                yield from b.to_pylist()

    def __iter__(self):
        return self.iter_rows()

    def show(self, n: int = 8) -> None:
        print(self.limit(n)._preview_string(n))

    def _preview_string(self, n: int = 8) -> str:
        parts = self.limit(n)._materialize()
        mp = MicroPartition.concat(parts) if parts else MicroPartition.empty(self.schema)
        return _format_table(mp, self.schema)

    # ---- conversions -------------------------------------------------------------
    def _encoded(self, convert):
        """The result as one client-side object: run the query (once), then
        concat its partitions and `convert` them — the `result.encode` span,
        a root of its own that shares the query's qid."""
        from ..observability.runtime_stats import profile_span, qid_scope

        with qid_scope():
            parts = self._materialize()
            with profile_span("result.encode", "host") as sp:
                mp = MicroPartition.concat(parts) if parts \
                    else MicroPartition.empty(self.schema)
                if sp is not None:
                    sp.args["rows"] = mp.num_rows
                return convert(mp)

    def to_pydict(self) -> Dict[str, list]:
        return self._encoded(MicroPartition.to_pydict)

    def to_pylist(self) -> List[dict]:
        return list(self.iter_rows())

    def to_arrow(self):
        return self._encoded(MicroPartition.to_arrow)

    def to_arrow_iter(self):
        for part in self.iter_partitions():
            for b in part.batches:
                yield from b.to_arrow().to_batches()

    def to_pandas(self):
        return self._encoded(lambda mp: mp.to_arrow().to_pandas())

    def to_torch_map_dataset(self):
        from .to_torch import DataFrameMapDataset

        return DataFrameMapDataset(self)

    def to_torch_iter_dataset(self):
        from .to_torch import DataFrameIterDataset

        return DataFrameIterDataset(self)

    def to_jax(self, pad_to: Optional[int] = None) -> Dict[str, Any]:
        """Materialize device-compatible columns as jax Arrays (host→HBM transfer)."""
        parts = self._materialize()
        mp = MicroPartition.concat(parts) if parts else MicroPartition.empty(self.schema)
        batch = mp.concat_or_empty()
        out = {}
        for s in batch.columns:
            if s.dtype.is_device_compatible():
                out[s.name] = s.to_device(pad_to=pad_to)
        return out

    # ---- writes ------------------------------------------------------------------
    def write_parquet(self, root_dir: str, compression: str = "snappy",
                      partition_cols: Optional[List[ColumnInput]] = None,
                      write_mode: str = "append", checkpoint=None) -> "DataFrame":
        """checkpoint=(CheckpointStore, key_column) enables resume: rows whose
        key a prior run sealed are skipped (reference: daft-checkpoint)."""
        from ..io.writers import WriteInfo

        info = WriteInfo("parquet", root_dir, {"compression": compression},
                         _to_exprs(partition_cols) if partition_cols else None, write_mode,
                         checkpoint=checkpoint)
        return self._write(info)

    def write_csv(self, root_dir: str, partition_cols: Optional[List[ColumnInput]] = None,
                  write_mode: str = "append") -> "DataFrame":
        from ..io.writers import WriteInfo

        info = WriteInfo("csv", root_dir, {},
                         _to_exprs(partition_cols) if partition_cols else None, write_mode)
        return self._write(info)

    def write_json(self, root_dir: str, write_mode: str = "append") -> "DataFrame":
        from ..io.writers import WriteInfo

        info = WriteInfo("json", root_dir, {}, None, write_mode)
        return self._write(info)

    def pipe(self, fn, *args, **kwargs):
        """Apply fn(self, *args, **kwargs) — fluent composition helper."""
        return fn(self, *args, **kwargs)

    def transform(self, fn, *args, **kwargs) -> "DataFrame":
        out = fn(self, *args, **kwargs)
        if not isinstance(out, DataFrame):
            raise ValueError(f"transform fn must return a DataFrame, got {type(out).__name__}")
        return out

    def drop_null(self, *cols: ColumnInput) -> "DataFrame":
        """Drop rows with nulls in the given columns (all columns if none)."""
        exprs = _to_exprs(cols) if cols else [_to_expr(c) for c in self.column_names]
        pred = exprs[0].not_null()
        for e in exprs[1:]:
            pred = pred & e.not_null()
        return self.where(pred)

    def drop_nan(self, *cols: ColumnInput) -> "DataFrame":
        """Drop rows with NaNs in the given float columns (all float columns
        if none)."""
        if cols:
            exprs = _to_exprs(cols)
        else:
            exprs = [_to_expr(f.name) for f in self.schema if f.dtype.is_floating()]
        if not exprs:
            return self
        pred = None
        for e in exprs:
            c = e.is_null() | ~e.float.is_nan()
            pred = c if pred is None else pred & c
        return self.where(pred)

    def describe(self) -> "DataFrame":
        """Per-numeric-column summary: count / mean / stddev / min / max
        (reference: DataFrame.describe / summarize)."""
        from ..expressions import col as _col

        aggs = []
        for f in self.schema:
            if f.dtype.is_numeric() and not f.dtype.is_decimal():
                c = _col(f.name)
                aggs += [c.count().alias(f"{f.name}_count"),
                         c.mean().alias(f"{f.name}_mean"),
                         c.stddev().alias(f"{f.name}_stddev"),
                         c.min().alias(f"{f.name}_min"),
                         c.max().alias(f"{f.name}_max")]
        if not aggs:
            raise ValueError("describe() needs at least one numeric column")
        return self.agg(*aggs)

    summarize = describe

    def write_sink(self, sink) -> "DataFrame":
        """Write through a custom DataSink (reference: daft/io/sink.py —
        start() once, write() per partition, finalize() -> result table)."""
        from ..io.sink import _SinkWriteInfo

        return self._write(_SinkWriteInfo(sink))

    def write_deltalake(self, table_path: str, mode: str = "append",
                        partition_cols: Optional[List[str]] = None) -> "DataFrame":
        """Write as a Delta Lake table: parquet data files + a JSON
        transaction-log commit (reference: DataFrame.write_deltalake)."""
        from ..io.delta import write_deltalake

        return write_deltalake(self, table_path, mode, partition_cols)

    def write_iceberg(self, table_path: str, mode: str = "append",
                      partition_cols: Optional[List[str]] = None) -> "DataFrame":
        """Write as an Iceberg v2 table: parquet data files + Avro manifests +
        metadata JSON (reference: DataFrame.write_iceberg via pyiceberg)."""
        from ..io.iceberg import write_iceberg

        return write_iceberg(self, table_path, mode, partition_cols)

    def write_sql(self, table_name: str, connection,
                  mode: str = "append") -> "DataFrame":
        """Write rows into a SQL table through a DB-API connection or a
        zero-arg connection factory (reference: DataFrame.write_sql via
        SQLAlchemy; here plain DB-API keeps it dependency-free — sqlite3
        from the stdlib works out of the box)."""
        from ..io.sql_writer import write_sql

        return write_sql(self, table_name, connection, mode)

    def write_lance(self, uri: str, mode: str = "create", **kwargs) -> "DataFrame":
        """Write a Lance dataset (requires the `lance` package, like the
        reference's DataFrame.write_lance)."""
        try:
            import lance
        except ImportError as e:
            raise ImportError(
                "write_lance requires the 'lance' package (pip install pylance)"
            ) from e
        table = self.to_arrow()
        lance.write_dataset(table, uri, mode=mode, **kwargs)
        import daft_tpu

        return daft_tpu.from_pydict({"uri": [uri], "rows": [table.num_rows]})

    def write_huggingface(self, repo_id: str, **kwargs) -> "DataFrame":
        """Push to a HuggingFace dataset repo (requires `huggingface_hub`,
        like the reference's DataFrame.write_huggingface)."""
        try:
            from huggingface_hub import HfApi  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "write_huggingface requires the 'huggingface_hub' package"
            ) from e
        raise NotImplementedError(
            "huggingface_hub is available but this build has no network egress; "
            "use write_parquet + huggingface_hub.upload_file")

    def skip_existing(self, existing_path, key_column: Union[str, List[str]],
                      file_format: str = "parquet") -> "DataFrame":
        """Drop rows whose key already appears in previously-written output
        (reference: DataFrame.skip_existing — resume semantics for bulk
        writes). Reads only the key column(s) from existing_path."""
        import daft_tpu

        keys = [key_column] if isinstance(key_column, str) else list(key_column)
        paths = existing_path if isinstance(existing_path, list) else [existing_path]
        readers = {"parquet": daft_tpu.read_parquet, "csv": daft_tpu.read_csv,
                   "json": daft_tpu.read_json}
        if file_format not in readers:
            raise ValueError(f"unsupported file_format {file_format!r}")
        import glob as _glob
        import os as _os

        existing = None
        for p in paths:
            if _os.path.isdir(p):
                ext = "json" if file_format == "json" else file_format
                files = sorted(_glob.glob(_os.path.join(p, f"**/*.{ext}"),
                                          recursive=True))
            else:
                files = [p] if _os.path.exists(p) else []
            for fp in files:
                part = readers[file_format](fp).select(*[col(k) for k in keys])
                existing = part if existing is None else existing.concat(part)
        if existing is None:
            return self
        kexprs = [col(k) for k in keys]
        return self.join(existing.distinct(), left_on=kexprs, right_on=kexprs,
                         how="anti")

    # ---- external-framework conversions -------------------------------------------
    def to_ray_dataset(self):
        """Convert to a Ray Dataset (requires `ray`, like the reference's
        DataFrame.to_ray_dataset)."""
        try:
            import ray.data
        except ImportError as e:
            raise ImportError("to_ray_dataset requires the 'ray' package") from e
        return ray.data.from_arrow(self.to_arrow())

    def to_dask_dataframe(self, npartitions: Optional[int] = None):
        """Convert to a Dask DataFrame (requires `dask`, like the reference's
        DataFrame.to_dask_dataframe)."""
        try:
            import dask.dataframe as dd
        except ImportError as e:
            raise ImportError("to_dask_dataframe requires the 'dask' package") from e
        return dd.from_pandas(self.to_pandas(),
                              npartitions=npartitions or max(self.num_partitions(), 1))

    def _write(self, info) -> "DataFrame":
        return DataFrame(self._builder.write(info)).collect()

    # ---- misc --------------------------------------------------------------------
    def num_partitions(self) -> int:
        if self._result is not None:
            return len(self._result)
        return 1


class GroupedDataFrame:
    def __init__(self, df: DataFrame, group_by: List[Expression]):
        self._df = df
        self._group_by = group_by

    def agg(self, *aggs: Expression) -> DataFrame:
        return self._df._next(self._df._builder.aggregate(_flatten_aggs(aggs), self._group_by))

    def sum(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).sum() for c in cols])

    def mean(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).mean() for c in cols])

    def min(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).min() for c in cols])

    def max(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).max() for c in cols])

    def count(self, *cols: ColumnInput) -> DataFrame:
        if not cols:
            return self.agg(lit(1).count("all").alias("count"))
        return self.agg(*[_to_expr(c).count() for c in cols])

    def any_value(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).any_value() for c in cols])

    def agg_list(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[AggExpr("list", _to_expr(c)) for c in cols])

    def agg_concat(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[AggExpr("concat", _to_expr(c)) for c in cols])

    def agg_set(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[AggExpr("set", _to_expr(c)) for c in cols])

    list_agg = agg_list
    list_agg_distinct = agg_set

    def stddev(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).stddev() for c in cols])

    def var(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).var() for c in cols])

    def skew(self, *cols: ColumnInput) -> DataFrame:
        return self.agg(*[_to_expr(c).skew() for c in cols])

    def string_agg(self, *cols: ColumnInput, delimiter: str = "") -> DataFrame:
        names = [_to_expr(c).name() for c in cols]
        gnames = [e.name() for e in self._group_by]
        out = self.agg(*[AggExpr("list", _to_expr(c)).alias(n)
                         for c, n in zip(cols, names)])
        from ..expressions import col as _col

        keep = [_col(n) for n in gnames]
        keep += [_col(n).list.join(delimiter).alias(n) for n in names]
        return out.select(*keep)

    def map_groups(self, udf_expr: Expression) -> DataFrame:
        """Apply a UDF to each group's rows; the UDF may emit any number of
        rows per group (reference: GroupedDataFrame.map_groups)."""
        from ..plan import logical as lp

        df = self._df
        plan = lp.MapGroups(df._builder.plan, self._group_by, udf_expr)
        return df._next(df._builder._next(plan))


def _flatten_aggs(aggs) -> List[Expression]:
    out: List[Expression] = []
    for a in aggs:
        if isinstance(a, (list, tuple)):
            out.extend(_flatten_aggs(a))
        else:
            out.append(a)
    return out


def _format_table(mp: MicroPartition, schema: Schema, max_width: int = 30) -> str:
    d = mp.to_pydict()
    names = schema.column_names()
    dtypes = [str(schema[n].dtype) for n in names]
    rows = mp.num_rows

    def fmt(v) -> str:
        s = "None" if v is None else str(v)
        return s if len(s) <= max_width else s[: max_width - 1] + "…"

    cols = [[fmt(v) for v in d[n]] for n in names]
    widths = [max(len(n), len(t), *(len(v) for v in c) if c else (0,)) for n, t, c in zip(names, dtypes, cols)]
    sep = "╭" + "┬".join("─" * (w + 2) for w in widths) + "╮"
    mid = "├" + "┼".join("─" * (w + 2) for w in widths) + "┤"
    bot = "╰" + "┴".join("─" * (w + 2) for w in widths) + "╯"
    lines = [sep]
    lines.append("│" + "│".join(f" {n:<{w}} " for n, w in zip(names, widths)) + "│")
    lines.append("│" + "│".join(f" {t:<{w}} " for t, w in zip(dtypes, widths)) + "│")
    lines.append(mid)
    for i in range(rows):
        lines.append("│" + "│".join(f" {c[i]:<{w}} " for c, w in zip(cols, widths)) + "│")
    lines.append(bot)
    lines.append(f"(Showing {rows} rows)")
    return "\n".join(lines)
