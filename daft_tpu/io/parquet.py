"""Parquet scan operator.

Reference parity: src/daft-parquet/src/read.rs:440,490 (bulk + streaming reads,
row-group pruning via statistics) and src/daft-scan/src/glob.rs. Host-side IO is
pyarrow-backed; tasks split per file (and per row-group for large files) so the
executor can parallelize and the optimizer's pushdowns (columns/filters/limit)
prune IO before any byte is read.
"""

from __future__ import annotations

import os
from typing import Any, Iterator, List, Optional, Tuple, Union

import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from ..core.micropartition import MicroPartition
from ..observability.metrics import registry
from ..observability.runtime_stats import profile_span
from ..schema import Schema
from .paths import expand_paths
from .scan import Pushdowns, ScanOperator, ScanTask

def _scan_batch_rows() -> int:
    """Target rows per emitted MicroPartition batch chunk — the config's
    morsel_size_rows (read at READ time, not plan time, so DAFT_TPU_MORSEL_SIZE
    and batching-strategy resizes reach scan-fed pipelines). Was a hardcoded
    128Ki that silently ignored the knob (PR 4 unified the executor's
    partial-agg splitter; this closes the scan side)."""
    from ..config import execution_config

    return max(execution_config().morsel_size_rows, 1)


class ParquetScanOperator(ScanOperator):
    def __init__(self, path: Union[str, List[str]], schema: Optional[Schema] = None,
                 row_groups_per_task: Optional[int] = None, **_options):
        with profile_span("scan.plan", "scan", step="glob") as sp:
            self._paths = expand_paths(path, (".parquet", ".pq"))
            if sp is not None:
                sp.args["files"] = len(self._paths)
        if not self._paths:
            raise FileNotFoundError(f"no parquet files matched {path!r}")
        self._schema = schema
        self._row_groups_per_task = row_groups_per_task

    def name(self) -> str:
        return f"ParquetScan({len(self._paths)} files)"

    def schema(self) -> Schema:
        if self._schema is None:
            from .object_store import open_input

            # schema inference from the first file (reference: schema_inference.rs);
            # remote objects read only the footer via ranged reads
            with profile_span("scan.plan", "scan", step="schema", files=1):
                self._schema = Schema.from_arrow(pq.read_schema(open_input(self._paths[0])))
        return self._schema

    def can_absorb_select(self) -> bool:
        return True

    def can_absorb_filter(self) -> bool:
        return True

    def can_absorb_limit(self) -> bool:
        return True

    def approx_num_rows(self, pushdowns: Pushdowns) -> Optional[float]:
        from .object_store import open_input

        total = 0
        for p in self._paths:
            try:
                total += pq.ParquetFile(open_input(p)).metadata.num_rows
            except Exception:  # lint: ignore[broad-except] -- row estimate is advisory
                return None
        if pushdowns.limit is not None:
            total = min(total, pushdowns.limit)
        return float(total)

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        with profile_span("scan.plan", "scan", step="tasks") as sp:
            tasks, pruned = self._plan_tasks(pushdowns)
            if sp is not None:
                sp.args.update(files=len(self._paths), tasks=len(tasks),
                               row_groups_pruned=pruned)
        if pruned:
            registry().inc("scan_row_groups_pruned", pruned)
        return tasks

    def _plan_tasks(self, pushdowns: Pushdowns):
        """(the scan tasks, row groups whose statistics proved them empty of
        matches and that no task reads)."""
        schema = self.schema()
        columns = pushdowns.columns
        out_schema = Schema([schema[c] for c in columns]) if columns is not None else schema
        arrow_filter = _expr_to_arrow_filter(pushdowns.filters) if pushdowns.filters is not None else None

        from ..config import execution_config
        from .object_store import is_remote

        split_bytes = execution_config().scan_split_bytes
        tasks = []
        pruned = 0
        conjuncts = _zone_map_conjuncts(pushdowns.filters) if pushdowns.filters is not None else []
        filter_columns = _referenced_columns(pushdowns.filters) \
            if arrow_filter is not None else frozenset()
        for path in self._paths:
            remote = is_remote(path)
            size = os.path.getsize(path) if os.path.exists(path) else None
            want_split = (not remote and size is not None
                          and (self._row_groups_per_task is not None
                               or (split_bytes and size > split_bytes)))
            # one footer parse per local file serves BOTH zone-map pruning
            # and split planning (a filtered many-file scan used to pay two)
            md = _local_metadata(path) if not remote and (conjuncts or want_split) \
                else None
            if conjuncts:
                if md is not None:
                    if _prunable_md(md, conjuncts):
                        pruned += md.num_row_groups
                        continue  # zone map proved no row can match
                elif remote:
                    groups = _file_prunable(path, conjuncts)
                    if groups:
                        pruned += groups
                        continue  # same proof via ranged footer reads
            if want_split and md is not None:
                split, excluded = _row_group_split_tasks(
                    path, md, columns, out_schema, conjuncts,
                    split_bytes or size, self._row_groups_per_task)
                if split is not None:
                    tasks.extend(split)
                    pruned += excluded
                    continue
            tasks.append(ScanTask(
                read=_make_reader(path, columns, arrow_filter, pushdowns.limit, out_schema,
                                  md=md, filter_columns=filter_columns),
                schema=out_schema,
                size_bytes=size,
                # remote readers don't evaluate the predicate; the executor
                # re-applies it post-scan
                filters_applied=arrow_filter is not None and not is_remote(path),
                limit_applied=False,
                source_label=path,
            ))
        return tasks, pruned


def _referenced_columns(expr) -> frozenset:
    from ..expressions import ColumnRef

    return frozenset(e._name for e in expr.walk() if isinstance(e, ColumnRef))


# a column chunk that the writer dictionary-encoded throughout and whose
# pages hold at most this many bytes a value is read as a dictionary
_DICT_READ_MAX_BYTES_PER_VALUE = 2


def _dictionary_candidates(columns, out_schema: Schema, skip=frozenset()) -> List[str]:
    """The string and binary columns a reader is asked for, less `skip`."""
    return sorted(f.name for f in out_schema
                  if (f.dtype.is_string() or f.dtype.is_binary())
                  and f.name not in skip
                  and (columns is None or f.name in columns))


def _dictionary_columns(md, candidates: List[str]) -> List[str]:
    """Those of `candidates` to read AS dictionaries (`read_dictionary`): the
    columns every row group of the file stores dictionary-encoded in a few
    bits a value (a writer that fell back to plain pages for a chunk makes it
    large). pyarrow then hands over the file's own codes instead of building
    every string, and `Series.from_arrow` keeps them as the column's
    `dict_codes`, so a grouped stage fed from the scan does not hash a key
    column again in every morsel. [] on any doubt."""
    if md is None or not candidates or md.num_row_groups == 0:
        return []
    try:
        rg0 = md.row_group(0)
        index = {rg0.column(i).path_in_schema: i for i in range(rg0.num_columns)}
        out = []
        for name in candidates:
            i = index.get(name)
            if i is None:
                continue
            chunks = [md.row_group(g).column(i) for g in range(md.num_row_groups)]
            if all(c.has_dictionary_page and c.total_uncompressed_size
                   <= _DICT_READ_MAX_BYTES_PER_VALUE * max(c.num_values, 1)
                   for c in chunks):
                out.append(name)
        return out
    except Exception:  # lint: ignore[broad-except] -- unreadable statistics: read plain
        return []


def _zone_map_conjuncts(expr) -> List[tuple]:
    """Extract (column, op, literal) constraints usable against row-group
    min/max statistics (reference: daft-parquet statistics/ + daft-stats
    zone-map pruning). Only top-level AND conjuncts of simple comparisons."""
    from ..expressions import Between, BinaryOp, ColumnRef, Literal

    out = []

    def walk(e):
        if isinstance(e, BinaryOp) and e.op == "and":
            walk(e.left)
            walk(e.right)
            return
        if isinstance(e, BinaryOp) and e.op in ("lt", "le", "gt", "ge", "eq"):
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}
            if isinstance(e.left, ColumnRef) and isinstance(e.right, Literal):
                out.append((e.left._name, e.op, e.right.value))
            elif isinstance(e.right, ColumnRef) and isinstance(e.left, Literal):
                out.append((e.right._name, flip[e.op], e.left.value))
            return
        if isinstance(e, Between) and isinstance(e.child, ColumnRef):
            if isinstance(e.lower, Literal) and isinstance(e.upper, Literal):
                out.append((e.child._name, "ge", e.lower.value))
                out.append((e.child._name, "le", e.upper.value))

    walk(expr)
    return out


def _rg_excluded(rg, conjuncts: List[tuple]) -> bool:
    """True iff row-group statistics PROVE no row in `rg` satisfies some
    conjunct (shared by file-level pruning and split planning)."""
    cols = {rg.column(i).path_in_schema: rg.column(i).statistics
            for i in range(rg.num_columns)}
    for name, op, value in conjuncts:
        st = cols.get(name)
        if st is None or not st.has_min_max:
            continue
        try:
            if op == "lt" and not (st.min < value):
                return True
            if op == "le" and not (st.min <= value):
                return True
            if op == "gt" and not (st.max > value):
                return True
            if op == "ge" and not (st.max >= value):
                return True
            if op == "eq" and not (st.min <= value <= st.max):
                return True
        except TypeError:
            continue  # incomparable stats (e.g. logical-type mismatch)
    return False


def _local_metadata(path: str):
    """Parsed footer metadata of a LOCAL parquet file, or None when the
    footer is unreadable (callers degrade to whole-file/no-prune planning)."""
    try:
        return pq.ParquetFile(path).metadata
    except Exception:  # lint: ignore[broad-except] -- unreadable footer: plan without metadata
        return None


def _prunable_md(md, conjuncts: List[tuple]) -> bool:
    """True iff the statistics in `md` PROVE no row satisfies the predicate
    — every row group must be excluded by some conjunct."""
    for rg_i in range(md.num_row_groups):
        if not _rg_excluded(md.row_group(rg_i), conjuncts):
            return False  # this row group might match
    return md.num_row_groups > 0


def _file_prunable(path: str, conjuncts: List[tuple]) -> int:
    """Remote-object variant of _prunable_md: reads just the footer via
    ranged gets; never prunes on metadata trouble. Returns the row groups
    the file's pruning spares (0: the file has to be read)."""
    from .object_store import open_input

    try:
        md = pq.ParquetFile(open_input(path)).metadata
        return md.num_row_groups if _prunable_md(md, conjuncts) else 0
    except Exception:  # lint: ignore[broad-except] -- never prune on metadata trouble
        return 0


def _row_group_split_tasks(path: str, md, columns, out_schema: Schema,
                           conjuncts: List[tuple], split_bytes: int,
                           row_groups_per_task: Optional[int]
                           ) -> Tuple[Optional[List[ScanTask]], int]:
    """Split one large local parquet file into row-group-aligned ScanTasks
    so no single scan task materializes more than ~split_bytes (reference:
    daft-scan's ScanTask-per-row-group splitting). `md` is the caller's
    already-parsed footer metadata. Row groups a zone-map conjunct excludes
    are dropped at plan time. Returns (tasks, row groups dropped); tasks is
    None when the file can't split (one row group, everything pruned into
    one task) — the caller falls back to the whole-file task, which reads
    every row group.

    Split tasks read via ``ParquetFile.iter_batches(row_groups=...)`` with
    column pruning but WITHOUT the arrow predicate (``filters_applied`` is
    False, so the executor re-applies the pushed filter post-scan — exactly
    the remote-reader contract)."""
    if md.num_row_groups <= 1:
        return None, 0
    groups: List[List[int]] = []
    sizes: List[int] = []
    rows: List[int] = []
    cur: List[int] = []
    cur_bytes = cur_rows = 0
    for rg_i in range(md.num_row_groups):
        rg = md.row_group(rg_i)
        if conjuncts and _rg_excluded(rg, conjuncts):
            continue  # zone map: no row in this group can match
        # ON-DISK bytes (compressed), not rg.total_byte_size (uncompressed):
        # whole-file tasks report file size, and planner byte estimates /
        # task merging must see one unit, or the same table looks several
        # times bigger once split (flipping broadcast-join eligibility)
        nb = sum(rg.column(ci).total_compressed_size
                 for ci in range(rg.num_columns))
        if cur and (cur_bytes + nb > split_bytes
                    or (row_groups_per_task is not None
                        and len(cur) >= row_groups_per_task)):
            groups.append(cur)
            sizes.append(cur_bytes)
            rows.append(cur_rows)
            cur, cur_bytes, cur_rows = [], 0, 0
        cur.append(rg_i)
        cur_bytes += nb
        cur_rows += rg.num_rows
    if cur:
        groups.append(cur)
        sizes.append(cur_bytes)
        rows.append(cur_rows)
    if len(groups) <= 1:
        return None, 0

    dict_cols = _dictionary_columns(md, _dictionary_candidates(columns, out_schema))

    def make_read(rgs: List[int]):
        def read():
            pf = pq.ParquetFile(path, read_dictionary=dict_cols or None)
            yield from _decoded(pf.iter_batches(batch_size=_scan_batch_rows(),
                                                row_groups=rgs, columns=columns),
                                out_schema, None, len(rgs))

        return _maybe_prefetch(read)

    registry().inc("scan_tasks_split", len(groups))
    return [
        ScanTask(
            read=make_read(g),
            schema=out_schema,
            size_bytes=nb,
            num_rows=nr,
            filters_applied=False,
            limit_applied=False,
            source_label=f"{path}[rg{g[0]}..{g[-1]}]",
        )
        for g, nb, nr in zip(groups, sizes, rows)
    ], md.num_row_groups - sum(len(g) for g in groups)


def _maybe_prefetch(read_factory):
    """Budgeted decode-ahead for scan readers: under a host memory budget,
    run the parquet decode loop on the spill IO pool with a depth-bounded
    queue (DAFT_TPU_SPILL_PREFETCH_BATCHES), overlapping decompress with the
    operators consuming the scan. Unbudgeted queries get the factory back
    untouched — they never see the pool, queue, or counters (the
    zero-overhead guard); the budget check runs at READ time, not task-build
    time, so tasks built outside a query scope still honor the budget their
    executing query runs under."""

    def read_prefetched():
        from ..config import execution_config
        from ..memory.manager import manager

        cfg = execution_config()
        if (manager().limit_bytes() > 0 and cfg.spill_io_threads > 0
                and cfg.spill_prefetch_batches > 0):
            from ..memory.spill import prefetch_iter

            yield from prefetch_iter(read_factory, cfg.spill_prefetch_batches,
                                     cfg.spill_io_threads, counters=False)
        else:
            yield from read_factory()

    return read_prefetched


def _decoded(batches, out_schema: Schema, limit: Optional[int],
             row_groups: Optional[int]) -> Iterator[MicroPartition]:
    """The morsels of one scan task: one MicroPartition for each record batch
    pyarrow decodes, up to `limit` rows. Each pull of `batches` (read,
    decompress, decode) and the wrapping of what it gave is one `scan.decode`
    span, a leaf under the task's `scan.stream`; the pull that finds the
    input exhausted is one too (a pushed-down filter can read a whole file to
    find no further match). Counts what was decoded (`RecordBatch.nbytes` of
    what pyarrow returned: no walk of the buffers) and the row groups the
    task was given, once, as the task ends."""
    it = iter(batches)
    produced = decoded = 0
    try:
        while limit is None or produced < limit:
            with profile_span("scan.decode", "scan") as sp:
                rb = next(it, None)
                if rb is None:
                    return
                nbytes = rb.nbytes
                decoded += nbytes
                t = pa.Table.from_batches([rb])
                if limit is not None and produced + t.num_rows > limit:
                    t = t.slice(0, limit - produced)
                produced += t.num_rows
                mp = MicroPartition.from_arrow(t).cast_to_schema(out_schema)
                if sp is not None:
                    sp.args.update(rows=t.num_rows, bytes=nbytes)
            yield mp
    finally:
        reg = registry()
        if decoded:
            reg.inc("scan_decoded_bytes", decoded)
        if row_groups:
            reg.inc("scan_row_groups", row_groups)


def _make_reader(path: str, columns, arrow_filter, limit, out_schema: Schema,
                 md=None, filter_columns=frozenset()):
    """The reader of one whole file. `md`: the file's footer, where the plan
    has read it already (else the reader does). `filter_columns`: what
    `arrow_filter` reads."""
    from .object_store import is_remote

    if is_remote(path):
        def read_remote():
            from .object_store import open_input

            # ranged-read file: column pruning downloads only touched byte
            # ranges; predicate re-applied by the executor (filters_applied is
            # False for remote tasks)
            pf = pq.ParquetFile(open_input(path))
            dict_cols = _dictionary_columns(
                pf.metadata, _dictionary_candidates(columns, out_schema))
            if dict_cols:
                pf = pq.ParquetFile(open_input(path), metadata=pf.metadata,
                                    read_dictionary=dict_cols)
            yield from _decoded(
                pf.iter_batches(batch_size=_scan_batch_rows(), columns=columns),
                out_schema, limit, pf.metadata.num_row_groups)

        return _maybe_prefetch(read_remote)

    def read():
        # not the columns the scanner evaluates the pushed-down filter on
        candidates = _dictionary_candidates(columns, out_schema, filter_columns)
        footer = md if md is not None or not candidates else _local_metadata(path)
        dict_cols = _dictionary_columns(footer, candidates)
        fmt = pads.ParquetFileFormat(read_options=pads.ParquetReadOptions(
            dictionary_columns=dict_cols)) if dict_cols else "parquet"
        ds = pads.dataset(path, format=fmt)
        scanner = ds.scanner(columns=columns, filter=arrow_filter,
                             batch_size=_scan_batch_rows())
        groups = footer.num_row_groups if footer is not None else sum(
            f.num_row_groups for f in ds.get_fragments())
        yield from _decoded(scanner.to_batches(), out_schema, limit, groups)

    return _maybe_prefetch(read)


def _expr_to_arrow_filter(expr) -> Optional[pads.Expression]:
    """Best-effort translation of our Expression IR to a pyarrow dataset filter.
    Returns None when any node has no arrow equivalent (filter then re-applied
    post-scan by the executor; translate() checks filters_applied)."""
    import pyarrow.compute as pc

    from ..expressions import Between, BinaryOp, ColumnRef, IsIn, Literal, UnaryOp

    def conv(e):
        if isinstance(e, ColumnRef):
            return pads.field(e._name)
        if isinstance(e, Literal):
            return pa.scalar(e.value)
        if isinstance(e, BinaryOp):
            l, r = conv(e.left), conv(e.right)
            if l is None or r is None:
                return None
            ops = {
                "add": lambda a, b: a + b, "sub": lambda a, b: a - b,
                "mul": lambda a, b: a * b, "div": lambda a, b: a / b,
                "eq": lambda a, b: a == b, "neq": lambda a, b: a != b,
                "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
                "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
                "and": lambda a, b: a & b, "or": lambda a, b: a | b,
            }
            f = ops.get(e.op)
            return f(l, r) if f else None
        if isinstance(e, UnaryOp):
            c = conv(e.child)
            if c is None:
                return None
            if e.op == "not":
                return ~c
            if e.op == "is_null":
                return c.is_null()
            if e.op == "not_null":
                return c.is_valid()
            return None
        if isinstance(e, Between):
            c, lo, hi = conv(e.child), conv(e.lower), conv(e.upper)
            if c is None or lo is None or hi is None:
                return None
            return (c >= lo) & (c <= hi)
        if isinstance(e, IsIn):
            c = conv(e.child)
            vals = []
            for item in e.items:
                if not isinstance(item, Literal):
                    return None
                vals.append(item.value)
            return c.isin(vals) if c is not None else None
        return None

    try:
        return conv(expr)
    except Exception:  # lint: ignore[broad-except] -- unconvertible filter: scan without pushdown
        return None
