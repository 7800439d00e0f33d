"""Single-host streaming executor.

Reference parity: src/daft-local-execution ("Swordfish", run.rs:397 + pipeline.rs:358).
This is the pull-based core: each physical node is interpreted as a generator of
MicroPartitions, so streaming ops (project/filter/limit) never materialize the
whole input, while blocking ops (sort/agg/join build side) gather what they need.

Device (TPU) execution: the planner lowers qualifying (filter+)aggregate chains
to DeviceFilterAgg / DeviceGroupedAgg nodes (plan/physical.py translate); this
executor runs them on the JAX device via ops/stage.py / ops/grouped_stage.py when
the config allows (device_mode on, or auto with a large-enough first morsel and a
real accelerator backend), with a semantics-identical host fallback otherwise.
ops/counters.py records which path actually ran.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import threading
from typing import Iterator, List, Optional

import numpy as np

from ..core import relational as rel
from ..core.micropartition import MicroPartition
from ..core.recordbatch import RecordBatch
from ..device.residency import expr_structure, identity_token
from ..expressions import ColumnRef, Expression
from ..expressions.eval import eval_expression, eval_projection
from ..observability import placement as _placement
from ..observability.runtime_stats import profile_span as _profile_span
from ..ops import costmodel as _costmodel
from ..plan import physical as pp
from ..utils.env import env_bool as _env_bool


def execute_plan(plan: pp.PhysicalPlan) -> Iterator[MicroPartition]:
    """Stream result MicroPartitions for a physical plan."""
    return _exec(plan)


def _exec(node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
    """Dispatch one physical node; wraps its stream with per-operator runtime
    stats when a collector is active (subscribers / explain_analyze), else the
    zero-overhead direct generator. In pipeline mode (config.pipeline_mode ==
    "on", the default) substantial operators additionally run on their own
    stage thread behind a bounded channel, so the whole plan executes as
    concurrent tasks with backpressure (reference: pipeline.rs:358 +
    channel.rs)."""
    from ..observability.runtime_stats import current_collector, span_iter

    c = current_collector()
    gen = _exec_impl(node)
    if c is not None:
        gen = c.wrap(node, gen)
    # timeline profiling: one span per physical operator, first pull to
    # exhaustion, on whichever thread pulls it (`gen` itself with no recorder)
    gen = span_iter("op." + node.name(), "host", gen)
    if isinstance(node, _STAGE_NODES) and _pipeline_on():
        from .pipeline import spawn_stage

        # node identity rides along so the stage channel can attribute
        # put-side backpressure to this operator (no-op without a collector)
        gen = spawn_stage(gen, node=node)
    return gen


def _decide_span(decide):
    """One `placement.decide` span over a decider's whole body (peek and
    pricing included), carrying the tier it chose, whether the verdict came
    from a cache and the rows a dispatch a join's device arm was priced at.
    The deciders nest (`_select_mesh_tier` asks
    `_mesh_wins`): a reader takes the union."""
    @functools.wraps(decide)
    def decided(*args, **kwargs):
        with _profile_span("placement.decide", "host",
                          decider=decide.__name__) as sp:
            out = decide(*args, **kwargs)
            if sp is not None:
                sp.args["tier"] = str(out[0])
                sp.args["cached"] = bool(getattr(out[-1], "cached", False))
                # rows a dispatch the chosen device arm was priced at (a
                # join's decision; 0 where the host won or nothing was priced)
                sp.args["priced_rows"] = int(getattr(out[-1], "priced_rows", 0))
            return out

    return decided


def _pipeline_on() -> bool:
    from ..config import execution_config
    from ..utils.pool import pool_width

    mode = execution_config().pipeline_mode
    if mode == "force":
        return True
    # on a single-core host, fan-out and stage threads are pure overhead
    return mode == "on" and pool_width() > 1


def _map_op(stream: Iterator[MicroPartition], fn) -> Iterator[MicroPartition]:
    """Run fn(part, index) over a partition stream. Pipeline mode: morselize
    oversized partitions into zero-copy slices and fan out across the compute
    pool, yielding in order (reference: intermediate_op.rs:45-59 — every
    intermediate op runs N concurrent workers over morsels). Off mode: plain
    sequential map.

    Morsel sizing consults the configured BatchingStrategy
    (execution/batching.py): "static" keeps the fixed cfg.morsel_size_rows on
    the exact pre-strategy code path (no strategy allocation — the tier-1
    zero-overhead guarantee); "dynamic"/"latency" give this operator its own
    feedback-driven strategy, fed per-morsel timings by pmap_stream."""
    from ..config import execution_config

    if _pipeline_on():
        from .pipeline import morsel_stream, pmap_stream

        cfg = execution_config()
        if cfg.batching_mode == "static":
            yield from pmap_stream(morsel_stream(stream, cfg.morsel_size_rows), fn)
        else:
            from .batching import adaptive_morsel_stream, make_strategy

            strat = make_strategy(cfg)
            yield from pmap_stream(adaptive_morsel_stream(stream, strat), fn,
                                   strategy=strat)
    else:
        for i, part in enumerate(stream):
            yield fn(part, i)


def _exec_impl(node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
    if isinstance(node, pp.InMemoryScan):
        yield from node.partitions
        return

    if isinstance(node, pp.StreamingScan):
        yield from _streaming_scan(node)
        return

    if isinstance(node, pp.TaskScan):
        from ..utils.pool import compute_pool, pool_width

        remaining = node.post_limit

        def read_task(task):
            out = []
            for part in task.read():
                if node.post_filter is not None and not task.filters_applied:
                    part = _filter_part(part, node.post_filter)
                out.append(part)
            return out

        if len(node.tasks) > 1 and remaining is None:
            # IO-parallel scan with a bounded in-flight window: parallelism without
            # buffering the whole dataset ahead of the consumer
            window = pool_width()
            futures = []
            ti = 0
            while ti < len(node.tasks) or futures:
                while ti < len(node.tasks) and len(futures) < window:
                    futures.append(compute_pool().submit(read_task, node.tasks[ti]))
                    ti += 1
                f = futures.pop(0)
                yield from f.result()
            return
        for task in node.tasks:
            for part in task.read():
                if node.post_filter is not None and not task.filters_applied:
                    part = _filter_part(part, node.post_filter)
                if remaining is not None:
                    if remaining <= 0:
                        return
                    if part.num_rows > remaining:
                        part = part.head(remaining)
                    remaining -= part.num_rows
                yield part
        return

    if isinstance(node, pp.Project):
        def _project(part, _i):
            batches = [eval_projection(b, node.projection) for b in part.batches]
            return MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])

        yield from _map_op(_exec(node.input), _project)
        return

    if isinstance(node, pp.UDFProject):
        # sequential: UDFs may hold non-thread-safe state (heavy ones run on the
        # process pool via the UDF tier; concurrency is governed there)
        exprs = list(node.passthrough) + [node.udf_expr]
        for part in _exec(node.input):
            batches = [eval_projection(b, exprs) for b in part.batches]
            yield MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])
        return

    if isinstance(node, pp.DeviceUdfProject):
        yield from _exec_device_udf(node)
        return

    if isinstance(node, pp.PhysFilter):
        yield from _map_op(_exec(node.input),
                           lambda part, _i: _filter_part(part, node.predicate,
                                                         node.keep, node.schema))
        return

    if isinstance(node, pp.PhysLimit):
        to_skip = node.offset
        remaining = node.limit if node.limit >= 0 else None
        for part in _exec(node.input):
            if to_skip > 0:
                if part.num_rows <= to_skip:
                    to_skip -= part.num_rows
                    continue
                part = part.slice(to_skip, part.num_rows)
                to_skip = 0
            if remaining is None:
                yield part
                continue
            if remaining <= 0:
                return
            if part.num_rows > remaining:
                part = part.head(remaining)
            remaining -= part.num_rows
            yield part
            if remaining <= 0:
                return
        return

    if isinstance(node, pp.PhysExplode):
        def _explode(part, _i):
            batches = [rel.explode(b, node.to_explode, node.schema) for b in part.batches]
            return MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])

        yield from _map_op(_exec(node.input), _explode)
        return

    if isinstance(node, pp.PhysUnpivot):
        def _unpivot(part, _i):
            batches = [rel.unpivot(b, node.ids, node.values, node.variable_name,
                                   node.value_name, node.schema) for b in part.batches]
            return MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])

        yield from _map_op(_exec(node.input), _unpivot)
        return

    if isinstance(node, pp.PhysSample):
        # sequential (sampling is cheap). Seeded without-replacement sampling
        # is position-hashed (rel.sample_at), so the chosen rows do not depend
        # on how upstream operators batched the stream — the same seed gives
        # the same rows in pipeline and sequential modes on any host.
        offset = 0
        for i, part in enumerate(_exec(node.input)):
            batches = []
            for b in part.batches:
                if node.seed is not None and not node.with_replacement:
                    batches.append(rel.sample_at(b, node.fraction, node.seed, offset))
                else:
                    s = None if node.seed is None else node.seed + i
                    batches.append(rel.sample(b, node.fraction, node.with_replacement, s))
                offset += b.num_rows
            yield MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])
        return

    if isinstance(node, pp.PhysMonotonicId):
        # 36-bit local row counter + 28-bit partition id, like the reference's scheme
        from ..core.series import Series
        from ..datatype import DataType

        for part_id, part in enumerate(_exec(node.input)):
            offset = 0
            batches = []
            for b in part.batches:
                ids = (np.uint64(part_id) << np.uint64(36)) + np.arange(
                    offset, offset + b.num_rows, dtype=np.uint64
                )
                offset += b.num_rows
                id_col = Series.from_numpy(ids, node.column_name, DataType.uint64())
                cols = [id_col] + list(b.columns)
                batches.append(RecordBatch(node.schema, cols, b.num_rows))
            yield MicroPartition(node.schema, batches or [RecordBatch.empty(node.schema)])
        return

    if isinstance(node, pp.PhysSort):
        yield from _sort_exec(node)
        return

    if isinstance(node, pp.PhysTopN):
        # streaming top-n: keep only best (limit+offset) rows seen so far
        k = node.limit + node.offset
        best: Optional[RecordBatch] = None
        for part in _exec(node.input):
            for b in part.batches:
                cur = b if best is None else RecordBatch.concat([best, b])
                keys = [eval_expression(cur, e) for e in node.sort_by]
                srt = cur.sort(keys, node.descending, node.nulls_first)
                best = srt.head(k)
        out = best if best is not None else RecordBatch.empty(node.schema)
        if node.offset:
            out = out.slice(min(node.offset, out.num_rows), out.num_rows)
        yield MicroPartition(node.schema, [out])
        return

    if isinstance(node, pp.UngroupedAggregate):
        out = _two_phase_agg(node.input, [], node.aggregations, ungrouped=True,
                             node=node)
        yield MicroPartition(node.schema, [out.cast_to_schema(node.schema)])
        return

    if isinstance(node, pp.HashAggregate):
        out = _two_phase_agg(node.input, node.groupby, node.aggregations,
                             ungrouped=False, node=node)
        yield MicroPartition(node.schema, [out.cast_to_schema(node.schema)])
        return

    if isinstance(node, pp.PhysMapGroups):
        yield _exec_map_groups(node)
        return

    if isinstance(node, (pp.DeviceFilterAgg, pp.DeviceGroupedAgg)):
        yield _exec_device_agg(node)
        return

    if isinstance(node, pp.DeviceJoinAgg):
        yield _exec_device_join_agg(node)
        return

    if isinstance(node, pp.DeviceJoinTopN):
        yield _exec_device_join_topn(node)
        return

    if isinstance(node, pp.Dedup):
        # streaming dedup, keep-first: each batch dedups internally, then drops
        # rows whose keys were already seen — probed against an amortized
        # ProbeTable over older rows (rebuilt only when the recent buffer
        # doubles past it: O(n log n) total instead of re-running distinct over
        # the whole accumulated set per batch). Nulls equal nulls, matching
        # distinct()/make_groups semantics.
        from ..core.kernels.join import ProbeTable
        from ..core.relational import _eval_keys
        from ..expressions import col as _col

        key_exprs = list(node.on) if node.on else \
            [_col(f.name) for f in node.input.schema]
        table: Optional[ProbeTable] = None
        base: List[RecordBatch] = []     # rows the probe table covers
        recent: List[RecordBatch] = []   # rows seen since the last rebuild
        base_rows = recent_rows = 0
        emitted = False
        for part in _exec(node.input):
            for b in part.batches:
                if b.num_rows == 0:
                    continue
                nb = rel.distinct(b, node.on)
                if table is not None and nb.num_rows:
                    lidx, _ = table.probe(_eval_keys(nb, key_exprs), "anti")
                    nb = nb.take(lidx)
                if recent and nb.num_rows:
                    seen_recent = RecordBatch.concat(recent)
                    nb = rel.hash_join(nb, seen_recent, key_exprs, key_exprs,
                                       "anti", nb.schema, [], {}, True)
                if nb.num_rows:
                    emitted = True
                    recent.append(nb)
                    recent_rows += nb.num_rows
                    yield MicroPartition(node.schema, [nb])
                if recent_rows > max(64 * 1024, base_rows):
                    base.extend(recent)
                    base_rows += recent_rows
                    recent, recent_rows = [], 0
                    seen_all = RecordBatch.concat(base)
                    base = [seen_all]
                    key_dtypes = [e.to_field(node.input.schema).dtype for e in key_exprs]
                    table = ProbeTable(_eval_keys(seen_all, key_exprs), key_dtypes,
                                       null_equals_null=True)
        if not emitted:
            yield MicroPartition.empty(node.schema)
        return

    if isinstance(node, pp.PhysPivot):
        batch = _gather(node.input, node.input.schema)
        out = rel.pivot(batch, node.groupby, node.pivot_col, node.value_col,
                        node.agg_op, node.names, node.schema)
        yield MicroPartition(node.schema, [out])
        return

    if isinstance(node, pp.PhysWindow):
        yield from _window_exec(node)
        return

    if isinstance(node, pp.PhysConcat):
        for child in node.inputs:
            yield from _exec(child)
        return

    if isinstance(node, pp.HashJoin):
        yield from _join_exec(node)
        return

    if isinstance(node, pp.CrossJoin):
        right = _gather(node.right, node.right.schema)
        for part in _exec(node.left):
            for b in part.batches:
                out = rel.cross_join(b, right, node.schema, node.right_rename)
                yield MicroPartition(node.schema, [out])
        return

    if isinstance(node, pp.PhysRepartition):
        yield from _repartition(node)
        return

    if isinstance(node, pp.PhysIntoBatches):
        buffer: List[RecordBatch] = []
        buffered = 0
        for part in _exec(node.input):
            for b in part.batches:
                buffer.append(b)
                buffered += b.num_rows
                while buffered >= node.batch_size:
                    big = RecordBatch.concat(buffer)
                    out = big.head(node.batch_size)
                    rest = big.slice(node.batch_size, big.num_rows)
                    yield MicroPartition(node.schema, [out])
                    buffer = [rest] if rest.num_rows else []
                    buffered = rest.num_rows
        if buffered:
            yield MicroPartition(node.schema, [RecordBatch.concat(buffer)])
        return

    if isinstance(node, pp.PhysWrite):
        yield from node.info.execute_write(_exec(node.input), node.input.schema)
        return

    if isinstance(node, pp.ShuffleWrite):
        from ..distributed.shuffle import MapOutputWriter

        out = MapOutputWriter(node.shuffle_dir, node.shuffle_id, node.map_id,
                              node.num_partitions)
        try:
            for j, piece in _hash_buckets(_exec(node.input), node.by, node.num_partitions):
                out.append(j, piece)
        finally:
            out.close()
        return

    if isinstance(node, pp.ShuffleRead):
        expected = getattr(node, "expected_maps", None)
        if node.fetch_endpoints:
            from ..distributed.fetch_server import fetch_partition

            yield from fetch_partition(node.fetch_endpoints, node.shuffle_id,
                                       node.partition_idx, node.schema,
                                       expected_maps=expected)
            return
        from ..distributed import shuffle as shf

        yield from shf.read_partition(node.shuffle_dir, node.shuffle_id,
                                      node.partition_idx, node.schema,
                                      expected_maps=expected)
        return

    raise NotImplementedError(f"executor: unhandled node {type(node).__name__}")


def _streaming_scan(node) -> Iterator[MicroPartition]:
    """Execute a StreamingScan: morsels yielded incrementally, never a whole
    source in host RAM.

    Tasks are split at scan_split_bytes (io/parquet.py row-group planning)
    and never merged past it (io/scan.py merge_small_tasks), so even the
    IO-parallel window holds at most window x scan_split_bytes in flight.
    Backpressure is two-layered: the bounded stage channel (pipeline.py —
    StreamingScan is a stage node) limits morsels between scan and consumer,
    and the host memory ledger's pressure signal (daft_tpu/memory) stalls
    the scan — boundedly, never as a correctness gate — while a downstream
    blocking operator is at the memory wall and about to spill. Attribution:
    scan_tasks (the tasks the scan was given), scan_batches/rows/bytes,
    scan_backpressure_stalls + scan_stall_ms counters, and a per-task
    "scan.stream" span while the timeline profiler is active."""
    from ..memory import manager as _host_manager
    from ..observability.metrics import registry
    from ..observability.runtime_stats import (current_collector, current_spans,
                                               span_iter)
    from ..utils.pool import compute_pool, pool_width

    mgr = _host_manager()
    budgeted = mgr.limit_bytes() > 0
    reg = registry()
    reg.inc("scan_tasks", len(node.tasks))
    c = current_collector()
    if c is not None:
        c.annotate(node, f"streaming: {len(node.tasks)} tasks")

    # per-morsel accounting is LOCAL (one list, no registry lock) and
    # flushed per scan task: the unbudgeted fast path pays neither three
    # locked increments nor the arrow-buffer walk of size_bytes() per
    # morsel — scan_bytes is only meaningful (and only counted) when a
    # budget makes morsel sizing load-bearing
    acc = [0, 0, 0]  # batches, rows, bytes

    def count(part: MicroPartition) -> MicroPartition:
        acc[0] += 1
        acc[1] += part.num_rows
        if budgeted:
            acc[2] += part.size_bytes()
        return part

    def flush() -> None:
        if acc[0]:
            reg.inc("scan_batches", acc[0])
            reg.inc("scan_rows", acc[1])
            if acc[2]:
                reg.inc("scan_bytes", acc[2])
            acc[0] = acc[1] = acc[2] = 0

    def task_parts(task) -> Iterator[MicroPartition]:
        if task.size_bytes:
            reg.inc("scan_file_bytes", task.size_bytes)
        inner = task.read()
        for part in span_iter("scan.stream", "scan", inner,
                              source=task.source_label):
            if node.post_filter is not None and not task.filters_applied:
                part = _filter_part(part, node.post_filter)
            yield part

    try:
        remaining = node.post_limit
        if remaining is not None or len(node.tasks) <= 1 or not _pipeline_on():
            # fully streaming: one morsel resident at a time per task
            for task in node.tasks:
                if budgeted:
                    mgr.wait_for_headroom()
                for part in task_parts(task):
                    if remaining is not None:
                        if remaining <= 0:
                            return
                        if part.num_rows > remaining:
                            part = part.head(remaining)
                        remaining -= part.num_rows
                    yield count(part)
                    if budgeted and mgr.under_pressure():
                        mgr.wait_for_headroom()
                flush()
            return

        # IO-parallel scan with a bounded in-flight window: each future
        # materializes ONE (split) task, so in-flight memory is bounded by
        # window x scan_split_bytes instead of the whole dataset
        def read_task(task):
            return list(task_parts(task))

        recording = current_spans() is not None
        window = pool_width()
        futures = []
        ti = 0
        while ti < len(node.tasks) or futures:
            while ti < len(node.tasks) and len(futures) < window:
                if budgeted and mgr.under_pressure():
                    mgr.wait_for_headroom()
                if recording:
                    # the pool thread works in a copy of this context, so the
                    # task's scan.stream span (and the scan.decode spans under
                    # it) hang under the scan's operator and carry the qid
                    futures.append(compute_pool().submit(
                        contextvars.copy_context().run, read_task, node.tasks[ti]))
                else:
                    futures.append(compute_pool().submit(read_task, node.tasks[ti]))
                ti += 1
            for part in futures.pop(0).result():
                yield count(part)
            flush()
    finally:
        # early close (limit hit, failed consumer) still lands the partial
        # task's counts — scan_rows stays exact for what was yielded
        flush()


def _agg_morsel_rows() -> int:
    """Morsel size for the partial-agg splitter in _two_phase_agg — the
    config's morsel_size_rows (the batching strategies also initialize from
    it). Was a hardcoded 256Ki that silently drifted from the 128Ki config
    default and ignored DAFT_TPU_MORSEL_SIZE."""
    from ..config import execution_config

    return max(execution_config().morsel_size_rows, 1)


# Operators that run as their own concurrent stage in pipeline mode. Excluded:
# InMemoryScan (yields references), PhysConcat (pass-through), PhysLimit/TopN/
# IntoBatches (cheap sequential state machines), ShuffleWrite/PhysWrite (sinks
# driven by their consumer), UDFProject (UDF concurrency is governed by the
# UDF tier).
_STAGE_NODES = (pp.TaskScan, pp.Project, pp.PhysFilter, pp.PhysExplode,
                pp.PhysUnpivot, pp.PhysSample, pp.PhysSort, pp.UngroupedAggregate,
                pp.HashAggregate, pp.DeviceFilterAgg, pp.DeviceGroupedAgg,
                pp.Dedup, pp.PhysPivot, pp.PhysWindow, pp.HashJoin, pp.CrossJoin,
                pp.PhysRepartition)


def _region_keep_columns(node, grouped) -> Optional[List[str]]:
    """Referenced-column subset of a Device*Agg node's input, or None when
    the node already reads (essentially) its whole input width. Input order
    preserved so narrowing is a pure column slice."""
    from ..ops.region import referenced_columns

    need = referenced_columns(node.predicate,
                              node.groupby if grouped else [],
                              node.aggregations)
    have = node.input.schema.column_names()
    if not need or need >= set(have):
        return None
    return [c for c in have if c in need]


def _exec_device_agg(node) -> MicroPartition:
    """Run a DeviceFilterAgg/DeviceGroupedAgg node: device stage or host fallback.

    Device when device_mode == "on", or "auto" on a real accelerator backend
    when the measured cost model (ops/costmodel.py: live-calibrated d2h round
    trip + h2d bandwidth for non-resident columns + compute-rate terms) says
    the device beats the host numpy/C++ path for this stage's shape.

    The device stage is one path whatever the devices: _select_mesh_tier says
    how many local devices the run's dispatches shard their rows over (forced
    by mesh_devices >= 2, or because the mesh won its placement), and the
    same stage, feed loop, coalescer, pin scope and finalize run it
    (stage.start_run(mesh_devices=...)); 0 or 1 is the single chip.
    """
    import itertools

    from ..config import execution_config

    cfg = execution_config()
    grouped = isinstance(node, pp.DeviceGroupedAgg)
    if (not grouped and cfg.device_mode == "on"
            and getattr(cfg, "region_mode", "on") != "off"
            and _unwrap_udf_agg_input(node.input)[0] is not None):
        # device-UDF -> device-agg fusion: the UDF's output plane feeds the
        # agg program on device with no intermediate d2h (the split rule's
        # rename Project between the two is seen through). Qualification
        # failures return None before any input executes; grouped stages run
        # unfused (keys factorize on host anyway).
        fused = _try_fused_udf_agg(node, cfg)
        if fused is not None:
            return fused
    stream = _exec(node.input)

    use_device = cfg.device_mode == "on"
    prec = None  # placement ledger record for the costed/forced decision
    if cfg.device_mode == "auto":
        first = next(stream, None)
        if first is not None:
            second = None
            if first.num_rows >= cfg.device_min_rows:
                import jax

                if jax.default_backend() not in ("cpu",):
                    from .batching import coalesce_target_rows

                    if coalesce_target_rows(cfg) > 0:
                        # peek one partition further: observed second-
                        # partition morsels widen the coalesce horizon in
                        # the cost decision (skipped when coalescing is off)
                        second = next(stream, None)
                    use_device, prec = _device_wins(node, first, grouped,
                                                    second=second)
                else:
                    # the common dev/CI backend under the default auto mode:
                    # recorded only into an active query scope, never the
                    # process ledger (the zero-overhead contract)
                    _placement.ledger().gate(
                        "grouped agg" if grouped else "agg", "cpu backend",
                        first.num_rows, only_scoped=True)
            else:
                # the common tiny-host-query bail: recorded only when an
                # explain_placement()/query scope is actually listening
                _placement.ledger().gate(
                    "grouped agg" if grouped else "agg",
                    "below device_min_rows", first.num_rows,
                    only_scoped=True)
            stream = itertools.chain(
                [first] if second is None else [first, second], stream)

    keep = _region_keep_columns(node, grouped)
    if keep is not None:
        # A captured region that absorbed a pruning Project sits on the FULL
        # base width; narrow to the referenced columns before anything
        # filters, buffers or coalesces the stream (the device stage only
        # uploads referenced columns, but the host fallback and the
        # whole-region rerun buffer would otherwise carry every base column
        # — wide string payloads included — through filter/concat).
        stream = (p.select_columns(keep) for p in stream)

    def _host_agg(s):
        if node.predicate is not None:
            s = (_filter_part(p, node.predicate) for p in s)
        out = _two_phase_agg(node.input, node.groupby if grouped else [],
                             node.aggregations, ungrouped=not grouped,
                             stream=s, node=node)
        return MicroPartition(node.schema, [out.cast_to_schema(node.schema)])

    # how many local devices the stage's dispatches shard their rows over:
    # 0 or 1 is the single chip
    mesh_n = 0
    if not use_device:
        # 3-way auto tier: a compute-bound stage can lose to the host on ONE
        # chip yet win across the mesh (compute / mesh width). _mesh_wins
        # requires beating BOTH host and single-chip, so this only flips
        # stages the mesh genuinely earns.
        if cfg.device_mode == "auto" and cfg.mesh_devices == 0:
            import jax

            if jax.default_backend() not in ("cpu",):
                mesh_n, stream, prec = _select_mesh_tier(node, stream,
                                                         grouped, cfg)
        if not mesh_n:
            return _host_agg(stream)
    elif cfg.mesh_devices != 1:
        mesh_n, stream, mrec = _select_mesh_tier(node, stream, grouped, cfg)
        if mesh_n:
            prec = mrec

    from ..core.series import Series
    from ..device.residency import manager as _residency

    if mesh_n:
        from ..observability.runtime_stats import current_collector

        c = current_collector()
        if c is not None:
            c.annotate(node, f"mesh: {mesh_n} devices")
    site = "grouped agg" if grouped else "agg"
    if prec is None and cfg.device_mode == "on":
        # forced run: recorded so the ledger attributes the dispatch; priced
        # too under DAFT_TPU_PLACEMENT_PRICE_FORCED so forced runs yield
        # predicted-vs-observed calibration samples (the calibrate tool)
        if _env_bool("DAFT_TPU_PLACEMENT_PRICE_FORCED", False):
            first = next(stream, None)
            if first is not None:
                stream = itertools.chain([first], stream)
                _w, prec = _device_wins(node, first, grouped, forced=True)
        if prec is None:
            prec = _placement.ledger().record(site, "device", forced=True)
    from ..ops import counters as _counters
    from ..ops.region import node_region_ops

    region_ops = node_region_ops(node)
    bound = node.bound_stage()
    assert bound is not None, f"planner emitted {node.name()} for a non-qualifying plan"
    stage, literals = bound
    # morsels of a file scan die with the query: their planes are uploaded
    # without a content fingerprint (residency.pin_scope)
    streamed = not _resident_source_rec(node.input)
    if grouped:
        from ..ops.grouped_stage import DeviceFallback

        run = stage.start_run(literals, mesh_devices=mesh_n)
        coal = _make_coalescer(run.feed_batch, cfg)
        feed = coal.add if coal is not None else run.feed_batch
        buffered: List[MicroPartition] = []
        fed_rows = 0
        d0 = _counters.device_grouped_batches
        try:
            # pin the query's resident planes so a tight HBM budget cannot
            # evict buffers this run still reads; released at scope exit
            with _placement.feedback(prec) as fb, _residency().pin_scope(transient=streamed):
                for part in stream:
                    buffered.append(part)
                    fed_rows += part.num_rows
                    for b in part.batches:
                        feed(b)
                if coal is not None:
                    coal.close()
                fb.set_rows(fed_rows)
                key_rows, results = run.finalize()
        except DeviceFallback:
            # runtime shape outside the device kernel envelope (e.g. group count
            # beyond the matmul segment ceiling, raised before any dispatch for
            # the offending batch): rerun the WHOLE buffered region on host —
            # the composed region expressions evaluate compositionally, so
            # the host result is bit-identical to the fused device program's
            return _host_agg(itertools.chain(buffered, stream))
        _note_region(node, region_ops, _counters.device_grouped_batches - d0)
        return _grouped_output(node.schema, node.groupby, node.aggregations,
                               key_rows, results)

    run = stage.start_run(literals, mesh_devices=mesh_n)
    coal = _make_coalescer(run.feed_batch, cfg)
    feed = coal.add if coal is not None else run.feed_batch
    fed_rows = 0
    d0 = _counters.device_stage_batches
    with _placement.feedback(prec) as fb, _residency().pin_scope(transient=streamed):
        for part in stream:
            fed_rows += part.num_rows
            for b in part.batches:
                feed(b)
        if coal is not None:
            coal.close()
        fb.set_rows(fed_rows)
        final = run.finalize()
    _note_region(node, region_ops, _counters.device_stage_batches - d0)
    cols = []
    for name, _agg in stage.aggs:
        f = node.schema[name]
        cols.append(Series.from_pylist([final[name]], f.name, dtype=f.dtype))
    out = RecordBatch(node.schema, cols, 1)
    return MicroPartition(node.schema, [out.cast_to_schema(node.schema)])


def _exec_device_udf(node) -> Iterator[MicroPartition]:
    """Run a DeviceUdfProject (ops/udf_stage.py): the staged device-UDF tier,
    or the plain batch-UDF host path with identical semantics.

    Device when device_mode == "on", or "auto" on a real accelerator when
    ``device_udf_cost`` (model flops at the device rate + per-morsel input
    h2d + RTT divided by the coalesce horizon; weights amortized to zero via
    residency) beats the host flop rate — cached per (fn fingerprint, batch
    layout) under the usual decision-cache discipline. The device path feeds
    the stage through the DispatchCoalescer (super-batches at the configured
    fill target, capped by Func.batch_size), pins weights for the query via
    the residency pin scope, and d2h's every output in one finalize fetch.
    """
    from ..config import execution_config
    from ..ops import counters as _counters

    cfg = execution_config()
    call = pp.device_udf_call(node.udf_expr)
    stream = _exec(node.input)

    def _host(s):
        exprs = list(node.passthrough) + [node.udf_expr]
        for part in s:
            batches = [eval_projection(b, exprs) for b in part.batches]
            yield MicroPartition(node.schema,
                                 batches or [RecordBatch.empty(node.schema)])

    if call is None or cfg.device_mode == "off":
        yield from _host(stream)
        return
    prec = None
    if cfg.device_mode == "auto":
        import jax

        if jax.default_backend() in ("cpu",):
            _counters.reject("cost", "device udf: cpu backend")
            _counters.bump("device_udf_fallbacks")
            _placement.ledger().gate("udf", "cpu backend", only_scoped=True)
            yield from _host(stream)
            return
        first = next(stream, None)
        if first is None:
            yield MicroPartition.empty(node.schema)
            return
        stream = itertools.chain([first], stream)
        from ..ops.udf_stage import func_fingerprint

        dk = ("udf", func_fingerprint(call.func), cfg.device_mode,
              cfg.batch_fill_target, cfg.morsel_size_rows,
              _batch_layout(first))
        wins = _DECISION_CACHE.get(dk)
        if wins is None:
            wins, prec = _udf_device_wins(call.func, first,
                                          _coalesce_horizon([first]))
            _DECISION_CACHE.put(dk, wins)
        else:
            # accelerator-backend-only path: count the cached verdict
            prec = _placement.ledger().record(
                "udf", "device" if wins else "host", first.num_rows,
                cached=True, detail=call.func.name)
        if not wins:
            _counters.reject("cost", "device udf: host wins cost model")
            _counters.bump("device_udf_fallbacks")
            yield from _host(stream)
            return
    elif cfg.device_mode == "on":
        if _env_bool("DAFT_TPU_PLACEMENT_PRICE_FORCED", False):
            first = next(stream, None)
            if first is None:
                yield MicroPartition.empty(node.schema)
                return
            stream = itertools.chain([first], stream)
            _w, prec = _udf_device_wins(call.func, first,
                                        _coalesce_horizon([first]),
                                        forced=True)
        if prec is None:
            prec = _placement.ledger().record("udf", "device", forced=True,
                                              detail=call.func.name)
    yield _run_device_udf_stage(node, call, stream, cfg, prec)


def _udf_device_wins(func, first: MicroPartition, coal: float,
                     forced: bool = False):
    """Cost decision for one device-UDF stage; returns (wins,
    placement_record). The flops estimate is coarse (2 x weight scalars per
    row — a dense forward's order of magnitude); both sides use the same
    estimate, so the verdict hangs on the measured rates, the per-morsel
    input upload, and the coalesce-amortized RTT. Weight upload is priced at
    zero: it is a residency-managed one-time investment (flat across
    repeats), exactly like resident column planes."""
    from ..ops import costmodel
    from ..ops.udf_stage import func_weight_nbytes

    cal = costmodel.calibrate()
    rows = first.num_rows
    w_nbytes = func_weight_nbytes(func)  # loads the model once per process
    w_scalars = (w_nbytes // 4) if w_nbytes else 1 << 20
    flops = 2.0 * w_scalars * rows
    in_bytes = rows * 1024        # tokenized ids+mask order of magnitude
    fetch_bytes = rows * 512      # output rows (embedding dim order)
    dev = costmodel.device_udf_cost(cal, rows, in_bytes, flops, fetch_bytes,
                                    coalesce=coal)
    host = costmodel.host_udf_cost(cal, flops)
    wins = dev < host
    rec = _placement.ledger().record(
        "udf", "device" if (wins or forced) else "host", rows, forced=forced,
        device=dev, host=host, detail=func.name)
    return wins, rec


def _run_device_udf_stage(node, call, stream, cfg, prec=None) -> MicroPartition:
    """Drive one DeviceUdfProject on the device tier: coalesced dispatch-only
    feeds under a residency pin scope, one finalize d2h, output assembled as
    passthrough columns + the decoded UDF column. A runtime DeviceFallback
    (misaligned prepare output, non-array result) reruns the buffered stream
    on the host path — results identical, fallback counted."""
    from ..core.series import Series
    from ..device.residency import manager as _residency
    from ..observability.runtime_stats import current_collector
    from ..ops import counters as _counters
    from ..ops.grouped_stage import DeviceFallback
    from ..ops.udf_stage import (_finish_values, build_device_udf_stage,
                                 func_weight_nbytes)

    func = call.func
    out_name = node.udf_expr.name()
    stage = build_device_udf_stage(func, call.args, out_name)
    buffered: List[MicroPartition] = []
    fed_rows = 0
    try:
        with _placement.feedback(prec) as fb, _residency().pin_scope():
            run = stage.start_run()
            coal = _make_coalescer(run.feed_batch, cfg)
            feed = coal.add if coal is not None else run.feed_batch
            for part in stream:
                buffered.append(part)
                fed_rows += part.num_rows
                for b in part.batches:
                    if b.num_rows:
                        feed(b)
            if coal is not None:
                coal.close()
            fb.set_rows(fed_rows)
            out, valid = run.finalize()
    except DeviceFallback as e:
        _counters.bump("device_udf_fallbacks")
        _counters.reject("runtime", "device udf: fallback", str(e))
        exprs = list(node.passthrough) + [node.udf_expr]
        batches = [eval_projection(b, exprs)
                   for p in itertools.chain(buffered, stream)
                   for b in p.batches]
        return MicroPartition(node.schema,
                              batches or [RecordBatch.empty(node.schema)])
    c = current_collector()
    if c is not None:
        mb = func_weight_nbytes(func) / 1e6
        c.annotate(node, f"device udf: {func.name}, weights {mb:.1f}MB resident")
    big = _concat_parts(buffered, node.input.schema)
    vals = _finish_values(func, out, valid)
    f = node.schema[out_name]
    udf_col = Series.from_pylist(vals, f.name, dtype=f.dtype)
    cols = [eval_expression(big, e) for e in node.passthrough] + [udf_col]
    out_batch = RecordBatch(node.schema, cols, big.num_rows)
    return MicroPartition(node.schema, [out_batch.cast_to_schema(node.schema)])


def _unwrap_udf_agg_input(agg_input):
    """The region builder's UDF→agg peephole (ops/region.py) — only ever
    called on the device_mode=on path, so the device-tier import is safe."""
    from ..ops.region import unwrap_udf_agg_input

    return unwrap_udf_agg_input(agg_input)


def _note_region(node, region_ops, dispatches: int) -> None:
    """Attribution for one completed fused-region run: every device dispatch
    the region issued covered len(region_ops) operators in one RTT. Counted
    only for genuine regions (>= 2 fused ops) so ops_fused / dispatches
    measures fusion, not bare aggs; the EXPLAIN ANALYZE
    line makes the amortization visible per node."""
    if dispatches <= 0 or len(region_ops) < 2:
        return
    from ..observability.runtime_stats import current_collector
    from ..ops import counters as _counters
    from ..ops.region import region_label

    _counters.bump("device_region_dispatches", dispatches)
    _counters.bump("device_region_ops_fused", dispatches * len(region_ops))
    c = current_collector()
    if c is not None:
        d = "1 dispatch" if dispatches == 1 else f"{dispatches} dispatches"
        c.annotate(node, f"fused region: {len(region_ops)} ops "
                         f"({region_label(region_ops)}), {d}")


def _try_fused_udf_agg(node, cfg) -> Optional[MicroPartition]:
    """Fuse a DeviceUdfProject feeding a DeviceFilterAgg: each coalesced
    batch dispatches the UDF program and hands its OUTPUT device plane
    straight into the agg program's column dict (ops/udf_stage.py
    FusedUdfAggFeeder) — the score column never round-trips to host between
    the stages. Engages under device_mode="on" for scalar-numeric UDF
    outputs; every qualification failure returns None BEFORE any input
    executes, so the caller's unfused path starts clean."""
    from ..core.series import Series
    from ..device.residency import manager as _residency
    from ..observability.runtime_stats import current_collector
    from ..ops import counters as _counters
    from ..ops.grouped_stage import DeviceFallback

    udf_node, rename = _unwrap_udf_agg_input(node.input)
    if udf_node is None:
        return None
    call = pp.device_udf_call(udf_node.udf_expr)
    if call is None:
        return None
    internal = udf_node.udf_expr.name()
    bound = node.bound_stage()
    if bound is None:
        return None
    agg_stage, literals = bound
    # split the agg program's columns into the UDF output plane(s) and the
    # passthrough columns, mapping agg-visible names to UDF-input sources
    udf_plane_names = [c for c in agg_stage._input_cols
                       if rename.get(c) == internal]
    other = {c: rename.get(c, c) for c in agg_stage._input_cols
             if rename.get(c) != internal}
    if not udf_plane_names:
        return None  # the agg never reads the UDF output: nothing to fuse
    if not all(node.input.schema[c].dtype.is_numeric()
               for c in udf_plane_names):
        return None  # only scalar planes slot into the agg program
    in_cols = set(udf_node.input.schema.column_names())
    if not all(src in in_cols for src in other.values()):
        return None
    from ..ops.udf_stage import FusedUdfAggFeeder, build_device_udf_stage

    from ..ops.region import node_region_ops

    udf_stage = build_device_udf_stage(call.func, call.args, internal)
    agg_run = agg_stage.start_run(literals)
    in_stream = _exec(udf_node.input)
    buffered: List[MicroPartition] = []
    # the UDF plane feeds the agg program in the SAME dispatch, so the
    # region spans the UDF op plus whatever chain the planner fused
    region_ops = ("udf",) + node_region_ops(node)
    d0 = _counters.device_stage_batches
    # fusion only engages under device_mode=on: a forced ledger record so the
    # fused dispatch still lands in placement telemetry
    prec = _placement.ledger().record("udf+agg fused", "device", forced=True,
                                      detail=call.func.name)
    fed_rows = 0
    try:
        with _placement.feedback(prec) as fb, _residency().pin_scope():
            udf_run = udf_stage.start_run()
            feeder = FusedUdfAggFeeder(udf_run, agg_run, udf_plane_names,
                                       other, f32=not agg_stage._use_f64)
            coal = _make_coalescer(feeder.feed_batch, cfg)
            feed = coal.add if coal is not None else feeder.feed_batch
            for part in in_stream:
                buffered.append(part)
                fed_rows += part.num_rows
                for b in part.batches:
                    if b.num_rows:
                        feed(b)
            if coal is not None:
                coal.close()
            fb.set_rows(fed_rows)
            final = agg_run.finalize()
    except DeviceFallback as e:
        _counters.bump("device_udf_fallbacks")
        _counters.reject("runtime", "fused device udf: fallback", str(e))
        exprs = list(udf_node.passthrough) + [udf_node.udf_expr]

        def _udf_parts():
            for p in itertools.chain(buffered, in_stream):
                bs = [eval_projection(b, exprs) for b in p.batches]
                if node.input is not udf_node:  # reapply the rename Project
                    bs = [eval_projection(b, node.input.projection) for b in bs]
                yield MicroPartition(node.input.schema,
                                     bs or [RecordBatch.empty(node.input.schema)])

        s = _udf_parts()
        if node.predicate is not None:
            s = (_filter_part(p, node.predicate) for p in s)
        host = _two_phase_agg(node.input, [], node.aggregations,
                              ungrouped=True, stream=s, node=node)
        return MicroPartition(node.schema, [host.cast_to_schema(node.schema)])
    _note_region(node, region_ops, _counters.device_stage_batches - d0)
    c = current_collector()
    if c is not None:
        c.annotate(node, f"fused device udf: {call.func.name}")
    cols = []
    for name, _agg in agg_stage.aggs:
        f = node.schema[name]
        cols.append(Series.from_pylist([final[name]], f.name, dtype=f.dtype))
    out = RecordBatch(node.schema, cols, 1)
    return MicroPartition(node.schema, [out.cast_to_schema(node.schema)])


def _make_coalescer(feed, cfg, shards: int = 1, resident_rows: int = 0):
    """DispatchCoalescer for one device stage run (ops/stage.py), or None when
    coalescing is disabled (batch_fill_target == 0) — morsels then dispatch
    one-to-one, the pre-coalescing behavior. The flush threshold
    (batching.coalesce_target_rows) makes one compiled dispatch cover N small
    morsels with its bucket at least batch_fill_target full. A run that
    shards a dispatch's rows over `shards` devices fills a bucket a shard.
    `resident_rows` (a join run whose programs walk a long dispatch in
    segments, over a fact that reads that many rows of resident tables and
    still comes through the pipeline, being more than a select over one of
    them): contiguous morsels of a table, which glue at no copy, are held to
    the longer target of coalesce_target_rows(resident_rows=...), the
    length _feed_resident cuts a table it reads directly."""
    from .batching import coalesce_target_rows

    target = coalesce_target_rows(cfg, shards)
    if target <= 0:
        return None
    from ..ops.stage import DispatchCoalescer

    return DispatchCoalescer(
        feed, target_rows=target, latency_s=cfg.batch_latency_ms / 1e3,
        resident_target_rows=coalesce_target_rows(
            cfg, shards, resident_rows=resident_rows) if resident_rows else 0)


def _exec_device_join_agg(node) -> MicroPartition:
    """Run a DeviceJoinAgg node: the gather-join device program, or the
    untouched host plan (config off, small input, or runtime DeviceFallback).
    """
    from ..ops.device_join import DeviceJoinGroupedRun, DeviceJoinUngroupedRun

    def make_run(stage, grouped, ctx, shards):
        return (DeviceJoinGroupedRun(stage, ctx, shards) if grouped
                else DeviceJoinUngroupedRun(stage, ctx, shards))

    def assemble(run, stage, grouped):
        if grouped:
            key_rows, results = run.finalize()
            return _grouped_output(node.schema, node.spec.groupby,
                                   node.spec.aggregations, key_rows, results)
        from ..core.series import Series

        final = run.finalize()
        cols = []
        for name, _agg in stage.aggs:
            f = node.schema[name]
            cols.append(Series.from_pylist([final[name]], f.name, dtype=f.dtype))
        out = RecordBatch(node.schema, cols, 1)
        return MicroPartition(node.schema, [out.cast_to_schema(node.schema)])

    return _run_device_join(node, "join agg", make_run, assemble,
                            grouped_required=False, topn=False)


def _exec_device_join_topn(node) -> MicroPartition:
    """Run a DeviceJoinTopN node: the fused join+agg+sort+limit device
    program, or the untouched host plan (config off, cost model, or runtime
    DeviceFallback)."""
    from ..ops.device_join import DeviceJoinTopNRun

    def make_run(stage, grouped, ctx, shards):
        return DeviceJoinTopNRun(stage, ctx, node.topn, shards)

    def assemble(run, stage, grouped):
        key_rows, results = run.finalize_topn()
        from ..core.series import Series

        cols = []
        for f, (kind, idx) in zip(node.schema, node.out_map):
            if kind == "group":
                cols.append(Series.from_pylist([k[idx] for k in key_rows],
                                               f.name, dtype=f.dtype))
            else:
                vals, valid = results[idx]
                data = [v.item() if ok else None
                        for v, ok in zip(vals, valid)]
                cols.append(Series.from_pylist(data, f.name, dtype=f.dtype))
        out = RecordBatch(node.schema, cols, len(key_rows))
        return MicroPartition(node.schema, [out.cast_to_schema(node.schema)])

    return _run_device_join(node, "join topn", make_run, assemble,
                            grouped_required=True, topn=True)


def _run_device_join(node, label: str, make_run, assemble,
                     grouped_required: bool, topn: bool) -> MicroPartition:
    """Shared driver for the device join nodes: mode/backend gates, dim
    materialization, the cost-model decision (dims first — the joined group
    cardinality is sampled through the real join indices), feed, assembly,
    and host fallback with a recorded reason. Steady-state per-query device
    traffic is tiny (gathers read resident planes; every dim-sized upload is
    series_keyed-cached), so the decision weighs the amortized upload and
    factorize investment + one d2h round trip against host probe+agg passes.

    A fact or a dimension whose plan is a select over ONE in-memory table is
    read from the table on this thread (_resident_select: the dimension
    whole, the fact in the ranges it is dispatched in, _feed_resident); any
    other plan, the host plan and every fallback run through the pipeline.

    The tiers are priced at the dispatch the run delivers: a fact read as
    ranges at the range _feed_resident cuts (batching.resident_dispatch_rows,
    the one arithmetic: eight buckets a device of a long fact, so the device
    arms' round trip is shared by eight partitions, 32 over a mesh of four;
    _resident_horizon), a fact that comes through the pipeline at what its
    leading morsels promise of the coalescer (_coalesce_horizon). The rows a
    dispatch the chosen device arm was priced at are counted
    (`join_priced_dispatch_rows`) and ride the `placement.decide` span.
    """
    from ..config import execution_config
    from ..ops import counters as _counters
    from ..ops.device_join import _JoinContext, build_join_stage
    from ..ops.grouped_stage import DeviceFallback

    cfg = execution_config()

    def _host() -> MicroPartition:
        parts = list(_exec(node.host_plan))
        batch = _concat_parts(parts, node.schema)
        return MicroPartition(node.schema, [batch])

    if cfg.device_mode == "off":
        # config may have changed between translation (which gated capture)
        # and lazy execution — the off switch must hold at run time too
        return _host()
    if cfg.device_mode == "auto":
        import jax

        if jax.default_backend() in ("cpu",):
            _counters.reject("cost", f"{label}: cpu backend")
            _placement.ledger().gate(label, "cpu backend", only_scoped=True)
            return _host()

    # config/spec-only check BEFORE any subtree executes (the fallback path
    # must not pay a fact peek just to learn the stage can't build)
    stage, grouped = build_join_stage(node.spec)
    if stage is None or (grouped_required and not grouped):
        return _host()

    # A fact that is a select over one resident table is read as ranges of
    # the table, cut here, on the thread that dispatches: no stage thread, no
    # pool task and no channel stand between the table and its dispatch. The
    # decision below still looks at the first two MORSELS the pipeline would
    # have cut (iter_morsels makes those two and no more). Any other fact
    # comes through the pipeline, a closeable generator (cancellation target).
    table = _resident_select(node.fact) if _cuts_fixed_morsels(cfg) else None
    if table is not None:
        raw_stream = None
        fact_morsels = _table_morsels(table, cfg)
    else:
        raw_stream = fact_morsels = _exec(node.fact)

    def _close_fact() -> None:
        # a table read in ranges leaves nothing to close: no stage was
        # started and no generator is suspended over it
        if raw_stream is not None:
            raw_stream.close()

    try:
        first = next(fact_morsels, None)
        if first is None:
            _close_fact()
            return _host()
        if cfg.device_mode == "auto" and first.num_rows < cfg.device_min_rows:
            _counters.reject("cost", f"{label}: below device_min_rows",
                             f"({first.num_rows} rows)")
            _placement.ledger().gate(label, "below device_min_rows",
                                     first.num_rows, only_scoped=True)
            _close_fact()
            return _host()
        # a previously-rejected query shape skips dim materialization + the
        # sampled-cardinality estimate entirely (repeated interactive queries
        # must not pay the decision machinery per run). The coalesce horizon
        # is data-dependent, so the fact's FIRST-partition batch layout is
        # part of the cached verdict's identity — the same shape arriving as
        # one big batch vs eight small ones is a DIFFERENT costed decision.
        # The layout signature is computable without the second-partition
        # peek below, so cached-reject repeats pay for NO extra partition.
        # a fused TopN whose group ids hold for the whole run takes the
        # fact as the other join nodes do, batch after batch (what only the
        # dims can still refuse, their size, is seen once they are made)
        stream_wide = not topn or _topn_takes_stream(node.spec, stage)
        dk = _decision_key(node, first.num_rows, cfg, topn,
                           _batch_layout(first))
        if cfg.device_mode == "auto" and _DECISION_CACHE.get(dk) is False:
            _counters.reject("cost", f"{label}: host wins (cached decision)")
            # accelerator-backend-only path: safe to count the cached verdict
            _placement.ledger().record(label, "host", first.num_rows,
                                       cached=True,
                                       reason="host wins (cached decision)")
            _close_fact()
            return _host()
        second = None
        if cfg.device_mode == "auto" and stream_wide:
            from .batching import coalesce_target_rows

            if coalesce_target_rows(cfg) > 0:
                # peek one partition further (cached REJECTS returned above
                # without paying this; cached accepts consume the stream on
                # the device path anyway): observed second-partition morsels
                # widen the coalesce horizon. Skipped entirely when
                # coalescing is disabled — the horizon is 1.0 regardless.
                second = next(fact_morsels, None)
        if table is not None:
            # the peeks consumed nothing: the table is walked from its start
            fact_stream = _table_morsels(table, cfg)
        else:
            fact_stream = itertools.chain(
                [first] if second is None else [first, second], raw_stream)
        from ..ops.region import single_batch_horizon

        # a fused TopN held to one batch is a one-batch region by
        # construction; its RTT pricing comes from the shared region builder,
        # not a local constant (ops/region.py single_batch_horizon)
        dim_batches = {}
        for name, plan in node.dim_plans:
            dim = _resident_select(plan)
            if dim is not None:
                # a select over one resident table is the table's own
                # columns: taken whole, here, not cut into morsels and glued
                _counters.bump("join_resident_dims")
            else:
                dim = list(_exec(plan))
            dim_batches[name] = _concat_parts(dim, plan.schema)
        ctx = _JoinContext(node.spec, dim_batches)
        batch0 = next((b for b in first.batches if b.num_rows > 0), None)
        # a join whose group ids are not made on the host a batch at a time
        # takes a resident fact DISPATCH_SEGMENTS buckets a dispatch (a
        # sharded one does by what it is: sharded_join_reason): the ranges
        # of a table read directly, or what the coalescer of a fact that came
        # through the pipeline tells from the morsels themselves (below)
        from ..ops.device_join import host_ids_reason

        long_chip = batch0 is not None and not host_ids_reason(
            ctx, stage, grouped, topn, batch0)
        seen = [first] if second is None else [first, second]
        fact_rows = _resident_rows(node.fact) or 0

        def horizon(shards: int) -> float:
            """Partitions a dispatch over `shards` devices covers, as the
            tiers are priced (the docstring above says by what)."""
            if not stream_wide:
                return single_batch_horizon()
            if table is not None:
                ranged = _resident_horizon(
                    table, cfg, shards,
                    fact_rows if shards > 1 or long_chip else 0)
                if ranged is not None:
                    return ranged
            # (the one chip's arm never took the stream's length: as it was)
            return _coalesce_horizon(
                seen, shards=shards,
                stream_rows=(fact_rows or None) if shards > 1 else None)

        coal = horizon(1)

        # Mesh CANDIDATE resolution happens BEFORE pricing: the mesh arm is
        # only priced where the sharded dispatch takes this join, so a
        # "mesh" verdict is always executable and forced-priced records name
        # the tier that will really execute — the calibrate tool keys samples
        # on `chosen`, so a mismatch there poisons its suggestions.
        mesh_width = _join_mesh_width(cfg)
        if cfg.device_mode == "on" and cfg.mesh_devices < 2:
            # "on" forces the SINGLE-CHIP device path: the mesh engages only
            # via an explicit mesh_devices width (or by winning the auto-mode
            # cost decision) — a default-config 4-chip host must not silently
            # route every forced join onto the mesh
            mesh_width = 0
        if cfg.mesh_devices >= 2 and mesh_width == 0:
            # forced mesh, local devices short: LOUD single-chip fallback
            # (same semantics as the agg stages)
            import jax

            _counters.bump("mesh_unavailable_fallbacks")
            _counters.reject(
                "runtime", f"{label}: fewer local devices than mesh_devices",
                f"({len(jax.devices())} < {cfg.mesh_devices})")
        # What the mesh arm runs: the single chip's join dispatch on every
        # shard of the fact (ops/device_join.py, `mesh_devices`). A shape
        # that dispatch declines (sharded_join_reason: group codes or TopN
        # ids that need a host factorization of every batch, a forced Pallas
        # probe) is not over the mesh: from here it is priced and run as on
        # a one-chip host, and the rejection log and the placement record
        # say why.
        declined = ""
        if mesh_width >= 2 and batch0 is not None:
            from ..ops.device_join import sharded_join_reason

            declined = sharded_join_reason(ctx, stage, grouped, topn, batch0,
                                           mesh_width)
            if declined:
                _counters.reject(
                    "runtime", f"{label}: not sharded over the mesh",
                    f"({declined})")
                mesh_width = 0
        elif mesh_width >= 2:
            mesh_width = 0

        prec = None
        tier = False
        mesh_coal = horizon(mesh_width) if mesh_width >= 2 else coal
        if cfg.device_mode == "auto":
            if batch0 is not None:
                tier, prec = _join_device_wins(
                    node, ctx, batch0, first.num_rows, grouped, stage,
                    topn=topn, label=label, coalesce=coal,
                    mesh_ndev=mesh_width, mesh_coalesce=mesh_coal,
                    mesh_forced=cfg.mesh_devices >= 2 and mesh_width >= 2)
            _DECISION_CACHE.put(dk, tier)
        elif cfg.device_mode == "on":
            tier = "mesh" if mesh_width >= 2 else "chip"
            if _env_bool("DAFT_TPU_PLACEMENT_PRICE_FORCED", False):
                if batch0 is not None:
                    # forced run, priced anyway: the ledger record carries
                    # every tier's CostBreakdown (mesh arm included) so
                    # forced runs yield calibration samples + the
                    # three-way what-if in EXPLAIN PLACEMENT; `chosen` is
                    # pinned to the tier that executes below
                    _t, prec = _join_device_wins(
                        node, ctx, batch0, first.num_rows, grouped, stage,
                        topn=topn, label=label, coalesce=coal,
                        mesh_ndev=mesh_width, mesh_coalesce=mesh_coal,
                        forced=True, forced_tier=tier)
            if prec is None:
                prec = _placement.ledger().record(
                    label, "mesh" if tier == "mesh" else "device",
                    first.num_rows, forced=True)

        if declined:
            _placement.ledger().annotate(
                prec, f"not sharded over the mesh: {declined}")
        if not tier:    # `auto`: the host won
            _close_fact()
            return _host()
        shards = mesh_width if tier == "mesh" else 1
        run = make_run(stage, grouped, ctx, shards)
        from ..device.residency import manager as _residency

        # pin-scope the feed + finalize: entries this query touches (packed
        # planes, index planes, resident columns) cannot be evicted mid-run
        # by a tight HBM budget; the budget re-enforces at scope exit
        fed_rows = 0
        region_ops = ("join", "agg", "topn") if topn else ("join", "agg")
        d0 = _counters.device_join_batches
        with _placement.feedback(prec) as fb, _residency().pin_scope():
            if topn and not run.run_wide:
                # this run's group ids hold for ONE fact batch (a group-by
                # outside one dimension's key space): bail on sighting a
                # SECOND (before any device work, without draining the stream)
                why = run.one_batch_reason
                first_b = None
                for part in fact_stream:
                    for b in part.batches:
                        if b.num_rows == 0:
                            continue
                        if first_b is not None:
                            _counters.reject(
                                "runtime", f"{label}: multi-batch fact and no "
                                "run-wide group ids", f"({why})")
                            fb.cancel()  # no dispatch happened: nothing to observe
                            _close_fact()
                            return _host()
                        first_b = b
                if first_b is not None:
                    fed_rows = first_b.num_rows
                    run.feed_batch(first_b)
            else:
                # One gather-join dispatch covers DISPATCH_SEGMENTS buckets a
                # device of a resident fact, fewer where the fact is short (a
                # dispatch is never all of it: batching.
                # resident_dispatch_segments): a zero-copy range of the table,
                # so series_keyed slots, keyed on the rows a batch views and
                # not on its objects, hit on a repeat query. A table read
                # directly is cut into those ranges here (_feed_resident); a
                # fact that came through the pipeline is coalesced like the
                # agg paths' (a single-batch flush hands the batch through as
                # it is, contiguous morsels of a resident table glue back to
                # the range they were cut from: Series.concat).
                resident_rows = fact_rows if shards > 1 or long_chip else 0
                if table is not None:
                    fed_rows = _feed_resident(table, run, cfg, shards, resident_rows)
                else:
                    coalescer = _make_coalescer(run.feed_batch, cfg, shards,
                                                resident_rows=resident_rows)
                    feed = coalescer.add if coalescer is not None else run.feed_batch
                    for part in fact_stream:
                        fed_rows += part.num_rows
                        for b in part.batches:
                            feed(b)
                    if coalescer is not None:
                        coalescer.close()
            fb.set_rows(fed_rows)
            out = assemble(run, stage, grouped)
        if shards > 1:
            _counters.bump("mesh_join_runs")
        _note_region(node, region_ops, _counters.device_join_batches - d0)
        return out
    except DeviceFallback as e:
        _counters.reject("runtime", f"{label}: device fallback", str(e))
        _close_fact()
        return _host()


class _BoundedDecisionCache:
    """Thread-safe bounded FIFO verdict cache. Concurrent serving queries hit
    the decision/mesh-tier caches from many threads at once; a plain dict's
    `pop(next(iter(d)))` eviction under concurrent insertion can raise
    RuntimeError mid-query, so reads and the insert+evict pair are locked
    (coarse events only — one probe per cost decision, never per row)."""

    def __init__(self, cap: int = 512):
        self._lock = threading.Lock()
        self._d: dict = {}
        self.cap = cap

    def get(self, key, default=None):
        with self._lock:
            return self._d.get(key, default)

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            while len(self._d) > self.cap:
                self._d.pop(next(iter(self._d)))

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


_DECISION_CACHE = _BoundedDecisionCache()


def _batch_layout(part: MicroPartition) -> tuple:
    """Batch-granularity signature of one partition: (nonempty batch count,
    mean batch rows padded to its bucket). The coalesce horizon derives from
    this, so it identifies a cached cost verdict without needing the
    second-partition peek."""
    from ..ops.stage import pad_bucket

    sizes = [b.num_rows for b in part.batches if b.num_rows > 0]
    if not sizes:
        return (0, 0)
    return (len(sizes), pad_bucket(int(sum(sizes) / len(sizes))))


def _topn_takes_stream(spec, stage) -> bool:
    """Whether a fused TopN over `spec` keeps its group tables for the whole
    run, as far as the plan alone says (ops/device_join.run_wide_groups): its
    fact is then fed batch after batch and priced as a coalesced stream."""
    from ..ops.device_join import run_wide_groups

    return run_wide_groups(spec)[0] is not None and stage.run_wide_reason() is None


def _resident_rows(n) -> Optional[int]:
    """Rows a fact plan over in-memory tables reads, or None where a leaf is
    anything else: what tells a fact of one batch from one of 458 before the
    stream has been walked."""
    kids = n.children()
    if not kids:
        if not isinstance(n, pp.InMemoryScan):
            return None
        return sum(p.num_rows for p in n.partitions)
    total = 0
    for k in kids:
        rows = _resident_rows(k)
        if rows is None:
            return None
        total += rows
    return total


def _is_column_select(e: Expression) -> bool:
    from ..expressions.expressions import Alias

    while isinstance(e, Alias):
        e = e.child
    return isinstance(e, ColumnRef)


def _resident_select(plan) -> Optional[List[MicroPartition]]:
    """The partitions of the one in-memory table a join's fact or dimension
    plan selects from, the plan's projection applied, or None. It answers
    only where the plan is a chain of Projects whose expressions are column
    references or aliases of them over ONE InMemoryScan: a select, which
    views a range of any length at no copy, so the pool's width has no work
    to do on it. A computed projection, a filter, a streamed or task scan, a
    concat: None, and the plan runs through the pipeline (_exec)."""
    selects = []
    n = plan
    while isinstance(n, pp.Project):
        if not all(_is_column_select(e) for e in n.projection):
            return None
        selects.append(n)
        n = n.input
    if not selects or not isinstance(n, pp.InMemoryScan):
        return None
    parts = n.partitions
    for sel in reversed(selects):
        parts = [MicroPartition(
            sel.schema, [eval_projection(b, sel.projection) for b in p.batches]
            or [RecordBatch.empty(sel.schema)]) for p in parts]
    return parts


def _cuts_fixed_morsels(cfg) -> bool:
    """Whether a Project over a table would hand it on as morsels of the
    fixed size, through a stage thread and the pool: what the join driver
    can cut itself. With the pipeline off nothing is cut and nothing is
    handed over; a feedback-driven strategy sizes its morsels from the pool's
    timings: such a fact keeps the road through _exec."""
    return cfg.batching_mode == "static" and _pipeline_on()


def _table_morsels(table: List[MicroPartition], cfg) -> Iterator[MicroPartition]:
    """The morsels a Project over `table` hands on (_map_op), made one at a
    time on the calling thread."""
    from .pipeline import iter_morsels

    return (m for part in table for m in iter_morsels(part, cfg.morsel_size_rows))


def _feed_resident(table: List[MicroPartition], run, cfg, shards: int,
                   resident_rows: int) -> int:
    """Hand a resident fact to `run.feed_batch` on this thread; the rows fed.

    A batch the pipeline would have cut into morsels (pipeline.cut_batches)
    goes as zero-copy ranges of itself: batching.resident_dispatch_rows, as
    many whole morsels as reach coalesce_target_rows(resident_rows=...) where
    the run takes a long dispatch (`resident_rows` > 0), one morsel where it
    does not: the length the join's tiers were priced at. Those are
    the starts and lengths a DispatchCoalescer flushes of the same morsels,
    so the programs, their shapes and the slots keyed on the rows a range
    views are the same; a range never spans two batches. A batch that is not
    cut (a short one) is no view of a longer one: it goes through a
    coalescer at the plain threshold, which glues short batches by copy."""
    from ..ops import counters as _counters
    from .batching import resident_dispatch_rows
    from .pipeline import cut_batches

    morsel = cfg.morsel_size_rows
    # whole morsels until the target is reached
    range_rows = resident_dispatch_rows(cfg, shards, resident_rows)
    coalescer = _make_coalescer(run.feed_batch, cfg, shards)
    fed = 0
    for part in table:
        fed += part.num_rows
        for b, cut in cut_batches(part, morsel, range_rows):
            if not cut:
                (coalescer.add if coalescer is not None else run.feed_batch)(b)
                continue
            if coalescer is not None:
                coalescer.flush()    # what came whole before this batch goes first
            # the extent a coalescer's flush had around a dispatch
            with _profile_span("join.range", "device", rows=b.num_rows):
                run.feed_batch(b)
            _counters.bump("join_resident_ranges")
    if coalescer is not None:
        coalescer.close()
    return fed


def _resident_horizon(table: List[MicroPartition], cfg, shards: int,
                      resident_rows: int) -> Optional[float]:
    """Morsels one dispatch of _feed_resident covers of `table`: the first
    range it cuts (batching.resident_dispatch_rows, by pipeline.cut_batches'
    own rule) over the morsel the decision looks at. None where the table's
    first batch is not cut into ranges (a short one goes whole, through a
    coalescer at the plain threshold)."""
    from .batching import resident_dispatch_rows
    from .pipeline import cut_batches

    morsel = cfg.morsel_size_rows
    range_rows = resident_dispatch_rows(cfg, shards, resident_rows)
    for part in table:
        for b, cut in cut_batches(part, morsel, range_rows):
            if b.num_rows:
                return b.num_rows / morsel if cut else None
    return None


def _decision_key(node, rows: int, cfg, topn: bool, layout: tuple) -> tuple:
    """Structural identity of one cost decision: the captured spec's shape +
    input size + the config knobs the decision reads + the data-dependent
    fact batch layout the coalesce horizon derives from.

    The cache is a repeat-query heuristic, not an exact memo: inputs that
    would require paying the decision machinery per run are deliberately NOT
    keyed — the second-partition peek (whether the stream continues past the
    first partition) and the live HBM residency picture both shift the
    costs, and a repeat whose tail or residency differs reuses the prior
    verdict. Both paths stay correct; only placement can be stale, and a
    config change to any keyed knob re-decides."""
    spec = node.spec
    return (
        # a fused TopN over the whole stream prices its one select against all
        # of the fact's batches, and every join over a resident fact prices
        # the dispatch the fact's length delivers (_resident_horizon): the
        # first partition's layout alone would let a one-batch fact's verdict
        # serve a 458-batch one
        topn, rows, _resident_rows(node.fact),
        cfg.device_mode, cfg.device_amortize_runs,
        # the coalescing horizon feeds the costed decision: a config change to
        # the coalescer knobs OR a different fact batch layout must re-decide,
        # not hit a stale cached verdict
        cfg.batch_fill_target, cfg.morsel_size_rows, layout,
        # the mesh arm reads the mesh knob: flipping it re-decides the tier
        cfg.mesh_devices,
        # the predicate's skeleton, not its values: _join_device_wins prices
        # the host arm by plan/stats.selectivity, which reads the operators
        # and never a literal, so another DATE is the same decision (an is_in
        # of another length is another skeleton)
        None if spec.predicate is None else expr_structure(spec.predicate)[0],
        tuple(repr(g) for g in spec.groupby),
        tuple(repr(a) for a in spec.aggregations),
        tuple((d.key_col, d.parent) for d in spec.dims),
        # dim source identity via monotonic tokens (device/residency.py): a
        # rewritten/grown dim table must re-decide. Raw id() here could pin a
        # stale routing decision when CPython reuses a freed object's id
        tuple(identity_token(part)
              for _n, plan in node.dim_plans
              for part in getattr(plan, "partitions", ())),
    )


def _join_mesh_width(cfg) -> int:
    """Mesh width the join cost decision should PRICE: 0 when the mesh tier
    is disabled (mesh_devices == 1) or fewer than 2 local devices exist,
    else the full local mesh (or the forced width). Pricing-only — forcing
    semantics live in _run_device_join."""
    if cfg.mesh_devices == 1:
        return 0
    import jax

    ndev = len(jax.devices())
    if cfg.mesh_devices >= 2:
        return cfg.mesh_devices if ndev >= cfg.mesh_devices else 0
    return ndev if ndev >= 2 else 0


@_decide_span
def _join_device_wins(node, ctx, batch, rows: int, grouped: bool, stage,
                      topn: bool = False, label: str = "join agg",
                      coalesce: float = 1.0, mesh_ndev: int = 0,
                      forced: bool = False, forced_tier=None,
                      mesh_forced: bool = False, mesh_coalesce: float = 1.0):
    """Cost-model decision for a DeviceJoinAgg node (see ops/costmodel.py).
    Returns (tier, placement_record) with tier in {"mesh", "chip", False} —
    ALL priced tiers' CostBreakdowns land in the ledger so EXPLAIN PLACEMENT
    can show per-term why a star join cost-rejected to host (the engine's
    headline loss) and what the mesh arm would have cost.

    The mesh arm (mesh_ndev >= 2: the caller passes a width only where
    device_join.sharded_join_reason takes the join) prices what will run,
    the single chip's join dispatch on every shard of the fact
    (ops/device_join.py with `mesh_devices`): the chip arm's own terms at
    rows / width (float32 planes, int32 index planes, no host
    factorization), the round trip shared by the `mesh_coalesce` partitions
    a sharded dispatch covers, and what spanning the devices adds
    (costmodel.over_mesh; a run-wide TopN's tables cross the chips once a
    run). Mesh must beat BOTH the single chip and the host — same
    discipline as _mesh_wins. The chip arm is not eligible where the fact's
    planes and the run's tables would not fit one chip's HBM budget, or
    where a fused TopN's tables pass the one chip's ceiling and the fact
    has more batches than the one-batch form takes.

    One-time investments (fact column uploads, index planes, joined-key
    factorize) amortize over device_amortize_runs when the fact source is a
    resident in-memory table — they are all series_keyed-cached, so reps pay
    only dispatches + one fetch.

    `forced=True` (device_mode=on under DAFT_TPU_PLACEMENT_PRICE_FORCED)
    runs the same pricing purely to populate the ledger — the caller ignores
    the verdict, the record is marked forced, and its `chosen` is pinned to
    `forced_tier` (the tier the caller will actually execute — the calibrate
    tool attributes observed seconds to the CHOSEN tier's prediction, so
    recording the priced winner instead would poison its samples)."""
    from ..config import execution_config
    from ..ops import costmodel, counters as _counters
    from ..ops.device_join import DeviceJoinGroupedRun, estimate_joined_cardinality
    from ..ops.grouped_stage import MAX_MATMUL_SEGMENTS, _pad_groups
    from ..ops.stage import pad_bucket

    spec = node.spec
    cal = costmodel.calibrate()
    bucket = pad_bucket(batch.num_rows)
    # coalesce horizon computed by the caller (from the fact's batch layout;
    # 1.0 for TopN — its one-batch fact can never coalesce, so pricing an
    # amortized RTT would flip marginal host-wins shapes to a device run
    # that pays the full round trip, and cache the wrong verdict)
    coal = max(coalesce, 1.0)
    amort = max(execution_config().device_amortize_runs, 1) \
        if _resident_source_rec(node.fact) else 1

    # The HOST plan pushes the lifted conjuncts back below the join, so its
    # probe/agg passes see only the filtered stream; the device program sees
    # every row (filters are masks). Price them accordingly.
    host_rows = rows
    if spec.predicate is not None:
        from ..plan.stats import selectivity

        host_rows = max(int(rows * min(selectivity(spec.predicate), 1.0)), 1)

    fact_cols = [c for c in stage._input_cols
                 if spec.col_side.get(c) == "fact" and c not in spec.fact_synthetic]
    dim_cols = [c for c in stage._input_cols
                if spec.col_side.get(c) not in ("fact", None)]
    nonres = res = 0
    for c in fact_cols:
        if batch.get_column(c).is_device_resident(bucket, f32=True):
            res += batch.num_rows * 5  # residency credit: priced at zero h2d
        else:
            nonres += batch.num_rows * 5
    # padded per-dim index planes: residency-aware — a repeat query whose
    # index planes are already in HBM is costed with zero transfer for them
    nonres += ctx.nonresident_index_bytes(batch, bucket)
    n_gathers = len(dim_cols) + len(spec.dims)  # value planes + visibility

    # mesh arm inputs, probed against the mesh's OWN residency slots so a
    # warm mesh repeat prices at zero transfer like the single-chip arm does
    mesh_nonres = mesh_res = 0
    sharded = mesh_ndev >= 2
    if sharded:
        from ..ops.stage import MESH_AXIS, mesh_total

        # the chip arm's planes in the mesh's layout (a sharded dispatch
        # covers several partitions, whose planes are slots of their own:
        # this batch's probe finds them only where it was dispatched alone)
        mesh_pad = mesh_total(batch.num_rows, mesh_ndev)
        for c in fact_cols:
            if batch.get_column(c).is_device_resident(
                    mesh_pad, f32=True, mesh_devices=mesh_ndev):
                mesh_res += batch.num_rows * 5
            else:
                mesh_nonres += batch.num_rows * 5
        mesh_nonres += ctx.nonresident_index_bytes(
            batch, mesh_pad, ("mesh", mesh_ndev, MESH_AXIS))

    # Pallas hash-probe what-if arm: total padded table slots over the
    # fact-adjacent dims (the kernel's brute-force probe is rows x slots
    # cells; chained dims keep the host probe, so they contribute none).
    # Priced for EVERY decision — the breakdown rides the record even when
    # the stage is Pallas-ineligible, and the verdict feeds the ctx's auto
    # gate preference.
    probe_slots = 0
    for d in spec.dims:
        if d.parent[0] == "fact":
            t = 128
            while t < max(ctx.batches[d.name].num_rows, 1):
                t *= 2
            probe_slots += t
    chip_ok = True
    mesh_cost = None
    fact_rows = _resident_rows(node.fact) or rows
    # the sharded dispatch's arm is the chip arm's own terms at a shard's rows
    shard_rows = max(-(-rows // mesh_ndev), 1) if sharded else rows
    if grouped:
        import math

        from ..ops.device_join import DeviceJoinTopNRun

        wide = mwide = None
        if topn:
            from ..ops.device_join import topn_run_wide

            wide, wide_cap, _why = topn_run_wide(ctx, stage)
            if sharded:
                # (the ceiling is held to a chip's share of the ids)
                mwide, mwide_cap, _why = topn_run_wide(ctx, stage, mesh_ndev)
            if wide is None and fact_rows > rows:
                # the one chip's other form takes a fact of one batch only
                chip_ok = False
        ceiling = DeviceJoinTopNRun.max_segments if topn \
            else DeviceJoinGroupedRun.max_segments
        if wide is not None or mwide is not None:
            # the tables that will be built are the dimension's padded rows
            # long whatever a batch holds: nothing is sampled (and the
            # run-wide ceiling was held when `wide` was found)
            card = max(ctx.batches[(wide or mwide).dim.name].num_rows, 1)
            cap_est = ceiling = wide_cap if wide is not None else mwide_cap
        else:
            card = estimate_joined_cardinality(ctx, batch, stage.groupby)
            cap_est = _pad_groups(min(max(card, 1), 2 * ceiling))
        if cap_est > ceiling and not forced:
            # both device tiers pay the same finalize-fetch/table budget.
            # A FORCED run executes regardless, so gating here would write a
            # host-gate record + cost rejects that contradict the forced
            # device record for the same query — forced pricing proceeds.
            _counters.reject("cost", f"{label}: est group count over ceiling",
                             f"({card} > {ceiling})")
            _placement.ledger().gate(label, "est group count over ceiling",
                                     rows)
            return False, None
        if wide is None and cap_est > MAX_MATMUL_SEGMENTS and (
                stage._sct_specs or stage._use_f64):
            # the local-dense program (group codes factorized on the host)
            # cannot serve 64-bit scatter/f64 stages. A mesh arm is priced
            # only for a join that makes no such codes (sharded_join_reason),
            # so it stays eligible.
            chip_ok = False
            if not sharded and not forced:
                _counters.reject(
                    "cost", f"{label}: high-cardinality stage needs 64-bit "
                    "scatter/f64 (no local-dense program)")
                _placement.ledger().gate(
                    label, "high-cardinality stage needs 64-bit scatter/f64",
                    rows)
                return False, None
        n_mm = len(stage._mm_specs)
        n_ext = len(stage._ext_specs)
        n_sct = len(stage._sct_specs)
        if topn:
            k_total = node.topn.offset + node.topn.limit
            fetch = k_total * (n_mm + n_ext + n_sct + 1) * 8
        else:
            fetch = cap_est * (n_mm + n_ext + n_sct) * 8
        # one select and one fetch a run: this partition carries its
        # share of them, by its rows over the fact's where those are known
        share = min(rows / max(fact_rows, 1), 1.0)

        def run_wide_arm(groups, arm_rows, select_ids, arm_nonres, arm_res,
                         arm_coal):
            """A partition of a fused TopN run that keeps run-wide tables:
            `arm_rows` rows a device, a select over `select_ids` ids."""
            from ..ops.grouped_stage import CHUNK_LOCAL

            return costmodel.device_join_topn_run_cost(
                cal, arm_rows, arm_nonres // amort, n_gathers, n_mm, select_ids,
                min(CHUNK_LOCAL, bucket),
                ctx.ids_locally_dense(batch, groups.dim.name), fetch, share,
                len(node.topn.keys), rows // amort, coalesce=arm_coal,
                resident_bytes=arm_res)

        if wide is not None:
            dev_cost = run_wide_arm(wide, rows, cap_est, nonres, res, coal)
        else:
            nonres += bucket * 4               # codes plane (host-factorize case)
            dev_cost = costmodel.device_join_agg_cost(
                cal, rows, nonres // amort, n_gathers, n_mm, n_ext, n_sct,
                cap_est, fetch, rows // amort, MAX_MATMUL_SEGMENTS, coalesce=coal,
                resident_bytes=res)
        pallas_cost = costmodel.device_join_pallas_cost(
            cal, rows, nonres // amort, probe_slots, n_mm, n_ext, n_sct,
            cap_est, fetch, rows // amort, coalesce=coal, resident_bytes=res)
        if topn and wide is None:
            # device multi-key sort over the cap-length planes
            nkeys = len(node.topn.keys) + 2
            dev_cost.add("compute",
                         cap_est * max(math.log2(max(cap_est, 2)), 1.0)
                         * nkeys / cal.mm_plane_rows_per_s)
        host_cost = costmodel.host_join_agg_cost(
            cal, host_rows, len(spec.dims), len(stage.aggs), True, False)
        if spec.predicate is not None:
            host_cost.add("compute", _host_filter_seconds(spec, rows, cal))
        if topn:
            # host additionally sorts the aggregate's output rows (once a
            # run: a partition of a streamed fact carries its share)
            host_cost.add("compute",
                          (share if (wide or mwide) is not None else 1.0)
                          * card * max(math.log2(max(card, 2)), 1.0)
                          / cal.host_agg_rate)
        if sharded:
            if topn:
                # every chip adds into tables of all the ids, selects over
                # its share of them, and the tables cross the chips once a run
                table_bytes = mwide_cap * (n_mm * 8 + 4)
                mesh_cost = costmodel.over_mesh(
                    run_wide_arm(mwide, shard_rows, mwide_cap // mesh_ndev,
                                 mesh_nonres, mesh_res, mesh_coalesce),
                    cal, mesh_ndev, 0, coalesce=mesh_coalesce)
                mesh_cost.add("ici", share * table_bytes * (mesh_ndev - 1)
                              / mesh_ndev / cal.ici_bytes_per_s)
                mesh_cost.add("d2h", share * (mesh_ndev - 1) * fetch
                              / cal.d2h_bytes_per_s)
            else:
                # dictionary group codes, combined on the device: one small
                # table a shard comes back, merged on the host
                mesh_cost = costmodel.over_mesh(
                    costmodel.device_join_agg_cost(
                        cal, shard_rows, mesh_nonres // amort, n_gathers, n_mm,
                        n_ext, n_sct, cap_est, fetch, rows // amort,
                        MAX_MATMUL_SEGMENTS, coalesce=mesh_coalesce,
                        resident_bytes=mesh_res),
                    cal, mesh_ndev, fetch, coalesce=mesh_coalesce)
        detail = (f"{len(spec.dims)} dims, {len(stage.aggs)} aggs, "
                  f"~{card} joined groups")
    else:
        fetch = 256 * max(len(stage.aggs), 1)
        dev_cost = costmodel.device_join_agg_cost(
            cal, rows, nonres // amort, n_gathers, max(len(stage.aggs), 1),
            0, 0, 1, fetch, rows // amort, MAX_MATMUL_SEGMENTS, coalesce=coal,
            resident_bytes=res)
        pallas_cost = costmodel.device_join_pallas_cost(
            cal, rows, nonres // amort, probe_slots,
            max(len(stage.aggs), 1), 0, 0, 1, fetch, rows // amort,
            coalesce=coal, resident_bytes=res)
        host_cost = costmodel.host_join_agg_cost(
            cal, host_rows, len(spec.dims), len(stage.aggs), False, False)
        if spec.predicate is not None:
            host_cost.add("compute", _host_filter_seconds(spec, rows, cal))
        if sharded:
            mesh_cost = costmodel.over_mesh(
                costmodel.device_join_agg_cost(
                    cal, shard_rows, mesh_nonres // amort, n_gathers,
                    max(len(stage.aggs), 1), 0, 0, 1, fetch, rows // amort,
                    MAX_MATMUL_SEGMENTS, coalesce=mesh_coalesce,
                    resident_bytes=mesh_res),
                cal, mesh_ndev, fetch, coalesce=mesh_coalesce)
        detail = f"{len(spec.dims)} dims, {len(stage.aggs)} aggs"

    if chip_ok and _resident_source_rec(node.fact):
        # what one chip would have to hold for the whole of a resident fact:
        # the planes and index planes of every batch, and a run's tables
        from ..device.residency import manager as _residency

        chip_bytes = fact_rows * (5 * len(fact_cols) + 4 * len(ctx._adjacent()))
        if grouped and wide is not None:
            chip_bytes += wide_cap * (len(stage._mm_specs) * 8 + 4)
        budget = _residency().budget_bytes()     # (0: unbounded)
        if 0 < budget < chip_bytes:
            chip_ok = False

    wins_chip = chip_ok and dev_cost < host_cost
    if mesh_forced:
        # explicit mesh_devices width under auto: the device side IS the
        # mesh (the chip is not an option), so the decision — and the
        # record's chosen, which calibration samples key on — is mesh vs
        # host only
        tier = "mesh" if (mesh_cost is not None
                          and mesh_cost < host_cost) else False
    else:
        wins_mesh = (mesh_cost is not None
                     and (not chip_ok or mesh_cost < dev_cost)
                     and mesh_cost < host_cost)
        tier = "mesh" if wins_mesh else ("chip" if wins_chip else False)
    if not tier and not forced:
        msg = (f"(host {host_cost*1e3:.0f}ms vs device "
               f"{dev_cost*1e3:.0f}ms est")
        if mesh_cost is not None:
            msg += f" vs mesh {mesh_cost*1e3:.0f}ms"
        _counters.reject("cost", f"{label}: host wins cost model", msg + ")")
    if forced:
        # the record must name the tier that EXECUTES, not the priced winner
        chosen = {"mesh": "mesh", "chip": "device"}.get(forced_tier, "device")
    else:
        chosen = {"mesh": "mesh", "chip": "device", False: "host"}[tier]
    # the auto Pallas-probe gate reads this preference on silicon: the kernel
    # arm must beat the XLA gather arm for THIS join's shape, and only joins
    # with fact-adjacent dims are probe-eligible at all
    ctx.pallas_probe_preferred = bool(probe_slots) and pallas_cost < dev_cost
    rec = _placement.ledger().record(
        label, chosen, rows,
        forced=forced, device=dev_cost, host=host_cost, mesh=mesh_cost,
        pallas=pallas_cost,
        detail=detail + (f", mesh x{mesh_ndev}" if mesh_ndev >= 2 else ""))
    if chosen != "host":
        # the rows a dispatch the arm that will run was priced at: beside
        # device_join_batches and the rows fed it says whether price and
        # delivery still agree
        rec.priced_rows = int(round(rows * max(
            mesh_coalesce if chosen == "mesh" else coal, 1.0)))
        _counters.bump("join_priced_dispatch_rows", rec.priced_rows)
    return tier, rec


def _host_filter_seconds(spec, rows: int, cal) -> float:
    """What the host plan's filter costs a partition of `rows` rows: one
    vectorized pass, and for each string membership on a FACT column
    (`spec.fact_synthetic`: an `is_in` or an equality the device reads as a
    resident plane) the dictionary encoding of the partition's fresh slice,
    which is how the host evaluates it (series.dict_encode: the larger half of
    what TPC-H q12 and q19 cost on the host tier). Without the term the two
    tiers of such a join priced within a few per cent of each other and the
    probed round trip of the minute decided."""
    return rows / cal.host_agg_rate \
        + len(spec.fact_synthetic) * rows / cal.host_dict_encode_rate


def _dict_build_rows(key_series, rows: int, cal) -> int:
    """What the dictionary builds of one batch's group keys cost, as rows at
    `cal.host_factorize_rate` (the unit the grouped cost functions take): a
    key whose dictionary is cached costs nothing, a string or binary key
    without nulls is encoded by Arrow at `host_dict_encode_rate`
    (Series._arrow_dict_codes), any other goes through make_groups at the
    full rate."""
    total = 0.0
    for s in key_series:
        if getattr(s, "_dict_codes", None) is not None:
            continue
        if s.arrow_encodes():
            total += rows * cal.host_factorize_rate / cal.host_dict_encode_rate
        else:
            total += rows
    return int(total)


def _resident_source_rec(n) -> bool:
    """True if every leaf under `n` is an in-memory scan (resident table)."""
    kids = n.children()
    if not kids:
        return isinstance(n, pp.InMemoryScan)
    return all(_resident_source_rec(k) for k in kids)


def _grouped_output(schema, groupby, aggregations, key_rows, results) -> MicroPartition:
    """Assemble a grouped-agg result batch from key tuples + per-agg
    (values, valid) arrays — shared by the single-chip and mesh device paths
    so null/dtype semantics cannot drift."""
    from ..core.series import Series

    cols = []
    for i, g in enumerate(groupby):
        f = schema[g.name()]
        cols.append(Series.from_pylist([k[i] for k in key_rows], f.name, dtype=f.dtype))
    for e, (vals, valid) in zip(aggregations, results):
        f = schema[e.name()]
        data = [v.item() if ok else None for v, ok in zip(vals, valid)]
        cols.append(Series.from_pylist(data, f.name, dtype=f.dtype))
    out = RecordBatch(schema, cols, len(key_rows))
    return MicroPartition(schema, [out.cast_to_schema(schema)])


_MESH_TIER_CACHE = _BoundedDecisionCache()


def _invalidate_costed_verdicts() -> None:
    """costmodel.reset_calibration() hook: every cached placement verdict was
    priced under the Calibration being discarded — a recalibrated process
    (e.g. after exporting the calibrate tool's suggested cost overrides)
    must re-decide placements, not replay stale ones."""
    _DECISION_CACHE.clear()
    _MESH_TIER_CACHE.clear()


_costmodel.on_calibration_reset(_invalidate_costed_verdicts)


@_decide_span
def _select_mesh_tier(node, stream, grouped: bool, cfg):
    """Pick the mesh width for one device agg stage; 0 = single-chip.

    Forced (cfg.mesh_devices >= 2): exactly that many local devices, with a
    LOUD fallback (counter + rejection record) when fewer exist — the old
    gate fell back silently. Auto (mesh_devices == 0): the mesh must WIN its
    placement, never be config-forced — the first morsel's shape is costed
    (_mesh_wins) and the mesh tier is taken only when it beats BOTH the
    single-chip device and the host; verdicts are cached per stage shape like
    the join decision cache (not per residency: like _decision_key, a repeat
    whose residency differs reuses the verdict; _mesh_wins itself reads
    residency in the f32 layout the sharded stage asks for, so the first,
    deciding query of a warm table is not priced as an upload). Returns
    (n_devices, stream, placement_record) with any peeked partition chained
    back."""
    import jax

    from ..ops import counters as _counters

    ndev = len(jax.devices())
    if cfg.mesh_devices >= 2:
        if ndev >= cfg.mesh_devices:
            rec = _placement.ledger().record("mesh tier", "mesh", forced=True,
                                             detail=f"{cfg.mesh_devices} devices")
            return cfg.mesh_devices, stream, rec
        _counters.bump("mesh_unavailable_fallbacks")
        _counters.reject("runtime", "mesh: fewer local devices than mesh_devices",
                         f"({ndev} < {cfg.mesh_devices})")
        _placement.ledger().gate(
            "mesh tier", "fewer local devices than mesh_devices")
        return 0, stream, None
    if ndev < 2:
        return 0, stream, None
    first = next(stream, None)
    if first is None:
        return 0, iter(()), None
    stream = itertools.chain([first], stream)
    if first.num_rows < cfg.device_min_rows:
        return 0, stream, None
    from ..ops.stage import pad_bucket

    # the compiled stage, so the query's shape and not its literals' values:
    # _mesh_wins prices rows, planes and residency and reads no value
    bound = node.bound_stage()
    if bound is None:
        return 0, stream, None
    key = (grouped, ndev, pad_bucket(first.num_rows),
           cfg.batch_fill_target, cfg.morsel_size_rows, bound[0])
    wins = _MESH_TIER_CACHE.get(key)
    rec = None
    if wins is None:
        wins, rec = _mesh_wins(node, first, grouped, ndev)
        _MESH_TIER_CACHE.put(key, wins)
    elif wins:
        # cached-accept repeat: still a ledger entry so the dispatched run's
        # observed seconds have a record to land in
        rec = _placement.ledger().record(
            "mesh tier", "mesh", first.num_rows, cached=True,
            detail=f"{ndev} devices")
    else:
        _placement.ledger().gate("mesh tier", "no-mesh (cached verdict)",
                                 first.num_rows, only_scoped=True)
    return (ndev if wins else 0), stream, rec


@_decide_span
def _mesh_wins(node, first: MicroPartition, grouped: bool, ndev: int):
    """Cost-model tier decision: mesh vs single-chip vs host for one stage
    shape. The mesh runs the single chip's program on every shard, so its
    arm is the single-chip arm at rows / ndev, with residency probed in the
    layout the sharded stage asks for, plus what spanning the devices adds
    (costmodel.over_mesh: the launch premium and the fetch of one partial
    table a shard, as _probe_mesh_terms measured them). Uploads amortize
    exactly like the single-chip decision when the source table is resident.
    Returns (wins, placement_record) — the record carries all THREE tiers'
    CostBreakdowns (mesh / device / host)."""
    from ..config import execution_config
    from ..ops import costmodel, counters as _counters
    from ..ops.stage import mesh_total, pad_bucket

    batch = next((b for b in first.batches if b.num_rows > 0), None)
    if batch is None:
        return False, None
    bound = node.bound_stage()
    if bound is None:
        return False, None
    rows = first.num_rows
    shard_rows = max((rows + ndev - 1) // ndev, 1)
    cal = costmodel.calibrate()
    coal = _coalesce_horizon([first])
    amort = max(execution_config().device_amortize_runs, 1) \
        if _resident_source_rec(node.input) else 1
    mesh_pad = mesh_total(batch.num_rows, ndev)
    bucket = pad_bucket(batch.num_rows)

    def plane_bytes(stage, pad_to, mesh_devices):
        """(non-resident, resident) bytes of the stage's input planes in one
        layout: the dtype the stage uploads, 5 B a row with validity."""
        nonres = res = 0
        for c in stage._input_cols:
            if batch.get_column(c).is_device_resident(
                    pad_to, f32=not stage._use_f64, mesh_devices=mesh_devices):
                res += batch.num_rows * 5
            else:
                nonres += batch.num_rows * 5
        return nonres, res

    if grouped:
        from ..ops.grouped_stage import (MAX_MATMUL_SEGMENTS, _pad_groups,
                                         estimate_key_cardinality,
                                         resolve_key_series)

        stage = bound[0]
        key_series = resolve_key_series(batch, stage.groupby, batch.num_rows)
        card = max(estimate_key_cardinality(key_series), 1)
        cap_est = _pad_groups(min(card, 2 * MAX_MATMUL_SEGMENTS))
        # factorize pricing MUST match _device_wins, on both arms: dictionary
        # keys amortize (cached per Series, and shared by the two layouts'
        # code planes), host-mode keys re-factorize per run at full price
        if stage.dict_keys:
            fact_rows = _dict_build_rows(key_series, batch.num_rows,
                                         cal) // amort
        else:
            fact_rows = batch.num_rows
        n_planes = (len(stage._mm_specs) + len(stage._ext_specs)
                    + len(stage._sct_specs))

        def arm(arm_rows, nonres, res):
            if card > MAX_MATMUL_SEGMENTS:
                return costmodel.device_grouped_sort_cost(
                    cal, arm_rows, nonres // amort, n_planes=n_planes,
                    factorize_rows=fact_rows, coalesce=coal,
                    resident_bytes=res)
            return costmodel.device_grouped_cost(
                cal, arm_rows, nonres // amort, n_mm=len(stage._mm_specs),
                n_ext=len(stage._ext_specs), n_sct=len(stage._sct_specs),
                cap=cap_est, factorize_rows=fact_rows, coalesce=coal,
                resident_bytes=res)

        table_bytes = cap_est * n_planes * 8
        host_cost = costmodel.host_agg_cost(
            cal, rows, len(node.aggregations), grouped=True,
            has_predicate=node.predicate is not None)
    else:
        stage = bound[0]
        n_partials = max(len(stage.aggs), 1)

        def arm(arm_rows, nonres, res):
            return costmodel.device_ungrouped_cost(
                cal, arm_rows, nonres // amort, n_partials=n_partials,
                coalesce=coal, resident_bytes=res)

        table_bytes = n_partials * 16
        host_cost = costmodel.host_agg_cost(
            cal, rows, len(node.aggregations), grouped=False,
            has_predicate=node.predicate is not None)
    single_cost = arm(rows, *plane_bytes(stage, bucket, 0))
    mesh_cost = costmodel.over_mesh(
        arm(shard_rows, *plane_bytes(stage, mesh_pad, ndev)), cal, ndev,
        table_bytes)
    wins = mesh_cost < single_cost and mesh_cost < host_cost
    if not wins:
        _counters.reject(
            "cost", "mesh: single-chip/host wins tier decision",
            f"(mesh {mesh_cost*1e3:.1f}ms vs chip {single_cost*1e3:.1f}ms "
            f"vs host {host_cost*1e3:.1f}ms est)")
    # the 3-way record: which tier the cost model ranked first, all three
    # breakdowns attached so explain_placement can show the full what-if
    chosen = "mesh" if wins else \
        ("device" if single_cost <= host_cost else "host")
    rec = _placement.ledger().record(
        "mesh tier", chosen, rows, device=single_cost, host=host_cost,
        mesh=mesh_cost, detail=f"{ndev} devices")
    return wins, rec


@_decide_span
def _device_wins(node, first: MicroPartition, grouped: bool,
                 second: Optional[MicroPartition] = None,
                 forced: bool = False):
    """Cost-model decision for one device-agg stage based on the first morsel.
    Returns (wins, placement_record) — the record carries both tiers'
    CostBreakdowns into the ledger and receives the run's observed timings.

    One-time cacheable costs (column upload, key-dictionary builds) amortize
    over cfg.device_amortize_runs when the source is a resident in-memory table
    (they persist on the Series across queries); streaming scans pay in full.

    `forced=True` (device_mode=on with DAFT_TPU_PLACEMENT_PRICE_FORCED) runs
    the SAME pricing but only to populate the ledger — the verdict is ignored
    by the caller and the record is marked forced, so the calibrate tool gets
    predicted-vs-observed samples from forced runs too.
    """
    from ..config import execution_config
    from ..ops import costmodel
    from ..ops.stage import pad_bucket

    site = "grouped agg" if grouped else "agg"
    batch = next((b for b in first.batches if b.num_rows > 0), None)
    if batch is None:
        return False, None
    bound = node.bound_stage()
    if bound is None:
        return False, None
    rows = first.num_rows
    cal = costmodel.calibrate()
    coal = _coalesce_horizon([first] if second is None else [first, second])

    def _resident_source(n) -> bool:
        while n is not None:
            if isinstance(n, pp.InMemoryScan):
                return True
            n = getattr(n, "input", None)
        return False

    amort = max(execution_config().device_amortize_runs, 1) \
        if _resident_source(node.input) else 1

    # region ops the host fallback evaluates BEYOND the filter+agg that
    # host_agg_cost's base terms already price (absorbed projects/filters)
    from ..ops.region import node_region_ops

    extra_ops = max(len(node_region_ops(node))
                    - (2 if node.predicate is not None else 1), 0)

    if grouped:
        stage = bound[0]
        bucket = pad_bucket(batch.num_rows)
        nonres = res = 0
        for c in stage._input_cols:
            if batch.get_column(c).is_device_resident(bucket, f32=True):
                res += batch.num_rows * 5
            else:
                nonres += batch.num_rows * 5
        from ..ops.grouped_stage import (MAX_MATMUL_SEGMENTS, _pad_groups,
                                         estimate_key_cardinality,
                                         resolve_key_series)

        key_series = resolve_key_series(batch, stage.groupby, batch.num_rows)
        card = max(estimate_key_cardinality(key_series), 1)
        cap_est = _pad_groups(min(card, 2 * MAX_MATMUL_SEGMENTS))
        if stage.dict_keys:
            # dictionary builds are cached per Series -> amortized like uploads
            factorize_cost_rows = _dict_build_rows(key_series, batch.num_rows,
                                                   cal) // amort
        else:
            # host-mode keys re-factorize on every run: full price, no amortization
            factorize_cost_rows = batch.num_rows
        if card > MAX_MATMUL_SEGMENTS:
            # sort-based segmented-reduction path prices by n log n, not cells
            n_planes = (len(stage._mm_specs) + len(stage._ext_specs)
                        + len(stage._sct_specs))
            dev_cost = costmodel.device_grouped_sort_cost(
                cal, rows, nonres // amort, n_planes=n_planes,
                factorize_rows=factorize_cost_rows, coalesce=coal,
                resident_bytes=res)
        else:
            dev_cost = costmodel.device_grouped_cost(
                cal, rows, nonres // amort, n_mm=len(stage._mm_specs),
                n_ext=len(stage._ext_specs), n_sct=len(stage._sct_specs),
                cap=cap_est, factorize_rows=factorize_cost_rows, coalesce=coal,
                resident_bytes=res)
        host_cost = costmodel.host_agg_cost(
            cal, rows, len(node.aggregations), grouped=True,
            has_predicate=node.predicate is not None,
            n_region_ops=extra_ops)
        # what-if arm for the Pallas segment-reduce kernel: recorded on every
        # grouped decision (even Pallas-ineligible stages) so ledger dumps
        # carry the breakdown calibrate's DAFT_TPU_COST_PALLAS_RATE
        # suggestion reads
        pallas_cost = costmodel.device_grouped_pallas_cost(
            cal, rows, nonres // amort, n_mm=len(stage._mm_specs),
            n_ext=len(stage._ext_specs), cap=cap_est,
            factorize_rows=factorize_cost_rows, coalesce=coal,
            resident_bytes=res)
        detail = (f"{len(node.groupby)} keys, {len(node.aggregations)} aggs, "
                  f"~{card} groups")
    else:
        stage = bound[0]
        bucket = pad_bucket(batch.num_rows)
        nonres = res = 0
        for c in stage._input_cols:
            if batch.get_column(c).is_device_resident(bucket, f32=True):
                res += batch.num_rows * 5
            else:
                nonres += batch.num_rows * 5
        dev_cost = costmodel.device_ungrouped_cost(
            cal, rows, nonres // amort, n_partials=max(len(stage.aggs), 1),
            coalesce=coal, resident_bytes=res)
        host_cost = costmodel.host_agg_cost(
            cal, rows, len(node.aggregations), grouped=False,
            has_predicate=node.predicate is not None,
            n_region_ops=extra_ops)
        detail = (f"{len(node.aggregations)} aggs"
                  + (", filtered" if node.predicate is not None else ""))
        pallas_cost = None
    wins = dev_cost < host_cost
    rec = _placement.ledger().record(
        site, "device" if (wins or forced) else "host", rows, forced=forced,
        device=dev_cost, host=host_cost, pallas=pallas_cost, detail=detail)
    return wins, rec


def _coalesce_horizon(parts, shards: int = 1,
                      stream_rows: Optional[int] = None) -> float:
    """Expected dispatch-coalescing factor from the OBSERVED leading
    partitions' batch granularity (`parts`: the first partition, plus a
    peeked second when the caller got one): what prices a stream that comes
    through the pipeline and a DispatchCoalescer. A join's fact that is read
    as ranges of its resident table is not priced here: its dispatch is cut
    by arithmetic, not promised by morsels (_resident_horizon). The coalescer merges
    RecordBatches, so the morsel size that matters is the mean nonempty
    BATCH size, not the partition row count — a 128Ki-row partition of
    8Ki-row batches genuinely coalesces 8:1 even though the partition
    itself clears every gate.

    Capped by the TOTAL batch count actually observed: the cost model must
    never price an RTT amortization the coalescer cannot deliver, so a lone
    single-batch partition earns no optimism however small, and a confirmed
    two-partition stream earns at most 2x until more morsels are seen
    (conservative for long streams — the decision only needs to be right
    within ~2x, and under-promising keeps marginal shapes on the safe host
    side). The horizon also assumes morsels arrive within batch_latency_ms
    of each other; a trickling stream flushes on the deadline and realizes
    less amortization than priced — inter-arrival times are unknowable
    before execution, so that optimism is accepted and bounded by the
    observed-morsel cap. Note the repeat-query direction is conservative
    too: planes a
    prior COALESCED run left resident anchor on the concatenated super-batch
    (reached via content-addressed rebind at upload time), which the
    per-batch residency probes here cannot see, so repeat uploads price at
    full h2d even when the rebind makes them free. 1.0 when coalescing is
    disabled.

    `shards`: the run shards a dispatch's rows over that many devices, so
    its coalescer fills a bucket a shard (batching.coalesce_target_rows).
    `stream_rows`: the rows of the whole stream where they are known (a
    resident table): the batches still to come are then no guess."""
    from ..config import execution_config
    from ..ops.costmodel import expected_coalesce_factor
    from .batching import coalesce_target_rows

    cfg = execution_config()
    target = coalesce_target_rows(cfg, shards)
    if target <= 0:
        return 1.0
    sizes = [b.num_rows for p in parts for b in p.batches if b.num_rows > 0]
    if not sizes:
        return 1.0
    mean_rows = int(sum(sizes) / len(sizes))
    seen = max(len(sizes), (stream_rows or 0) // max(mean_rows, 1))
    if seen <= 1:
        return 1.0
    return min(expected_coalesce_factor(mean_rows, target), float(seen))




def _batch_iter(stream) -> Iterator[RecordBatch]:
    for p in stream:
        for b in p.batches:
            if b.num_rows > 0:
                yield b


def _drain_prefix(budget, batches: List[RecordBatch], it) -> Iterator[RecordBatch]:
    """Chain the buffered over-budget prefix onto the rest of the stream,
    releasing each prefix batch's ledger bytes only AFTER the consumer has
    processed it (written it to spill / folded it into a partial) — an early
    wholesale release would let concurrent operators admit a second working
    set while the prefix still sits in RAM, transiently doubling the
    process's real footprint past the budget. The prefix list is consumed
    DESTRUCTIVELY for the same reason: a released batch must actually be
    droppable, not pinned alive by the caller's list until the operator
    finishes."""
    while batches:
        b = batches.pop(0)
        yield b
        budget.release(b.size_bytes())
        del b
    yield from it


def _annotate_spill(node, nbytes: int, what: str) -> None:
    """EXPLAIN ANALYZE attribution for one operator's spill activity —
    rendered beside the operator name ("memory: spilled 12.5 MB, 8 runs")."""
    from ..observability.runtime_stats import current_collector

    c = current_collector()
    if c is not None and node is not None:
        c.annotate(node, f"memory: spilled {nbytes / 1e6:.1f} MB, {what}")


def _two_phase_agg(child: pp.PhysicalPlan, groupby, aggs, ungrouped: bool,
                   stream=None, node=None) -> RecordBatch:
    """Partial aggregation per morsel on the compute pool, then a final combine
    (reference: two-stage aggregation in translate.rs + partial-agg thresholds).

    Out-of-core: input batches are admitted against the process-wide host
    memory ledger (daft_tpu/memory — DAFT_TPU_MEMORY_LIMIT shared by every
    concurrent query); once the LEDGER is over budget the aggregation
    switches to its spilling strategy — streamed partials for ungrouped aggs,
    Grace hash-partitioned spill (of shrunken partials when the aggs split,
    of raw rows otherwise) for grouped aggs (reference: blocking_sink.rs +
    resource_manager.rs memory gating). Tracked bytes release as buffers
    flush to disk and unconditionally when the operator finishes.
    """
    from .. import memory as mem

    budget = mem.operator_budget()
    try:
        return _two_phase_agg_impl(child, groupby, aggs, ungrouped, stream,
                                   node, budget)
    finally:
        budget.close()


def _two_phase_agg_impl(child: pp.PhysicalPlan, groupby, aggs, ungrouped: bool,
                        stream, node, budget) -> RecordBatch:
    from .. import memory as mem
    from ..plan.agg_split import split_aggs
    from ..utils.pool import pool_map

    if stream is None:
        stream = _exec(child)
    it = _batch_iter(stream)
    batches: List[RecordBatch] = []
    over = False
    for b in it:
        batches.append(b)
        if not budget.admit(b.size_bytes()):
            over = True
            break

    split = split_aggs(aggs)
    from ..expressions import col as _col

    if not over:
        if not batches:
            big = _concat_parts([], child.schema)
            return rel.ungrouped_agg(big, aggs) if ungrouped \
                else rel.grouped_agg(big, groupby, aggs)
        # small total input or unsplittable aggs: one-phase in memory
        total_rows = sum(b.num_rows for b in batches)
        morsel_rows = _agg_morsel_rows()
        if split is None or total_rows <= morsel_rows:
            big = batches[0] if len(batches) == 1 else RecordBatch.concat(batches)
            return rel.ungrouped_agg(big, aggs) if ungrouped \
                else rel.grouped_agg(big, groupby, aggs)
        # re-chunk into morsels so partials parallelize even for one big batch
        if len(batches) == 1:
            b = batches[0]
            batches = [b.slice(s, s + morsel_rows)
                       for s in range(0, b.num_rows, morsel_rows)]
        if ungrouped:
            partials = pool_map(lambda b: rel.ungrouped_agg(b, split.partial), batches)
            final = rel.ungrouped_agg(RecordBatch.concat(partials), split.final)
            return eval_projection(final, split.projection)
        partials = pool_map(lambda b: rel.grouped_agg(b, groupby, split.partial), batches)
        key_names = [e.name() for e in groupby]
        final = rel.grouped_agg(RecordBatch.concat(partials),
                                [_col(k) for k in key_names], split.final)
        return eval_projection(final, [_col(k) for k in key_names] + split.projection)

    # ---- over budget: out-of-core paths ------------------------------------------
    # the buffered prefix flushes to disk/partials as `rest` is consumed;
    # each prefix batch hands its ledger bytes back as it is processed
    rest = _drain_prefix(budget, batches, it)

    if ungrouped:
        if split is None:
            return _ungrouped_agg_spilled(child, aggs, rest, node)
        # streamed partials: memory is one 1-row partial batch per morsel
        partials = [rel.ungrouped_agg(b, split.partial) for b in rest]
        final = rel.ungrouped_agg(RecordBatch.concat(partials), split.final)
        return eval_projection(final, split.projection)

    from ..observability.runtime_stats import profile_span

    K = 32
    key_names = [e.name() for e in groupby]
    key_cols = [_col(k) for k in key_names]
    if split is not None:
        # Grace over *partials*: each morsel partially aggregates (shrinks),
        # partials spill hash-partitioned by group key, each spill partition
        # final-aggregates independently (keys are disjoint across partitions)
        from ..schema import Schema

        partial_schema = Schema([e.to_field(child.schema)
                                 for e in list(groupby) + list(split.partial)])
        sp = mem.SpillPartitions(partial_schema, K)
        try:
            with profile_span("spill.grace_agg", "spill", partitions=K):
                for b in rest:
                    pb = rel.grouped_agg(b, groupby, split.partial)
                    sp.append_partitioned(pb, key_cols)
            _annotate_spill(node, sp.bytes_written, f"{K} partitions")
            outs = []
            for f in sp.files:
                bs = list(f.read())
                if not bs:
                    continue
                final = rel.grouped_agg(RecordBatch.concat(bs), key_cols, split.final)
                outs.append(eval_projection(final, key_cols + split.projection))
            if not outs:
                return rel.grouped_agg(RecordBatch.empty(child.schema), groupby, aggs)
            return RecordBatch.concat(outs)
        finally:
            sp.delete()
    # unsplittable grouped aggs: Grace over raw rows
    sp = mem.SpillPartitions(child.schema, K)
    try:
        with profile_span("spill.grace_agg", "spill", partitions=K):
            for b in rest:
                sp.append_partitioned(b, groupby)
        _annotate_spill(node, sp.bytes_written, f"{K} partitions")
        outs = []
        for f in sp.files:
            bs = list(f.read())
            if not bs:
                continue
            outs.append(rel.grouped_agg(RecordBatch.concat(bs), groupby, aggs))
        if not outs:
            return rel.grouped_agg(RecordBatch.empty(child.schema), groupby, aggs)
        return RecordBatch.concat(outs)
    finally:
        sp.delete()


def _ungrouped_agg_spilled(child: pp.PhysicalPlan, aggs, stream,
                           node=None) -> RecordBatch:
    """Over-budget ungrouped aggregation with unsplittable aggs: spill the raw
    stream once, then evaluate each aggregation with bounded memory —
    count_distinct Grace-partitions its OWN value column (distinct values land
    in exactly one partition, so per-partition counts sum exactly); aggs that
    split individually stream partials from the spill; anything else gathers
    only its value column (one column, not the whole table). Reference:
    blocking_sink.rs memory gating + grouped spill strategies."""
    from .. import memory as mem
    from ..core.series import Series
    from ..expressions import col as _col
    from ..expressions.expressions import AggExpr, Alias
    from ..plan.agg_split import split_aggs
    from ..schema import Schema

    spill = mem.SpillFile(child.schema)
    try:
        from ..observability.runtime_stats import profile_span

        with profile_span("spill.raw", "spill"):
            for b in stream:
                spill.append(b)
        _annotate_spill(node, spill.bytes_written, "1 raw run")

        cols: List[Series] = []
        for e in aggs:
            inner = e
            while isinstance(inner, Alias):
                inner = inner.child
            name = e.name()
            out_field = e.to_field(child.schema)
            if isinstance(inner, AggExpr) and inner.op == "count_distinct":
                K = 32
                val_field = inner.child.to_field(child.schema)
                vschema = Schema([val_field])
                sp = mem.SpillPartitions(vschema, K)
                try:
                    for b in spill.read():
                        s = eval_expression(b, inner.child).rename(val_field.name)
                        sp.append_partitioned(RecordBatch(vschema, [s], len(s)),
                                              [_col(val_field.name)])
                    total = 0
                    for f in sp.files:
                        bs = list(f.read())
                        if not bs:
                            continue
                        u = rel.distinct(RecordBatch.concat(bs), None)
                        uv = u.get_column(val_field.name)
                        total += int(uv.validity_numpy().sum())  # non-null distinct
                finally:
                    sp.delete()
                cols.append(Series.from_pylist([total], name, dtype=out_field.dtype))
                continue
            single = split_aggs([e])
            if single is not None:
                partials = [rel.ungrouped_agg(b, single.partial) for b in spill.read()]
                final = rel.ungrouped_agg(RecordBatch.concat(partials), single.final)
                projected = eval_projection(final, single.projection)
                cols.append(projected.get_column(name))
                continue
            # e.g. approx_count_distinct: gather just the value column
            val_field = inner.child.to_field(child.schema) if isinstance(inner, AggExpr) \
                else None
            if val_field is None:
                big = RecordBatch.concat(list(spill.read()))
                cols.append(rel.ungrouped_agg(big, [e]).get_column(name))
            else:
                vschema = Schema([val_field])
                parts = []
                for b in spill.read():
                    s = eval_expression(b, inner.child).rename(val_field.name)
                    parts.append(RecordBatch(vschema, [s], len(s)))
                big = RecordBatch.concat(parts) if parts else RecordBatch.empty(vschema)
                one = AggExpr(inner.op, _col(val_field.name), dict(inner.params)).alias(name)
                cols.append(rel.ungrouped_agg(big, [one]).get_column(name))
        return RecordBatch(Schema([e.to_field(child.schema) for e in aggs]), cols, 1)
    finally:
        spill.delete()


def _sort_exec(node: pp.PhysSort) -> Iterator[MicroPartition]:
    """Sort with out-of-core fallback: buffer within the host memory budget;
    once the ledger says over, switch to sorted-RUN generation — each
    budget-sized buffer sorts in memory and spills as one compressed IPC run
    — followed by a streaming k-way merge of the runs (reference:
    sinks/sort.rs external sort; fan-in capped, over-wide merges cascade
    through intermediate runs).

    Bit-identical to the in-memory path including tie order: runs partition
    the input stream in order, the per-run sort is stable (np.lexsort), and
    the merge breaks cross-run ties by run index — exactly the order a
    stable sort of the whole stream produces."""
    from .. import memory as mem
    from ..observability.metrics import registry
    from ..observability.runtime_stats import profile_span

    budget = mem.operator_budget()
    try:
        it = _batch_iter(_exec(node.input))
        buffered: List[RecordBatch] = []
        over = False
        for b in it:
            buffered.append(b)
            if not budget.admit(b.size_bytes()):
                over = True
                break

        if not over:
            batch = RecordBatch.concat(buffered) if buffered else RecordBatch.empty(node.schema)
            keys = [eval_expression(batch, e) for e in node.sort_by]
            yield MicroPartition(node.schema, [batch.sort(keys, node.descending, node.nulls_first)])
            return

        # ---- external sort: sorted runs + k-way merge --------------------------
        runs: List = []

        def flush_run(bufs: List[RecordBatch]) -> None:
            big = RecordBatch.concat(bufs) if len(bufs) > 1 else bufs[0]
            keys = [eval_expression(big, e) for e in node.sort_by]
            srt = big.sort(keys, node.descending, node.nulls_first)
            f = mem.SpillFile(node.schema)
            step = _agg_morsel_rows()
            with profile_span("spill.sort_run", "spill", rows=srt.num_rows):
                # chunked append so read-back streams morsel-sized batches
                for s in range(0, srt.num_rows, step):
                    f.append(srt.slice(s, min(s + step, srt.num_rows)))
                # publish behind the queued writes without joining: the
                # producer goes back to buffering the next run while this
                # run's tail lands on the spill IO pool
                f.finish_async()
            registry().inc("spill_runs")
            runs.append(f)
            budget.release_all()  # the buffer now lives on disk

        try:
            flush_run(buffered)
            buffered = []
            for b in it:
                buffered.append(b)
                if not budget.admit(b.size_bytes()):
                    flush_run(buffered)
                    buffered = []
            if buffered:
                flush_run(buffered)
                buffered = []
            _annotate_spill(node, sum(f.bytes_written for f in runs),
                            f"{len(runs)} runs")
            yield from _merge_sorted_runs(node, runs)
        finally:
            for f in runs:
                f.delete()
    finally:
        budget.close()


# merge fan-in cap: one k-way merge holds ~one batch per input run (plus the
# carried overflow), so capping the width bounds merge memory; wider run sets
# cascade through intermediate merged runs
_MERGE_FANIN = 16


def _merge_sorted_runs(node: pp.PhysSort, runs) -> Iterator[MicroPartition]:
    """Merge sorted spill runs into one globally sorted stream, cascading
    through intermediate runs while the fan-in exceeds _MERGE_FANIN."""
    from .. import memory as mem
    from ..observability.metrics import registry

    live = [f for f in runs if f.rows > 0]
    intermediates: List = []
    try:
        while len(live) > _MERGE_FANIN:
            merged = []
            for i in range(0, len(live), _MERGE_FANIN):
                chunk = live[i:i + _MERGE_FANIN]
                if len(chunk) == 1:
                    merged.append(chunk[0])
                    continue
                f = mem.SpillFile(node.schema)
                for part in _kway_merge(node, chunk):
                    for b in part.batches:
                        # already morsel-sized: the merge emits step-row
                        # chunks directly, so no re-chunk loop here
                        f.append(b)
                f.finish_async()
                registry().inc("spill_merge_passes")
                intermediates.append(f)
                merged.append(f)
                for g in chunk:
                    g.delete()  # idempotent with the caller's finally
            live = merged
        yield from _kway_merge(node, live)
    finally:
        for f in intermediates:
            f.delete()


def _merge_ord_col(series, descending: bool, nulls_first: bool):
    """Cross-batch comparable ordering arrays for one sort column:
    ``(null_key, vals, flip)``. null_key compares ascending and dominates
    (the kernels/sort._column_keys null-placement encoding); vals carries
    the value order. For numeric/bool/temporal the value transform is
    _column_keys' own (NaN->inf, bool->int8, descending via bitwise-not /
    negation), so scalar comparisons agree with lexsort order EXACTLY. For
    string/binary/decimal, _column_keys' np.unique rank codes are
    batch-local, so vals keeps the raw comparable values (objects, the
    encode_column domains) and ``flip`` asks the comparator to reverse —
    descending baked into the comparison rather than the array. Nested
    falls back to hash order, matching encode_column's fallback."""
    dt = series.dtype
    valid = series.validity_numpy()
    null_key = np.where(valid, np.int8(0), np.int8(-1 if nulls_first else 1))
    if (dt.is_numeric() or dt.is_boolean() or dt.is_temporal()) \
            and not dt.is_decimal():
        vals = np.asarray(series.to_numpy())
        if vals.dtype.kind == "f":
            nan = np.isnan(vals)
            if nan.any():
                vals = np.where(nan, np.inf, vals)
        if vals.dtype.kind == "b":
            vals = vals.astype(np.int8)
        if descending:
            vals = np.bitwise_not(vals) if vals.dtype.kind in "iu" else -vals
        vals = np.where(valid, vals, vals.dtype.type(0))
        return null_key, vals, False
    if dt.is_decimal():
        from decimal import Decimal

        pyvals = series.to_pylist()
        vals = np.empty(len(series), dtype=object)
        for i in range(len(pyvals)):
            vals[i] = pyvals[i] if pyvals[i] is not None else Decimal(0)
        return null_key, vals, descending
    if dt.is_string() or dt.is_binary():
        vals = np.asarray(series.to_arrow().to_numpy(zero_copy_only=False))
        vals = np.where(valid, vals, "" if dt.is_string() else b"")
        return null_key, vals, descending
    vals = series.hash().to_numpy()  # nested: hash order, as encode_column
    if descending:
        vals = np.bitwise_not(vals) if vals.dtype.kind in "iu" else -vals
    vals = np.where(valid, vals, vals.dtype.type(0))
    return null_key, vals, False


def _cmp_rows(a_cols, ai: int, b_cols, bi: int) -> int:
    """Compare row ai of one segment against row bi of another under the
    user sort order (-1 / 0 / 1). Null placement decides first; two nulls in
    a column tie (value slots hold fill garbage); valid values compare by
    the _merge_ord_col transform, reversed where flip is set."""
    for (a_nk, a_v, flip), (b_nk, b_v, _f) in zip(a_cols, b_cols):
        an, bn = a_nk[ai], b_nk[bi]
        if an != bn:
            return -1 if an < bn else 1
        if an:
            continue  # both null: equal in this column
        x, y = a_v[ai], b_v[bi]
        if x < y:
            return 1 if flip else -1
        if y < x:
            return -1 if flip else 1
    return 0


class _MergeSeg:
    """One sorted in-memory slice of a run inside _kway_merge: the batch,
    its once-evaluated sort-key Series, the comparable ordering arrays, and
    a consumed-prefix cursor. Segments never re-sort or re-key."""

    __slots__ = ("run", "batch", "keys", "ords", "pos", "n")

    def __init__(self, run: int, batch: RecordBatch, keys, ords):
        self.run = run
        self.batch = batch
        self.keys = keys
        self.ords = ords
        self.pos = 0
        self.n = batch.num_rows


def _kway_merge(node: pp.PhysSort, files) -> Iterator[MicroPartition]:
    """Streaming carry-preserving k-way merge of sorted runs with bounded
    memory: one batch per run in flight plus the carried (not-yet-emittable)
    overflow.

    Every pulled batch becomes a _MergeSeg: sort keys evaluated ONCE, plus
    cross-batch comparable ordering arrays (_merge_ord_col). Per round, each
    live run's newest segment contributes its LAST row as that run's
    boundary; the horizon is the smallest boundary (run index breaks ties).
    A row is emittable iff it sorts strictly before the horizon, or ties
    with it from a run index <= the horizon run — exactly the
    marker-ordering rule (data key run*2 vs marker key run*2+1) the previous
    implementation encoded into a per-round full argsort. Because segments
    stay sorted, each segment's emittable prefix falls out of one binary
    search against the horizon row, and only the EMITTED rows (each exactly
    once per merge level) pay a lexsort — interleaving the prefixes via
    multi_argsort over the already-evaluated key Series plus an int64
    run-index tiebreak column, so cross-run ties resolve by run (= stream)
    order and within-run order rides on lexsort stability. Total key-eval /
    sort work drops from O(rows x fan-in) per level to O(rows) key-eval +
    O(rows log rows) sort, counted by spill_merge_sort_rows (rows through
    the interleave argsort; single-source rounds skip it entirely).

    Output is emitted in morsel-sized batches (_agg_morsel_rows) directly,
    so cascade levels append merge output without re-chunking."""
    from ..core.kernels.sort import multi_argsort
    from ..core.series import Series
    from ..datatype import DataType
    from ..observability.metrics import registry

    if not files:
        return
    nkeys = len(node.sort_by)
    desc = list(node.descending) if node.descending else [False] * nkeys
    nf = list(node.nulls_first) if node.nulls_first else list(desc)

    if len(files) == 1:
        for b in files[0].read():
            yield MicroPartition(node.schema, [b])
        return

    step = _agg_morsel_rows()
    its = [f.read() for f in files]
    need = set(range(len(its)))
    segs: List[_MergeSeg] = []   # within a run, in pull (= stream) order
    bounds: dict = {}            # run idx -> (ord arrays, last-row index)
    outbuf: List[RecordBatch] = []
    out_rows = 0

    def sorted_pieces(pieces) -> Optional[RecordBatch]:
        """Interleave emittable prefixes into one batch in the total order."""
        if not pieces:
            return None
        bats = [s.batch.slice(a, b) for s, a, b in pieces]
        if len(bats) == 1:
            return bats[0]  # one source segment: already sorted, no argsort
        big = RecordBatch.concat(bats)
        key_cols = []
        for k in range(nkeys):
            sl = [s.keys[k].slice(a, b).rename("k") for s, a, b in pieces]
            key_cols.append(Series.concat(sl))
        mrg = np.concatenate([np.full(b - a, s.run, dtype=np.int64)
                              for s, a, b in pieces])
        key_cols.append(Series.from_numpy(mrg, "__mrg__", DataType.int64()))
        idx = multi_argsort(key_cols, desc + [False], nf + [False])
        registry().inc("spill_merge_sort_rows", len(idx))
        return big.take(idx)

    def push(batch: RecordBatch) -> Iterator[MicroPartition]:
        """Accumulate sorted output; release exact morsel-sized batches."""
        nonlocal out_rows, outbuf
        outbuf.append(batch)
        out_rows += batch.num_rows
        if out_rows < step:
            return
        big = RecordBatch.concat(outbuf) if len(outbuf) > 1 else outbuf[0]
        full = (out_rows // step) * step
        for s in range(0, full, step):
            yield MicroPartition(node.schema, [big.slice(s, s + step)])
        rest = big.slice(full, out_rows)
        outbuf = [rest] if rest.num_rows else []
        out_rows = rest.num_rows

    while True:
        for i in sorted(need):
            b = next(its[i], None)
            while b is not None and b.num_rows == 0:
                b = next(its[i], None)
            if b is None:
                bounds.pop(i, None)        # run exhausted: no boundary
            else:
                keys = [eval_expression(b, e) for e in node.sort_by]
                ords = [_merge_ord_col(k, d, n)
                        for k, d, n in zip(keys, desc, nf)]
                segs.append(_MergeSeg(i, b, keys, ords))
                bounds[i] = (ords, b.num_rows - 1)
        need.clear()

        if not bounds:
            # every run exhausted: the remainder is emittable wholesale
            big = sorted_pieces([(s, s.pos, s.n) for s in segs
                                 if s.pos < s.n])
            if big is not None:
                yield from push(big)
            if outbuf:
                tail = RecordBatch.concat(outbuf) \
                    if len(outbuf) > 1 else outbuf[0]
                yield MicroPartition(node.schema, [tail])
            return

        # horizon: smallest boundary; equal boundaries go to the smaller
        # run index (whose equal-keyed rows sort first in stream order)
        r = -1
        for i in sorted(bounds):
            if r < 0 or _cmp_rows(bounds[i][0], bounds[i][1],
                                  bounds[r][0], bounds[r][1]) < 0:
                r = i
        b_ord, b_idx = bounds[r]

        pieces = []
        for s in segs:
            lo, hi = s.pos, s.n
            while lo < hi:
                mid = (lo + hi) // 2
                c = _cmp_rows(s.ords, mid, b_ord, b_idx)
                if c < 0 or (c == 0 and s.run <= r):
                    lo = mid + 1
                else:
                    hi = mid
            if lo > s.pos:
                pieces.append((s, s.pos, lo))
                s.pos = lo
        segs = [s for s in segs if s.pos < s.n]
        big = sorted_pieces(pieces)
        if big is not None:
            yield from push(big)
        # refill the horizon run (its in-memory rows all drained: every row
        # is <= its boundary and ties from run r are emittable)
        need.add(r)
        del bounds[r]


def _window_exec(node) -> Iterator[MicroPartition]:
    """Window evaluation with out-of-core partitioning: input is admitted
    against the operator memory budget; once over budget (and the window has
    PARTITION BY keys) the stream Grace-partitions into K spill files by
    partition-key hash, and each spill partition evaluates independently —
    window partitions are wholly contained in one spill file, so results are
    exact (reference: sinks/window_partition_only.rs partitioned evaluation).
    Partitions evaluate on the pool in pipeline mode. Global windows (no
    PARTITION BY) need every row in one frame and still gather.

    Output row order: under budget, original input order (results scatter
    back); spilled, rows come out grouped by spill partition."""
    from .. import memory as mem
    from ..observability.runtime_stats import profile_span
    from .window import eval_window

    budget = mem.operator_budget()
    try:
        it = _batch_iter(_exec(node.input))
        buffered: List[RecordBatch] = []
        over = False
        for b in it:
            buffered.append(b)
            if not budget.admit(b.size_bytes()):
                over = True
                break

        if not over or not node.spec.partition_by_exprs:
            rest = list(it) if over else []
            all_batches = buffered + rest
            batch = RecordBatch.concat(all_batches) if all_batches \
                else RecordBatch.empty(node.input.schema)
            out = eval_window(batch, node.window_exprs, node.spec, node.schema)
            yield MicroPartition(node.schema, [out])
            return

        K = 16
        sp = mem.SpillPartitions(node.input.schema, K)
        try:
            with profile_span("spill.grace_window", "spill", partitions=K):
                # prefix batches release (and drop) one by one as they land
                # on disk; per-partition evaluation below runs with the
                # prefix genuinely freed, not just un-ledgered
                for b in _drain_prefix(budget, buffered, it):
                    sp.append_partitioned(b, node.spec.partition_by_exprs)
            _annotate_spill(node, sp.bytes_written, f"{K} partitions")

            def eval_file(f, _i):
                bs = list(f.read())
                if not bs:
                    return MicroPartition.empty(node.schema)
                out = eval_window(RecordBatch.concat(bs), node.window_exprs,
                                  node.spec, node.schema)
                return MicroPartition(node.schema, [out])

            if _pipeline_on():
                from .pipeline import pmap_stream

                yield from pmap_stream(iter(sp.files), eval_file)
            else:
                for i, f in enumerate(sp.files):
                    yield eval_file(f, i)
        finally:
            sp.delete()
    finally:
        budget.close()


def _join_exec(node: pp.HashJoin) -> Iterator[MicroPartition]:
    """Hash join with a spillable build side: the right (build) side is
    admitted against the process-wide host memory ledger; if the LEDGER goes
    over budget, both sides Grace-partition into K co-partitioned spill files
    by join-key hash and the join runs per partition (correct for every join
    type since equal keys land in the same partition)."""
    from .. import memory as mem

    budget = mem.operator_budget()
    try:
        yield from _join_exec_impl(node, budget)
    finally:
        budget.close()


def _join_exec_impl(node: pp.HashJoin, budget) -> Iterator[MicroPartition]:
    from .. import memory as mem
    from ..observability.runtime_stats import profile_span

    right_it = _batch_iter(_exec(node.right))
    right_parts: List[RecordBatch] = []
    over = False
    for b in right_it:
        right_parts.append(b)
        if not budget.admit(b.size_bytes()):
            over = True
            break

    left_prefix: List[RecordBatch] = []
    left_it = None
    if not over:
        right = RecordBatch.concat(right_parts) if right_parts \
            else RecordBatch.empty(node.right.schema)
        if node.how not in ("right", "outer"):
            if node.strategy == "sort_merge":
                # sort-merge strategy: per-batch order-preserving encode +
                # sorted merge (no probe table)
                def _sm(part, _i):
                    outs = [rel.hash_join(b, right, node.left_on, node.right_on,
                                          node.how, node.schema, node.merged_keys,
                                          node.right_rename, node.null_equals_null,
                                          algorithm="sort_merge")
                            for b in part.batches if b.num_rows]
                    return MicroPartition(node.schema, outs or [RecordBatch.empty(node.schema)])

                yield from _map_op(_exec(node.left), _sm)
                return
            # probe side streams morsel-by-morsel: never materialized. The
            # probe table is built ONCE from the build side; each morsel is an
            # index lookup, fanned across the pool in pipeline mode.
            probe = rel.JoinProbe(right, node.left_on, node.right_on, node.how,
                                  node.schema, node.merged_keys, node.right_rename,
                                  node.null_equals_null, node.left.schema)

            # Filter->probe fusion (late materialization): when the probe child
            # is a filter and the keys are plain column refs, stream the RAW
            # batches, turn the mask into a selection vector, and let the probe
            # gather non-key columns once via composed indices instead of
            # filter-take + join-take (reference: the Rust engine's selection-
            # vector-carrying morsels serve the same purpose).
            probe_child = node.left
            fused_pred = None
            fused_keep = None
            if (isinstance(probe_child, pp.PhysFilter)
                    and all(isinstance(e, ColumnRef) for e in node.left_on)):
                fused_pred = probe_child.predicate
                fused_keep = probe_child.keep
                probe_child = probe_child.input

            def _probe(part, _i):
                outs = []
                for b in part.batches:
                    if not b.num_rows:
                        continue
                    if fused_pred is None:
                        outs.append(probe.probe(b))
                        continue
                    mask = eval_expression(b, fused_pred)
                    sel = _selection_vector(b, mask)
                    braw = b if fused_keep is None else b.select(fused_keep)
                    if sel is None:  # non-arrow mask: materialize + plain probe
                        outs.append(probe.probe(braw.filter_by_mask(mask)))
                    elif len(sel):
                        outs.append(probe.probe_filtered(braw, sel))
                return MicroPartition(node.schema, outs or [RecordBatch.empty(node.schema)])

            yield from _map_op(_exec(probe_child), _probe)
            return
        # right/outer need the full left side to find unmatched build rows
        # exactly once — admit it against the budget too
        left_it = _batch_iter(_exec(node.left))
        for b in left_it:
            left_prefix.append(b)
            if not budget.admit(b.size_bytes()):
                over = True
                break
        if not over:
            left = RecordBatch.concat(left_prefix) if left_prefix \
                else RecordBatch.empty(node.left.schema)
            out = rel.hash_join(left, right, node.left_on, node.right_on, node.how,
                                node.schema, node.merged_keys, node.right_rename,
                                node.null_equals_null,
                                algorithm=node.strategy or "hash")
            yield MicroPartition(node.schema, [out])
            return

    K = 16
    spr = mem.SpillPartitions(node.right.schema, K)
    spl = mem.SpillPartitions(node.left.schema, K)
    try:
        with profile_span("spill.grace_join", "spill", partitions=K):
            # prefix batches (right build, and left for right/outer joins)
            # release their ledger bytes one by one as they land on disk
            for b in _drain_prefix(budget, right_parts, right_it):
                spr.append_partitioned(b, node.right_on)
            if left_it is None:
                left_it = _batch_iter(_exec(node.left))
            for b in _drain_prefix(budget, left_prefix, left_it):
                spl.append_partitioned(b, node.left_on)
        _annotate_spill(node, spr.bytes_written + spl.bytes_written,
                        f"{K}x2 partitions")
        for fl, fr in zip(spl.files, spr.files):
            lbs = list(fl.read())
            rbs = list(fr.read())
            if not lbs and node.how in ("inner", "left", "semi", "anti"):
                continue
            left = RecordBatch.concat(lbs) if lbs else RecordBatch.empty(node.left.schema)
            right = RecordBatch.concat(rbs) if rbs else RecordBatch.empty(node.right.schema)
            out = rel.hash_join(left, right, node.left_on, node.right_on, node.how,
                                node.schema, node.merged_keys, node.right_rename,
                                node.null_equals_null)
            if out.num_rows:
                yield MicroPartition(node.schema, [out])
    finally:
        spr.delete()
        spl.delete()


def _exec_map_groups(node) -> MicroPartition:
    """Group rows by the keys, evaluate the UDF expression over each group's
    rows, replicate the group's key values per emitted row (reference:
    ray runner's partition-wise map_groups; one group may emit any number
    of rows, e.g. 1 for a reduction UDF)."""
    from ..core.kernels.groupby import make_groups
    from ..core.series import Series

    batch = _gather(node.input, node.input.schema)
    if batch.num_rows == 0:
        return MicroPartition(node.schema, [RecordBatch.empty(node.schema)])
    key_series = [eval_expression(batch, e) for e in node.groupby]
    first_idx, group_ids, _counts = make_groups(key_series)
    num_groups = len(first_idx)
    order = np.argsort(group_ids, kind="stable")
    sorted_gids = group_ids[order]
    bounds = np.concatenate([[0], np.flatnonzero(np.diff(sorted_gids)) + 1,
                             [len(order)]]).astype(np.int64)

    out_vals: List[Series] = []
    rows_per_group: List[int] = []
    for g in range(num_groups):
        seg = order[bounds[g]:bounds[g + 1]]
        sub = batch.take(seg)
        res = eval_expression(sub, node.udf_expr)
        out_vals.append(res)
        rows_per_group.append(len(res))

    udf_col = Series.concat(out_vals) if out_vals else None
    reps = np.repeat(np.arange(num_groups, dtype=np.int64),
                     np.asarray(rows_per_group, dtype=np.int64))
    key_rows = [ks.take(first_idx).take(reps) for ks in key_series]
    cols = key_rows + ([udf_col] if udf_col is not None else [])
    out = RecordBatch(node.schema, [c.cast(f.dtype) if c.dtype != f.dtype else c
                                    for c, f in zip(cols, node.schema.fields)],
                      int(reps.shape[0]))
    return MicroPartition(node.schema, [out])


def _selection_vector(b, mask):
    """Row indices where mask is true (nulls drop, matching filter_by_mask);
    scalar masks broadcast. None when the mask isn't arrow-backed."""
    if len(mask) == 1 and b.num_rows != 1:
        val = mask.to_pylist()[0]
        return np.arange(b.num_rows, dtype=np.int64) if val \
            else np.empty(0, dtype=np.int64)
    if mask._pyobjs is not None:
        return None
    from ..native import native_mask_indices

    arr = mask._arrow
    idx = native_mask_indices(arr)
    if idx is not None:
        return idx
    import pyarrow.compute as pc

    if arr.null_count:
        arr = pc.fill_null(arr, False)
    return np.flatnonzero(arr.to_numpy(zero_copy_only=False)).astype(np.int64)


def _filter_part(part: MicroPartition, predicate: Expression,
                 keep=None, out_schema=None) -> MicroPartition:
    """keep: late materialization — the mask is computed over the full batch,
    but only these columns are gathered into the output (the rest exist solely
    for the predicate)."""
    schema = out_schema if keep is not None else part.schema
    batches = []
    for b in part.batches:
        mask = eval_expression(b, predicate)
        if keep is not None:
            b = b.select(keep)
        if len(mask) == 1 and b.num_rows != 1:
            val = mask.to_pylist()[0]
            batches.append(b if val else b.head(0))
        else:
            batches.append(b.filter_by_mask(mask))
    return MicroPartition(schema, batches or [RecordBatch.empty(schema)])


def _gather(node: pp.PhysicalPlan, schema) -> RecordBatch:
    parts = list(_exec(node))
    return _concat_parts(parts, schema)


def _concat_parts(parts: List[MicroPartition], schema) -> RecordBatch:
    batches = [b for p in parts for b in p.batches if b.num_rows > 0]
    if not batches:
        return RecordBatch.empty(schema)
    if len(batches) == 1:
        return batches[0]
    # a join's dimension that is a select over one resident table arrives
    # here as the table's own batch (_resident_select: one batch, returned
    # above); the morsels a Project cut of a table on the pipeline's road
    # glue back to the table's own columns without a copy (Series.concat:
    # contiguous views of one root). Either way a dim keeps its identity, its
    # dictionary codes and its residency slots across queries; anything else
    # concatenates
    return RecordBatch.concat(batches)


def _hash_buckets(stream, by: List[Expression], n: int):
    """Yield (partition_idx, RecordBatch) pieces hash-partitioned on `by` —
    shared by in-memory repartition and the disk-backed shuffle writer."""
    for part in stream:
        for b in part.batches:
            if b.num_rows == 0:
                continue
            keys = [eval_expression(b, e) for e in by]
            for j, piece in enumerate(b.partition_by_hash(keys, n)):
                if piece.num_rows:
                    yield j, piece


def _mesh_repart_eligible(node, n: int) -> bool:
    """Static gate for the intra-host ICI repartition: explicit mesh opt-in
    (mesh_devices >= 2), one partition per mesh worker, every column
    device-representable, and enough local devices. Decided WITHOUT touching
    the input stream, so the host path starts clean on a reject — and the
    default config never imports a device module here (zero-overhead)."""
    from ..config import execution_config

    cfg = execution_config()
    if cfg.device_mode == "off" or cfg.mesh_devices < 2 \
            or n != cfg.mesh_devices or not node.by:
        return False
    for f in node.schema:
        if not (f.dtype.is_numeric() or f.dtype.is_boolean()):
            return False
    import jax

    if len(jax.devices()) < n:
        from ..ops import counters as _counters

        _counters.bump("mesh_unavailable_fallbacks")
        _counters.reject("runtime",
                         "repartition: fewer local devices than mesh_devices")
        return False
    return True


def _mesh_repartition(node, n: int) -> Iterator[MicroPartition]:
    """Hash repartition routed over ICI (SURVEY §7's two-tier shuffle: the
    exchange between co-located mesh workers is ONE jax.lax.all_to_all
    program instead of the host shuffle's write-files/fetch round trip —
    zero shuffle wire bytes move). Destination buckets are computed on host
    with the exact partition_by_hash function, each shard stable-sorts its
    rows by destination on device, and the exchanged planes come back in
    (source shard, stream order) — bit-identical partition contents and row
    order versus the host path (tests/test_mesh_join.py). A column with no
    device layout falls back to host bucketing of the already-collected
    batches (results identical, rejection counted); a program that does not
    lower or run raises."""
    from ..config import execution_config
    from ..ops import counters as _counters
    from ..ops.grouped_stage import DeviceFallback

    cfg = execution_config()
    parts = list(_exec(node.input))
    batches = [b for p in parts for b in p.batches if b.num_rows > 0]

    def _host_buckets() -> List[MicroPartition]:
        buckets: List[List[RecordBatch]] = [[] for _ in range(n)]
        for b in batches:
            keys = [eval_expression(b, e) for e in node.by]
            for j, piece in enumerate(b.partition_by_hash(keys, n)):
                if piece.num_rows:
                    buckets[j].append(piece)
        return [MicroPartition(node.schema, bs) if bs
                else MicroPartition.empty(node.schema) for bs in buckets]

    rows = sum(b.num_rows for b in batches)
    if not batches or rows < cfg.device_min_rows:
        yield from _host_buckets()
        return
    try:
        # materialize BEFORE yielding: a failure after partial emission would
        # otherwise fall back to the full host bucket set and hand the
        # consumer duplicated rows
        parts = list(_mesh_repartition_exchange(node, batches, rows, n))
    except DeviceFallback as e:
        _counters.reject("runtime", "repartition: mesh all_to_all fallback",
                         str(e))
        parts = _host_buckets()
    yield from parts


def _ring_permute_gate(n: int) -> Optional[bool]:
    """Pallas gate for the fused ring-permute repartition exchange: returns
    the kernel's `interpret` flag when it should engage (True = CPU
    interpreter off the chip), None when the standalone all_to_all tier
    serves the exchange. Engages under pallas_mode="on" ONLY: the kernel
    issues remote DMAs under a barrier and has no hardware run on record, so
    `auto` keeps the all_to_all tier on every backend."""
    from ..config import execution_config

    if getattr(execution_config(), "pallas_mode", "auto") != "on":
        return None
    import jax

    return jax.default_backend() != "tpu"


def _mesh_repartition_exchange(node, batches: List[RecordBatch], rows: int,
                               n: int) -> Iterator[MicroPartition]:
    import jax

    from ..core.kernels.hashing import combine_hashes
    from ..core.series import Series
    from ..ops import counters as _counters
    from ..ops.grouped_stage import DeviceFallback
    from ..ops.stage import mesh_row_mask, mesh_total, shard_rows
    from ..parallel.distributed import (default_mesh,
                                        sharded_alltoall_repartition_step,
                                        sharded_ring_repartition_step)

    big = batches[0] if len(batches) == 1 else RecordBatch.concat(batches)
    keys = [eval_expression(big, e) for e in node.by]
    hashes = combine_hashes([s.hash().to_numpy().astype(np.uint64)
                             for s in keys])
    dest = (hashes % np.uint64(n)).astype(np.int64)
    mesh = default_mesh(n)
    total = mesh_total(rows, n)
    S = total // n
    ring = _ring_permute_gate(n)
    cols = []
    dtypes: List = []
    host_dtypes: List = []  # per column, the dtype its plane comes back as
    for col in big.columns:
        vals = col.to_numpy()
        if vals.dtype == object:
            raise DeviceFallback(f"column {col.name!r} has no device layout")
        host_dtypes.append(vals.dtype)
        if vals.dtype == np.float64:
            # a TPU has no IEEE f64: the device holds an f64 plane as a pair
            # of f32 and hands back other bits than it was given, and its
            # compiler cannot take the bits of an f64 for the ring kernel's
            # uint32 words. A repartition must move values bit for bit, so
            # f64 planes cross as their uint64 host view (64-bit integers
            # are exact on the device) and are viewed back after the fetch.
            vals = vals.view(np.uint64)
        cols.append((vals, col.validity_numpy()))
        dtypes += [vals.dtype, np.bool_]
    flat = []
    ici_bytes = 0
    for vals, valid in cols:
        flat += [shard_rows(mesh, vals, total), shard_rows(mesh, valid, total)]
        # the exchanged scratch is [n, S] per shard per plane: every plane
        # crosses the interconnect once at its padded size
        ici_bytes += n * total * vals.dtype.itemsize + n * total
    args = (shard_rows(mesh, dest, total), mesh_row_mask(mesh, rows, total))
    if ring is not None:
        # a kernel that does not lower raises: no tier replaces it
        step = sharded_ring_repartition_step(mesh, dtypes, interpret=ring)
        counts, planes = step(*args, *flat)
        _counters.bump("mesh_fused_permute_dispatches")
    else:
        step = sharded_alltoall_repartition_step(mesh, dtypes)
        counts, planes = step(*args, *flat)
        _counters.bump("mesh_alltoall_dispatches")
    counts_np = np.asarray(jax.device_get(counts))
    planes_np = [np.asarray(p) for p in jax.device_get(list(planes))]
    for i, dt in enumerate(host_dtypes):
        planes_np[2 * i] = planes_np[2 * i].view(dt)
    _counters.bump("mesh_alltoall_rows", rows)
    _counters.bump("mesh_alltoall_ici_bytes", ici_bytes)

    import pyarrow as pa

    for d in range(n):
        per_src = [(j, int(counts_np[d * n + j])) for j in range(n)
                   if counts_np[d * n + j] > 0]
        out_cols = []
        for i, f in enumerate(node.schema):
            v = [planes_np[2 * i][d * n + j][:c] for j, c in per_src]
            m = [planes_np[2 * i + 1][d * n + j][:c] for j, c in per_src]
            vv = np.concatenate(v) if v else np.empty(0, host_dtypes[i])
            mm = np.concatenate(m) if m else np.empty(0, bool)
            arr = pa.array(vv, mask=~mm) if not mm.all() else pa.array(vv)
            out_cols.append(Series.from_arrow(arr, f.name, dtype=f.dtype))
        total_d = sum(c for _j, c in per_src)
        out = RecordBatch(node.schema, out_cols, total_d)
        yield MicroPartition(node.schema,
                             [out.cast_to_schema(node.schema)])


def _repartition(node: pp.PhysRepartition) -> Iterator[MicroPartition]:
    n = node.num_partitions or 1
    if node.scheme == "into":
        batch = _gather(node.input, node.schema)
        rows = batch.num_rows
        sizes = [rows // n + (1 if i < rows % n else 0) for i in range(n)]
        start = 0
        for size in sizes:
            yield MicroPartition(node.schema, [batch.slice(start, start + size)])
            start += size
        return

    buckets: List[List[RecordBatch]] = [[] for _ in range(n)]
    if node.scheme == "hash":
        if _mesh_repart_eligible(node, n):
            yield from _mesh_repartition(node, n)
            return
        for j, piece in _hash_buckets(_exec(node.input), node.by, n):
            buckets[j].append(piece)
    elif node.scheme == "random":
        for i, part in enumerate(_exec(node.input)):
            for b in part.batches:
                for j, piece in enumerate(b.partition_by_random(n, seed=i)):
                    if piece.num_rows:
                        buckets[j].append(piece)
    else:
        raise NotImplementedError(f"repartition scheme {node.scheme}")
    for j in range(n):
        if buckets[j]:
            yield MicroPartition(node.schema, buckets[j])
        else:
            yield MicroPartition.empty(node.schema)
