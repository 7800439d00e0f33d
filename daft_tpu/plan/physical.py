"""Physical plan + logical→physical translation.

Reference parity: src/daft-local-plan/src/plan.rs:61-115 (LocalPhysicalPlan enum)
and src/daft-local-plan/src/translate.rs:21. Physical nodes are what the executor
interprets; translation picks join strategies and lowers logical ops.
"""

from __future__ import annotations

from typing import Any, List, Optional

from ..expressions import Expression
from ..schema import Schema
from . import logical as lp


class PhysicalPlan:
    def __init__(self) -> None:
        self.schema: Schema = None  # type: ignore[assignment]

    def children(self) -> List["PhysicalPlan"]:
        return []

    def name(self) -> str:
        return type(self).__name__

    def display(self) -> str:
        lines: List[str] = []

        def rec(node, depth):
            lines.append("  " * depth + "* " + node.name())
            for c in node.children():
                rec(c, depth + 1)

        rec(self, 0)
        return "\n".join(lines)

    def walk(self):
        yield self
        for c in self.children():
            yield from c.walk()


class _Unary(PhysicalPlan):
    def __init__(self, input: PhysicalPlan, schema: Schema):
        super().__init__()
        self.input = input
        self.schema = schema

    def children(self):
        return [self.input]


class InMemoryScan(PhysicalPlan):
    def __init__(self, partitions: List[Any], schema: Schema):
        super().__init__()
        self.partitions = partitions
        self.schema = schema


class TaskScan(PhysicalPlan):
    """Scan over materialized ScanTasks (post-MaterializeScans)."""

    def __init__(self, tasks: List[Any], schema: Schema,
                 post_filter: Optional[Expression], post_limit: Optional[int]):
        super().__init__()
        self.tasks = tasks
        self.schema = schema
        self.post_filter = post_filter
        self.post_limit = post_limit


class StreamingScan(TaskScan):
    """Out-of-core scan: tasks arrive split at ``scan_split_bytes`` (row-group
    splits in io/parquet.py) and merged toward the scan's bytes over the
    pool's width, never past ``scan_split_bytes`` (io/scan.py), and the
    executor streams morsels incrementally under the host memory ledger
    (execution/executor.py _streaming_scan) —
    a source is never materialized whole, and a fast scan paces itself
    against memory pressure from downstream spilling operators. Subclasses
    TaskScan so the distributed planner's task partitioning and every
    isinstance gate keep working unchanged."""

    def name(self) -> str:
        return f"StreamingScan({len(self.tasks)} tasks)"


class Project(_Unary):
    def __init__(self, input: PhysicalPlan, projection: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.projection = projection


class UDFProject(_Unary):
    def __init__(self, input: PhysicalPlan, udf_expr: Expression, passthrough: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.udf_expr = udf_expr
        self.passthrough = passthrough


class DeviceUdfProject(_Unary):
    """A UDFProject whose UDF is a jax-traceable device Func
    (``@daft_tpu.func(on_device=True)``) — eligible for the device-UDF tier
    (ops/udf_stage.py): weights resident in HBM via the residency manager,
    morsels coalesced into super-batches, one compiled dispatch per
    super-batch, and fusion into a downstream device agg stage with no
    intermediate d2h. The executor decides device vs host per run (cost
    model / backend / config); the host fallback is the plain batch-UDF
    evaluation with identical semantics."""

    def __init__(self, input: PhysicalPlan, udf_expr: Expression,
                 passthrough: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.udf_expr = udf_expr
        self.passthrough = passthrough

    def name(self) -> str:
        return f"DeviceUdfProject({self.udf_expr.name()})"


def device_udf_call(expr: Expression):
    """The UdfCall at the root of `expr` (aliases unwrapped) when it is a
    kwarg-free device Func call — the shape the device-UDF tier lowers.
    None otherwise. Pure structural check: imports nothing from the tier, so
    host-UDF-only plans keep the zero-overhead contract."""
    from ..expressions.expressions import Alias

    e = expr
    while isinstance(e, Alias):
        e = e.child
    func = getattr(e, "func", None)
    if func is None or not getattr(func, "on_device", False):
        return None
    if getattr(e, "kwargs", None):
        return None  # kwargs don't cross the array contract
    if not getattr(e, "args", None):
        return None
    return e


class PhysFilter(_Unary):
    def __init__(self, input: PhysicalPlan, predicate: Expression, schema: Schema,
                 keep=None):
        super().__init__(input, schema)
        self.predicate = predicate
        self.keep = keep  # output-column subset (late materialization)


class PhysLimit(_Unary):
    def __init__(self, input: PhysicalPlan, limit: int, offset: int, schema: Schema):
        super().__init__(input, schema)
        self.limit = limit
        self.offset = offset


class PhysExplode(_Unary):
    def __init__(self, input: PhysicalPlan, to_explode: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.to_explode = to_explode


class PhysUnpivot(_Unary):
    def __init__(self, input: PhysicalPlan, ids, values, variable_name, value_name, schema: Schema):
        super().__init__(input, schema)
        self.ids = ids
        self.values = values
        self.variable_name = variable_name
        self.value_name = value_name


class PhysSample(_Unary):
    def __init__(self, input: PhysicalPlan, fraction: float, with_replacement: bool,
                 seed: Optional[int], schema: Schema):
        super().__init__(input, schema)
        self.fraction = fraction
        self.with_replacement = with_replacement
        self.seed = seed


class PhysMonotonicId(_Unary):
    def __init__(self, input: PhysicalPlan, column_name: str, schema: Schema):
        super().__init__(input, schema)
        self.column_name = column_name


class PhysSort(_Unary):
    def __init__(self, input: PhysicalPlan, sort_by, descending, nulls_first, schema: Schema):
        super().__init__(input, schema)
        self.sort_by = sort_by
        self.descending = descending
        self.nulls_first = nulls_first


class PhysTopN(_Unary):
    def __init__(self, input: PhysicalPlan, sort_by, descending, nulls_first, limit, offset, schema: Schema):
        super().__init__(input, schema)
        self.sort_by = sort_by
        self.descending = descending
        self.nulls_first = nulls_first
        self.limit = limit
        self.offset = offset


class UngroupedAggregate(_Unary):
    def __init__(self, input: PhysicalPlan, aggregations: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.aggregations = aggregations


class HashAggregate(_Unary):
    def __init__(self, input: PhysicalPlan, groupby: List[Expression],
                 aggregations: List[Expression], schema: Schema):
        super().__init__(input, schema)
        self.groupby = groupby
        self.aggregations = aggregations


class PhysMapGroups(_Unary):
    def __init__(self, input: PhysicalPlan, groupby: List[Expression],
                 udf_expr: Expression, schema: Schema):
        super().__init__(input, schema)
        self.groupby = groupby
        self.udf_expr = udf_expr


class DeviceFilterAgg(_Unary):
    """Fused (optional filter)+ungrouped-agg stage eligible for the JAX device.

    The executor decides device vs host per run (config device_mode/min-rows);
    host fallback has identical semantics. Reference wiring point:
    src/daft-local-execution/src/pipeline.rs:358 operator selection.
    """

    def __init__(self, input: PhysicalPlan, predicate: Optional[Expression],
                 aggregations: List[Expression], schema: Schema,
                 region_ops=None, bound_stage=None):
        super().__init__(input, schema)
        self.predicate = predicate
        self.aggregations = aggregations
        # source-first fused-op chain from the region capture, e.g.
        # ("filter", "project", "agg") — attribution + EXPLAIN only; the
        # fused semantics live in predicate/aggregations themselves.
        self.region_ops = tuple(region_ops) if region_ops else None
        self._bound_stage = bound_stage  # where the translation has it

    def bound_stage(self):
        """(compiled stage of this node's shape, this node's literal values)
        (ops/stage.bind_filter_agg_stage), assembled once a node: the
        placement deciders and the run of one execution share it."""
        if self._bound_stage is None:
            from ..ops.stage import bind_filter_agg_stage

            self._bound_stage = bind_filter_agg_stage(
                self.input.schema, self.predicate, self.aggregations)
        return self._bound_stage

    def name(self) -> str:
        if self.region_ops and len(self.region_ops) > 2:
            return f"DeviceFilterAgg[{'+'.join(self.region_ops)}]"
        return "DeviceFilterAgg"


class DeviceJoinAgg(PhysicalPlan):
    """Star-schema join + aggregate fused for the device (ops/device_join.py):
    the fact side streams; each dim materializes once per run and joins as a
    device gather through static per-row indices; the aggregation rides the
    MXU segment-reduction stages. `host_plan` is the untouched translation of
    the same logical subtree — the executor's fallback (config off, runtime
    DeviceFallback, or cost model says host)."""

    def __init__(self, fact: PhysicalPlan, dim_plans, spec, host_plan: PhysicalPlan,
                 schema: Schema):
        super().__init__()
        self.fact = fact
        self.dim_plans = dim_plans  # [(name, PhysicalPlan)] base dims, parents first
        self.spec = spec            # ops.device_join.JoinAggSpec
        self.host_plan = host_plan
        self.schema = schema

    def children(self):
        return [self.fact] + [p for _n, p in self.dim_plans]

    def name(self) -> str:
        return f"DeviceJoinAgg({len(self.dim_plans)} dims)"


class DeviceJoinTopN(PhysicalPlan):
    """Star join + grouped aggregate + ORDER BY + LIMIT fused for the device
    (ops/device_join.py DeviceJoinTopNRun): group tables stay on device; a
    multi-key lax.sort picks the K winners and only K rows are fetched.
    `host_plan` is the untouched translation of the same TopN subtree."""

    def __init__(self, fact: PhysicalPlan, dim_plans, spec, topn, out_map,
                 host_plan: PhysicalPlan, schema: Schema):
        super().__init__()
        self.fact = fact
        self.dim_plans = dim_plans
        self.spec = spec            # ops.device_join.JoinAggSpec
        self.topn = topn            # ops.device_join.TopNSpec
        self.out_map = out_map      # [(kind, index)] per output column
        self.host_plan = host_plan
        self.schema = schema

    def children(self):
        return [self.fact] + [p for _n, p in self.dim_plans]

    def name(self) -> str:
        return f"DeviceJoinTopN({len(self.dim_plans)} dims, k={self.topn.limit})"


class DeviceGroupedAgg(_Unary):
    """Fused (optional filter)+grouped-agg stage eligible for the JAX device.

    Keys factorize on host (any dtype); value reductions segment-reduce on
    device. Executor decides device vs host per run.
    """

    def __init__(self, input: PhysicalPlan, predicate: Optional[Expression],
                 groupby: List[Expression], aggregations: List[Expression], schema: Schema,
                 region_ops=None, bound_stage=None):
        super().__init__(input, schema)
        self.predicate = predicate
        self.groupby = groupby
        self.aggregations = aggregations
        self.region_ops = tuple(region_ops) if region_ops else None
        self._bound_stage = bound_stage  # where the translation has it

    def bound_stage(self):
        """(compiled stage of this node's shape, this node's literal values)
        (ops/grouped_stage.bind_grouped_agg_stage), assembled once a node."""
        if self._bound_stage is None:
            from ..ops.grouped_stage import bind_grouped_agg_stage

            self._bound_stage = bind_grouped_agg_stage(
                self.input.schema, self.predicate, self.groupby, self.aggregations)
        return self._bound_stage

    def name(self) -> str:
        if self.region_ops and len(self.region_ops) > 2:
            return f"DeviceGroupedAgg[{'+'.join(self.region_ops)}]"
        return "DeviceGroupedAgg"


class Dedup(_Unary):
    def __init__(self, input: PhysicalPlan, on: Optional[List[Expression]], schema: Schema):
        super().__init__(input, schema)
        self.on = on


class PhysPivot(_Unary):
    def __init__(self, input: PhysicalPlan, groupby, pivot_col, value_col, agg_op, names, schema: Schema):
        super().__init__(input, schema)
        self.groupby = groupby
        self.pivot_col = pivot_col
        self.value_col = value_col
        self.agg_op = agg_op
        self.names = names


class PhysWindow(_Unary):
    def __init__(self, input: PhysicalPlan, window_exprs, spec, schema: Schema):
        super().__init__(input, schema)
        self.window_exprs = window_exprs
        self.spec = spec


class PhysConcat(PhysicalPlan):
    def __init__(self, inputs: List[PhysicalPlan], schema: Schema):
        super().__init__()
        self.inputs = inputs
        self.schema = schema

    def children(self):
        return self.inputs


class HashJoin(PhysicalPlan):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, left_on, right_on, how,
                 merged_keys, right_rename, schema: Schema, null_equals_null: bool = False,
                 strategy: Optional[str] = None):
        super().__init__()
        self.left = left
        self.right = right
        self.left_on = left_on
        self.right_on = right_on
        self.how = how
        self.merged_keys = merged_keys
        self.right_rename = right_rename
        self.schema = schema
        self.null_equals_null = null_equals_null
        # None/'hash' = probe-table join; 'sort_merge' = order-preserving
        # encode + sorted merge (executor algorithm switch)
        self.strategy = strategy

    def children(self):
        return [self.left, self.right]


class CrossJoin(PhysicalPlan):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, right_rename, schema: Schema):
        super().__init__()
        self.left = left
        self.right = right
        self.right_rename = right_rename
        self.schema = schema

    def children(self):
        return [self.left, self.right]


class PhysRepartition(_Unary):
    def __init__(self, input: PhysicalPlan, num_partitions, scheme, by, schema: Schema):
        super().__init__(input, schema)
        self.num_partitions = num_partitions
        self.scheme = scheme
        self.by = by


class PhysIntoBatches(_Unary):
    def __init__(self, input: PhysicalPlan, batch_size: int, schema: Schema):
        super().__init__(input, schema)
        self.batch_size = batch_size


class PhysWrite(_Unary):
    def __init__(self, input: PhysicalPlan, info: Any, schema: Schema):
        super().__init__(input, schema)
        self.info = info


class ShuffleWrite(_Unary):
    """Terminal node of a distributed map task: hash-partition the input stream
    and persist per-partition Arrow IPC files to the shuffle directory
    (reference: src/daft-shuffles/src/shuffle_cache.rs:39 InProgressShuffleCache).
    Yields nothing; consumers use ShuffleRead."""

    def __init__(self, input: PhysicalPlan, shuffle_id: str, map_id: int,
                 num_partitions: int, by: List[Expression], shuffle_dir: str,
                 schema: Schema):
        super().__init__(input, schema)
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.num_partitions = num_partitions
        self.by = by
        self.shuffle_dir = shuffle_dir


class ShuffleRead(PhysicalPlan):
    """Leaf of a distributed reduce task: stream every map's IPC file for one
    shuffle partition (reference: daft-shuffles flight client do_get). With
    `fetch_endpoints` set, files come over the authenticated fetch-server
    sockets instead of the local filesystem (multi-host topology)."""

    def __init__(self, shuffle_id: str, partition_idx: int, shuffle_dir: str,
                 schema: Schema, fetch_endpoints=None, expected_maps=None):
        super().__init__()
        self.shuffle_id = shuffle_id
        self.partition_idx = partition_idx
        self.shuffle_dir = shuffle_dir
        self.schema = schema
        self.fetch_endpoints = fetch_endpoints  # [(host, port, authkey_hex)]
        # map ids the driver's lineage says wrote rows for THIS partition
        # (distributed/planner.py derives them from TaskResult.map_outputs).
        # Readers verify the files exist and raise ShuffleDataLost naming the
        # missing ids — a dead worker's lost outputs become a recoverable
        # event instead of a silently-short reduce input. None = no check
        # (legacy dirs, direct callers).
        self.expected_maps = tuple(expected_maps) if expected_maps else None


# ======================================================================================
# Translation
# ======================================================================================


def _translate_agg_host(plan, config) -> PhysicalPlan:
    """Translate an Aggregate subtree with plain host operators (the fallback
    plan carried by DeviceJoinAgg)."""
    child = translate(plan.input, config)
    if plan.groupby:
        return HashAggregate(child, plan.groupby, plan.aggregations, plan.schema)
    return UngroupedAggregate(child, plan.aggregations, plan.schema)


def translate(plan: lp.LogicalPlan, config: Any = None) -> PhysicalPlan:
    """Lower an (optimized) logical plan to a physical plan."""
    if isinstance(plan, lp.InMemorySource):
        return InMemoryScan(plan.partitions, plan.schema)

    if isinstance(plan, lp.ScanSource):
        tasks = plan.scan_op.to_scan_tasks(plan.pushdowns)
        from ..config import execution_config

        cfg = config or execution_config()
        target = getattr(cfg, "scan_split_bytes", 0)
        if target and len(tasks) > 1:
            from ..io.scan import merge_small_tasks
            from ..utils.pool import pool_width

            tasks = merge_small_tasks(tasks, target, pool_width())
        post_filter = None
        post_limit = plan.pushdowns.limit
        if plan.pushdowns.filters is not None:
            if not all(t.filters_applied for t in tasks):
                post_filter = plan.pushdowns.filters
        if post_limit is not None and all(t.limit_applied for t in tasks):
            # limit fully absorbed per-task; still cap globally
            pass
        return StreamingScan(tasks, plan.schema, post_filter, post_limit)

    if isinstance(plan, lp.Project):
        return Project(translate(plan.input, config), plan.projection, plan.schema)

    if isinstance(plan, lp.UDFProject):
        from ..config import execution_config

        cfg = config or execution_config()
        if getattr(cfg, "device_mode", "off") != "off" \
                and device_udf_call(plan.udf_expr) is not None:
            # device-UDF tier capture; the executor re-checks mode/cost at
            # run time and falls back to the plain UDF path loudly
            return DeviceUdfProject(translate(plan.input, config), plan.udf_expr,
                                    plan.passthrough, plan.schema)
        return UDFProject(translate(plan.input, config), plan.udf_expr, plan.passthrough, plan.schema)

    if isinstance(plan, lp.Filter):
        return PhysFilter(translate(plan.input, config), plan.predicate, plan.schema,
                          plan.keep)

    if isinstance(plan, lp.Limit):
        return PhysLimit(translate(plan.input, config), plan.limit, 0, plan.schema)

    if isinstance(plan, lp.Offset):
        # standalone offset = skip n rows
        return PhysLimit(translate(plan.input, config), -1, plan.offset, plan.schema)

    if isinstance(plan, lp.Explode):
        return PhysExplode(translate(plan.input, config), plan.to_explode, plan.schema)

    if isinstance(plan, lp.Unpivot):
        return PhysUnpivot(translate(plan.input, config), plan.ids, plan.values,
                           plan.variable_name, plan.value_name, plan.schema)

    if isinstance(plan, lp.Sample):
        return PhysSample(translate(plan.input, config), plan.fraction, plan.with_replacement,
                          plan.seed, plan.schema)

    if isinstance(plan, lp.MonotonicallyIncreasingId):
        return PhysMonotonicId(translate(plan.input, config), plan.column_name, plan.schema)

    if isinstance(plan, lp.Sort):
        return PhysSort(translate(plan.input, config), plan.sort_by, plan.descending,
                        plan.nulls_first, plan.schema)

    if isinstance(plan, lp.TopN):
        from ..config import execution_config

        cfg = config or execution_config()
        if getattr(cfg, "device_mode", "off") != "off":
            from ..ops import counters
            from ..ops.device_join import try_capture_join_topn

            try:
                cap3 = try_capture_join_topn(plan)
            except Exception:
                # capture must never break planning, but a capture BUG must
                # not silently cost every query its device tier either
                counters.reject("capture", "join TopN capture raised")
                cap3 = None
            if cap3 is not None:
                jspec, topn, out_map = cap3
                host = PhysTopN(translate(plan.input, config), plan.sort_by,
                                plan.descending, plan.nulls_first, plan.limit,
                                plan.offset, plan.schema)
                return DeviceJoinTopN(
                    translate(jspec.fact, config),
                    [(d.name, translate(d.base, config)) for d in jspec.dims],
                    jspec, topn, out_map, host, plan.schema)
        return PhysTopN(translate(plan.input, config), plan.sort_by, plan.descending,
                        plan.nulls_first, plan.limit, plan.offset, plan.schema)

    if isinstance(plan, lp.Aggregate):
        # Device-stage fusion: Aggregate(+optional Filter) whose expressions are
        # device-evaluable lowers to a fused Device*Agg node — and when the
        # input is a star-shaped inner-join tree, to a DeviceJoinAgg gather
        # program; the executor picks device vs host at runtime.
        from ..config import execution_config

        cfg = config or execution_config()
        if getattr(cfg, "device_mode", "off") != "off":
            from ..ops import counters
            from ..ops.device_join import try_capture_join_agg

            try:
                jspec = try_capture_join_agg(plan)
            except Exception:
                # same contract as the TopN capture above: degrade AND count
                counters.reject("capture", "join agg capture raised")
                jspec = None
            if jspec is not None:
                host = _translate_agg_host(plan, config)
                return DeviceJoinAgg(
                    translate(jspec.fact, config),
                    [(d.name, translate(d.base, config)) for d in jspec.dims],
                    jspec, host, plan.schema)
            # Whole-stage fused-region capture: collapse the maximal
            # Filter/Project chain under the aggregate into composed
            # expressions over the chain's base, then qualify candidates
            # most-fused-first against the device stage builders. The last
            # candidate reproduces the legacy one-Filter peel, so nothing
            # that fused before stops fusing.
            if getattr(cfg, "region_mode", "on") != "off":
                from ..ops.region import agg_region_candidates

                try:
                    cands = agg_region_candidates(plan)
                except Exception:
                    counters.reject("capture", "fused region capture raised")
                    cands = []
            else:
                from ..ops.region import RegionCapture

                src = plan.input
                predicate = None
                ops = ("agg",)
                if isinstance(src, lp.Filter):
                    predicate = src.predicate
                    src = src.input
                    ops = ("filter", "agg")
                cands = [RegionCapture(src, predicate, plan.groupby,
                                       plan.aggregations, ops)]
            for cand in cands:
                if plan.groupby:
                    from ..ops.grouped_stage import bind_grouped_agg_stage

                    bound = bind_grouped_agg_stage(
                        cand.source.schema, cand.predicate, cand.groupby,
                        cand.aggregations)
                    if bound is not None:
                        return DeviceGroupedAgg(
                            translate(cand.source, config), cand.predicate,
                            cand.groupby, cand.aggregations, plan.schema,
                            region_ops=cand.ops, bound_stage=bound)
                else:
                    from ..ops.stage import bind_filter_agg_stage

                    bound = bind_filter_agg_stage(
                        cand.source.schema, cand.predicate, cand.aggregations)
                    if bound is not None:
                        return DeviceFilterAgg(
                            translate(cand.source, config), cand.predicate,
                            cand.aggregations, plan.schema,
                            region_ops=cand.ops, bound_stage=bound)
        child = translate(plan.input, config)
        if plan.groupby:
            return HashAggregate(child, plan.groupby, plan.aggregations, plan.schema)
        return UngroupedAggregate(child, plan.aggregations, plan.schema)

    if isinstance(plan, lp.MapGroups):
        return PhysMapGroups(translate(plan.input, config), plan.groupby,
                             plan.udf_expr, plan.schema)

    if isinstance(plan, lp.Distinct):
        return Dedup(translate(plan.input, config), plan.on, plan.schema)

    if isinstance(plan, lp.Pivot):
        return PhysPivot(translate(plan.input, config), plan.groupby, plan.pivot_col,
                         plan.value_col, plan.agg_op, plan.names, plan.schema)

    if isinstance(plan, lp.Window):
        return PhysWindow(translate(plan.input, config), plan.window_exprs, plan.spec, plan.schema)

    if isinstance(plan, lp.Concat):
        return PhysConcat([translate(c, config) for c in plan.inputs], plan.schema)

    if isinstance(plan, lp.Join):
        merged_keys, right_rename = plan.output_naming()
        if plan.how == "cross":
            return CrossJoin(translate(plan.left, config),
                             translate(plan.right, config), right_rename, plan.schema)
        # Cost-based build-side selection (reference: translate_join.rs strategy
        # pick + broadcast_join_size_bytes): the right side is the hash build;
        # when the LEFT side is estimated much smaller (and small enough to
        # hold), swap sides so the small side builds, restoring the original
        # column order with a Project.
        if plan.how == "inner" and plan.strategy is None and not right_rename:
            from ..expressions import col as _col
            from .stats import estimate_bytes

            lb = estimate_bytes(plan.left)
            rb = estimate_bytes(plan.right)
            # build on the smaller side unconditionally (no absolute size cap:
            # the build side is fully materialized either way, so picking the
            # smaller one strictly reduces memory AND build time; the 2x
            # hysteresis avoids churn on near-equal estimates)
            if lb is not None and rb is not None and lb < rb / 2:
                swapped = lp.Join(plan.right, plan.left, plan.right_on, plan.left_on,
                                  "inner")
                s_merged, s_rename = swapped.output_naming()
                if not s_rename and (set(swapped.schema.column_names())
                                     == set(plan.schema.column_names())):
                    hj = HashJoin(translate(plan.right, config),
                                  translate(plan.left, config),
                                  plan.right_on, plan.left_on, "inner",
                                  s_merged, s_rename, swapped.schema,
                                  plan.null_equals_null)
                    return Project(hj, [_col(f.name) for f in plan.schema], plan.schema)
        return HashJoin(translate(plan.left, config), translate(plan.right, config),
                        plan.left_on, plan.right_on, plan.how,
                        merged_keys, right_rename, plan.schema, plan.null_equals_null,
                        plan.strategy)

    if isinstance(plan, lp.Repartition):
        return PhysRepartition(translate(plan.input, config), plan.num_partitions,
                               plan.scheme, plan.by, plan.schema)

    if isinstance(plan, lp.IntoPartitions):
        return PhysRepartition(translate(plan.input, config), plan.num_partitions,
                               "into", None, plan.schema)

    if isinstance(plan, lp.IntoBatches):
        return PhysIntoBatches(translate(plan.input, config), plan.batch_size, plan.schema)

    if isinstance(plan, lp.Sink):
        return PhysWrite(translate(plan.input, config), plan.info, plan.schema)

    raise NotImplementedError(f"cannot translate {type(plan).__name__}")
