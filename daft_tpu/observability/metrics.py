"""Thread-safe metrics registry: named counters and gauges with snapshot/diff.

Reference parity: src/common/metrics/src/ops.rs — the reference defines a
per-operator metrics vocabulary behind one process-wide registry that
subscribers snapshot per query. Here the registry is the single home for
engine-path attribution counters (device batches, shuffle bytes, fetch-server
requests); `ops/counters.py` re-exports the device names for backward
compatibility, and runners record a per-query `diff()` into QueryEnd so
device/shuffle attribution lands in EXPLAIN ANALYZE and the event log.

Zero-overhead contract: nothing in the engine's hot path reads the registry;
writes only happen on coarse events (a device dispatch, a shuffle file, a
fetch request), never per row. An unobserved host-only query moves one
counter, its own wall time (`query_wall_us`, one `inc` as it ends).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Optional


class MetricsRegistry:
    """Named monotonically-increasing counters + last-value gauges.

    All methods are safe to call from any thread (executor stage threads,
    shuffle fetch threads, the worker heartbeat thread).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}

    # ---- writes ------------------------------------------------------------------
    def declare(self, *names: str) -> None:
        """Pre-register counters at 0 so they always appear in snapshots."""
        with self._lock:
            for n in names:
                self._counters.setdefault(n, 0)

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def set_gauge_max(self, name: str, value: float) -> None:
        """High-water gauge: keep the largest value ever reported (e.g.
        shuffle_fetch_inflight — the deepest the prefetch queue got)."""
        with self._lock:
            if value > self._gauges.get(name, float("-inf")):
                self._gauges[name] = value

    # ---- reads -------------------------------------------------------------------
    def get(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self) -> Dict[str, float]:
        """Point-in-time copy of every counter and gauge."""
        with self._lock:
            out: Dict[str, float] = dict(self._counters)
            out.update(self._gauges)
            return out

    def export(self) -> "tuple[Dict[str, int], Dict[str, float]]":
        """(counters, gauges) as separate copies — the Prometheus exposition
        needs the TYPE distinction that snapshot() flattens away."""
        with self._lock:
            return dict(self._counters), dict(self._gauges)

    def diff(self, before: Dict[str, float]) -> Dict[str, float]:
        """Counter deltas since `before` (a prior snapshot); gauges report
        their current value, but only when it CHANGED since `before` — a
        standing gauge (e.g. hbm_bytes_resident left by an earlier query)
        must not show up in the per-query record of a query that never
        touched it (the zero-overhead guard depends on this). Zero counter
        deltas are dropped so per-query records stay small; negative deltas
        (a reset() between the snapshots) clamp to zero and drop rather than
        reporting nonsense."""
        now = self.snapshot()
        out: Dict[str, float] = {}
        with self._lock:
            gauges = set(self._gauges)
        for k, v in now.items():
            if k in gauges:
                if v != before.get(k, 0):
                    out[k] = v
                continue
            d = v - before.get(k, 0)
            if d > 0:
                out[k] = d
        return out

    def reset(self, names: Optional[Iterable[str]] = None) -> None:
        """Zero counters (all, or just `names`) and drop gauges."""
        with self._lock:
            if names is None:
                for k in self._counters:
                    self._counters[k] = 0
                self._gauges.clear()
            else:
                for k in names:
                    if k in self._counters:
                        self._counters[k] = 0
                    self._gauges.pop(k, None)


_REGISTRY = MetricsRegistry()

# ---- the metric-name vocabulary -----------------------------------------------------
# Single home for every counter/gauge name the engine writes. The lint rule
# `counter-discipline` (daft_tpu/tools/lint/) checks each literal
# registry().inc()/set_gauge()/bump() name in the codebase against the
# DECLARED_COUNTERS / DECLARED_GAUGES tuples below, and everything here is
# pre-declared at import time so the Prometheus exposition is import-order
# independent: a scraper sees every series at 0 from the first scrape of a
# fresh process, not only after the owning (often lazily-imported) module
# happens to load or the first increment lands.

# Device/mesh/UDF path attribution. ops/counters.py re-exports this group as
# COUNTER_NAMES (PEP 562 attribute views + the scoped test reset).
DEVICE_COUNTER_NAMES = (
    "device_stage_batches",    # batches through FilterAggStage (ungrouped)
    "device_grouped_batches",  # batches through GroupedAggStage
    # how the one-hot tier's program reduced a dispatch's chunks
    # (grouped_stage._reduce_form: a function of the group capacity)
    "device_grouped_reduce_select",  # a masked sum a group, on the VPU
    "device_grouped_reduce_matmul",  # the one-hot product, on the MXU
    "device_stage_runs",       # completed device agg node executions
    # the aggregate stages take a query's literal values as arguments (ops/
    # stage.py, grouped_stage.py): a program is traced for a query shape, a
    # bucket and a mesh width, never for a value
    "device_stage_program_traces",  # stage programs traced (0 on a repeat query shape)
    "device_literal_args",     # literal values passed to stage programs, summed over launches
    # a stage dispatch whose rows were sharded over more than one local device
    # (stage.note_mesh_dispatch): it is counted as a device_stage_batches or
    # device_grouped_batches dispatch too
    "device_mesh_batches",     # dispatches that spanned more than one device
    "device_mesh_shards",      # devices summed over those dispatches
    "mesh_grouped_runs",       # grouped aggs executed via the mesh-sharded path
    "mesh_dispatches",         # multi-device shard_map/pjit dispatches issued
    "mesh_unavailable_fallbacks",  # forced mesh_devices > local devices -> single-chip
    "device_join_batches",     # batches through the gather-join device stages
    # a join whose fact or dimension plan is a select over one resident
    # table reads the table on the dispatching thread (executor.
    # _resident_select): no morsel comes in, so the coalescer's counters
    # below stand still for it
    "join_resident_ranges",    # zero-copy ranges of a resident fact handed to a join run's feed_batch
    "join_resident_dims",      # dimensions taken whole from their resident table
    "join_provision_calls",    # join dispatches whose columns came from one traced program
    "join_provision_traces",   # provisioning programs traced (0 on a repeat query shape)
    "join_window_gathers",     # adjacent-dimension gathers that read a batch-long window of the pack, summed over those dispatches
    "join_unwindowed_gathers",  # adjacent-dimension gathers that read the whole pack (the fact is not ordered by that dimension's key), summed over those dispatches
    "join_priced_dispatch_rows",  # rows a dispatch the chosen device arm of a join's costed decision was priced at, summed over those decisions
    # a dim filter's literal values are arguments of the subtree's visibility
    # program (ops/device_join.py verdict_plane): traced for a list of filter
    # skeletons, a dimension length and a mesh width, never for a value
    "join_filter_program_traces",  # visibility programs traced (0 on a repeat query shape)
    "join_filter_literal_args",    # literal values passed to visibility programs, summed over calls
    "device_topn_runs",        # join+agg+TopN fused device programs completed
    # the fused TopN's group tables stay on the device for a whole run where
    # the group-by spans one dimension's key space (ops/device_join.py
    # DeviceJoinTopNRun): its fact may then come in any number of batches
    "device_join_topn_batches",  # fact batches fused TopN runs took in
    "device_topn_fetched_rows",  # rows their finalizes brought back (at most a limit + offset each)
    "device_topn_table_bytes",   # bytes of the run-wide group tables, summed over the runs that built them
    "join_topn_compact_batches",  # of those batches, the ones whose kept rows were compacted on the device before the scatters (a count a chip over a mesh)
    "join_topn_ordered_batches",  # of the segments that took the dense form, the ones whose kept ids never decrease, so that their first rows rode the product (a count a chip over a mesh)
    "join_topn_folds",  # the dispatches of those runs that held a sparse (compacted or scattered) segment and so folded their float32 partial into the run's sums, one table-long two-sum a plane (a count a chip over a mesh; 0 where every segment was dense)
    # a join dispatch whose fact rows were sharded over more than one local
    # device, each running the single chip's programs on its shard
    # (ops/device_join.py, `mesh_devices` > 1): counted as a
    # device_join_batches and a device_mesh_batches dispatch too
    "device_join_mesh_batches",  # join dispatches that spanned more than one device
    "device_join_mesh_shards",   # devices summed over those dispatches
    "device_topn_combine_bytes",  # bytes the run-wide tables' cross-chip combines moved between chips
    "mesh_join_runs",         # device joins whose dispatches spanned a mesh (ops/device_join.py, `mesh_devices` > 1)
    # intra-host ICI repartition (jax.lax.all_to_all over the local mesh —
    # the in-mesh replacement for the host shuffle between co-located workers)
    "mesh_alltoall_dispatches",    # all_to_all exchange programs dispatched
    "mesh_alltoall_rows",          # rows routed over ICI instead of the host shuffle
    "mesh_alltoall_ici_bytes",     # plane bytes the exchange moved over ICI
    # device-UDF tier (ops/udf_stage.py): jax-traceable model UDFs as stages
    "device_udf_dispatches",   # compiled UDF program dispatches (super-batches)
    "device_udf_rows",         # real rows through device UDF dispatches
    "device_udf_runs",         # completed DeviceUdfProject device executions
    "device_udf_fallbacks",    # device-UDF stages rerouted to the host path
    "device_udf_weight_h2d_bytes",  # model weight bytes uploaded (flat on repeats)
    "rejection_log_dropped",   # reject() entries dropped once rejection_log filled
    # adaptive batching + device dispatch coalescing (execution/batching.py,
    # ops/stage.py DispatchCoalescer)
    "dispatch_coalesced",      # super-batch dispatches issued by the coalescer
    "coalesce_morsels_in",     # morsels consumed (÷ dispatch_coalesced = amortization)
    "bucket_fill_rows",        # real rows covered by coalesced dispatches
    "bucket_capacity_rows",    # padded bucket rows (fill ratio denominator)
    "morsel_resize",           # adaptive batching morsel-size changes
    # HBM residency manager (daft_tpu/device/residency.py)
    "hbm_cache_hits",          # residency lookups served from HBM
    "hbm_cache_misses",        # residency lookups that built/uploaded
    "hbm_lineage_hits",        # hits under another view object of the same rows
    "hbm_literal_rebuilds",    # entries found under their key whose literals differed: rebuilt in place
    "hbm_evictions",           # entries evicted under the HBM budget
    "hbm_eviction_bytes",      # device bytes released by evictions
    "hbm_pins",                # entries pinned by an executing query
    "hbm_h2d_bytes",           # host->device column upload bytes
    "h2d_upload_us",           # host µs in column uploads (pad + device_put)
    "h2d_prepare_us",          # of those, µs making the padded host planes (the put is the rest)
    "h2d_transfers",           # calls that moved host planes to the device (upload path)
    "h2d_planes",              # host planes those calls carried (÷ h2d_transfers: a morsel's travel together)
    "dict_encode_us",          # host µs dictionary-encoding key columns (first touch)
    # the other cold sites (runtime_stats.timed_span(counter=...)): host µs of
    # work that only a first execution does, counted where it happens, each
    # less what the cold sites inside it counted (self times: they add up)
    "jax_trace_us",            # JAX tracing Python to jaxprs (self time: utils/jax_setup)
    "jax_lower_us",            # JAX lowering jaxprs to MLIR modules
    "xla_compile_us",          # XLA compiling, or retrieving from the persistent cache
    "calibrate_us",            # the cost model's live probes (costmodel.calibrate)
    "content_hash_us",         # hashing a column's content for its stable residency key
    "content_hash_bytes",      # bytes those hashes read (÷ content_hash_us: the hash's rate)
    "content_hash_inplace",    # columns hashed over their Arrow buffers where they lie
    "content_hash_copied",     # columns hashed after a normalising copy (nulls, a string
                               # slice's offsets, booleans, fixed-shape and nested types)
    "residency_build_us",      # residency misses building their values, less the uploads,
                               # encodes and program builds inside them
    "query_wall_us",           # wall µs of every query (NativeRunner), warm ones too
    "hbm_stable_rehits",       # slots rebound by content identity (repeat sub-plans)
    "hbm_evict_cost_saved",    # µs of rebuild cost avoided vs pure-LRU eviction
    # distributed cache-affinity scheduling (distributed/scheduler.py)
    "sched_affinity_hits",     # tasks placed on a worker holding their planes
    "sched_affinity_misses",   # fingerprinted tasks spread off a full preferred worker
    "sched_affinity_skips",    # hard-affinity heap skips (head-of-line guard)
    "sched_bytes_avoided",     # est. h2d bytes saved by affinity placements
    # speculative re-execution (distributed/worker.py dispatcher)
    "sched_speculative_dispatches",
    "sched_speculative_wins",  # races the speculative copy actually won
    # serving tier (daft_tpu/serving/): admission + prepared-query cache
    "admission_waits_total",   # queries queued at the HBM admission controller
    "serve_queries_total",     # queries executed through a ServingSession
    "serve_prepared_hits",     # prepared-query cache hits (planning skipped)
    "serve_prepared_misses",   # prepared-query cache misses (planned + cached)
    "serve_pin_calibrations",  # reservations shrunk toward observed pin high-water
    # checkpoint store GC (checkpoint/stages.py sweep_expired)
    "checkpoint_stages_gced",  # committed stages removed by the TTL sweep
    # whole-stage fused regions (ops/region.py capture + executor wiring):
    # a dispatch of a node whose fused chain spans >= 2 operators counts
    # once here and len(chain) times in ops_fused, so
    # ops_fused / dispatches = mean operators amortized per RTT.
    "device_region_dispatches",   # device dispatches issued by fused regions
    "device_region_ops_fused",    # operators covered by those dispatches
    # Pallas kernel tier (ops/pallas_kernels.py: segment-reduce groupby,
    # hash-probe join, in-kernel ICI ring permute)
    "pallas_dispatches",       # grouped-agg batches through the Pallas kernel
    "pallas_probe_dispatches",  # join index planes probed in-kernel
    # intra-host repartition exchanged by the in-kernel ring permute instead
    # of a standalone all_to_all dispatch (mesh_alltoall_dispatches stays 0)
    "mesh_fused_permute_dispatches",
)

# Serving-tier counters OUTSIDE the ops/counters.py reset scope (cancellation
# is resolved on the session thread; a test's device-counter reset must
# not wipe it mid-session).
SERVING_COUNTER_NAMES = (
    "serve_cancelled_total",
    "serve_over_cap_rejections",  # submits refused at a tenant queue-depth cap
)

# Gateway tier (daft_tpu/gateway/): the wire-protocol serving front door and
# its cross-tenant result cache. Connection/auth/protocol failures count here
# (they never reach a ServeQueryRecord); result-cache hits make repeat
# traffic skip execution entirely, so the hit/miss split is the headline
# serving-economics number.
GATEWAY_COUNTER_NAMES = (
    "gateway_connections_total",   # TCP connections accepted
    "gateway_disconnects_total",   # connections closed (any reason)
    "gateway_requests_total",      # wire requests served (all verbs)
    "gateway_queries_total",       # execute verbs admitted (any source)
    "gateway_auth_failures",       # hello rejected (bad token / unknown tenant)
    "gateway_errors_total",        # protocol/IO errors answered or logged
    "gateway_bytes_streamed",      # Arrow IPC payload bytes sent to clients
    "result_cache_hits",           # queries served from the result cache
    "result_cache_misses",         # result-cache lookups that executed
    "result_cache_evictions",      # entries evicted under the byte budget
)

# Shuffle/transport volume (distributed/shuffle.py ShuffleRecorder rollups,
# distributed/fetch_server.py).
SHUFFLE_COUNTER_NAMES = (
    "shuffle_bytes_written",      # logical Arrow bytes into map files
    "shuffle_logical_bytes",      # alias kept distinct for compression ratio
    "shuffle_rows_written",
    "shuffle_wire_bytes",         # bytes that actually hit disk/the wire
    "shuffle_bytes_fetched",      # wire bytes received by reduce fetches
    "shuffle_rows_fetched",
    "shuffle_fetch_seconds",      # cumulative per-request in-flight time
    "shuffle_fetch_wall_seconds", # union transfer window
    "shuffle_overlap_seconds",    # cumulative - wall = transfer overlapped
    "shuffle_fetch_server_requests",
    "shuffle_fetch_server_bytes",
    "shuffle_reduce_spill_bytes",  # reduce-input bytes diverted to spill when
                                   # the budgeted consumer's prefetch queue
                                   # stayed full (fetch_server._fetch_pipelined)
)

# Elastic fault tolerance (distributed/worker.py liveness monitor,
# distributed/planner.py lost-map regeneration, checkpoint/stages.py,
# fetch_server.py transient retry): recovery is exactly the regime where a
# scraper must see the series from scrape one.
FAULT_COUNTER_NAMES = (
    "worker_failures_total", "tasks_requeued_total", "worker_respawns_total",
    "shuffle_maps_regenerated_total", "fetch_retries_total",
    "checkpoint_stages_committed", "checkpoint_stages_skipped",
    "checkpoint_commit_failures",
    "checkpoint_restore_failures",  # committed stage unreadable -> stage re-run
)

# Observability self-monitoring: subscriber callbacks that raised (swallowed
# so a broken subscriber can't fail a query — counted so it isn't invisible).
OBS_COUNTER_NAMES = ("subscriber_errors",)

# Flight recorder (observability/flight.py): ONLY anomalies touch the
# registry — ring appends and cap eviction are registry-silent so the
# always-on recorder preserves the per-query empty-diff guarantee.
FLIGHT_COUNTER_NAMES = (
    "flight_anomalies_total",  # anomaly triggers fired (incl. cooldown-suppressed)
    "flight_dumps_total",      # ring snapshots written to the dump dir
    "flight_dump_failures",    # dump writes that failed (unwritable dir)
)

# Placement observability (observability/placement.py): the cost-model
# decision ledger. Counters move ONLY on costed/forced placement decisions —
# pre-cost gate rejections (cpu backend, below device_min_rows) are ledger
# records without registry writes, preserving the unobserved-path
# empty-registry-diff guarantee.
PLACEMENT_COUNTER_NAMES = (
    "placement_decisions_total",   # costed auto-tier placement decisions
    "placement_device_wins",       # decisions that chose the single-chip device
    "placement_host_wins",         # decisions that kept the stage on host
    "placement_mesh_wins",         # decisions that took the mesh tier
    "placement_cached_verdicts",   # verdicts served from the bounded caches
    "placement_forced_runs",       # device_mode=on runs recorded uncosted
    "placement_feedback_total",    # dispatched stages reporting actual seconds
    "placement_records_dropped",   # ledger appends evicted at the bounded cap
)

# Host memory manager spill (daft_tpu/memory/ documents the semantics;
# execution/memory.py is the compatibility view).
SPILL_COUNTER_NAMES = (
    "spill_batches",        # batches written to spill files
    "spill_bytes",          # logical Arrow bytes of those batches
    "spill_wire_bytes",     # bytes that actually hit disk (IPC body compression)
    "spill_files",          # spill files opened (runs + Grace partitions)
    "spill_runs",           # sorted runs generated by the external sort
    "spill_merge_passes",   # intermediate k-way merge passes (fan-in capping)
    "spill_dirs_gced",      # stale spill artifacts swept from dead processes
    # async spill IO attribution (spill_io_threads > 0 only — the synchronous
    # threads=0 path never touches these, preserving the compat guard).
    # Overlap discipline mirrors the PR 5 shuffle fetch split: cumulative
    # off-thread seconds vs the wall seconds the CALLER actually paid
    # (queue-full stalls + finish joins / prefetch-queue waits): the IO the
    # pool hid is max(write - write_wall, 0) + max(read - read_wall, 0).
    "spill_write_seconds",       # cumulative IO-thread compress+write time
    "spill_write_wall_seconds",  # wall seconds spill writes cost the producer
    "spill_read_seconds",        # cumulative IO-thread decode time (prefetch)
    "spill_read_wall_seconds",   # wall seconds consumers blocked on read-ahead
    "spill_merge_sort_rows",     # rows through the k-way merge's argsort —
                                 # the carry-preserving merge's work bound
                                 # (<= total rows; the old merge re-sorted
                                 # the carry every round, ~rows x fan-in)
)

# Out-of-core streaming scans (execution/executor.py _streaming_scan over
# io/parquet.py split planning) + the host memory ledger (daft_tpu/memory/).
MEMORY_COUNTER_NAMES = (
    "scan_batches",             # morsels yielded by streaming scans
    "scan_rows",                # rows through streaming scans
    "scan_bytes",               # logical bytes through BUDGETED streaming scans
                                # (sizing morsels walks arrow buffers — skipped
                                # on the unbudgeted zero-overhead path)
    "scan_file_bytes",          # on-disk bytes of the files / row groups of the
                                # scan tasks a streaming scan started to read
                                # (ScanTask.size_bytes; unknown sizes, as a
                                # remote object's, count nothing)
    "scan_decoded_bytes",       # Arrow bytes the parquet readers decoded:
                                # RecordBatch.nbytes of what pyarrow returned
                                # (after its filter), no buffer walk
    "scan_row_groups",          # parquet row groups handed to readers
    "scan_row_groups_pruned",   # row groups zone maps excluded at plan time
    "scan_tasks",               # scan tasks streaming scans were given, after
                                # split and merge (one scan.stream span each)
    "scan_tasks_split",         # scan tasks produced by row-group splitting
    "scan_tasks_merged",        # small scan tasks absorbed by task merging
    "scan_backpressure_stalls", # times a scan stalled on host memory pressure
    "scan_stall_ms",            # cumulative milliseconds of those stalls
    "host_over_budget_events",  # operators that crossed the host budget -> spill
)

DECLARED_COUNTERS = (DEVICE_COUNTER_NAMES + SERVING_COUNTER_NAMES +
                     GATEWAY_COUNTER_NAMES +
                     SHUFFLE_COUNTER_NAMES + FAULT_COUNTER_NAMES +
                     SPILL_COUNTER_NAMES + MEMORY_COUNTER_NAMES +
                     OBS_COUNTER_NAMES + PLACEMENT_COUNTER_NAMES +
                     FLIGHT_COUNTER_NAMES)

DECLARED_GAUGES = (
    "serve_queue_depth",       # admission queue depth (serving/session.py)
    "result_cache_bytes",      # gateway result-cache resident payload bytes
    "gateway_active_connections",  # live gateway client connections
    "hbm_bytes_resident",      # device bytes the residency manager holds
    "hbm_bytes_high_water",
    "hbm_reserved_bytes",      # admission-controller reservations outstanding
    "host_bytes_tracked",      # host bytes admitted against the memory ledger
    "host_bytes_high_water",   # ledger high-water since process start / clear()
    "shuffle_fetch_inflight",  # high-water concurrent fetch requests
    "spill_prefetch_inflight",  # high-water decoded batches queued per reader
    "mesh_devices_used",       # devices of the last mesh dispatch
    "bucket_fill_ratio",       # coalescer padding efficiency (per run)
    # cost-model observability (ops/costmodel.py + observability/placement.py)
    "cost_model_error_ratio",  # last dispatched stage: observed/predicted s/row
    # the effective Calibration terms, exported at calibrate() so every
    # scrape states the calibration the process ran under
    "cost_rtt_s",
    "cost_h2d_bytes_per_s",
    "cost_d2h_bytes_per_s",
    "cost_ici_bytes_per_s",
    "cost_mesh_dispatch_s",
    "cost_udf_flops_per_s",
)


def declare_vocabulary(reg: "MetricsRegistry") -> None:
    """Pre-register the full vocabulary (counters at 0, gauges seeded 0.0) —
    called on the process registry at import; tests call it on fresh
    registries to assert first-scrape visibility."""
    reg.declare(*DECLARED_COUNTERS)
    for g in DECLARED_GAUGES:
        reg.set_gauge(g, 0.0)


declare_vocabulary(_REGISTRY)


def registry() -> MetricsRegistry:
    """The process-wide registry (one per driver / worker process)."""
    return _REGISTRY


# ---- Prometheus text exposition ------------------------------------------------------

_NAME_SANITIZE = None  # compiled lazily; /metrics is a cold path


def _prom_name(name: str) -> str:
    global _NAME_SANITIZE
    if _NAME_SANITIZE is None:
        import re

        _NAME_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")
    return _NAME_SANITIZE.sub("_", name)


def prometheus_text(prefix: str = "daft_tpu_",
                    extra_gauges: Optional[Dict[str, float]] = None,
                    histograms: Optional[Dict[str, "Histogram"]] = None,
                    labeled_histograms: Optional[
                        "Dict[str, Dict[str, Histogram]]"] = None) -> str:
    """The whole registry in Prometheus text exposition format (version
    0.0.4): every counter as `<prefix><name>` TYPE counter, every gauge TYPE
    gauge, plus caller-supplied live gauges (e.g. hbm_bytes_resident read
    straight off the residency manager) and fixed-bucket histograms. Served
    by the dashboard's /metrics endpoint; scrapeable by any standard infra.

    `labeled_histograms` maps a metric name to {label_string: Histogram}
    (label_string like 'tenant="acme"'): every labeled series shares one
    metric family — one TYPE line, the label riding each sample — which is
    how the serving tier exposes its per-tenant query-latency split. A name
    present in BOTH dicts emits the unlabeled aggregate and the labeled
    series under a single TYPE line."""
    counters, gauges = _REGISTRY.export()
    if extra_gauges:
        for k, v in extra_gauges.items():
            counters.pop(k, None)
            gauges[k] = v
    lines = []
    for name in sorted(counters):
        m = prefix + _prom_name(name)
        lines.append(f"# TYPE {m} counter")
        lines.append(f"{m} {counters[name]}")
    for name in sorted(gauges):
        m = prefix + _prom_name(name)
        lines.append(f"# TYPE {m} gauge")
        lines.append(f"{m} {gauges[name]}")
    labeled = labeled_histograms or {}
    for name in sorted(set(histograms or ()) | set(labeled)):
        m = prefix + _prom_name(name)
        lines.append(f"# TYPE {m} histogram")
        if histograms and name in histograms:
            lines.extend(histograms[name].prometheus_lines(m, include_type=False))
        for label in sorted(labeled.get(name, ())):
            lines.extend(labeled[name][label].prometheus_lines(
                m, labels=label, include_type=False))
    return "\n".join(lines) + "\n"


class Histogram:
    """Fixed-bucket cumulative histogram (Prometheus semantics: bucket counts
    are cumulative, le labels are upper bounds). Fixed buckets make p50/p99
    derivable by any scraper via histogram_quantile; the default bucket set
    spans interactive sub-second queries through multi-minute batch scans."""

    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                       1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

    def __init__(self, buckets: Optional[Iterable[float]] = None):
        self.buckets = tuple(sorted(buckets)) if buckets else self.DEFAULT_BUCKETS
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.buckets) + 1)  # +1 for +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        with self._lock:
            self._sum += value
            self._count += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (the upper bound of the bucket
        the q-th observation falls in) — what a scraper's
        histogram_quantile() would report, computable locally."""
        with self._lock:
            total = self._count
            if total == 0:
                return 0.0
            rank = q * total
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += self._counts[i]
                if cum >= rank:
                    return b
            return float("inf")

    def prometheus_lines(self, metric: str, labels: str = "",
                         include_type: bool = True) -> list:
        """Text-exposition sample lines. `labels` is an optional pre-rendered
        label string ('tenant="acme"') merged with the le bucket label —
        per-tenant latency series share one metric family this way."""
        with self._lock:
            counts = list(self._counts)
            total_sum, total_count = self._sum, self._count
        lines = [f"# TYPE {metric} histogram"] if include_type else []
        sep = f"{labels}," if labels else ""
        suffix = f"{{{labels}}}" if labels else ""
        cum = 0
        for b, c in zip(self.buckets, counts[:-1]):
            cum += c
            lines.append(f'{metric}_bucket{{{sep}le="{b}"}} {cum}')
        cum += counts[-1]
        lines.append(f'{metric}_bucket{{{sep}le="+Inf"}} {cum}')
        lines.append(f"{metric}_sum{suffix} {total_sum}")
        lines.append(f"{metric}_count{suffix} {total_count}")
        return lines
