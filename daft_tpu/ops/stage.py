"""Device stage compiler: fuse Filter→Project→Aggregate chains into ONE jit program.

This is the TPU replacement for the reference's per-operator pipeline
(src/daft-local-execution intermediate ops): instead of running project/filter/agg
as separate vectorized kernels over morsels, the whole chain is traced into a
single XLA computation per stage, so elementwise work fuses into one HBM pass and
reductions stay on-chip (SURVEY.md §7 "Swordfish morsel pipeline" mapping).

Dynamic shapes: XLA requires static shapes, so batches are padded to power-of-two
length buckets (padding rows ride along with validity=False) — SURVEY.md §7's
"quantized batching" answer to data-dependent row counts. The jit cache is then
bounded by O(log max_rows) compilations per stage structure.

Stages are split into an immutable compiled *program* (cached process-wide, so
repeated queries reuse jitted XLA executables) and a per-run accumulator object
(`FilterAggRun`) created via `start_run()` — an interrupted or failed run can
never leak partial state into the next run of the same query, and concurrent
identical queries never share accumulators.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from ..expressions.expressions import AggExpr, Alias, Expression
from ..observability.runtime_stats import profile_span, timed_span
from ..schema import Schema
from . import counters
from . import device_eval as dev

_MIN_BUCKET = 512


def pad_bucket(n: int) -> int:
    """Smallest power-of-two >= n (>= _MIN_BUCKET) — quantized padding length."""
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


# the axis a mesh of local devices shards rows over (parallel/distributed.py's)
MESH_AXIS = "dp"

_ROW_MASK_CACHE: Dict[Tuple[int, int, int], object] = {}
# concurrent serving queries share this module's caches (PR 8 discipline)
_CACHE_LOCK = threading.Lock()


def _row_sharded(mesh, host: np.ndarray):
    """A host array placed row-sharded over `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(host, NamedSharding(mesh, PartitionSpec(MESH_AXIS)))


def device_row_mask(n: int, bucket: int, mesh=None):
    """bool[bucket] with the first n rows set, cached on device; with `mesh`,
    row-sharded over it.

    The mask depends only on (n, bucket, mesh size); without the cache every
    dispatch re-uploads bucket bytes (8MB at bucket=8M).
    """
    key = (n, bucket, 1 if mesh is None else int(mesh.shape[MESH_AXIS]))
    with _CACHE_LOCK:
        cached = _ROW_MASK_CACHE.get(key)
    if cached is not None:
        return cached
    m = np.zeros(bucket, dtype=bool)
    m[:n] = True
    # h2d upload stays outside the lock
    dev_mask = jnp.asarray(m) if mesh is None else _row_sharded(mesh, m)
    with _CACHE_LOCK:
        _ROW_MASK_CACHE[key] = dev_mask
        while len(_ROW_MASK_CACHE) > 64:
            _ROW_MASK_CACHE.pop(next(iter(_ROW_MASK_CACHE)))
    return dev_mask


def mesh_row_mask(mesh, n: int, total: int):
    """device_row_mask over a mesh, under the name and argument order the
    repartition step calls it by."""
    return device_row_mask(n, total, mesh)


def shard_rows(mesh, arr: np.ndarray, total: int):
    """Row-shard one host array over the mesh (padded with zeros to total),
    with h2d attribution like Series.to_device."""
    from ..observability.metrics import registry

    if len(arr) < total:
        pad_shape = (total - len(arr),) + arr.shape[1:]
        arr = np.concatenate([arr, np.zeros(pad_shape, dtype=arr.dtype)])
    registry().inc("hbm_h2d_bytes", int(arr.nbytes))
    return _row_sharded(mesh, arr)


def _padded_codes(codes: np.ndarray, rows: int, cap: int) -> np.ndarray:
    """Dictionary codes as the device takes them: int32, padded to `cap` with
    code 0 (THE one place the padding-rows-are-code-0 invariant lives). Codes
    that fill their bucket go as they are, uncopied."""
    if rows == cap:
        return np.ascontiguousarray(codes, dtype=np.int32)
    padded = np.zeros(cap, dtype=np.int32)
    padded[:rows] = codes
    return padded


def _codes_slot(cap: int, mesh=None) -> tuple:
    """The residency slot key of a Series' dictionary code plane."""
    return ("dictcodes", cap) if mesh is None else \
        ("dictcodes", cap, "mesh", int(mesh.shape[MESH_AXIS]), MESH_AXIS)


def cached_dict_code_plane(src, codes, rows: int, cap: int, mesh=None):
    """Device plane of dictionary codes padded to `cap`, registered in the
    HBM residency manager anchored on the Series (grouped stages and the
    join stage share it). With `mesh` the plane is row-sharded over it, under
    a slot key of its own like a column plane's (Series.to_device_cached).
    `codes`: the rows' codes, or a callable that makes them, asked only where
    the plane has to be built."""
    from ..core.series import note_upload
    from ..device.residency import manager

    def build():
        padded = _padded_codes(codes() if callable(codes) else codes, rows, cap)
        note_upload(transfers=1, planes=1)
        return jnp.asarray(padded) if mesh is None \
            else shard_rows(mesh, padded, cap)

    # rebuild_rows: losing this plane re-runs the host dictionary factorize
    # over the source rows — weigh that in cost-ordered eviction
    return manager().get_or_build(src, _codes_slot(cap, mesh), (), build,
                                  rebuild_rows=rows)


def batch_planes(batch, names: Sequence[str], bucket: int, f32: bool,
                 mesh=None, key_codes: Sequence[Tuple[object, np.ndarray]] = ()):
    """What a dispatch over `batch` reads from the device: ({name: (values,
    validity)} for the columns `names`, padded to `bucket` rows; [a code
    plane for each (key Series, its rows' dictionary codes) of `key_codes`]).
    Every plane is looked up in the residency manager, and the device is
    brought what it lacks; how follows the lifetime of the data.

    Planes that stay (a resident table at first touch, a mesh placement) are
    built a column at a time, each with a validity plane of its own, by
    Series.to_device_cached and cached_dict_code_plane: the tiers that read
    them later take a column's own validity, and a table's padded host planes
    are never all held at once. Planes of a streamed morsel, which die with
    the query (ResidencyManager.pin_scope(transient=True)), cost a fixed price
    a transfer and not their bytes, so what the morsel lacks is padded first
    and moved in ONE transfer, the code planes with it; and a column without
    nulls uploads no validity plane at all: on the device its validity is
    the dispatch's row mask (rows valid, padding not), the array the program
    is passed as `row_mask` anyway. Each plane is still a slot of its own in
    the manager, pinned for the scope and weighed against the budget."""
    from ..core.series import note_upload
    from ..device.residency import manager

    n = batch.num_rows
    cols = [batch.get_column(name) for name in names]
    mgr = manager()
    if mesh is not None or not mgr.in_transient_scope():
        dcols = {name: s.to_device_cached(bucket, f32=f32, mesh=mesh)
                 for name, s in zip(names, cols)}
        return dcols, [cached_dict_code_plane(s, codes, n, bucket, mesh)
                       for s, codes in key_codes]

    def host_planes(i: int) -> tuple:
        """Slot i's planes on the host: (values,) or (values, validity) of a
        column, (codes,) of a key."""
        if i >= len(cols):
            return (_padded_codes(key_codes[i - len(cols)][1], n, bucket),)
        values, validity = cols[i]._padded_planes(bucket, f32, own_validity=False)
        return (values,) if validity is None else (values, validity)

    def build(missing: List[int]) -> list:
        with timed_span("device.upload", "device", counter="h2d_upload_us",
                        rows=n) as sp:
            with timed_span("device.upload.prepare", "host", counter="h2d_prepare_us",
                            part=True, rows=n, pad_to=bucket):
                host = [host_planes(i) for i in missing]
            flat = [p for planes in host for p in planes]
            sp.args.update(bytes=sum(int(p.nbytes) for p in flat),
                           planes=len(flat))
            placed = iter(jax.device_put(flat))
            row_mask = device_row_mask(n, bucket)
            out = []
            for i, planes in zip(missing, host):
                first = next(placed)
                if i >= len(cols):
                    out.append(first)
                else:
                    out.append((first, next(placed) if len(planes) == 2 else row_mask))
        note_upload(transfers=1, planes=len(flat))
        return out

    slots = [(s, s.plane_slot(bucket, f32), 0) for s in cols] \
        + [(s, _codes_slot(bucket), n) for s, _codes in key_codes]
    planes = mgr.get_or_build_many(slots, build)
    return dict(zip(names, planes)), planes[len(cols):]


def mesh_total(n: int, n_devices: int) -> int:
    """Global padded row count for an n-row batch sharded over n_devices:
    each shard pads to a power-of-two bucket (jit cache stays O(log rows))."""
    per = pad_bucket(max((n + n_devices - 1) // n_devices, 1))
    return per * n_devices


def local_mesh(n_devices: int):
    """The mesh of the first `n_devices` local devices a stage run shards its
    rows over, or None for one device: a mesh of one device is a chip."""
    if n_devices <= 1:
        return None
    from ..parallel.distributed import default_mesh

    return default_mesh(n_devices, MESH_AXIS)


def over_shards(fn: Callable, mesh, replicated_tail: int = 0) -> Callable:
    """`fn`, a stage program written for one chip's rows, run by every device
    of `mesh` on its own shard of the row-sharded arguments (the last
    `replicated_tail` arguments go to every shard whole). No collective: each
    output comes back with a leading axis of one entry a shard, [n_devices,
    ...], and the run's finalize combines the shards' partials on the host
    exactly as it combines the partials of successive batches."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def on_shard(*args):
        return jax.tree_util.tree_map(lambda x: x[None], fn(*args))

    def program(*args):
        n_rows = len(args) - replicated_tail
        # check_vma off: a scan's carry starts from constants and ends
        # depending on the shard
        return shard_map(on_shard, mesh=mesh,
                         in_specs=(P(MESH_AXIS),) * n_rows + (P(),) * replicated_tail,
                         out_specs=P(MESH_AXIS), check_vma=False)(*args)

    return program


def note_mesh_dispatch(n_devices: int) -> None:
    """One dispatch that spanned `n_devices` > 1 devices."""
    from ..observability.metrics import registry

    counters.bump("device_mesh_batches")
    counters.bump("device_mesh_shards", n_devices)
    counters.bump("mesh_dispatches")
    registry().set_gauge("mesh_devices_used", float(n_devices))


def _decompose_agg(op: str) -> List[str]:
    """Partial aggregations needed to compute `op` across batches/shards."""
    if op == "mean":
        return ["sum", "count"]
    if op in ("sum", "count", "min", "max"):
        return [op]
    raise ValueError(f"agg {op!r} has no device decomposition")


def _combine_partials(op: str, parts: List[Dict[str, Tuple[float, bool]]], name: str):
    """Combine per-batch partials on host into the final scalar (None if no valid rows)."""
    if op == "count":
        return int(sum(p[(name, "count")][0] for p in parts))
    vals = [p[(name, op if op != "mean" else "sum")] for p in parts]
    if op == "mean":
        total = sum(v for v, ok in vals if ok)
        cnt = sum(p[(name, "count")][0] for p in parts)
        return (total / cnt) if cnt else None
    good = [v for v, ok in vals if ok]
    if not good:
        return None
    if op == "sum":
        total = sum(good)
        if all(isinstance(v, int) for v in good):
            # an integer sum is int64 on the device and on the host engine,
            # where it wraps: partials that wrapped add up the same way
            total = (total + (1 << 63)) % (1 << 64) - (1 << 63)
        return total
    return min(good) if op == "min" else max(good)


def _shard_parts(res: Dict) -> List[Dict[str, Tuple[float, bool]]]:
    """One fetched dispatch result as the parts _combine_partials takes: one,
    or (a sharded dispatch, whose values carry a leading shard axis) one a
    shard."""
    first = next(iter(res.values()))[0]
    if np.ndim(first) == 0:
        return [{k: (v[0].item(), bool(v[1])) for k, v in res.items()}]
    return [{k: (v[0][s].item(), bool(v[1][s])) for k, v in res.items()}
            for s in range(len(first))]


def stage_structure(predicate: Optional[Expression], exprs) -> Tuple[tuple, tuple]:
    """(skeletons, literals) of a stage's expressions in slot order: the
    predicate's literals, then each aggregate's (device/residency.
    exprs_structure: the repo's one definition of a skeleton)."""
    from ..device.residency import exprs_structure

    return exprs_structure(([] if predicate is None else [predicate]) + list(exprs))


def stage_literals(predicate: Optional[Expression], exprs) -> tuple:
    """The literals of one execution, as a run takes them (start_run)."""
    return stage_structure(predicate, exprs)[1]


def note_program_trace() -> None:
    """Called inside the traced function of a stage program: counts the
    programs traced (a new shape, bucket or mesh width), never a launch."""
    counters.bump("device_stage_program_traces")


class _LiteralBinding:
    """What a run holds of its execution's literals: the (dtype-repr, value)
    pairs until the first dispatch, then the arrays the program takes them
    as, packed once for all of the run's dispatches. A launch carries them as
    host arrays (one small transfer inside the call). A filter-aggregate
    run's are the same at every dispatch, so one that dispatches a second
    time on a single device puts them there once and every later launch
    passes those: a run of one dispatch, the common one over a resident
    table, pays the one transfer in its call (an explicit device_put costs
    more than that: 0.7 against 0.46 ms on the chip's host, PERF.md section 6)
    and a run of many pays two. Not over a mesh: a program compiled for host
    arrays is traced and compiled again for arrays committed to the mesh.
    Nor where a launch's run values travel with them (a grouped run's row
    offset is another number at every launch)."""

    def __init__(self, slots: dev.LiteralSlots, literals: Sequence):
        self._slots = slots
        self._literals = tuple(literals)
        self._packed: Optional[tuple] = None
        self._launches = 0
        self._on_device: Optional[tuple] = None

    def args(self, run_values: Sequence[int] = (), mesh=None) -> tuple:
        """The program's literal argument for one launch (dev.LiteralSlots:
        one small array, whatever the number of literals), with the launch's
        run values where the program takes any."""
        slots = self._slots
        if self._packed is None:
            with profile_span("device.literals", "host", slots=slots.n_args):
                self._packed = slots.pack(self._literals)
        counters.bump("device_literal_args", slots.n_args)
        self._launches += 1
        host = slots.with_run_values(self._packed, run_values)
        if slots.run_values or mesh is not None or self._launches == 1:
            return host
        if self._on_device is None:
            with profile_span("device.literals", "host", slots=slots.n_args):
                self._on_device = jax.device_put(host)
        return self._on_device


class FilterAggStage:
    """Compiled scan→filter→ungrouped-agg program (the TPC-H Q6 shape).

    Immutable + shareable: holds only the expression structure and the jit
    cache. The program is compiled for the expressions' skeleton: a literal's
    value is an argument of it (dev.LiteralSlots), so the expressions kept
    here serve for their structure alone and no value of theirs is read.
    Call start_run(literals) for a fresh accumulator bound to one execution's
    values, feed it batches, then finalize().
    """

    def __init__(self, schema: Schema, predicate: Optional[Expression],
                 aggs: Sequence[Tuple[str, AggExpr]]):
        self.schema = schema
        self.predicate = predicate
        self.aggs = list(aggs)
        self._jitted: Dict[Tuple[int, int], Callable] = {}
        self._input_cols = self._referenced_columns()
        # float min/max must be EXACT (downstream equality joins against the
        # aggregate — TPC-H Q15 — would otherwise never match): such stages run
        # wholly in f64, trading the f32 fast path for bit-parity with host
        self._use_f64 = any(
            agg.op in ("min", "max") and agg.child.to_field(schema).dtype.is_floating()
            for _n, agg in self.aggs)
        self._slot_exprs = ([] if predicate is None else [predicate]) \
            + [agg.child for _n, agg in self.aggs]
        self.slots = dev.LiteralSlots(
            self._slot_exprs, jnp.float64 if self._use_f64 else jnp.float32)

    def _referenced_columns(self) -> List[str]:
        cols: List[str] = []
        exprs: List[Expression] = [a.child for _, a in self.aggs]
        if self.predicate is not None:
            exprs.append(self.predicate)
        for e in exprs:
            for c in e.referenced_columns():
                if c not in cols:
                    cols.append(c)
        return cols

    def start_run(self, literals: Sequence = (), mesh_devices: int = 1) -> "FilterAggRun":
        """A fresh accumulator for one execution, whose literal values are
        `literals` (stage_literals of that execution's predicate and
        aggregates); with `mesh_devices` > 1 its dispatches shard each
        batch's rows over that many local devices."""
        return FilterAggRun(self, literals, mesh_devices)

    def _build(self, mesh=None) -> Callable:
        """The program of one chip's rows; over `mesh`, every device runs it
        on its shard and the partials come back one a shard (over_shards).
        Its last argument is the literals' values (LiteralSlots.pack), whole
        on every shard."""
        fdt = jnp.float64 if self._use_f64 else jnp.float32
        slots = self.slots
        pred_fn, child_fns = compile_stage_exprs(self, fdt)
        agg_specs = []
        for (name, agg), child_fn in zip(self.aggs, child_fns):
            count_all = agg.op == "count" and agg.params.get("mode", "valid") == "all"
            agg_specs.append((name, agg.op, count_all, child_fn))

        def stage(cols: Dict[str, dev.DCol], row_mask, lit_args):
            note_program_trace()
            lits = slots.unpack(lit_args)
            if pred_fn is not None:
                pv, pm = pred_fn(cols, lits)
                keep = pv.astype(bool) & pm & row_mask
            else:
                keep = row_mask
            out = {}
            for name, op, count_all, child_fn in agg_specs:
                v, m = child_fn(cols, lits)
                m = dev._broadcast_valid(v, m) & keep
                if count_all:
                    m = dev._broadcast_valid(v, keep)
                for partial_op in _decompose_agg(op):
                    val, ok = dev.device_agg(partial_op, v, m)
                    out[(name, partial_op)] = (val, ok)
            return out

        return jax.jit(stage if mesh is None
                       else over_shards(stage, mesh, replicated_tail=1))

    def _jit_for(self, bucket: int, mesh_devices: int = 1) -> Callable:
        # one program serves every bucket (shapes differ per call; jit retraces
        # per shape internally) — keyed anyway so future bucket-specialized
        # programs stay cheap to add
        key = (bucket, mesh_devices)
        if key not in self._jitted:
            self._jitted[key] = self._build(local_mesh(mesh_devices))
        return self._jitted[key]


def compile_stage_exprs(stage, fdt) -> Tuple[Optional[Callable], List[Callable]]:
    """(predicate, [an aggregate's input, in stage.aggs order]) of a filter-
    or grouped-aggregate stage as fn(cols, lits): the skeletons, their literal
    slots numbered as stage.slots packs them."""
    fns = [dev.build_device_expr(e, stage.schema, float_dtype=fdt, first_slot=first)
           for e, first in zip(stage._slot_exprs, stage.slots.offsets)]
    return (None, fns) if stage.predicate is None else (fns[0], fns[1:])


class FilterAggRun:
    """Per-run accumulator for a FilterAggStage (fresh per query execution),
    bound to that execution's literal values.

    feed only *dispatches* (async); per-batch partial pytrees stay on device
    until finalize(), which fetches them all in ONE device_get — the d2h round
    trip is paid once per run, not once per batch.
    """

    def __init__(self, stage: FilterAggStage, literals: Sequence = (),
                 mesh_devices: int = 1):
        self.stage = stage
        self.mesh_devices = max(int(mesh_devices), 1)
        self.literals = _LiteralBinding(stage.slots, literals)
        self._device_partials: List[Dict] = []

    def _run(self, dcols: Dict[str, dev.DCol], n: int, bucket: int,
             mesh=None) -> None:
        """One dispatch over planes of `bucket` rows: on the default device,
        or with `mesh` (the planes row-sharded over it) on every device of it."""
        with profile_span("device.dispatch", "device", op="filter_agg",
                          rows=n, bucket=bucket):
            self._launch(dcols, n, bucket, mesh)

    def _launch(self, dcols: Dict[str, dev.DCol], n: int, bucket: int,
                mesh=None) -> None:
        """`_run` inside the caller's own `device.dispatch` span (a join run's
        dispatch holds its provisioning too)."""
        ndev = self.mesh_devices if mesh is not None else 1
        prog = self.stage._jit_for(bucket, ndev)
        mask = device_row_mask(n, bucket, mesh)
        lit_args = self.literals.args(mesh=mesh)
        with profile_span("device.launch", "device", op="filter_agg",
                          bucket=bucket, devices=ndev):
            res = prog(dcols, mask, lit_args)
        counters.bump("device_stage_batches")
        if ndev > 1:
            note_mesh_dispatch(ndev)
        self._device_partials.append(res)  # stays on device; fetched at finalize

    def feed(self, columns: Dict[str, Tuple[np.ndarray, np.ndarray]], n: int) -> None:
        bucket = pad_bucket(n)
        with profile_span("device.h2d", "device", rows=n, bucket=bucket):
            dcols = {}
            for name in self.stage._input_cols:
                vals, valid = columns[name]
                if vals.dtype == np.float64 and not self.stage._use_f64:
                    vals = vals.astype(np.float32)
                if len(vals) < bucket:
                    pad = bucket - len(vals)
                    vals = np.concatenate([vals, np.zeros(pad, dtype=vals.dtype)])
                    valid = np.concatenate([valid, np.zeros(pad, dtype=bool)])
                dcols[name] = (jnp.asarray(vals), jnp.asarray(valid))
        self._run(dcols, n, bucket)

    def feed_batch(self, batch) -> None:
        """Feed a host RecordBatch (referenced columns go to device, cached)."""
        n = batch.num_rows
        mesh = local_mesh(self.mesh_devices)
        bucket = pad_bucket(n) if mesh is None else mesh_total(n, self.mesh_devices)
        f32 = not self.stage._use_f64
        with profile_span("device.h2d", "device", rows=n, bucket=bucket):
            dcols, _codes = batch_planes(batch, self.stage._input_cols, bucket,
                                         f32, mesh)
        self._run(dcols, n, bucket, mesh)

    def finalize(self) -> Dict[str, Optional[float]]:
        with profile_span("stage.finalize", "host", op="filter_agg", groups=1):
            return self._finalize()

    def _finalize(self) -> Dict[str, Optional[float]]:
        with profile_span("device.d2h", "device", op="filter_agg",
                          batches=len(self._device_partials)):
            fetched = [part for res in jax.device_get(self._device_partials)  # one round trip
                       for part in _shard_parts(res)]
        out = {}
        for name, agg in self.stage.aggs:
            if not fetched:
                out[name] = 0 if agg.op == "count" else None
            else:
                out[name] = _combine_partials(agg.op, fetched, name)
        self._device_partials = []
        counters.bump("device_stage_runs")
        return out


def resident_rows_after(batch, prev=None) -> Optional[int]:
    """Rows of its table that lie after `batch`, where every column of it is
    a zero-copy view (Series.lineage) of the same rows of one longer column
    each, and, with `prev`, those rows start where `prev`'s end in the same
    columns: a morsel of a resident table, the next one of its run. None for
    anything else (a column that is its own root, columns that view other
    rows than their neighbours, a gap, another table)."""
    cols = batch.columns
    if not cols:
        return None
    before = None if prev is None else prev.columns
    if before is not None and len(before) != len(cols):
        return None
    start = after = None
    for i, s in enumerate(cols):
        root, off = s.lineage()
        if root is s:
            return None
        if start is None:
            start, after = off, len(root) - off - len(s)
        elif off != start or len(root) - off - len(s) != after:
            return None
        if before is not None:
            proot, poff = before[i].lineage()
            if proot is not root or poff + len(before[i]) != off:
                return None
    return after


class DispatchCoalescer:
    """Morsel→super-batch accumulator for one device stage run.

    Every compiled-program dispatch pays a fixed price (the dispatch round
    trip; not measured on this chip) and pads its rows to a
    power-of-two bucket, so a stream of small morsels pays the RTT per morsel
    and uploads mostly padding. The coalescer buffers incoming host
    RecordBatches and flushes ONE concatenated super-batch when either

    - pending rows reach ``target_rows`` (``batch_fill_target`` of the
      power-of-two bucket at the configured morsel size) — the bucket the
      flush pads to is then at least that full, or
    - a morsel ARRIVES after the oldest pending one has waited past the
      latency deadline (the coalescer is pull-driven: the deadline is checked
      at each add(), never by a timer thread — a stalled upstream flushes on
      the next arrival or at close()). On a flowing stream this keeps
      dispatch cadence bounded, with the H2D upload of super-batch k+1
      overlapping device compute of batch k (``feed`` must only *dispatch*;
      both agg run types defer every fetch to finalize, so nothing here
      blocks on a device result).

    One dispatch then covers N morsels and the RTT amortizes N-fold;
    finalize's d2h fetch is unchanged (packed aggregate rows ∝ groups, never
    the bucket). A single-batch flush hands the ORIGINAL batch through
    untouched, and contiguous morsels of one resident table concatenate to a
    zero-copy range of it, so device caches keyed on the rows a batch views
    (device_join series_keyed slots, resident-table repeat queries) still hit.

    ``resident_target_rows`` (a join run's, executor._make_coalescer): the
    length of a dispatch over a RESIDENT input that came through the
    pipeline as morsels. (A join whose fact is a select over ONE in-memory
    table never gets here: its driver cuts the table into ranges of this
    length itself, executor._feed_resident, and no morsel comes in. What
    still does is a fact whose plan holds more than that select, such as two
    concatenated tables: the Projects above them cut morsels, which are
    views.) What is pending is told from what it is, not from a setting:
    while every pending morsel is a zero-copy view of one table's rows, each
    starting where the one before ended (resident_rows_after), and the table
    has rows left to come, gluing them costs no copy and nothing waits on
    the next (a slice of a table that is there), so they are held until they
    reach this longer target, whatever the deadline, and one dispatch pays
    the host's look-ups and launches for all of them. A morsel that is no
    such view (a streamed or computed input, a gap, another table) puts the
    run back under ``target_rows``: there the concat copies.

    Counters (coarse, per flush — never per row): ``coalesce_morsels_in`` /
    ``dispatch_coalesced`` give the amortization factor (of what came in as
    morsels: a join's ranges of a table read directly count as
    ``join_resident_ranges``),
    ``bucket_fill_rows`` / ``bucket_capacity_rows`` the padding efficiency —
    the counter DELTAS are the per-query source of truth (they land in
    QueryEnd.metrics).
    The ``bucket_fill_ratio`` gauge is this coalescer's running fill /
    capacity, published for dashboard convenience — it is a process-wide
    last-writer-wins value, so with several coalesced stages or concurrent
    queries it shows the most recent run, not an aggregate.
    """

    def __init__(self, feed: Callable, target_rows: int, latency_s: float,
                 resident_target_rows: int = 0):
        self._feed = feed
        self._target = max(int(target_rows), 1)
        self._resident_target = max(int(resident_target_rows), self._target)
        self._latency = max(float(latency_s), 0.0)
        self._pending: List = []
        self._rows = 0
        self._oldest: Optional[float] = None
        # rows of their table left after the pending morsels, while those are
        # contiguous views of one resident table; None where they are not
        self._rows_after: Optional[int] = None
        # this RUN's fill accounting (the gauge must reflect the current
        # query, not a process-lifetime blend of every query's counters)
        self._filled = 0
        self._capacity = 0

    def add(self, batch) -> None:
        import time

        if batch.num_rows == 0:
            return
        counters.bump("coalesce_morsels_in")
        if self._resident_target > self._target:
            prev = self._pending[-1] if self._pending else None
            after = resident_rows_after(batch, prev)
            if after is None and self._rows_after is not None \
                    and self._rows >= self._target:
                # a resident run ends before this morsel: it goes as it is,
                # and the morsel may start the next one
                self.flush()
                after = resident_rows_after(batch)
            self._rows_after = after
        self._pending.append(batch)
        self._rows += batch.num_rows
        if self._rows_after:
            # a resident run with rows to come: held to the longer target
            if self._rows >= self._resident_target:
                self.flush()
            return
        now = time.perf_counter()
        if self._oldest is None:
            self._oldest = now
        if self._rows >= self._target or now - self._oldest >= self._latency:
            self.flush()

    def flush(self) -> None:
        if not self._pending:
            return
        morsels_in = len(self._pending)
        if morsels_in == 1:
            batch = self._pending[0]  # identity-preserving: device caches hit
        else:
            from ..core.recordbatch import RecordBatch

            batch = RecordBatch.concat(self._pending)
        self._pending = []
        self._rows = 0
        self._oldest = None
        self._rows_after = None
        with profile_span("device.coalesce_flush", "device",
                          morsels_in=morsels_in, rows=batch.num_rows,
                          fill_ratio=round(
                              batch.num_rows / pad_bucket(batch.num_rows), 4)):
            self._feed(batch)
        counters.bump("dispatch_coalesced")
        counters.bump("bucket_fill_rows", batch.num_rows)
        counters.bump("bucket_capacity_rows", pad_bucket(batch.num_rows))
        self._filled += batch.num_rows
        self._capacity += pad_bucket(batch.num_rows)
        from ..observability.metrics import registry

        registry().set_gauge("bucket_fill_ratio",
                             round(self._filled / self._capacity, 4))

    # stream exhausted: dispatch whatever is still pending
    close = flush


_STAGE_CACHE: Dict[tuple, FilterAggStage] = {}


def stage_cache_key(schema: Schema, predicate, exprs, static: tuple = (),
                    structure: Optional[Tuple[tuple, tuple]] = None) -> tuple:
    """What a compiled stage is cached under: the schema, the skeletons of
    the predicate and the aggregates (literals masked), each literal's dtype
    and whether it is null (`x < 5` and `x < 5.0` are two programs, a null
    literal stays a constant of its program), and whatever else of the
    expressions sets the program's structure (`static`). No literal's value:
    the cache is bounded by the query shapes a session uses. `structure` is
    stage_structure(predicate, exprs) where the caller has it already."""
    skels, lits = structure or stage_structure(predicate, exprs)
    return (
        tuple((f.name, repr(f.dtype)) for f in schema),
        predicate is not None,
        skels,
        tuple((dtype, value is None) for dtype, value in lits),
        static,
    )


def unwrap_aggs(agg_exprs: Sequence[Expression]) -> Optional[List[Tuple[str, AggExpr]]]:
    """[(output name, aggregate)] of a stage's aggregate expressions, their
    aliases taken off; None where one of them is no aggregate."""
    aggs: List[Tuple[str, AggExpr]] = []
    for e in agg_exprs:
        inner = e
        while isinstance(inner, Alias):
            inner = inner.child
        if not isinstance(inner, AggExpr):
            return None
        aggs.append((e.name(), inner))
    return aggs


def device_aggs(schema: Schema, agg_exprs: Sequence[Expression]
                ) -> Optional[List[Tuple[str, AggExpr]]]:
    """unwrap_aggs where every aggregate is one the device stages compute
    over an input they can evaluate; else None."""
    aggs = unwrap_aggs(agg_exprs)
    for _name, agg in aggs or ():
        if agg.op not in ("sum", "mean", "min", "max", "count"):
            return None
        if agg.op == "count" and agg.params.get("mode", "valid") == "null":
            return None
        if not dev.is_device_evaluable(agg.child, schema):
            return None
    return aggs


def bind_filter_agg_stage(schema: Schema, predicate: Optional[Expression],
                          agg_exprs: Sequence[Expression]
                          ) -> Optional[Tuple[FilterAggStage, tuple]]:
    """(stage, literals) for filter+ungrouped-agg if every expression
    qualifies: the compiled stage of the expressions' shape and the values of
    THESE expressions' literals, which a run of it takes (start_run). One walk
    of the expressions gives both.

    Stages (compiled programs only — no run state, no literal value) are
    cached by the (schema, predicate, aggs) skeletons, so runs of a query
    with whatever literal values reuse the jitted executables instead of
    retracing. Whoever needs an expression WITH its values (another tier's
    program, a selectivity) reads the query's own, never `stage.predicate`
    or `stage.aggs`, which are those of the first query of the shape.
    """
    structure = stage_structure(predicate, agg_exprs)
    key = stage_cache_key(schema, predicate, agg_exprs, structure=structure)
    stage = _STAGE_CACHE.get(key)
    if stage is None:
        if predicate is not None and not dev.is_device_evaluable(predicate, schema):
            return None
        aggs = device_aggs(schema, agg_exprs)
        if aggs is None:
            return None
        stage = FilterAggStage(schema, predicate, aggs)
        with _CACHE_LOCK:
            _STAGE_CACHE[key] = stage
    return stage, structure[1]


def try_build_filter_agg_stage(schema: Schema, predicate: Optional[Expression],
                               agg_exprs: Sequence[Expression]) -> Optional[FilterAggStage]:
    """bind_filter_agg_stage's stage alone: for whoever asks whether the
    device can run the shape, or prices it."""
    bound = bind_filter_agg_stage(schema, predicate, agg_exprs)
    return None if bound is None else bound[0]
