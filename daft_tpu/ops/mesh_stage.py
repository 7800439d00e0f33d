"""The mesh join tier: star joins sharded across the local chips.

The filter-aggregate and grouped-aggregate stages have no classes here: a
`FilterAggRun` or `GroupedAggRun` started with ``mesh_devices`` > 1
(ops/stage.py, ops/grouped_stage.py) shards each batch's f32 planes,
validity, dictionary code planes and row mask over the 'dp' axis of a mesh of
local devices and runs the single chip's own program on every shard
(``stage.over_shards``): predicate, aggregate children and group codes are
evaluated on the device inside the program's loop, nothing row-wide happens
on the host, and no collective runs. Each shard returns its partial table
(q1: [8, 11]); finalize fetches them in its one device_get and combines them
in f64 on the host exactly as it combines the partial tables of successive
batches, the first-row index carrying each shard's row offset so the groups'
order is the single chip's. A mesh of one device is a chip.

A star join spans the chips the same way (ops/device_join.py with
``mesh_devices`` > 1, the path the cell `tpch_sf30_mesh4.joins` measures): the
single chip's join dispatch on every shard of the fact, a fused TopN's
run-wide tables a set a chip and combined on the chips at the run's end.

What is left in this module is the OLDER fused join tier (MeshJoin*), which
the executor reaches only for the shapes that path declines
(device_join.sharded_join_reason: group codes that need a host factorization
of every batch, a TopN whose ids hold for one batch only, a forced Pallas
hash probe): fact morsels row-sharded over the mesh, dim planes replicated as
resident HBM slots, the probe a local gather, the cross-shard reduce one ICI
collective (parallel/distributed.py kernels), behind the same ``feed_batch()
/ finalize()`` contract. Its planes keep their native dtypes (f64 floats,
int64 sums): no cell of the benchmark runs it (ROADMAP D1).

Residency: sharded column planes go through ``Series.to_device_cached(mesh=)``
so repeat queries hit resident shards with zero re-upload, and they
participate in the executor's pin scopes like any single-chip plane.

Zero-overhead contract: nothing imports this module unless the executor's
join tier gate actually selects the mesh.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..expressions.expressions import AggExpr, Alias, Expression
from ..observability.metrics import registry
from ..observability.runtime_stats import profile_span
from . import counters
from .grouped_stage import DeviceFallback, _pad_groups
from .stage import (MESH_AXIS as _MESH_AXIS, _combine_partials,
                    _decompose_agg, mesh_row_mask, mesh_total, pad_bucket,
                    shard_rows as _shard_np)
from ..parallel.distributed import (default_mesh, sharded_gather_step,
                                    sharded_groupby_step,
                                    sharded_join_agg_step,
                                    sharded_join_grouped_stage_step,
                                    sharded_join_ungrouped_stage_step)

# concurrent serving queries share this module's caches (PR 8 discipline)
_CACHE_LOCK = threading.Lock()

def _replicate_np(mesh, arr: np.ndarray) -> jax.Array:
    """Broadcast one host array to every device (replicated dim planes for
    the mesh join feed's local-gather probe)."""
    registry().inc("hbm_h2d_bytes", int(arr.nbytes))
    return jax.device_put(arr, NamedSharding(mesh, P()))


def _note_dispatch(n_devices: int) -> None:
    counters.bump("mesh_dispatches")
    registry().set_gauge("mesh_devices_used", float(n_devices))


def _merge_partial(op: str, a: tuple, b: tuple) -> tuple:
    av, aok = a
    bv, bok = b
    if op in ("sum", "count"):
        if op == "count":
            return (av + bv, True)
        if not aok:
            return b
        if not bok:
            return a
        return (av + bv, True)
    # min / max
    if not aok:
        return b
    if not bok:
        return a
    return (min(av, bv) if op == "min" else max(av, bv), True)


def _column(slot_map: Dict[int, tuple], g: int) -> Tuple[np.ndarray, np.ndarray]:
    """Dense (values, valid) arrays from a slot->(value, ok) accumulator."""
    vals = [slot_map.get(i, (0, False))[0] for i in range(g)]
    valid = np.array([slot_map.get(i, (0, False))[1] for i in range(g)],
                     dtype=bool)
    return np.asarray(vals), valid


# ---- sharded join fact feed ----------------------------------------------------------


# ---- mesh join tier: MeshJoinStage behind the feed/finalize contract ----------------
#
# The executor's device_join path (execution/executor.py _run_device_join)
# selects this tier when the cost model's mesh arm wins (or mesh_devices
# forces it): fact morsels shard over the local mesh, dim planes replicate as
# resident HBM slots, the DispatchCoalescer feeds super-batches dispatch-only,
# and finalize pays ONE d2h. The tier divides the join+agg compute by the mesh
# width, so a star shape the host wins against a single chip can still win here.


class _MeshJoinCodes:
    """Host factorize of the joined group keys for one fact batch (cached via
    series_keyed): dense first-occurrence codes, lazy key tuples, and host
    order-rank planes for TopN group-key sorting. Dense codes double as the
    kernel's segment ids AND the group-table row index, so rank planes align
    with table rows by construction."""

    def __init__(self, codes: np.ndarray, num_groups: int, key_series,
                 first_idx: np.ndarray):
        self.codes = codes              # int64[n] dense first-occurrence ids
        self.num_groups = num_groups
        self.key_series = key_series    # gathered to fact length
        self.first_idx = first_idx
        self._rank_planes: Dict[tuple, tuple] = {}

    def rows_for(self, gids) -> List[tuple]:
        gids = np.asarray(gids, dtype=np.int64)
        take = self.first_idx[gids]
        return list(zip(*[s.take(take).to_pylist() for s in self.key_series])) \
            if len(gids) else []

    def rank_plane(self, key_index: int, cap: int):
        """(f64[cap], bool[cap]) numpy ORDER-RANK plane for one group-key
        column, indexed by dense code — exact for any dtype (strings sort in
        python), nulls rank last with a separate validity plane. Mirrors
        device_join._FactorizedCodes.rank_plane."""
        ck = (key_index, cap)
        if ck not in self._rank_planes:
            s_first = self.key_series[key_index].take(self.first_idx)
            n = len(s_first)
            valid = s_first.validity_numpy()
            rank = np.zeros(n, dtype=np.int64)
            dense = None
            try:
                vals = s_first.to_numpy()
                if vals.dtype.kind in "biufM":
                    _u, inv = np.unique(vals[valid], return_inverse=True)
                    dense = inv
            except Exception:  # lint: ignore[broad-except] -- falls back to python comparison
                dense = None
            if dense is None:
                arr = s_first.to_pylist()
                vv = [arr[i] for i in range(n) if valid[i]]
                order = {v: r for r, v in enumerate(sorted(set(vv)))}
                dense = np.asarray([order[v] for v in vv], dtype=np.int64)
            rank[valid] = dense
            plane = np.full(cap, float(cap), dtype=np.float64)
            plane[:n] = rank.astype(np.float64)
            vplane = np.zeros(cap, dtype=bool)
            vplane[:n] = valid
            self._rank_planes[ck] = (plane, vplane)
        return self._rank_planes[ck]


class MeshJoinStage:
    """Structural metadata + compiled-program cache for the mesh join tier.

    Shared by the grouped/ungrouped/TopN runs: the column feed plan (which
    joined columns ride which layout — fact planes row-sharded, dim planes
    replicated), the per-aggregate kernel slot decomposition (mean -> sum +
    count so per-batch tables merge exactly), and the memoized jitted steps
    (jax.jit caches on function identity, so the traced closures must be
    held here, not rebuilt per run).
    """

    def __init__(self, spec, predicate: Optional[Expression], groupby,
                 aggs: Sequence[Tuple[str, AggExpr]], n_devices: int,
                 grouped: bool):
        self.spec = spec
        self.predicate = predicate      # spec.predicate — join_ok is kernel-side
        self.groupby = list(groupby or [])
        self.aggs = list(aggs)
        self.n_devices = int(n_devices)
        self.grouped = grouped
        self._dim_index = {d.name: i for i, d in enumerate(spec.dims)}

        cols: List[str] = []
        exprs: List[Expression] = [a.child for _n, a in self.aggs]
        if predicate is not None:
            exprs.append(predicate)
        for e in exprs:
            for c in e.referenced_columns():
                if c not in cols and c != "__join_ok__":
                    cols.append(c)
        self.col_specs: List[Tuple[str, int]] = []
        for c in cols:
            side = spec.col_side.get(c)
            if side == "fact":
                self.col_specs.append((c, -1))
            else:
                self.col_specs.append((c, self._dim_index[side]))

        # grouped kernel layout: one (partial_op, count_all, child) slot per
        # decomposed partial, with per-agg slot indices for finalization
        self._kernel_slots: List[Tuple[str, bool, Expression]] = []
        self._agg_slots: List[List[Tuple[str, int]]] = []
        for _name, agg in self.aggs:
            count_all = (agg.op == "count"
                         and agg.params.get("mode", "valid") == "all")
            slots = []
            for partial in _decompose_agg(agg.op):
                slots.append((partial, len(self._kernel_slots)))
                self._kernel_slots.append(
                    (partial, count_all and partial == "count", agg.child))
            self._agg_slots.append(slots)
        self._steps: Dict[tuple, object] = {}

    def _ungrouped_step(self, mesh):
        key = ("u", mesh)
        with _CACHE_LOCK:
            step = self._steps.get(key)
        if step is None:
            agg_specs = []
            for name, agg in self.aggs:
                count_all = (agg.op == "count"
                             and agg.params.get("mode", "valid") == "all")
                agg_specs.append((name, agg.op, count_all, agg.child))
            step = sharded_join_ungrouped_stage_step(
                mesh, self.spec.schema, self.predicate, self.col_specs,
                agg_specs, len(self.spec.dims))
            with _CACHE_LOCK:
                self._steps[key] = step
        return step

    def _grouped_step(self, mesh, cap: int):
        key = ("g", mesh, cap)
        with _CACHE_LOCK:
            step = self._steps.get(key)
        if step is None:
            step = sharded_join_grouped_stage_step(
                mesh, self.spec.schema, self.predicate, self.col_specs,
                self._kernel_slots, cap, len(self.spec.dims))
            with _CACHE_LOCK:
                self._steps[key] = step
        return step


# stage-or-None per (spec structure, mesh width); None verdicts cache too
_JOIN_STAGE_CACHE: Dict[tuple, Optional[MeshJoinStage]] = {}
_UNSET = object()


def try_build_mesh_join_stage(spec, n_devices: int) -> Optional[MeshJoinStage]:
    """MeshJoinStage for a captured JoinAggSpec, or None when a needed plane
    cannot ride the mesh layout (a dim value column whose dtype has no device
    representation). Group keys are unconstrained — they factorize on host.
    Both verdicts cache per spec structure + mesh width: a repeated query
    over an unbuildable spec must not re-run build_join_stage + the dtype
    walk every execution."""
    from .device_join import build_join_stage

    key = (repr(spec.predicate),
           tuple(repr(g) for g in spec.groupby),
           tuple(repr(a) for a in spec.aggregations),
           tuple((d.key_col, d.parent) for d in spec.dims),
           int(n_devices))
    with _CACHE_LOCK:
        cached = _JOIN_STAGE_CACHE.get(key, _UNSET)
    if cached is not _UNSET:
        return cached
    # the shared stage says only whether the shape qualifies: it is kept
    # under the skeleton and its expressions are the first query's. The mesh
    # steps compile the values in (parallel/distributed.py), so the
    # predicate, the keys and the aggregates are THIS spec's own.
    stage, grouped = build_join_stage(spec)
    mesh_stage: Optional[MeshJoinStage] = None
    if stage is not None:
        from .stage import unwrap_aggs

        mesh_stage = MeshJoinStage(spec, spec.predicate,
                                   spec.groupby if grouped else None,
                                   unwrap_aggs(spec.aggregations), n_devices, grouped)
        for c, _src in mesh_stage.col_specs:
            dt = spec.schema[c].dtype
            if not (dt.is_numeric() or dt.is_boolean() or dt.is_temporal()):
                mesh_stage = None
                break
    with _CACHE_LOCK:
        _JOIN_STAGE_CACHE[key] = mesh_stage
        while len(_JOIN_STAGE_CACHE) > 64:
            _JOIN_STAGE_CACHE.pop(next(iter(_JOIN_STAGE_CACHE)))
    return mesh_stage


def _mesh_dim_visible(ctx, d) -> Optional[np.ndarray]:
    """Combined visibility for ALL of one dim's filters, evaluated on host
    (dims are small; host eval is exact for every dtype — the mesh tier
    folds visibility into the index planes instead of shipping per-dim
    visibility planes). None = no filters. Cached per (filters, series)."""
    from .device_join import series_keyed
    from ..device.residency import exprs_structure

    filters = ctx._dev_filters[d.name] + ctx._host_filters[d.name]
    if not filters:
        return None
    from ..expressions.eval import eval_expression

    b = ctx.batches[d.name]
    deps = tuple(b.get_column(c) for f in filters
                 for c in f.referenced_columns())
    anchor = deps[0] if deps else b.get_column(b.column_names()[0])

    def build():
        vis = np.ones(b.num_rows, dtype=bool)
        for f in filters:
            m = eval_expression(b, f)
            vis &= np.asarray(m.to_numpy(), dtype=bool) & m.validity_numpy()
        return vis

    skels, lits = exprs_structure(filters)
    return series_keyed(anchor, ("meshvis",) + skels, deps, build,
                        literals=lits)


def _mesh_effective_idx(ctx, batch, d, n: int) -> np.ndarray:
    """Visibility-folded fact->dim index plane (np): a row whose dim match is
    filtered out reads as a join miss (idx -1). Cached on the probe Series
    with the raw idx + visibility arrays as identity deps."""
    from .device_join import series_keyed

    idx = ctx.indices_for(batch)[d.name]
    vis = _mesh_dim_visible(ctx, d)
    if vis is None:
        return idx
    anchor = ctx._probe_anchor(batch, d)

    def build():
        safe = np.clip(idx, 0, max(len(vis) - 1, 0))
        ok = (idx >= 0) & (vis[safe] if len(vis) else False)
        return np.where(ok, idx, -1).astype(np.int32)

    return series_keyed(anchor, ("mjvidx", d.key_col, d.parent), (idx, vis),
                        build, rebuild_rows=n)


def _mesh_idx_plane(ctx, batch, d, idx_np: np.ndarray, n: int, total: int,
                    mesh) -> jax.Array:
    """Row-sharded int64 index plane (padding rows read as miss), resident in
    the manager on the probe Series — repeat queries re-shard nothing. The
    dim's filter STRUCTURE is part of the slot key (visibility folds into
    the indices, so a filtered and an unfiltered query over the same dim
    must hold SEPARATE planes — one shared slot would thrash on alternating
    queries); filter literals live in the entry, so varying-literal repeats
    rebuild one slot in place instead of growing HBM."""
    from ..device.residency import exprs_structure
    from .device_join import series_keyed

    anchor = ctx._probe_anchor(batch, d)
    fskels, flits = exprs_structure(
        ctx._dev_filters[d.name] + ctx._host_filters[d.name])

    def build():
        padded = np.full(total, -1, dtype=np.int64)
        padded[:n] = idx_np
        registry().inc("hbm_h2d_bytes", int(padded.nbytes))
        return jax.device_put(padded, NamedSharding(mesh, P(_MESH_AXIS)))

    return series_keyed(
        anchor, ("mjdidx", d.key_col, d.parent, total,
                 int(mesh.shape[_MESH_AXIS]), fskels),
        (idx_np,), build, literals=flits, rebuild_rows=n)


def _mesh_pallas_idx_plane(ctx, batch, d, n: int, total: int, mesh):
    """Row-sharded int64 index plane probed IN-KERNEL on each shard: fact key
    digit planes (sharded) matched against the replicated VMEM dim hash
    table via ops/pallas_kernels.hash_probe_index under shard_map — the host
    hash probe and the index-plane upload both disappear. Returns None when
    the ctx's Pallas probe gate keeps the host tier (mode off, chained dim)
    or when the dim carries filters (the host path folds visibility INTO the
    indices; the kernel probes raw keys). A kernel that does not lower
    raises."""
    from .device_join import series_keyed
    from ..core.kernels.encoding import _common_key_dtype

    if _mesh_dim_visible(ctx, d) is not None:
        return None
    interp = ctx._pallas_probe_gate(batch, d)
    if interp is None:
        return None
    from . import pallas_kernels as pk

    dim_b = ctx.batches[d.name]
    kdt = _common_key_dtype(
        ctx._probe_dtype(batch, d), dim_b.schema[d.key_col].dtype)
    tbl = ctx._pallas_probe_table_host(d, kdt)
    anchor = ctx._probe_anchor(batch, d)
    key_series = dim_b.get_column(d.key_col)
    ndev = int(mesh.shape[_MESH_AXIS])

    def build():
        from ..parallel.distributed import _shard_map

        vals, valid = ctx._probe_values(batch, d, {}, kdt)
        pv = np.full(total, pk.PROBE_SENTINEL, dtype=np.int64)
        pm = np.zeros(total, dtype=bool)
        pv[:n] = vals
        pm[:n] = valid
        hi = (pv >> 32).astype(np.int32)
        lo = (pv & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        registry().inc("hbm_h2d_bytes", int(hi.nbytes) + int(lo.nbytes))
        sharded = NamedSharding(mesh, P(_MESH_AXIS))
        fh = jax.device_put(hi, sharded)
        fl = jax.device_put(lo, sharded)
        rep = NamedSharding(mesh, P())
        th = jax.device_put(np.asarray(tbl[0]), rep)
        tl = jax.device_put(np.asarray(tbl[1]), rep)
        tr = jax.device_put(np.asarray(tbl[2]), rep)

        def local(fh, fl, th, tl, tr):
            return pk.hash_probe_index(
                fh, fl, th, tl, tr, interpret=interp).astype(jnp.int64)

        step = jax.jit(_shard_map(
            local, mesh,
            (P(_MESH_AXIS), P(_MESH_AXIS), P(), P(), P()),
            P(_MESH_AXIS)))
        out = step(fh, fl, th, tl, tr)
        counters.bump("pallas_probe_dispatches")
        return out

    return series_keyed(
        anchor, ("mjpdidx", d.key_col, d.parent, total, ndev),
        (key_series, tbl), build, rebuild_rows=n)


def _mesh_fact_membership(ctx, batch, syn: str, n: int, total: int, mesh):
    """Sharded bool (plane, valid) for a fact string membership predicate:
    dict codes compared on host (null rows invalid — SQL three-valued),
    sharded upload cached with the match values as slot literals."""
    from .device_join import series_keyed

    colname, values = ctx.spec.fact_synthetic[syn]
    s = batch.get_column(colname)

    def build():
        codes, vals, _k = s.dict_codes()
        match = np.array([i for i, v in enumerate(vals) if v in values],
                         dtype=np.int64)
        nulls = np.array([i for i, v in enumerate(vals) if v is None],
                         dtype=np.int64)
        plane = np.isin(codes, match)
        valid = ~np.isin(codes, nulls) if len(nulls) \
            else np.ones(n, dtype=bool)
        pp = np.zeros(total, dtype=bool)
        pp[:n] = plane
        pv = np.zeros(total, dtype=bool)
        pv[:n] = valid
        registry().inc("hbm_h2d_bytes", int(pp.nbytes) + int(pv.nbytes))
        sharding = NamedSharding(mesh, P(_MESH_AXIS))
        return (jax.device_put(pp, sharding), jax.device_put(pv, sharding))

    return series_keyed(s, ("mjfmem", syn, total,
                            int(mesh.shape[_MESH_AXIS])),
                        (), build, literals=values)


class _MeshJoinRunBase:
    """Shared feed plumbing for the mesh join runs: per-batch host index
    prep + sharded/replicated plane assembly. Feeds only dispatch; every
    result stays on device until finalize."""

    def __init__(self, stage: MeshJoinStage, ctx):
        self.stage = stage
        self.ctx = ctx
        self._pending: List = []

    def _planes(self, batch, n: int, total: int, mesh):
        """(idx_planes tuple, flat col planes) for one fact batch."""
        stage = self.stage
        ctx = self.ctx
        idxs_dev = []
        with profile_span("device.mesh_h2d", "device", op="mesh_join",
                          rows=n, total=total, devices=stage.n_devices):
            for d in stage.spec.dims:
                plane = _mesh_pallas_idx_plane(ctx, batch, d, n, total, mesh)
                if plane is None:
                    eff = _mesh_effective_idx(ctx, batch, d, n)
                    plane = _mesh_idx_plane(ctx, batch, d, eff, n,
                                            total, mesh)
                idxs_dev.append(plane)
            flat: List[jax.Array] = []
            for name, src in stage.col_specs:
                if src < 0:
                    if name in stage.spec.fact_synthetic:
                        dv, dm = _mesh_fact_membership(ctx, batch, name, n,
                                                       total, mesh)
                    else:
                        dv, dm = batch.get_column(name).to_device_cached(
                            total, f32=False, mesh=mesh)
                else:
                    side = stage.spec.dims[src].name
                    s = ctx._dim_source(side, name)
                    dv, dm = s.to_device_cached(
                        pad_bucket(max(len(s), 1)), f32=False, mesh=mesh,
                        replicated=True)
                flat += [dv, dm]
        return tuple(idxs_dev), flat


class MeshJoinUngroupedRun(_MeshJoinRunBase):
    """Star join + ungrouped aggregate sharded over the mesh: ONE fused
    program per super-batch (gather + predicate + partial aggs + psum),
    partials replicated on device until the single finalize device_get.
    Same finalize contract as DeviceJoinUngroupedRun ({name: scalar})."""

    def feed_batch(self, batch) -> None:
        n = batch.num_rows
        if n == 0:
            return
        stage = self.stage
        mesh = default_mesh(stage.n_devices)
        total = mesh_total(n, stage.n_devices)
        idxs, flat = self._planes(batch, n, total, mesh)
        step = stage._ungrouped_step(mesh)
        with profile_span("device.mesh_dispatch", "device",
                          op="mesh_join_agg", rows=n,
                          devices=stage.n_devices):
            out = step(mesh_row_mask(mesh, n, total), idxs, *flat)
        _note_dispatch(stage.n_devices)
        counters.bump("device_join_batches")
        self._pending.append(out)

    def finalize(self) -> Dict[str, Optional[float]]:
        pending, self._pending = self._pending, []
        with profile_span("device.mesh_d2h", "device", op="mesh_join_agg",
                          batches=len(pending)):
            fetched = [
                {k: (v[0].item(), bool(v[1])) for k, v in res.items()}
                for res in jax.device_get(pending)  # one round trip
            ]
        out = {}
        for name, agg in self.stage.aggs:
            if not fetched:
                out[name] = 0 if agg.op == "count" else None
            else:
                out[name] = _combine_partials(agg.op, fetched, name)
        counters.bump("device_stage_runs")
        counters.bump("mesh_join_runs")
        return out


# full-table-fetch ceiling for the non-TopN grouped mesh path — the finalize
# d2h is cap-sized, same budget as DeviceJoinGroupedRun.max_segments
MESH_JOIN_MAX_SEGMENTS = 1 << 16
# TopN fetches K rows; cap is bounded by per-device HBM for the group tables
MESH_TOPN_MAX_SEGMENTS = 1 << 22


class MeshJoinGroupedRun(_MeshJoinRunBase):
    """Star join + grouped aggregate sharded over the mesh.

    Group keys factorize on HOST over the static join indices (dense
    first-occurrence codes — the true joined group count, any key dtype,
    null keys their own group); the fused program gathers dim planes,
    applies the predicate, segment-reduces per shard into a dense-code
    table and merges tables with one psum/pmin/pmax per partial over ICI.
    Finalize fetches every batch's tables in one device_get and merges by
    key tuple in first-occurrence stream order — the exact contract of
    GroupedAggRun.finalize, so the executor assembles all tiers identically.
    """

    max_segments = MESH_JOIN_MAX_SEGMENTS

    def feed_batch(self, batch) -> None:
        n = batch.num_rows
        if n == 0:
            return
        stage = self.stage
        mesh = default_mesh(stage.n_devices)
        total = mesh_total(n, stage.n_devices)
        codes = self._group_codes(batch, n)
        cap = _pad_groups(max(codes.num_groups, 1))
        if cap > self.max_segments:
            raise DeviceFallback(
                f"mesh joined group count {cap} exceeds the "
                f"{'TopN' if self.max_segments > MESH_JOIN_MAX_SEGMENTS else 'full-fetch'} "
                f"ceiling {self.max_segments}")
        idxs, flat = self._planes(batch, n, total, mesh)
        dcodes = self._codes_plane(batch, codes, n, total, mesh)
        step = stage._grouped_step(mesh, cap)
        with profile_span("device.mesh_dispatch", "device",
                          op="mesh_join_grouped", rows=n, groups_cap=cap,
                          devices=stage.n_devices):
            out = step(dcodes, mesh_row_mask(mesh, n, total), idxs, *flat)
        _note_dispatch(stage.n_devices)
        counters.bump("device_join_batches")
        self._pending.append((out, codes))

    def _group_codes(self, batch, n: int) -> _MeshJoinCodes:
        """Host factorize of the joined group keys (cached on the first key
        Series via series_keyed — reps over a resident table factorize
        once). Join-miss rows factorize under a miss marker so they can
        never collide with a real group; the kernel masks them anyway, so
        their phantom groups finalize with rows == 0 and drop."""
        from .device_join import series_keyed
        from ..core.series import Series

        ctx = self.ctx
        spec = self.stage.spec
        idxs = ctx.indices_for(batch)
        key_cols = []
        for g in self.stage.groupby:
            node = g.child if isinstance(g, Alias) else g
            name = node._name
            side = spec.col_side.get(name)
            if side == "fact":
                key_cols.append(("fact", batch.get_column(name)))
            else:
                src = ctx.syn_series[side][name] if name.startswith("__syn_") \
                    else ctx.batches[side].get_column(name)
                key_cols.append((side, src))
        anchor = ctx.fact_anchor(batch)
        deps = tuple(s for _side, s in key_cols) + tuple(
            idxs[side] for side, _s in key_cols if side != "fact")

        def build():
            from ..core.kernels.groupby import make_groups

            series = []
            miss_marks = []
            for side, s in key_cols:
                if side == "fact":
                    series.append(s)
                elif len(s) == 0:
                    series.append(Series.from_pylist([None] * n, s.name,
                                                     dtype=s.dtype))
                    miss_marks.append(np.ones(n, dtype=bool))
                else:
                    idx = idxs[side]
                    safe = np.clip(idx, 0, len(s) - 1)
                    series.append(s.take(safe))
                    miss_marks.append(idx < 0)
            if miss_marks:
                miss = miss_marks[0]
                for m in miss_marks[1:]:
                    miss = miss | m
                series.append(Series.from_numpy(
                    miss.astype(np.int8), "__miss__"))
            first_idx, group_ids, _counts = make_groups(series)
            return _MeshJoinCodes(group_ids.astype(np.int64, copy=False),
                                  len(first_idx), series[:len(key_cols)],
                                  first_idx)

        return series_keyed(
            anchor,
            ("mjfact",) + tuple(repr(g) for g in self.stage.groupby),
            deps, build)

    def _codes_plane(self, batch, codes: _MeshJoinCodes, n: int, total: int,
                     mesh) -> jax.Array:
        from .device_join import series_keyed

        anchor = codes.key_series[0]

        def build():
            padded = np.full(total, -1, dtype=np.int64)
            padded[:n] = codes.codes
            registry().inc("hbm_h2d_bytes", int(padded.nbytes))
            return jax.device_put(padded, NamedSharding(mesh, P(_MESH_AXIS)))

        return series_keyed(
            anchor,
            ("mjcplane", total, int(mesh.shape[_MESH_AXIS]))
            + tuple(repr(g) for g in self.stage.groupby),
            (codes,), build, rebuild_rows=n)

    def finalize(self):
        """(key_rows, agg_results) in first-occurrence stream order."""
        stage = self.stage
        pending, self._pending = self._pending, []
        if not pending:
            counters.bump("device_stage_runs")
            counters.bump("mesh_join_runs")
            return [], [(np.empty(0), np.empty(0, dtype=bool))
                        for _ in stage.aggs]
        with profile_span("device.mesh_d2h", "device", op="mesh_join_grouped",
                          batches=len(pending)):
            fetched = jax.device_get([out for out, _ in pending])

        key_slot: Dict[tuple, int] = {}
        key_order: List[tuple] = []
        acc: List[Dict[int, tuple]] = [{} for _ in stage._kernel_slots]
        for (rows_tbl, overflow, results), (_out, codes) in zip(
                fetched, pending):
            if bool(np.asarray(overflow)):
                raise DeviceFallback(
                    "mesh join: group codes escaped the exact host capacity")
            present = np.flatnonzero(np.asarray(rows_tbl) > 0)
            keys = codes.rows_for(present)
            for local, key in zip(present, keys):
                slot = key_slot.get(key)
                if slot is None:
                    slot = len(key_order)
                    key_slot[key] = slot
                    key_order.append(key)
                for j, (op, _ca, _child) in enumerate(stage._kernel_slots):
                    val = np.asarray(results[j][0])[local]
                    ok = bool(np.asarray(results[j][1])[local])
                    cur = acc[j].get(slot)
                    if cur is None:
                        acc[j][slot] = (val, ok)
                    else:
                        acc[j][slot] = _merge_partial(op, cur, (val, ok))

        g = len(key_order)
        out_results = []
        for (_name, agg), slots in zip(stage.aggs, stage._agg_slots):
            op = agg.op
            if op == "mean":
                sums = _column(acc[slots[0][1]], g)
                cnts = _column(acc[slots[1][1]], g)
                cnt_v = np.maximum(cnts[0].astype(np.float64), 1.0)
                vals = sums[0].astype(np.float64) / cnt_v
                valid = cnts[0].astype(np.int64) > 0
                out_results.append((vals, valid))
            else:
                vals, valid = _column(acc[slots[0][1]], g)
                if op == "count":
                    valid = np.ones(g, dtype=bool)
                out_results.append((vals, valid))
        counters.bump("device_stage_runs")
        counters.bump("mesh_join_runs")
        return key_order, out_results


class MeshJoinTopNRun(MeshJoinGroupedRun):
    """Join + grouped aggregate + ORDER BY + LIMIT on the mesh: the merged
    group tables are REPLICATED device arrays, so the multi-key lax.sort
    runs where they already live and only the K winners' rows ever d2h —
    the mesh sibling of DeviceJoinTopNRun, which is what keeps
    orderkey-cardinality TopN joins (q3/q10) off the full-table fetch."""

    max_segments = MESH_TOPN_MAX_SEGMENTS

    def __init__(self, stage: MeshJoinStage, ctx, topn):
        super().__init__(stage, ctx)
        self.topn = topn

    def feed_batch(self, batch) -> None:
        if self._pending and batch.num_rows:
            raise DeviceFallback(
                "mesh TopN path requires a single fact batch")
        super().feed_batch(batch)

    def _topn_agg_plane(self, agg_idx: int, results):
        """(f64 value plane, valid plane) for one aggregation, computed on
        device from the kernel slot tables (f64 is ample for ordering)."""
        _name, agg = self.stage.aggs[agg_idx]
        slots = dict(self.stage._agg_slots[agg_idx])
        if agg.op == "count":
            v = results[slots["count"]][0].astype(jnp.float64)
            return v, jnp.ones(v.shape, dtype=bool)
        if agg.op == "mean":
            s = results[slots["sum"]][0].astype(jnp.float64)
            c = results[slots["count"]][0].astype(jnp.float64)
            return s / jnp.maximum(c, 1.0), c > 0
        v, ok = results[slots[agg.op]]
        return v.astype(jnp.float64), ok

    def finalize_topn(self):
        """(key_rows, agg_results) for the K winners, in final output order."""
        stage = self.stage
        pending, self._pending = self._pending, []
        if not pending:
            counters.bump("device_stage_runs")
            return [], [(np.empty(0), np.empty(0, dtype=bool))
                        for _ in stage.aggs]
        (rows_tbl, overflow, results), codes = pending[0]
        cap = int(rows_tbl.shape[0])
        k_eff = min(self.topn.offset + self.topn.limit, cap)
        mesh = default_mesh(stage.n_devices)
        repl = NamedSharding(mesh, P())

        present = rows_tbl > 0
        operands = [jnp.where(present, 0.0, 1.0).astype(jnp.float32)]
        for kind, idx_k, desc, nf in self.topn.keys:
            if kind == "agg":
                v, valid = self._topn_agg_plane(idx_k, results)
            else:
                plane, vplane = codes.rank_plane(idx_k, cap)
                v = jax.device_put(plane, repl)
                valid = jax.device_put(vplane, repl) & present
            if desc:
                v = -v
            v = jnp.where(valid, v, -jnp.inf if nf else jnp.inf)
            operands.append(v)
        gid = jnp.arange(cap, dtype=jnp.int32)
        sorted_ops = jax.lax.sort(tuple(operands) + (gid,),
                                  num_keys=len(operands) + 1)
        top = sorted_ops[-1][:k_eff]
        fetch = (overflow, top, rows_tbl[top],
                 tuple((v[top], ok[top]) for v, ok in results))
        with profile_span("device.mesh_d2h", "device", op="mesh_join_topn",
                          rows=int(k_eff)):
            ovf, gids, rows_top, slot_rows = jax.device_get(fetch)
        if bool(np.asarray(ovf)):
            raise DeviceFallback(
                "mesh join: group codes escaped the exact host capacity")
        counters.bump("device_stage_runs")
        counters.bump("mesh_join_runs")
        counters.bump("device_topn_runs")

        off = self.topn.offset
        keep = np.asarray(rows_top)[off:] > 0
        gids = np.asarray(gids)[off:][keep]
        slot_rows = [(np.asarray(v)[off:][keep], np.asarray(ok)[off:][keep])
                     for v, ok in slot_rows]
        g = len(gids)
        out_results = []
        for (_name, agg), slots in zip(stage.aggs, stage._agg_slots):
            op = agg.op
            sl = dict(slots)
            if op == "mean":
                s = slot_rows[sl["sum"]][0].astype(np.float64)
                c = slot_rows[sl["count"]][0].astype(np.float64)
                out_results.append((s / np.maximum(c, 1.0), c > 0))
            elif op == "count":
                out_results.append((slot_rows[sl["count"]][0],
                                    np.ones(g, dtype=bool)))
            else:
                out_results.append(slot_rows[sl[op]])
        return codes.rows_for(gids), out_results


def mesh_join_ungrouped_agg(mesh, n_rows: int,
                            idx_planes: Sequence[np.ndarray],
                            value_cols: Sequence[Tuple[np.ndarray, np.ndarray]],
                            specs: Sequence[Tuple[str, int]]):
    """Sharded star-join fact feed, ungrouped: fact rows row-sharded, dim
    value planes replicated, probe = local gather, reduce = psum/pmin/pmax
    over ICI (exact for int64 sums). specs[i] = (op, src) with src the dim
    index plane the i-th aggregate gathers through, or -1 for a fact-local
    column. Returns {i: python value or None} (None = no valid rows).
    """
    n_dev = int(mesh.shape[_MESH_AXIS])
    total = mesh_total(n_rows, n_dev)
    didx = tuple(_shard_np(mesh, ix.astype(np.int64), total)
                 for ix in idx_planes)
    flat: List[jax.Array] = []
    for (op, src), (vals, valid) in zip(specs, value_cols):
        if src >= 0:
            flat += [_replicate_np(mesh, vals), _replicate_np(mesh, valid)]
        else:
            flat += [_shard_np(mesh, vals, total),
                     _shard_np(mesh, valid, total)]
    step = sharded_join_agg_step(mesh, specs, len(idx_planes))
    out = step(mesh_row_mask(mesh, n_rows, total), didx, *flat)
    _note_dispatch(n_dev)
    fetched = {k: (v[0].item(), bool(v[1]))
               for k, v in jax.device_get(out).items()}
    results = {}
    for i, (op, _src) in enumerate(specs):
        parts = [{(str(i), p): fetched[(i, p)] for p in _decompose_agg(op)}]
        results[i] = _combine_partials(op, parts, str(i))
    return results


def mesh_join_grouped_agg(mesh, n_rows: int, idx: np.ndarray,
                          dim_codes: np.ndarray,
                          value_cols: Sequence[Tuple[np.ndarray, np.ndarray, int]],
                          ops: Sequence[str], num_codes: int):
    """Sharded star-join fact feed, grouped by a dim attribute: the dim's
    dense group-code plane is replicated, gathered to fact rows through the
    sharded index plane (local probe), then the exact sharded groupby merges
    per-shard tables with one all_gather. value_cols[i] = (vals, valid, src)
    with src = 0 to gather the plane from the dim, -1 for fact-local.
    Rows with idx < 0 (no dim match) drop — inner-join semantics.
    Returns (group_codes int64[g], [(values, valid)] per op).
    """
    n_dev = int(mesh.shape[_MESH_AXIS])
    total = mesh_total(n_rows, n_dev)
    didx = _shard_np(mesh, idx.astype(np.int64), total)
    row_mask = mesh_row_mask(mesh, n_rows, total)

    gather_cols = [(dim_codes.astype(np.int64), np.ones(len(dim_codes), bool))]
    for vals, valid, src in value_cols:
        if src >= 0:
            gather_cols.append((vals, valid))
    gstep = sharded_gather_step(mesh, len(gather_cols))
    gflat: List[jax.Array] = []
    for vals, valid in gather_cols:
        gflat += [_replicate_np(mesh, vals), _replicate_np(mesh, valid)]
    gathered = gstep(didx, row_mask, *gflat)
    _note_dispatch(n_dev)

    keys, key_valid = gathered[0]
    flat: List[jax.Array] = []
    gi = 1
    for (vals, valid, src) in value_cols:
        if src >= 0:
            dv, dm = gathered[gi]
            gi += 1
        else:
            dv = _shard_np(mesh, vals, total)
            dm = _shard_np(mesh, valid, total)
        flat += [dv, dm]
    cap = _pad_groups(num_codes + 1)
    step = sharded_groupby_step(mesh, list(ops), cap)
    gk, gv, overflow, results = step(keys, key_valid, *flat)
    _note_dispatch(n_dev)
    if bool(np.asarray(overflow)):
        raise DeviceFallback("mesh join feed: group table overflow")
    keep = np.asarray(gv)
    gk = np.asarray(gk)[keep]
    out_cols = [(np.asarray(v)[keep], np.asarray(ok)[keep])
                for v, ok in results]
    return gk, out_cols
