"""Device join+aggregate fusion: star-schema joins as gather networks on TPU.

Reference contrast: the reference executes joins as host probe tables
(src/daft-local-execution/src/join/build.rs + probe.rs) and then aggregates.
A TPU-native engine inverts the design: for the analytics shape — one large
fact relation inner-joined to smaller dims on unique keys, then aggregated —
the join never materializes. Each dim becomes

    per-fact-row index  idx_d[i] = dim row whose key equals the fact row's
                        key value (-1 = no match), a STATIC host-computed
                        int32 array cached per (fact column, dim key) pair

and every dim column the query touches is one device GATHER dim_col[idx_d].
Per query, only the dim filters' literal values change, and they are
arguments of a compiled program (_JoinContext.verdict_plane): the fact
columns, the join indices, the dims' packed planes and the planes their
filters read are resident in HBM and hold no value.
The aggregation then rides the existing MXU segment-reduction machinery
(ops/grouped_stage.py) / ungrouped stage (ops/stage.py) unchanged — the fused
program is filter -> gather-join -> segment-reduce in one XLA computation
chain with ONE d2h fetch per query.

Capture (plan/physical.py translate calls try_capture_join_agg):
    Aggregate <- [Project]* <- [Filter]* <- inner-join tree
flattened to relations + equality conditions; the largest relation is the
fact, the rest must connect as a tree of unique-key dims (extra equality
edges become device predicates). Dim-only subexpressions are hoisted to
host-evaluated synthetic dim columns (strings, LIKE, is_in — dims are small),
so the device only ever sees numeric/bool planes.

Fallback: any shape this file cannot prove safe returns None at capture time,
or raises DeviceFallback before the first dispatch at run time — the executor
then runs the untouched host plan (exact same semantics, tested side-by-side).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from ..core.kernels.encoding import _common_key_dtype, canonical_key_values
from ..datatype import DataType, Field
from ..device.residency import expr_structure, exprs_structure
from ..observability.runtime_stats import profile_span
from ..expressions.expressions import (AggExpr, Alias, BinaryOp, ColumnRef,
                                       Expression, IsIn, Literal)
from ..schema import Schema
from . import counters
from . import device_eval as dev
from .grouped_stage import (DeviceFallback, GroupedAggRun, GroupedAggStage,
                            MAX_MATMUL_SEGMENTS, _Decode,
                            _pad_groups, count_reduce,
                            try_build_grouped_agg_stage)
from .stage import (MESH_AXIS, FilterAggRun, FilterAggStage, batch_planes,
                    cached_dict_code_plane, device_row_mask, local_mesh,
                    mesh_total, note_mesh_dispatch, note_program_trace,
                    pad_bucket, shard_rows)


# ======================================================================================
# capture: logical plan -> JoinAggSpec
# ======================================================================================


@dataclass
class DimSpec:
    base: object                     # LOGICAL plan of the dim without trailing filters
    filters: List[Expression]        # dim-local filters (their values: arguments of the visibility program)
    key_col: str                     # dim-side unique join key column
    parent: Tuple[str, str]          # ("fact"|dim_name, column) providing probe values
    name: str                        # dim identifier (for caches/debug)
    synthetic: List[Tuple[str, Expression]] = field(default_factory=list)
    used_cols: List[str] = field(default_factory=list)


@dataclass
class JoinAggSpec:
    fact: object                     # LOGICAL plan of the fact side (filters stripped)
    dims: List[DimSpec]              # topologically ordered (parents first)
    schema: Schema                   # joined schema: fact + dim (+synthetic) columns
    col_side: Dict[str, str]         # column -> "fact" | dim name
    predicate: Optional[Expression]
    groupby: List[Expression]
    aggregations: List[Expression]
    # fact-side string membership predicates lowered to dictionary-code
    # comparisons: syn name -> (fact column, match values). The codes plane is
    # resident (Series dict codes); only the tiny match set is per-query.
    fact_synthetic: Dict[str, Tuple[str, tuple]] = field(default_factory=dict)


def _split_conjuncts(e: Expression) -> List[Expression]:
    if isinstance(e, BinaryOp) and e.op == "and":
        return _split_conjuncts(e.left) + _split_conjuncts(e.right)
    return [e]


def _flatten_joins(node) -> Optional[Tuple[list, list]]:
    """Flatten a tree of plain inner equi-joins into (relations, conditions);
    conditions are (left_col_name, right_col_name) pairs. Bails on renames or
    merged keys (capture requires globally unique column names)."""
    from ..plan import logical as lp

    rels: list = []
    conds: list = []

    def walk(n) -> bool:
        if isinstance(n, lp.Join) and n.how == "inner" and n.strategy is None \
                and not n.null_equals_null:
            merged, rename = n.output_naming()
            if merged or rename:
                return False
            if len(n.left_on) != len(n.right_on) or not n.left_on:
                return False
            pairs = []
            for le, re_ in zip(n.left_on, n.right_on):
                le = le.child if isinstance(le, Alias) else le
                re_ = re_.child if isinstance(re_, Alias) else re_
                if not (isinstance(le, ColumnRef) and isinstance(re_, ColumnRef)):
                    return False
                pairs.append((le._name, re_._name))
            if not walk(n.left):
                return False
            conds.extend(pairs)
            if not walk(n.right):
                return False
            return True
        rels.append(n)
        return True

    if not walk(node):
        return None
    names: set = set()
    for r in rels:
        cols = r.schema.column_names()
        if names & set(cols):
            return None  # duplicated names across relations: provenance ambiguous
        names |= set(cols)
    return rels, conds


def try_capture_join_agg(agg_plan) -> Optional[JoinAggSpec]:
    """Match Aggregate <- [Project]* <- [Filter]* <- inner-join tree into a
    JoinAggSpec, or None when the shape isn't provably safe."""
    from ..plan import logical as lp
    from ..plan.stats import estimate_rows

    groupby = list(agg_plan.groupby)
    aggs = list(agg_plan.aggregations)
    conjuncts: List[Expression] = []
    src = agg_plan.input

    def substitute(exprs: List[Expression], proj: List[Expression]) -> Optional[List[Expression]]:
        mapping: Dict[str, Expression] = {}
        for p in proj:
            inner = p.child if isinstance(p, Alias) else p
            mapping[p.name()] = inner
        out = []
        for e in exprs:
            def rw(node):
                if isinstance(node, ColumnRef) and node._name in mapping:
                    return mapping[node._name]
                return None

            ne = e.transform(rw)
            if ne.name() != e.name():
                ne = ne.alias(e.name())  # projections define output names
            out.append(ne)
        return out

    for _ in range(16):
        if isinstance(src, lp.Project):
            all_exprs = groupby + aggs + conjuncts
            new = substitute(all_exprs, src.projection)
            if new is None:
                return None
            groupby = new[:len(groupby)]
            aggs = new[len(groupby):len(groupby) + len(aggs)]
            conjuncts = new[len(groupby) + len(aggs):]
            src = src.input
        elif isinstance(src, lp.Filter):
            conjuncts.extend(_split_conjuncts(src.predicate))
            src = src.input
        else:
            break

    flat = _flatten_joins(src)
    if flat is None:
        return None
    rels, conds = flat
    if len(rels) < 2:
        return None

    # strip trailing filters per relation
    def strip_filters(n) -> Tuple[object, List[Expression]]:
        fs: List[Expression] = []
        while isinstance(n, lp.Filter):
            fs.extend(_split_conjuncts(n.predicate))
            n = n.input
        return n, fs

    # fact = the largest relation by UNFILTERED base size: the fact is the
    # relation that streams through the gather program, and dims must carry
    # unique keys — a heavily filtered fact is still the fact
    sizes = [estimate_rows(strip_filters(r)[0]) for r in rels]
    if any(s is None for s in sizes):
        return None
    fact_i = int(np.argmax(sizes))

    fact_base, fact_filters = strip_filters(rels[fact_i])
    conjuncts.extend(fact_filters)

    # column availability comes from the filter-stripped bases: keep-carrying
    # Filters narrow their output schema, but their predicates are lifted into
    # device conjuncts here, so the base's full column set is what's in play
    col_side: Dict[str, str] = {c: "fact" for c in fact_base.schema.column_names()}
    available = dict(col_side)

    # grow the dim tree from the fact over unique-key edges
    pending = [(i, r) for i, r in enumerate(rels) if i != fact_i]
    remaining_conds = list(conds)
    dims: List[DimSpec] = []
    progress = True
    while pending and progress:
        progress = False
        for pi, (ri, rel) in enumerate(pending):
            rel_cols = set(strip_filters(rel)[0].schema.column_names())
            edge = None
            for ci, (a, b) in enumerate(remaining_conds):
                if a in available and b in rel_cols:
                    edge = (ci, a, b)
                    break
                if b in available and a in rel_cols:
                    edge = (ci, b, a)
                    break
            if edge is None:
                continue
            ci, avail_col, dim_key = edge
            remaining_conds.pop(ci)
            base, filters = strip_filters(rel)
            name = f"d{len(dims)}"
            dims.append(DimSpec(base=base, filters=filters, key_col=dim_key,
                                parent=(available[avail_col], avail_col), name=name))
            for c in base.schema.column_names():
                col_side[c] = name
                available[c] = name
            pending.pop(pi)
            progress = True
            break
    if pending:
        return None
    # leftover equality edges: both sides now available -> device predicates.
    # Only integer-like columns: device eq runs on f32 planes, which would
    # corrupt float join-key semantics (f32 false-equals; NaN/-0.0 diverge
    # from the host's bit-canonicalized key equality)
    def _intish(colname: str) -> bool:
        for r in rels:
            rs = strip_filters(r)[0].schema
            if colname in rs.column_names():
                dt = rs[colname].dtype
                return (dt.is_integer() or dt.is_temporal() or dt.is_boolean())
        return False

    for a, b in remaining_conds:
        if a not in available or b not in available:
            return None
        if not (_intish(a) and _intish(b)):
            return None
        conjuncts.append(BinaryOp("eq", ColumnRef(a), ColumnRef(b)))

    # joined schema over original (globally unique) names — filter-stripped
    # bases again, so lifted predicates' columns stay resolvable
    fields: List[Field] = list(fact_base.schema.fields)
    for i, r in enumerate(rels):
        if i != fact_i:
            fields.extend(strip_filters(r)[0].schema.fields)
    schema = Schema(fields)

    # hoist maximal single-dim subexpressions to synthetic host-evaluated
    # dim columns (strings/likes/is_in run on the small dim side)
    dim_by_name = {d.name: d for d in dims}
    counter = [0]
    fact_synthetic: Dict[str, Tuple[str, tuple]] = {}

    def fact_string_membership(node) -> Optional[Tuple[str, tuple]]:
        """(fact string column, literal match values) for `col == lit` /
        `col.is_in([lits])` over a fact string column, else None."""
        if isinstance(node, IsIn) and isinstance(node.child, ColumnRef):
            cn = node.child._name
            if col_side.get(cn) == "fact" and schema[cn].dtype.is_string() \
                    and all(isinstance(it, Literal) for it in node.items):
                return cn, tuple(it.value for it in node.items)
        if isinstance(node, BinaryOp) and node.op == "eq":
            for a, b in ((node.left, node.right), (node.right, node.left)):
                if isinstance(a, ColumnRef) and isinstance(b, Literal) \
                        and col_side.get(a._name) == "fact" \
                        and schema[a._name].dtype.is_string() \
                        and isinstance(b.value, str):
                    return a._name, (b.value,)
        return None

    def hoist(e: Expression) -> Optional[Expression]:
        def side_of(expr) -> Optional[str]:
            sides = {col_side.get(c) for c in expr.referenced_columns()}
            sides.discard(None)
            if len(sides) == 1:
                return next(iter(sides))
            return None

        def rw(node):
            if isinstance(node, (ColumnRef, Alias)) or isinstance(node, AggExpr):
                return None
            s = side_of(node)
            if s is None or s == "fact":
                fsm = fact_string_membership(node)
                if fsm is not None:
                    syn = f"__fsyn_{counter[0]}__"
                    counter[0] += 1
                    fact_synthetic[syn] = fsm
                    return ColumnRef(syn)
                return None
            if not node.referenced_columns():
                return None
            dim_schema = dim_by_name[s].base.schema
            if dev.is_device_evaluable(node, schema) and all(
                    schema[c].dtype.is_numeric() or schema[c].dtype.is_boolean()
                    or schema[c].dtype.is_temporal()
                    for c in node.referenced_columns()):
                return None  # numeric dim math can gather its leaves directly
            try:
                node.to_field(dim_schema)
            except Exception:  # lint: ignore[broad-except] -- untypeable = not capturable
                return None
            syn = f"__syn_{s}_{counter[0]}__"
            counter[0] += 1
            dim_by_name[s].synthetic.append((syn, node))
            return ColumnRef(syn)

        return e.transform(rw)

    def hoist_named(e: Expression) -> Expression:
        out = hoist(e)
        if out.name() != e.name():
            out = out.alias(e.name())  # output column names are part of the schema
        return out

    groupby = [hoist_named(g) for g in groupby]
    aggs = [hoist_named(a) for a in aggs]
    conjuncts = [hoist(c) for c in conjuncts]

    # register synthetic columns in schema + provenance
    for d in dims:
        for syn, expr in d.synthetic:
            f = expr.to_field(d.base.schema)
            fields.append(Field(syn, f.dtype))
            col_side[syn] = d.name
    for syn in fact_synthetic:
        fields.append(Field(syn, DataType.bool()))
        col_side[syn] = "fact"
    schema = Schema(fields)

    # ---- eligibility over the joined schema --------------------------------------
    for g in groupby:
        node = g.child if isinstance(g, Alias) else g
        if not isinstance(node, ColumnRef):
            return None
    predicate = None
    for c in conjuncts:
        if not dev.is_device_evaluable(c, schema):
            return None
        predicate = c if predicate is None else (predicate & c)
    # dim join keys + parent columns must canonicalize to ints (num kind)
    for d in dims:
        kdt = d.base.schema[d.key_col].dtype
        if not ((kdt.is_numeric() and not kdt.is_decimal()) or kdt.is_temporal()):
            return None
    # record per-dim referenced columns (gather planes)
    referenced = set()
    for e in ([predicate] if predicate is not None else []) + groupby + aggs:
        referenced |= set(e.referenced_columns())
    for d in dims:
        d.used_cols = [c for c in referenced
                       if col_side.get(c) == d.name
                       and not c.startswith("__syn_")]
    # float min/max must be exact (see FilterAggStage._use_f64); the gather
    # path feeds f32 planes, so such stages stay on host
    for a in aggs:
        inner = a
        while isinstance(inner, Alias):
            inner = inner.child
        if isinstance(inner, AggExpr) and inner.op in ("min", "max") \
                and inner.child.to_field(schema).dtype.is_floating():
            return None
    spec = JoinAggSpec(fact=fact_base, dims=dims, schema=schema, col_side=col_side,
                       predicate=predicate, groupby=groupby, aggregations=aggs,
                       fact_synthetic=fact_synthetic)
    # eligibility == buildability of the REAL stage (with the join-ok plane)
    stage, _grouped = build_join_stage(spec)
    if stage is None:
        return None
    return spec


# ======================================================================================
# runtime: static join indices + gathered device columns
# ======================================================================================


def series_keyed(anchor, key: tuple, deps: tuple, build, literals=None,
                 rebuild_rows: int = 0):
    """Cache ``build()`` in the process-wide HBM residency manager, anchored
    on the identity of `anchor` Series' DATA under `key`, valid while every
    Series in `deps` holds the same data, every other object in `deps` is
    IDENTICAL (a strong ref is held in the entry, so a freed object can never
    alias a new one via id() reuse) and `literals` compare EQUAL.

    This is the identity spine of the join runtime. Per-rep plan objects, the
    RecordBatches a pruning Project re-creates and the morsel views a
    pipeline cuts a resident table into on every query are all transient;
    what is stable is the resident column and the rows of it a view covers.
    The manager therefore identifies a Series by its lineage
    (device/residency.py data_identity: root column, row offset, length; a
    column that is no view is its own root), so join indices, padded device
    index planes, visibility planes and synthetic dim columns survive across
    queries/reps whichever object presents the rows. Without it every rep
    re-probes the dims and re-uploads fact-bucket-sized arrays. `take`,
    `filter`, `cast` and computed columns make new data: their slots live
    and die with the object.

    `literals` carries the per-query predicate literal values for slots whose
    `key` is the filter STRUCTURE: varying-literal queries then reuse ONE slot
    per query shape (rebuilt in place on a literal change, counted as
    `hbm_literal_rebuilds`) instead of growing HBM by one entry per distinct
    literal. What is left of them on the join path: a dim filter the HOST
    evaluates (host_visible, _host_ok_plane), a synthetic dim column and a
    fact string membership plane. A dim filter the device evaluates has no
    such slot: its values are arguments of a program
    (_JoinContext.verdict_plane), and a pack holds none. The manager accounts
    every entry's device bytes and evicts LRU under DAFT_TPU_HBM_BUDGET.
    """
    from ..device.residency import manager

    return manager().get_or_build(anchor, key, deps, build, literals=literals,
                                  rebuild_rows=rebuild_rows)


class UniqueKeyLookup(NamedTuple):
    """What a probe of one dimension's unique key needs, made ONCE a key
    column (unique_key_lookup) and kept with it: the uniqueness check, the
    key range and the table are all as long as the dimension, and a fact of
    458 batches asks 458 times (at SF10 each build was 1.4 s over `orders`'
    15 M keys: 640 s of q3's first execution)."""
    form: str              # "dense": table[key - lo]; "hash": the native map; "sorted": binary search
    lo: int
    hi: int
    table: Optional[np.ndarray]   # dense: int32 row of each key of [lo, hi], -1 where absent
    slots: Optional[tuple]        # hash: native_i64_map_build's (slots, cap)
    keys: Optional[np.ndarray]    # sorted: the valid keys in ascending order
    rows: np.ndarray              # hash: row of each valid key; sorted: rows in `keys`' order


def _build_key_lookup(s, target_dtype) -> UniqueKeyLookup:
    from ..native import native_i64_map_build

    if s.dtype != target_dtype:
        s = s.cast(target_dtype)
    kind, vals, valid = canonical_key_values(s)
    if kind not in ("num",):
        raise DeviceFallback(f"dim key {s.name!r} is not an integer-like key")
    vals = vals.astype(np.int64, copy=False)
    vv = vals[valid] if not valid.all() else vals
    if len(np.unique(vv)) != len(vv):
        raise DeviceFallback(f"dim key {s.name!r} is not unique")
    lo = int(vv.min()) if len(vv) else 0
    hi = int(vv.max()) if len(vv) else -1
    domain = hi - lo + 1
    rows = np.nonzero(valid)[0]
    if 0 < domain <= max(4096, 8 * max(len(vv), 1)):
        table = np.full(domain, -1, dtype=np.int32)
        table[vv - lo] = rows
        return UniqueKeyLookup("dense", lo, hi, table, None, None, rows)
    hm = native_i64_map_build(vv)
    if hm is not None:
        return UniqueKeyLookup("hash", lo, hi, None, hm, None, rows)
    order = np.argsort(vv, kind="stable")
    return UniqueKeyLookup("sorted", lo, hi, None, None, vv[order], rows[order])


def unique_key_lookup(dim_key_series, target_dtype) -> UniqueKeyLookup:
    """The probe structure of a dimension's key column, resident with the
    column (a deps-free slot: found again by the column's content). Raises
    DeviceFallback when the keys are not unique (a join would multiply rows)
    or are not integer-encodable."""
    return series_keyed(dim_key_series, ("uklookup", repr(target_dtype)), (),
                        lambda: _build_key_lookup(dim_key_series, target_dtype))


def unique_key_index(dim_key_series, probe_vals: np.ndarray,
                     probe_valid: np.ndarray, target_dtype) -> np.ndarray:
    """idx[i] = dim row with key == probe value i, else -1. The work here is
    as long as the probe; what is as long as the dimension is
    unique_key_lookup's, once."""
    from ..native import native_i64_map_lookup

    lk = unique_key_lookup(dim_key_series, target_dtype)
    pv = probe_vals.astype(np.int64, copy=False)
    if lk.form == "dense":
        safe = np.clip(pv - lk.lo, 0, len(lk.table) - 1)
        idx = np.where((pv >= lk.lo) & (pv <= lk.hi), lk.table[safe], -1)
    elif lk.form == "hash":
        pos = native_i64_map_lookup(lk.slots[0], lk.slots[1], pv)
        idx = np.where(pos >= 0, lk.rows[np.clip(pos, 0, len(lk.rows) - 1)], -1) \
            if len(lk.rows) else np.full(len(pv), -1, dtype=np.int64)
    elif len(lk.keys):
        pos = np.minimum(np.searchsorted(lk.keys, pv), len(lk.keys) - 1)
        idx = np.where(lk.keys[pos] == pv, lk.rows[pos], -1)
    else:
        idx = np.full(len(pv), -1, dtype=np.int64)
    idx = np.where(probe_valid, idx, -1)
    return idx.astype(np.int32, copy=False)


@jax.jit
def _gather_col(arr, arr_valid, idx):
    safe = jnp.clip(idx, 0, arr.shape[0] - 1)
    ok = idx >= 0
    return arr[safe], arr_valid[safe] & ok


# the chip's lane width: a one-row window is read as rows of this many values
_LANES = 128


def _gather_rows(mat, idx, windowed: bool = False, segment: int = 0):
    """One gather of a packed [P, N] dim matrix along its MINOR axis — the
    per-batch join, traced inside the provisioning program. The pack is
    TRANSPOSED ([planes, rows], not [rows, planes]) because TPU tiled layouts
    pad the minor dimension to 128 lanes: a [64M, 5] gather output would
    materialize as [64M, 128] — 32GB — and OOM (observed at SF10); [5, 64M]
    pads only the 5 to 8 sublanes.

    `windowed`: the batch's matched indices lie within len(idx) of each other
    (_index_span; a fact ordered by this dim's key), so the gather reads a
    [P, len(idx)] slice of the matrix that starts at the least of them, found
    here from `idx` itself. On the chip a gather's time follows the length of
    what it gathers FROM: 131,072 indices out of [6, 2^24] take 3.58 ms, out
    of a [6, 2^17] slice of it 0.43 (PERF.md, PR 39). The same rows' same
    values: a miss reads row 0, as the plain form's clip makes it.

    `segment`: indices longer than that (a dispatch of DISPATCH_SEGMENTS
    morsels of a resident fact) are gathered a segment at a time, each from
    the window its own indices point into, and the pieces glued. Unrolled,
    not a loop: as a loop's invariant operand the chip's compiler lays the
    WHOLE pack out for the gather inside, a copy of it a dispatch (34 GB for
    `orders` at SF30: tests/test_chip_compile.py caught it with no chip)."""
    n = mat.shape[1]
    if not windowed:
        return mat[:, jnp.clip(idx, 0, n - 1)]
    if 0 < segment < idx.shape[0]:
        return jnp.concatenate([_gather_rows(mat, idx[at:at + segment], True)
                                for at in range(0, idx.shape[0], segment)], axis=1)
    w = idx.shape[0]
    hit = idx >= 0
    lo = jnp.clip(jnp.min(jnp.where(hit, idx, jnp.int32(n))), 0, n - w)
    win = jax.lax.dynamic_slice(mat, (jnp.int32(0), lo), (mat.shape[0], w))
    rel = jnp.clip(idx - lo, 0, w - 1)
    if mat.shape[0] == 1:       # (a bucket is a power of two from 512 up: whole rows of lanes)
        # a gather of single values is bound by its index count there (0.93 ms
        # for these indices whatever it reads from), a gather of rows is not:
        # the window as rows of one lane width, each index's row (0.20 ms),
        # then its lane of that row (0.10). The lane is kept by its bits, a sum
        # over the other lanes' zeros in int32, so a NaN or -0.0 stays itself
        taken = jax.lax.bitcast_convert_type(
            win.reshape(w // _LANES, _LANES)[rel // _LANES], jnp.int32)
        mine = (rel % _LANES)[:, None] == jnp.arange(_LANES, dtype=jnp.int32)
        rows = jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(mine, taken, jnp.int32(0)), axis=1, dtype=jnp.int32),
            jnp.float32)[None, :]
    else:
        rows = win[:, rel]
    return jnp.where(hit, rows, mat[:, :1])


# dimension rows _pack_lines lays as lines at a time (a power of two)
_LINES_PIECE = 1 << 17
# the longest pack a dispatch gathers from whole as the [P, N] matrix it is:
# up to here the chip's compiler moves the whole operand into fast memory
# ahead of the gather (`cross_program_prefetch` in the compiled text: 64 MB
# yes, 96 MB no, tests/test_chip_compile.py), where a gather costs what it
# costs out of a window (PERF.md, PR 39); a longer pack is read from HBM, P
# values a row apart an index, and is laid as lines instead
_FAST_PACK_BYTES = 64 << 20


def _lane_width(rows: int) -> int:
    """Lanes a dimension row's `rows` values take in a line (_pack_lines): the
    next power of two, so that a line holds a whole number of rows."""
    width = 1
    while width < rows:
        width *= 2
    return width


@jax.jit
def _pack_lines(mat):
    """A packed [P, N] dim matrix as lines of one lane width, a dimension
    row's P values SIDE BY SIDE (padded to a power of two, _lane_width) and
    128 // width rows a line: [N * width / 128, 128]. What a fact that is NOT
    ordered by this dimension's key gathers from (_gather_lines): its indices
    fit no window, and out of the [P, N] form every index reads P values N
    apart, where a line holds them together."""
    width = _lane_width(mat.shape[0])
    padded = jnp.pad(mat, ((0, width - mat.shape[0]), (0, 0)))
    # a piece at a time: the transposed [rows, width] form is held padded to a
    # lane width (1 GB for `part`'s 2^21 rows at once, 64 MB a piece)
    n = mat.shape[1]
    piece = min(n, _LINES_PIECE)
    return jnp.concatenate([
        padded[:, at:at + piece].T.reshape(piece * width // _LANES, _LANES)
        for at in range(0, n, piece)])


def _gather_lines(lines, idx, rows: int, segment: int = 0):
    """_gather_rows's plain form, value for value and bit for bit, out of the
    same pack laid as lines (_pack_lines): each index's line, then its own
    `rows` lanes of it, kept by their bits (a sum over the other rows' zeros
    in int32, so a NaN or -0.0 stays itself). A miss reads row 0, as the
    plain form's clip makes it. Indices longer than `segment` are gathered a
    segment at a time and the pieces glued: a gathered [segment, 128] piece
    is 64 MB at 131,072 rows, eight of them at once are not held."""
    width = _lane_width(rows)
    per = _LANES // width
    n = lines.shape[0] * per
    if 0 < segment < idx.shape[0]:
        return jnp.concatenate([_gather_lines(lines, idx[at:at + segment], rows)
                                for at in range(0, idx.shape[0], segment)], axis=1)
    safe = jnp.clip(idx, 0, n - 1)
    taken = jax.lax.bitcast_convert_type(lines[safe // per], jnp.int32)     # [indices, 128]
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    kept = jnp.where(lane // width == (safe % per)[:, None], taken, jnp.int32(0))
    # turned lanes-to-rows first, so that the fold of the `per` rows' lanes
    # onto the first `width` adds vectors as long as the indices (a reshape of
    # the lines to [indices, per, width] would pad every `width` to a lane
    # width), and what comes out is [width, indices]: the planes' own form
    got = jnp.sum(kept.T.reshape(per, width, idx.shape[0]), axis=0, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(got, jnp.float32)[:rows]


def _index_span(idx: np.ndarray) -> int:
    """Greatest less least matched row of a batch's host index, -1 where no
    row matched: what decides whether the batch's gather fits a window."""
    hit = idx[idx >= 0]
    return int(hit.max()) - int(hit.min()) if len(hit) else -1


@dataclass(frozen=True)
class _ProvisionLayout:
    """What one provisioning program is specialised on beside the shapes of
    its arguments: the structure the packs carry and the order of the columns
    asked for. It comes from the plan's shape and the dims' dictionaries,
    never from a filter literal, so a query with another literal finds the
    program the first one traced."""
    packs: tuple     # per adjacent dim: the row of what it gathers from that holds the query's verdict, None = existence check only
    windows: tuple   # per adjacent dim: a segment's indices fit one window of its pack (_gather_rows)
    columns: tuple   # per dim column handed on: (name, adjacent dim, digit rows, validity row)
    codes: tuple     # per group-by column: (adjacent dim or -1 = fact-side plane, row or position, radix)
    cap: int         # the combined codes are clipped to [0, cap); 0 where no codes are asked for
    devices: int = 1  # local devices the batch's rows are sharded over: each runs the program on its shard
    segment: int = 0  # rows of a device's share that read one window (a longer share is gathered in segments); 0: all
    lines: tuple = ()  # per adjacent dim: the rows of its pack where it comes as lines (_pack_lines: an unordered dimension's whole pack), 0 where as the pack; () where none does


class _CodePlan(NamedTuple):
    """The dictionary strategy's group codes, as the host knows them before
    the dispatch (DeviceJoinGroupedRun._dict_code_plan)."""
    cols: tuple                     # group-by columns, most significant first
    radices: tuple                  # per column, from the dictionaries' sizes
    cap: int                        # padded product of the sizes
    fact_planes: Dict[str, object]  # fact-side column -> resident code plane


@functools.lru_cache(maxsize=256)
def _provision_program(layout: _ProvisionLayout):
    """The jitted program that turns one fact batch's cache hits (each
    adjacent dim's packed matrix with the query's filter verdict as its last
    row, _JoinContext.query_pack: the one argument that holds a value of the
    query; its index plane; the fact-side code planes) into what the stage's
    program takes: the gathered dim columns with
    `__join_ok__`, and the radix-combined group codes where `layout.codes`
    asks for them. One call a dispatch; kept at module level under the
    layout because a _JoinContext lives for one query and the stages' own
    programs live by structure. With `layout.devices` > 1 the fact-long
    arguments (index planes, code planes) are row-sharded over that many
    local devices, the packs whole on each, and every device runs the one
    chip's program on its shard: a shard's window is found from its own
    indices.

    A device's share longer than `layout.segment` rows (a join dispatch over a
    resident fact: DISPATCH_SEGMENTS morsels glued) is gathered a segment at
    a time, each from its own window (_gather_rows)."""

    def run(mats, idxs, fact_codes):
        counters.bump("join_provision_traces")   # runs when traced, not when called
        gathered = []
        ok = None
        lines = layout.lines or (0,) * len(mats)
        for mat, didx, ok_row, windowed, as_lines in zip(mats, idxs, layout.packs,
                                                         layout.windows, lines):
            aok = didx >= 0
            rows = None
            if as_lines:
                rows = _gather_lines(mat, didx, as_lines, layout.segment)   # [P, bucket]
            elif mat is not None:
                rows = _gather_rows(mat, didx, windowed, layout.segment)   # [P, bucket]
            if ok_row is not None:
                aok = aok & (rows[ok_row] > 0.5)
            gathered.append(rows)
            ok = aok if ok is None else (ok & aok)
        dcols: Dict[str, dev.DCol] = {"__join_ok__": (ok, jnp.ones_like(ok))}
        for name, a, digits, valid_row in layout.columns:
            rows = gathered[a]
            if len(digits) == 1:
                v = rows[digits[0]]
            else:
                # wide integers: base-2^24 digit planes, most significant
                # first, each exact in f32. Recombined in int64 and handed on
                # as int64, NOT f64: the stage compiler's f32 fcast would
                # quantize a float plane past 2^24, silently corrupting
                # SUM/MIN/MAX over wide int dim columns (ADVICE r5 high); int
                # planes pass fcast untouched and the isum/i64-scatter agg
                # paths receive exact values
                v = rows[digits[0]].astype(jnp.int32).astype(jnp.int64)
                for d in digits[1:]:
                    v = v * (1 << 24) + rows[d].astype(jnp.int32).astype(jnp.int64)
            dcols[name] = (v, rows[valid_row] > 0.5)
        combined = None
        for a, at, radix in layout.codes:
            plane = fact_codes[at] if a < 0 else gathered[a][at].astype(jnp.int32)
            combined = plane * radix if combined is None else combined + plane * radix
        if combined is not None:
            # join-miss garbage is masked anyway
            combined = jnp.clip(combined, 0, layout.cap - 1)
        return dcols, combined

    if layout.devices > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        run = shard_map(run, mesh=local_mesh(layout.devices),
                        in_specs=(P(), P(MESH_AXIS), P(MESH_AXIS)),
                        out_specs=P(MESH_AXIS), check_vma=False)
    return jax.jit(run)


def _code_column(name: str) -> str:
    """The name a string column's dictionary code plane goes by inside a
    visibility program."""
    return f"__code_{name}__"


def _string_comparison(f: Expression, schema: Schema
                       ) -> Optional[Tuple[str, str, tuple]]:
    """(column, "eq" | "neq" | "is_in", the literal strings) where the dim
    filter `f` is, as a whole, `col == lit`, `col != lit` or
    `col.is_in([lits])` over a string column of `schema` with non-null string
    literals; else None. Such a filter is a comparison of dictionary codes on
    the device (_JoinContext._lowered_filter). Only a whole conjunct: under a
    `not` or an `or` a null row's verdict needs the column's validity, which
    a code plane does not carry."""
    while isinstance(f, Alias):
        f = f.child

    def string_col(e) -> Optional[str]:
        if isinstance(e, ColumnRef) and e._name in schema.column_names() \
                and schema[e._name].dtype.is_string():
            return e._name
        return None

    def string_lit(e) -> bool:
        return isinstance(e, Literal) and isinstance(e.value, str)

    if isinstance(f, IsIn):
        c = string_col(f.child)
        if c is not None and f.items and all(string_lit(it) for it in f.items):
            return c, "is_in", tuple(it.value for it in f.items)
    elif isinstance(f, BinaryOp) and f.op in ("eq", "neq"):
        for a, b in ((f.left, f.right), (f.right, f.left)):
            c = string_col(a)
            if c is not None and string_lit(b):
                return c, f.op, (b.value,)
    return None


_VISIBILITY_PROGRAMS: Dict[tuple, tuple] = {}
_VISIBILITY_LOCK = threading.Lock()


def _visibility_program(filters: Sequence[Expression], structure: Tuple[tuple, tuple],
                        dims: Sequence[DimSpec]):
    """(the jitted visibility program of the dim filters `filters`, its
    LiteralSlots): ONE program a list of filter skeletons, kept at module
    level under them (a _JoinContext lives for one query) and traced by JAX
    once a dimension length and mesh width. `structure` is
    exprs_structure(filters); `dims` are the subtree's, whose columns the
    filters read (a string column's code plane under _code_column's name).

    The program takes the filters' columns as planes in the subtree root's row
    space (name -> (values, validity); a code plane's validity is None: its
    nulls are a code of their own), the subtree's value-free verdict (the
    padding mask and the chain's links), the host-evaluated filters' plane or
    None, and the execution's literal values as LiteralSlots packs them
    (dates as days, a string as its dictionary code), and returns the
    float32 verdict plane the provisioning program gathers. No value of
    `filters` is read: whoever runs it passes its own query's."""
    skels, lits = structure
    key = (skels, tuple((dtype, value is None) for dtype, value in lits))
    hit = _VISIBILITY_PROGRAMS.get(key)
    if hit is not None:
        return hit
    fdt = jnp.float32
    fields = [f for d in dims for f in d.base.schema.fields]
    schema = Schema(fields + [Field(_code_column(f.name), DataType.int32())
                              for f in fields if f.dtype.is_string()])
    slots = dev.LiteralSlots(filters, fdt)
    fns = [dev.build_device_expr(f, schema, float_dtype=fdt, first_slot=first)
           for f, first in zip(filters, slots.offsets)]

    def join_filter_verdict(cols, base_ok, host_ok, lit_args):
        counters.bump("join_filter_program_traces")   # runs when traced, not when called
        values = slots.unpack(lit_args)
        always = jnp.ones((), dtype=bool)
        cols = {name: (v, always if m is None else m) for name, (v, m) in cols.items()}
        ok = base_ok if host_ok is None else (base_ok & host_ok)
        for fn in fns:
            v, m = fn(cols, values)
            ok = ok & v.astype(bool) & m
        return ok.astype(jnp.float32)

    # (its name is the device trace's: `jit_join_filter_verdict(...)` on the
    # XLA Modules line, which benchmark/filterbytes.py reads)
    with _VISIBILITY_LOCK:
        return _VISIBILITY_PROGRAMS.setdefault(
            key, (jax.jit(join_filter_verdict), slots))


@jax.jit
def _stack_verdict(mat, verdict):
    """A pack with the query's verdict plane laid under it as one more row:
    what a dispatch gathers from, made once a query (a copy of the pack: two
    queries with different values may hold one pack at once, so nothing is
    written into it)."""
    return jnp.concatenate([mat, verdict[None, :]], axis=0)


class _JoinContext:
    """Materialized dims + per-fact-batch index/gather preparation.

    Everything expensive is cached keyed on the identity of a Series' DATA
    (series_keyed: a resident column, or the rows of one a morsel views):
    host join indices, padded device index planes, the packs, the planes a
    dim filter reads, synthetic dim columns. Per-fact-batch slots anchor on a
    column of the fact batch (_probe_anchor, fact_anchor), so each morsel has
    its own and finds it again on the next query. Per-query work is then
    only: one call of the visibility program a filtered adjacent dim
    (verdict_plane) and ONE d2h fetch, and per fact batch the look-ups that
    find the cached arrays, one call of the traced provisioning program
    (_provision_program: row gathers, join-validity mask, plane splits,
    wide-integer recombination, group codes) and one of the stage's program.
    The context itself lives for one query; the provisioning and visibility
    programs live at module level under their layout and skeletons, so a
    repeat query traces nothing.

    What a filter VALUE costs: nothing that is kept. A dim filter that is
    device-evaluable over f32-exact columns (dates, booleans, small
    integers), or that compares a string column with literals (`==`, `!=`,
    `is_in`: a comparison of dictionary codes, the literal's code looked up
    on the host), takes its values as arguments of the visibility program:
    the query's verdict plane is made once, at its first dispatch, from
    resident planes that hold no value (the filters' columns carried into
    the adjacent dim's row space, the chain's links), laid under a copy of
    the dim's pack (query_pack) and kept by this context alone, so two
    queries with different values share every slot and neither waits for
    the other. Only a filter that stays on the host (LIKE,
    a function) keeps a slot whose literals are compared (host_visible,
    _host_ok_plane) and is rebuilt on a new value (`hbm_literal_rebuilds`).
    """

    def __init__(self, spec: JoinAggSpec, dim_batches: Dict[str, object]):
        self.spec = spec
        self.dims = spec.dims
        self.batches = dim_batches              # dim name -> RecordBatch (base rows)
        # Pallas hash-probe tier preference: set by the executor's
        # device_join_pallas_cost arm, read by _pallas_probe_gate's auto branch
        self.pallas_probe_preferred = False
        # the local devices a dispatch's fact rows are sharded over (set_mesh,
        # by the run that drives this context); None: one chip
        self.mesh_devices = 1
        self.mesh = None
        # rows of a segment: a device's share of a dispatch that is longer (a
        # run of morsels of a resident fact, glued by the coalescer) is
        # gathered and added up a segment at a time, each a morsel's bucket
        from ..config import execution_config

        self.segment_rows = pad_bucket(execution_config().morsel_size_rows)
        self.syn_series: Dict[str, Dict[str, object]] = {}
        self._dev_filters: Dict[str, List[Expression]] = {}
        self._host_filters: Dict[str, List[Expression]] = {}
        # the query's filter verdicts, an adjacent dim each (verdict_plane),
        # and the packs with them laid under (query_pack: the pack is kept
        # beside its copy so that its id stays its own)
        self._verdicts: Dict[str, object] = {}
        self._query_packs: Dict[tuple, tuple] = {}
        for d in self.dims:
            b = dim_batches[d.name]
            devf: List[Expression] = []
            hostf: List[Expression] = []
            for f in d.filters:
                # device filter eval reads f32 planes: only dtypes whose every
                # value is f32-exact qualify (dates < 2^24 days, small ints,
                # bools) — int64/timestamp/float comparisons stay on host,
                # which evaluated ALL dim filters exactly before this path.
                # A string column compared with literals is compared by its
                # dictionary codes (_lowered_filter)
                if _string_comparison(f, d.base.schema) is not None or (
                        dev.is_device_evaluable(f, d.base.schema) and all(
                            d.base.schema[c].dtype.kind in
                            ("date", "bool", "int8", "int16", "uint8", "uint16")
                            for c in f.referenced_columns())):
                    devf.append(f)
                else:
                    hostf.append(f)
            self._dev_filters[d.name] = devf
            self._host_filters[d.name] = hostf
            syn = {}
            for name, expr in d.synthetic:
                syn[name] = self._cached_syn(b, name, expr)
            self.syn_series[d.name] = syn

    def set_mesh(self, n_devices: int) -> None:
        """Every dispatch over this context shards its fact batch's rows over
        `n_devices` local devices (stage.local_mesh): the batch's fact planes,
        index planes and code planes are row-sharded (slots of their own,
        keyed with the mesh width), a dimension's pack is whole on every
        device, and each device runs the one chip's programs on its shard."""
        self.mesh_devices = max(int(n_devices), 1)
        self.mesh = local_mesh(self.mesh_devices)

    def bucket_for(self, n: int) -> int:
        """Padded rows of an n-row fact batch: a chip's bucket, or over a mesh
        a bucket a shard (stage.mesh_total)."""
        return pad_bucket(n) if self.mesh is None \
            else mesh_total(n, self.mesh_devices)

    def window_rows(self, bucket: int) -> int:
        """Rows of the stretch of a `bucket`-row dispatch whose indices read
        one window of a dimension's pack: a segment, or a device's share of
        the dispatch where that is no longer than one."""
        return min(bucket // self.mesh_devices, self.segment_rows)

    def _mesh_key(self) -> tuple:
        """What a slot's key carries where its arrays are laid out over the
        mesh (as stage._codes_slot keys a code plane)."""
        return () if self.mesh is None else ("mesh", self.mesh_devices, MESH_AXIS)

    def _idx_slot_key(self, family: str, d: DimSpec, bucket: int,
                      mesh_key: Optional[tuple] = None) -> tuple:
        """The slot of dim `d`'s padded device index plane of a fact batch,
        laid out as `mesh_key` says (_mesh_key: this context's, unless given).
        The plane's span is kept beside it, the widest of its segments', so
        the segment's length is part of the key where a device's share of the
        plane holds more than one."""
        if mesh_key is None:
            mesh_key = self._mesh_key()
        key = (family, d.key_col, d.parent, bucket) + mesh_key
        if bucket // (mesh_key[1] if mesh_key else 1) > self.segment_rows:
            key += ("segment", self.segment_rows)
        return key

    @staticmethod
    def _filter_anchor(batch, expr: Expression):
        refs = expr.referenced_columns()
        return batch.get_column(refs[0]) if refs else batch.get_column(
            batch.column_names()[0])

    def _cached_syn(self, dim_batch, name: str, expr: Expression):
        """Synthetic dim column, evaluated once per (expr, referenced-series)
        and reused across queries/reps — so its device upload is cached too.
        Keyed on the expression STRUCTURE; literal values live in the entry,
        so varying-literal predicates reuse one slot."""
        from ..expressions.eval import eval_expression

        refs = expr.referenced_columns()
        deps = tuple(dim_batch.get_column(c) for c in refs)
        skel, lits = expr_structure(expr)
        return series_keyed(
            self._filter_anchor(dim_batch, expr), ("syn", skel, name),
            deps, lambda: eval_expression(dim_batch, expr).rename(name),
            literals=lits)

    def host_visible(self, d: DimSpec) -> Optional[np.ndarray]:
        """Combined host-filter visibility for one dim (None = all pass);
        cached per (filters, referenced series)."""
        hostf = self._host_filters[d.name]
        if not hostf:
            return None
        from ..expressions.eval import eval_expression

        b = self.batches[d.name]
        deps = tuple(b.get_column(c) for f in hostf for c in f.referenced_columns())
        anchor = deps[0] if deps else b.get_column(b.column_names()[0])

        def build():
            vis = np.ones(b.num_rows, dtype=bool)
            for f in hostf:
                m = eval_expression(b, f)
                vis &= np.asarray(m.to_numpy(), dtype=bool) & m.validity_numpy()
            return vis

        skels, lits = exprs_structure(hostf)
        return series_keyed(anchor, ("hostvis",) + skels, deps, build,
                            literals=lits)

    def _fact_membership_plane(self, batch, bucket: int, syn: str) -> dev.DCol:
        """bool plane for a fact string membership predicate: resident dict
        codes compared against the (tiny) per-query match-code set. Null rows
        are invalid (SQL three-valued comparisons), matching host eval.
        One slot per (fact column, syn, bucket) — syn keeps two membership
        predicates over the SAME column in one query from thrashing a shared
        slot; the per-query match values are the slot's literals, so varying
        predicates rebuild in place."""
        colname, values = self.spec.fact_synthetic[syn]
        s = batch.get_column(colname)
        built = []

        def build():
            built.append(syn)
            codes, vals, _k = s.dict_codes()
            match = np.array([i for i, v in enumerate(vals) if v in values],
                             dtype=np.int32)
            null_codes = np.array([i for i, v in enumerate(vals) if v is None],
                                  dtype=np.int32)
            dcodes = cached_dict_code_plane(s, codes, batch.num_rows, bucket,
                                            self.mesh)
            plane = jnp.isin(dcodes, jnp.asarray(match))
            if len(null_codes):
                valid = ~jnp.isin(dcodes, jnp.asarray(null_codes))
            else:   # (over a mesh: an array laid out as the codes are)
                valid = jnp.ones(bucket, dtype=bool) if self.mesh is None \
                    else device_row_mask(bucket, bucket, self.mesh)
            return plane, valid

        # the look-up a dispatch: a hit on a repeat query, a build where the
        # plane was never made (or the query's match values changed)
        with profile_span("join.membership", "device", rows=batch.num_rows) as sp:
            plane = series_keyed(s, ("fmem", syn, bucket) + self._mesh_key(), (),
                                 build, literals=values)
            if sp is not None:
                sp.args["hit"] = not built
        return plane

    def _permuted_membership(self, batch, bucket: int, syn: str, perm) -> dev.DCol:
        colname, values = self.spec.fact_synthetic[syn]
        s = batch.get_column(colname)
        pperm_np, pdev = perm

        def build():
            plane, valid = self._fact_membership_plane(batch, bucket, syn)
            return (plane.astype(jnp.float32)[pdev] > 0.5), valid[pdev]

        return series_keyed(s, ("fmemp", syn, bucket), (pperm_np,), build,
                            literals=values)

    # ---- per fact batch -----------------------------------------------------------
    def _probe_anchor(self, batch, d: DimSpec):
        """The Series that per-fact-batch caches for dim `d` key on: the fact
        column that probes `d`, or (chained) the one that probes the root of
        d's chain. Always a column of the fact batch, so the slot follows the
        batch's rows: every morsel of a resident fact has its own (a slot
        anchored on the parent dim's column would be shared, and rebuilt, by
        all morsels of equal length), and a fresh view of the same rows finds
        it again."""
        root = self._root_of(d.name)
        return batch.get_column(
            next(dd for dd in self.dims if dd.name == root).parent[1])

    def fact_anchor(self, batch):
        """The anchor of slots built from a fact batch's rows through the
        join as a whole (joined group codes, their cardinality): the column
        that probes the first adjacent dim."""
        return self._probe_anchor(batch, self._adjacent()[0])

    def indices_for(self, batch) -> Dict[str, np.ndarray]:
        """Static per-fact-row dim indices. Cached per dim on the identity of
        the fact PROBE column's data (batch objects and the morsel views a
        pipeline cuts are transient; the rows of a resident column they view
        are not). Chained dims additionally depend on the parent dim's link
        column and on the parent's idx array identity, so a parent rebuild
        invalidates the chain."""
        with profile_span("join.index", "host", dim="*",
                          bucket=batch.num_rows):
            return self._indices_for(batch)

    def _indices_for(self, batch) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        n = batch.num_rows
        for d in self.dims:
            dim_b = self.batches[d.name]
            key_series = dim_b.get_column(d.key_col)
            kdt = _common_key_dtype(
                self._probe_dtype(batch, d), dim_b.schema[d.key_col].dtype)
            anchor = self._probe_anchor(batch, d)
            deps: tuple = (key_series,)
            if d.parent[0] != "fact":
                link = self.batches[d.parent[0]].get_column(d.parent[1])
                deps = deps + (link, out[d.parent[0]])

            def build(d=d, kdt=kdt, key_series=key_series, snapshot=dict(out)):
                probe_vals, probe_valid = self._probe_values(batch, d, snapshot, kdt)
                idx = unique_key_index(key_series, probe_vals, probe_valid, kdt)
                assert len(idx) == n
                return idx

            out[d.name] = series_keyed(
                anchor, ("uki", d.key_col, d.parent, repr(kdt), n), deps, build,
                rebuild_rows=n)
        return out

    def _probe_dtype(self, batch, d: DimSpec):
        side, colname = d.parent
        if side == "fact":
            return batch.schema[colname].dtype
        return self.batches[side].schema[colname].dtype

    def _probe_values(self, batch, d: DimSpec, idx_so_far: Dict[str, np.ndarray],
                      target_dtype) -> Tuple[np.ndarray, np.ndarray]:
        side, colname = d.parent
        if side == "fact":
            s = batch.get_column(colname)
            if s.dtype != target_dtype:
                s = s.cast(target_dtype)
            kind, vals, valid = canonical_key_values(s)
            if kind != "num":
                raise DeviceFallback(f"fact key {colname!r} is not integer-like")
            return vals.astype(np.int64, copy=False), valid
        # chained: gather the parent dim's column on host (static)
        pidx = idx_so_far[side]
        s = self.batches[side].get_column(colname)
        if s.dtype != target_dtype:
            s = s.cast(target_dtype)
        kind, vals, valid = canonical_key_values(s)
        if kind != "num":
            raise DeviceFallback(f"dim key {colname!r} is not integer-like")
        vals = vals.astype(np.int64, copy=False)
        if len(vals) == 0:  # empty parent dim: nothing can chain through it
            return (np.zeros(len(pidx), dtype=np.int64),
                    np.zeros(len(pidx), dtype=bool))
        safe = np.clip(pidx, 0, len(vals) - 1)
        pv = vals[safe]
        pvalid = (pidx >= 0) & valid[safe]
        return pv, pvalid

    # ---- Pallas hash-probe tier ----------------------------------------------------
    def _pallas_probe_gate(self, batch, d: DimSpec):
        """Whether dim `d`'s device index plane builds on the Pallas
        hash-probe kernel (ops/pallas_kernels.py hash_probe_index) instead of
        the host probe + upload. Returns the kernel's `interpret` flag when
        it should (True = CPU interpreter, for off-silicon parity under
        DAFT_TPU_PALLAS=on), None for the host tier. Same mode vocabulary as
        grouped_stage._pallas_gate; the auto branch additionally requires the
        executor's device_join_pallas_cost arm to have preferred the kernel
        for this join's shape. Chained dims keep the host path — their probe
        values flow through the parent's HOST index, so an in-kernel probe
        would not remove the host work it exists to skip."""
        if d.parent[0] != "fact" or self.mesh is not None:
            return None     # (the kernel is one chip's: a mesh takes the host's index)
        from ..config import execution_config

        mode = getattr(execution_config(), "pallas_mode", "auto")
        if mode == "off":
            return None
        from .pallas_kernels import MAX_PALLAS_BUCKET

        if self.batches[d.name].num_rows >= MAX_PALLAS_BUCKET:
            return None
        on_tpu = jax.default_backend() == "tpu"
        if mode == "on":
            return not on_tpu
        return False if (on_tpu and self.pallas_probe_preferred) else None

    def _pallas_probe_table_host(self, d: DimSpec, kdt):
        """Host (tbl_hi, tbl_lo, tbl_row) probe-table planes for dim `d`'s
        key column — built ONCE per resident dim key Series and cached in the
        ResidencyManager alongside the index planes, shared by the single-chip
        and mesh probe paths (each uploads into its own slot). Non-unique /
        non-integer / sentinel-valued keys raise DeviceFallback with the same
        semantics as unique_key_index, so both tiers reject identical dims."""
        from . import pallas_kernels as pk

        key_series = self.batches[d.name].get_column(d.key_col)

        def build():
            s = key_series
            if s.dtype != kdt:
                s = s.cast(kdt)
            kind, vals, valid = canonical_key_values(s)
            if kind != "num":
                raise DeviceFallback(
                    f"dim key {key_series.name!r} is not an integer-like key")
            try:
                return pk.build_probe_table(
                    vals.astype(np.int64, copy=False), valid)
            except ValueError as exc:
                raise DeviceFallback(
                    f"dim key {key_series.name!r}: {exc}") from exc

        return series_keyed(key_series, ("ptable", d.key_col, repr(kdt)),
                            (), build)

    def _pallas_dev_idx(self, batch, d: DimSpec, bucket: int, interp: bool):
        """Padded device index plane for one ADJACENT dim, probed IN-KERNEL:
        fact key digits matched against the VMEM-resident dim hash table —
        no host hash probe, no index-plane upload (the h2d is two int32 digit
        planes that the kernel consumes in place). Bit-identical to the host
        unique_key_index path (pinned in tests/test_pallas_join.py) and
        cached under its own slot key, so repeat queries re-probe nothing."""
        from . import pallas_kernels as pk

        dim_b = self.batches[d.name]
        kdt = _common_key_dtype(
            self._probe_dtype(batch, d), dim_b.schema[d.key_col].dtype)
        tbl = self._pallas_probe_table_host(d, kdt)
        anchor = self._probe_anchor(batch, d)
        key_series = dim_b.get_column(d.key_col)
        n = batch.num_rows

        def build():
            vals, valid = self._probe_values(batch, d, {}, kdt)
            pv = np.full(bucket, pk.PROBE_SENTINEL, dtype=np.int64)
            pm = np.zeros(bucket, dtype=bool)
            pv[:n] = vals
            pm[:n] = valid
            fh, fl = pk.probe_key_digits(jnp.asarray(pv), jnp.asarray(pm))
            idx = pk.hash_probe_index(
                fh, fl, jnp.asarray(tbl[0]), jnp.asarray(tbl[1]),
                jnp.asarray(tbl[2]), interpret=interp)
            counters.bump("pallas_probe_dispatches")
            return idx

        return series_keyed(anchor, ("pdidx", d.key_col, d.parent, bucket),
                            (key_series, tbl), build, rebuild_rows=n)

    def dev_idx(self, batch, dname: str, bucket: int, perm=None):
        """(padded device index plane, span) for one dim, cached on the probe
        Series (identity: the host idx array — itself cached — plus the dim
        key). The span (_index_span of the host index, kept with the plane so
        that a dispatch looks nothing else up) says whether the batch's gather
        fits a window of the dim's pack; the perm-folded plane holds the same
        indices in another order, so the same span.
        With `perm` (host group-sorted layout) the permutation is FOLDED INTO
        the indices, so the packed row-gather emits rows pre-sorted at zero
        extra cost. Under the Pallas gate the plain (un-permuted) plane is
        probed in-kernel instead, and the host, which then holds no index,
        gives no span (None); a kernel that does not lower raises.
        Over a mesh (set_mesh) the plane is row-sharded under a slot of its
        own. The span is the widest of the plane's stretches of window_rows
        (a device's share of the dispatch, cut into segments where it is
        longer than one): each is gathered from the window its own rows
        point into."""
        with profile_span("join.index", "host", dim=dname, bucket=bucket):
            return self._dev_idx(batch, dname, bucket, perm)

    def _dev_idx(self, batch, dname: str, bucket: int, perm):
        d = next(dd for dd in self.dims if dd.name == dname)
        anchor = self._probe_anchor(batch, d)
        n = batch.num_rows

        if perm is None:
            interp = self._pallas_probe_gate(batch, d)
            if interp is not None:
                return self._pallas_dev_idx(batch, d, bucket, interp), None
            idx_np = self._indices_for(batch)[dname]

            mesh, per = self.mesh, self.window_rows(bucket)

            def build():
                padded = np.full(bucket, -1, dtype=np.int32)
                padded[:n] = idx_np
                span = max(_index_span(padded[at:at + per])
                           for at in range(0, bucket, per))
                if mesh is None:
                    return jnp.asarray(padded), span
                return shard_rows(mesh, padded, bucket), span

            return series_keyed(anchor, self._idx_slot_key("didx", d, bucket),
                                (idx_np,), build, rebuild_rows=n)

        idx_np = self._indices_for(batch)[dname]
        pperm_np, _pdev = perm

        def build_p():
            padded = np.full(bucket, -1, dtype=np.int32)
            padded[:n] = idx_np[pperm_np[:n]]
            return jnp.asarray(padded), _index_span(idx_np)

        return series_keyed(anchor, ("didxp", d.key_col, d.parent, bucket),
                            (idx_np, pperm_np), build_p, rebuild_rows=n)

    def nonresident_index_bytes(self, batch, bucket: int,
                                mesh_key: tuple = ()) -> int:
        """h2d bytes the cost model should charge for dim index planes not
        already resident in HBM (advisory: mirrors dev_idx's cache keys —
        both the plain and the perm-folded local-dense variants — so a
        repeat query is costed with zero index-plane transfer). `mesh_key`:
        the planes as a mesh of that width lays them out (_mesh_key)."""
        from ..device.residency import manager

        total = 0
        for d in self.dims:
            anchor = self._probe_anchor(batch, d)
            if not any(manager().is_resident(
                    anchor, self._idx_slot_key(fam, d, bucket, mesh_key))
                    for fam in ("didx", "didxp", "pdidx")):
                total += bucket * 4
        return total

    def ids_locally_dense(self, batch, dname: str) -> bool:
        """Whether every chunk of CHUNK_LOCAL rows of `batch` holds `dname`
        row indices within CHUNK_LOCAL of each other (a fact sorted by that
        dimension's key): what the run-wide TopN program sees on the device
        at every dispatch, read on the host from the first batch's cached
        index so that placement can price the form that will run."""
        from .grouped_stage import CHUNK_LOCAL

        d = next(dd for dd in self.dims if dd.name == dname)
        idx = self._indices_for(batch)[dname]

        def build():
            chunk = min(CHUNK_LOCAL, pad_bucket(len(idx)))
            padded = np.full(-(-len(idx) // chunk) * chunk, -1, dtype=np.int64)
            padded[:len(idx)] = idx
            g = padded.reshape(-1, chunk)
            lo = np.where(g >= 0, g, np.iinfo(np.int64).max).min(axis=1)
            hi = g.max(axis=1)
            return bool(np.all((hi < 0) | (hi - lo < chunk)))

        return series_keyed(self._probe_anchor(batch, d),
                            ("idxdense", d.key_col, d.parent), (idx,), build)

    # ---- packed per-adjacent-dim planes ------------------------------------------
    #
    # TPU dynamic gathers are INDEX-COUNT bound: on v5e a single 8M-index
    # gather costs ~60ms regardless of payload width, while a row-gather of an
    # [N, P] matrix moves P columns for the same price (measured 8 separate
    # gathers = 584ms vs 1 packed row-gather = 146ms). So the snowflake is
    # denormalized ON DEVICE into one packed f32 matrix per FACT-ADJACENT dim
    # — chained dims' planes composed into their adjacency root's row space
    # with dim-sized (cheap) gathers — and each fact batch then pays exactly
    # ONE fact-length gather per adjacent dim. Packs are series_keyed-cached
    # per query shape; reps re-run only the fact gathers + the agg program.

    def _adjacent(self) -> List[DimSpec]:
        return [d for d in self.dims if d.parent[0] == "fact"]

    def _root_of(self, dname: str) -> str:
        d = next(dd for dd in self.dims if dd.name == dname)
        while d.parent[0] != "fact":
            d = next(dd for dd in self.dims if dd.name == d.parent[0])
        return d.name

    def _children_of(self, dname: str) -> List[DimSpec]:
        return [d for d in self.dims if d.parent[0] == dname]

    def _needed_split(self, needed: Sequence[str], groupby_cols: Sequence[str]):
        """(value_cols, code_cols) per dim name from the run's needs."""
        spec = self.spec
        vals: Dict[str, List[str]] = {d.name: [] for d in self.dims}
        codes: Dict[str, List[str]] = {d.name: [] for d in self.dims}
        for c in needed:
            side = spec.col_side.get(c)
            if side in vals and c != "__join_ok__":
                vals[side].append(c)
        for c in groupby_cols:
            side = spec.col_side.get(c)
            if side in codes:
                codes[side].append(c)
        return vals, codes

    def dim_space_idx(self, child: DimSpec) -> np.ndarray:
        """Host index array mapping PARENT-dim rows -> child rows (-1 miss)."""
        pname, pcol = child.parent
        probe = self.batches[pname].get_column(pcol)
        key_series = self.batches[child.name].get_column(child.key_col)
        kdt = _common_key_dtype(probe.dtype, key_series.dtype)

        def build():
            p = probe if probe.dtype == kdt else probe.cast(kdt)
            kind, vals, valid = canonical_key_values(p)
            if kind != "num":
                raise DeviceFallback(
                    f"chain key {pcol!r} is not integer-like")
            return unique_key_index(key_series, vals.astype(np.int64, copy=False),
                                    valid, kdt)

        return series_keyed(probe, ("dsidx", child.key_col, repr(kdt)),
                            (key_series,), build)

    def _dim_source(self, dname: str, col: str):
        if col.startswith("__syn_"):
            return self.syn_series[dname][col]
        return self.batches[dname].get_column(col)

    def _subtree(self, adj: DimSpec) -> List[DimSpec]:
        """`adj` and the dims chained from it, parents first."""
        return [adj] + [d for d in self.dims
                        if d.name != adj.name and self._root_of(d.name) == adj.name]

    def _subtree_deps(self, sub_dims: Sequence[DimSpec]) -> tuple:
        """Each subtree dim's key and parent-link columns: what a slot built
        through the chain depends on beside the columns it reads (a different
        chain through the same root must NOT reuse it)."""
        return tuple(self.batches[d.name].get_column(d.key_col) for d in sub_dims) \
            + tuple(self.batches[d.parent[0]].get_column(d.parent[1])
                    for d in sub_dims if d.parent[0] != "fact")

    @staticmethod
    def _chain_shape(sub_dims: Sequence[DimSpec]) -> tuple:
        return tuple((d.key_col,) + d.parent for d in sub_dims)

    def _child_idx_plane(self, child: DimSpec):
        """int32 device plane as long as the PARENT dim padded: the child row
        each parent row joins (-1: none). A slot of its own beside the packs
        and the filter planes that are built through it, so none of them
        uploads it again (64 MB for `orders` -> `customer` at SF10)."""
        b = self.batches[child.parent[0]]
        cap = pad_bucket(b.num_rows)
        idx = self.dim_space_idx(child)

        def build():
            padded = np.full(cap, -1, dtype=np.int32)
            padded[:b.num_rows] = idx
            return jnp.asarray(padded)

        return series_keyed(b.get_column(child.parent[1]),
                            ("dsdidx", child.key_col, cap), (idx,), build)

    def _at_root(self, root: DimSpec, d: DimSpec, v, m):
        """A (values, validity) plane over `d`'s rows carried into `root`'s
        row space: one dim-sized gather a link of the chain between them. A
        row the chain does not reach is invalid."""
        while d.name != root.name:
            v, m = _gather_col(v, m, self._child_idx_plane(d))
            d = next(dd for dd in self.dims if dd.name == d.parent[0])
        return v, m

    def _build_space(self, adj: DimSpec, sub_dims: Sequence[DimSpec],
                     vals: Dict[str, List[str]], codes: Dict[str, List[str]]):
        """(value planes, code planes, chain verdict or None) of `adj`'s
        subtree, all in adj's row space on device: the columns asked for of
        each dim (`vals`: name -> (values, validity); `codes`: name -> int32
        dictionary codes), and whether every link of the chain below a row
        finds its row (None where nothing is chained). Holds no filter value.
        Called inside the cached builds of packed_plane and _filter_planes."""
        planes: Dict[str, dev.DCol] = {}
        code_planes: Dict[str, object] = {}
        chain_ok = None
        for d in sub_dims:
            b = self.batches[d.name]
            cap_d = pad_bucket(b.num_rows)
            for c in vals[d.name]:
                planes[c] = self._at_root(adj, d, *self._dim_source(
                    d.name, c).to_device_cached(cap_d, f32=True))
            for c in codes[d.name]:
                src = self._dim_source(d.name, c)
                cds, _values, _k = src.dict_codes()
                cp = cached_dict_code_plane(src, cds, b.num_rows, cap_d)
                g, _m = self._at_root(adj, d, cp, jnp.ones(cp.shape[0], dtype=bool))
                code_planes[c] = g.astype(jnp.int32)
            if d.name != adj.name:
                parent = next(dd for dd in self.dims if dd.name == d.parent[0])
                link = self._child_idx_plane(d) >= 0
                lv, lm = self._at_root(adj, parent, link, jnp.ones_like(link))
                chain_ok = (lv & lm) if chain_ok is None else (chain_ok & lv & lm)
        return planes, code_planes, chain_ok

    def _whole_on_mesh(self, tree):
        """`tree`'s arrays whole on every device of the mesh (as they are on
        one chip): a shard's rows gather from any row of the dimension."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    def packed_plane(self, adj: DimSpec, needed: Sequence[str],
                     groupby_cols: Sequence[str]):
        """Packed [P, cap_d] f32 matrix + layout of the columns one adjacency
        subtree hands to the fact's rows, or None when it hands none (the
        subtree is an existence check, or only filters: verdict_plane).

        Returns (mat, layout, code_layout, wide) where layout[col] =
        (val_idx, valid_idx); 32- and 64-bit int columns split into two or
        three base-2^24 f32 digit planes (wide[col] = (digit rows, most
        significant first, then valid_idx)), which the provisioning program
        recombines in int64 after the fact gather, preserving exact values
        past 2^24.

        The pack holds NO filter value and no verdict: its slot is keyed on
        the chain's shape and the columns alone, so a query with other filter
        values hits it (what a value costs is one call of the visibility
        program, verdict_plane; a pack of `orders` at SF10 is 64 MB a row,
        and before it was rebuilt whole, eagerly, for every new value)."""
        spec = self.spec
        vals, codes = self._needed_split(needed, groupby_cols)
        sub_dims = self._subtree(adj)
        my_vals = [c for d in sub_dims for c in vals[d.name]]
        my_codes = [c for d in sub_dims for c in codes[d.name]]
        if not my_vals and not my_codes:
            return None

        anchor = self.batches[adj.name].get_column(adj.key_col)
        # deps: every source Series the pack reads — value/code columns, each
        # subtree dim's key and parent-link columns; key: the chain SHAPE
        deps = tuple(self._dim_source(spec.col_side[c], c)
                     for c in my_vals + my_codes) + self._subtree_deps(sub_dims)
        key = ("pack", tuple(my_vals), tuple(my_codes),
               self._chain_shape(sub_dims)) + self._mesh_key()

        def build():
            planes, code_planes, _chain_ok = self._build_space(
                adj, sub_dims, vals, codes)
            cols = []
            layout: Dict[str, Tuple[int, int]] = {}
            wide: Dict[str, Tuple[int, int, int]] = {}
            for c in my_vals:
                v, m = planes[c]
                kind = str(getattr(v, "dtype", ""))
                if kind in ("int64", "uint64"):
                    # 3-digit split: recombines exactly after the gather (the
                    # consumer pipeline holds |v| < 2^53, f64's own limit)
                    hi = jnp.floor_divide(v, 1 << 48).astype(jnp.float32)
                    mid = jnp.mod(jnp.floor_divide(v, 1 << 24),
                                  1 << 24).astype(jnp.float32)
                    lo = jnp.mod(v, 1 << 24).astype(jnp.float32)
                    wide[c] = (len(cols), len(cols) + 1, len(cols) + 2,
                               len(cols) + 3)
                    cols += [hi, mid, lo, m.astype(jnp.float32)]
                elif kind in ("int32", "uint32"):
                    # 2-digit split: exact over the full 32-bit domain (a
                    # single f32 plane quantizes past 2^24)
                    hi = jnp.floor_divide(v, 1 << 24).astype(jnp.float32)
                    lo = jnp.mod(v, 1 << 24).astype(jnp.float32)
                    wide[c] = (len(cols), len(cols) + 1, len(cols) + 2)
                    cols += [hi, lo, m.astype(jnp.float32)]
                else:
                    layout[c] = (len(cols), len(cols) + 1)
                    cols += [v.astype(jnp.float32), m.astype(jnp.float32)]
            code_layout: Dict[str, int] = {}
            for c in my_codes:
                code_layout[c] = len(cols)
                cols.append(code_planes[c].astype(jnp.float32))
            # [P, cap_d]: minor dim stays long; over a mesh whole on every
            # device (the one copy made here is dropped)
            mat = self._whole_on_mesh(jnp.stack(cols, axis=0))
            return mat, layout, code_layout, wide

        return series_keyed(anchor, key, deps, build)

    # ---- the query's filter verdict ------------------------------------------------
    #
    # Whether a row of a fact-adjacent dim lets a fact row through: its own
    # filters, its chained dims' filters and the chain's links. The pack
    # carried it as a row of its own once, so a new SEGMENT, REGION or DATE
    # rebuilt the pack. Now everything that is kept holds no value: the
    # filters' columns carried into the adjacent dim's row space
    # (_filter_planes) and one jitted program a list of filter skeletons
    # (_visibility_program), which takes the values as arguments. The verdict
    # itself is made once a query and dies with the context.

    def _dict_lookup(self, src) -> Dict[object, int]:
        """value -> dictionary code of a string column, made once a column
        (a handful of entries for a segment or a region's name)."""
        return series_keyed(
            src, ("dictlookup",), (),
            lambda: {v: i for i, v in enumerate(src.dict_codes()[1])})

    def _lowered_filter(self, d: DimSpec, f: Expression) -> Expression:
        """The device filter `f` of dim `d` as the visibility program
        compiles and binds it: itself, or for a string comparison
        (_string_comparison) the same comparison of the column's dictionary
        codes with the literals' codes, looked up on the host. A value the
        dictionary lacks gives -1, a code no row has; a null row's code
        equals no literal's, and `!=` names the nulls' code besides, so a
        null fails the filter as on the host."""
        cmp = _string_comparison(f, d.base.schema)
        if cmp is None:
            return f
        colname, op, values = cmp
        lookup = self._dict_lookup(self.batches[d.name].get_column(colname))
        code = ColumnRef(_code_column(colname))

        def lit(value) -> Literal:
            return Literal(int(lookup.get(value, -1)), DataType.int32())

        if op == "is_in":
            return IsIn(code, [lit(v) for v in values])
        if op == "eq":
            return BinaryOp("eq", code, lit(values[0]))
        return BinaryOp("and", BinaryOp("neq", code, lit(values[0])),
                        BinaryOp("neq", code, lit(None)))

    def _filter_columns(self, sub_dims: Sequence[DimSpec]):
        """(value columns, code columns) the subtree's device filters read,
        by dim name, each sorted."""
        vals: Dict[str, List[str]] = {d.name: [] for d in self.dims}
        codes: Dict[str, List[str]] = {d.name: [] for d in self.dims}
        for d in sub_dims:
            for f in self._dev_filters[d.name]:
                cmp = _string_comparison(f, d.base.schema)
                into, cols = (codes, [cmp[0]]) if cmp is not None \
                    else (vals, f.referenced_columns())
                into[d.name] = sorted(set(into[d.name]) | set(cols))
        return vals, codes

    def _filter_planes(self, adj: DimSpec, sub_dims: Sequence[DimSpec], cap: int):
        """({column: (values, validity)}, value-free verdict) in `adj`'s row
        space, whole on every device: the planes the subtree's device filters
        read (a string column's int32 dictionary codes under _code_column's
        name, validity None) and the bool plane of what no value decides (the
        padding mask and the chain's links). One slot a (chain, column set):
        resident, hit by every query whatever its values. A chained dim's
        columns are carried through the chain once, here; the adjacent dim's
        own are the resident column planes themselves on one chip (looked up,
        not kept twice) and copies whole on every device over a mesh."""
        vals, codes = self._filter_columns(sub_dims)
        own = self.mesh is None     # adj's own planes: read where they lie
        kept_vals = dict(vals, **({adj.name: []} if own else {}))
        kept_codes = dict(codes, **({adj.name: []} if own else {}))
        b = self.batches[adj.name]
        sources = [(d.name, c) for d in sub_dims
                   for c in vals[d.name] + codes[d.name]]
        key = ("jfilter", self._chain_shape(sub_dims), tuple(sources), cap) \
            + self._mesh_key()
        deps = tuple(self._dim_source(n, c) for n, c in sources) \
            + self._subtree_deps(sub_dims)

        def build():
            planes, code_planes, chain_ok = self._build_space(
                adj, sub_dims, kept_vals, kept_codes)
            base = jnp.arange(cap) < b.num_rows     # padding rows pass nothing
            if chain_ok is not None:
                base = base & chain_ok
            cols = dict(planes)
            cols.update((_code_column(c), (p, None)) for c, p in code_planes.items())
            return self._whole_on_mesh((cols, base))

        cols, base = series_keyed(b.get_column(adj.key_col), key, deps, build)
        if own:
            cols = dict(cols)
            for c in vals[adj.name]:
                cols[c] = b.get_column(c).to_device_cached(cap, f32=True)
            for c in codes[adj.name]:
                src = b.get_column(c)
                cols[_code_column(c)] = (cached_dict_code_plane(
                    src, src.dict_codes()[0], b.num_rows, cap), None)
        return cols, base

    def _host_ok_plane(self, adj: DimSpec, sub_dims: Sequence[DimSpec], cap: int):
        """bool[cap] device plane in `adj`'s row space: a row passes the
        subtree's HOST-evaluated filters (host_visible, carried through the
        chain); None where the subtree has none. These are the filters whose
        values no program takes (LIKE, functions): the slot is keyed on their
        skeletons, holds their values and is rebuilt on a new one."""
        filtered = [d for d in sub_dims if self._host_filters[d.name]]
        if not filtered:
            return None
        hostf = [f for d in filtered for f in self._host_filters[d.name]]
        skels, lits = exprs_structure(hostf)
        deps = tuple(self.batches[d.name].get_column(c) for d in filtered
                     for f in self._host_filters[d.name]
                     for c in f.referenced_columns()) + self._subtree_deps(sub_dims)

        def build():
            ok = None
            for d in filtered:
                rows = self.batches[d.name].num_rows
                padded = np.zeros(pad_bucket(rows), dtype=bool)
                padded[:rows] = self.host_visible(d)
                plane = jnp.asarray(padded)
                v, m = self._at_root(adj, d, plane, jnp.ones_like(plane))
                ok = (v & m) if ok is None else (ok & v & m)
            return self._whole_on_mesh(ok)

        return series_keyed(
            self.batches[adj.name].get_column(adj.key_col),
            ("hostok", self._chain_shape(sub_dims), cap) + skels + self._mesh_key(),
            deps, build, literals=lits)

    def verdict_plane(self, adj: DimSpec):
        """float32[cap_d] device plane, 1.0 where a row of the adjacent dim
        `adj` lets a fact row through (its filters, its chained dims' and the
        chain's links; padding rows 0.0), whole on every device; None where
        the subtree has no filter and no chain (`idx >= 0` says it all).
        Made ONCE A QUERY, at the first dispatch that asks, by one call of the
        subtree's visibility program with this query's literal values; kept
        by this context and nowhere else, so nothing is cached a value."""
        if adj.name in self._verdicts:
            return self._verdicts[adj.name]
        sub_dims = self._subtree(adj)
        filtered = any(self._dev_filters[d.name] or self._host_filters[d.name]
                       for d in sub_dims)
        verdict = None
        if filtered or len(sub_dims) > 1:
            rows = self.batches[adj.name].num_rows
            cap = pad_bucket(rows)
            with profile_span("join.filter", "device", dims=len(sub_dims),
                              rows=rows) as sp:
                lowered = [self._lowered_filter(d, f) for d in sub_dims
                           for f in self._dev_filters[d.name]]
                structure = exprs_structure(lowered)
                program, slots = _visibility_program(lowered, structure, sub_dims)
                cols, base = self._filter_planes(adj, sub_dims, cap)
                host_ok = self._host_ok_plane(adj, sub_dims, cap)
                if sp is not None:
                    sp.args["slots"] = slots.n_args
                counters.bump("join_filter_literal_args", slots.n_args)
                verdict = program(cols, base, host_ok,
                                  slots.with_run_values(slots.pack(structure[1])))
        self._verdicts[adj.name] = verdict
        return verdict

    def query_pack(self, adj: DimSpec, pack):
        """(what a dispatch gathers from for `adj`, the row of it that holds
        the query's verdict): `pack` (packed_plane's matrix, or None) with the
        verdict plane as one more row, the verdict alone as a one-row matrix
        where the dim hands on no column, the pack as it is (None) where the
        subtree has no filter and no chain, or (None, None) for a plain
        existence check. The stacked copy is made once a query and pack by
        this context (0.9 ms on the chip for `orders`' five rows of 2^24 at
        SF10), so the provisioning program gathers ONE matrix a dimension,
        the rows the pack carried while it held the verdict itself: laid
        under the pack's window inside the program instead, the chip's
        compiler no longer kept the window in fast memory and a q5 dispatch
        of eight segments took 0.67 ms longer (PERF.md section 6, PR 45)."""
        verdict = self.verdict_plane(adj)
        if verdict is None:
            return pack, None
        key = (adj.name, id(pack))
        if key not in self._query_packs:    # (once a query, not a dispatch)
            with profile_span("join.query_pack", "device", dim=adj.name,
                              rows=0 if pack is None else int(pack.shape[0])):
                self._query_packs[key] = (pack, verdict[None, :] if pack is None
                                          else _stack_verdict(pack, verdict))
        return self._query_packs[key][1], 0 if pack is None else pack.shape[0]

    def _gathers_lines(self, mat) -> bool:
        """Whether a dispatch that reads the whole of `mat` (a pack longer
        than a window, the fact not ordered by its dimension's key) gathers
        from the pack laid as lines (_pack_lines, _gather_lines) and not from
        the [P, N] matrix itself: a pack too long for the chip's fast memory
        (_FAST_PACK_BYTES), on one chip (over a mesh the packs are laid out
        whole on every chip as they are)."""
        return self.mesh is None and mat.nbytes > _FAST_PACK_BYTES

    def _lines_of(self, adj: DimSpec, mat):
        """`mat` (what query_pack gives for `adj`) as lines (_pack_lines), made
        once a query and matrix by this context: the stacked copy that holds
        the query's verdict is a query's own, so its lines are too."""
        key = (adj.name, "lines", id(mat))
        if key not in self._query_packs:    # (once a query, not a dispatch)
            with profile_span("join.pack_lines", "device", rows=int(mat.shape[1]),
                              bytes=int(mat.nbytes)):
                self._query_packs[key] = (mat, _pack_lines(mat))
        return self._query_packs[key][1]

    def _permuted_fact_plane(self, series, bucket: int, perm) -> dev.DCol:
        """Resident fact plane reordered by the group-sorted permutation —
        one device gather, cached per (series, perm) identity."""
        pperm_np, pdev = perm

        def build():
            v, m = series.to_device_cached(bucket, f32=True)
            return _gather_col(v, m, pdev)

        return series_keyed(series, ("permplane", bucket), (pperm_np,), build)

    def provision(self, batch, bucket: int, needed: Sequence[str],
                  codes: Optional[_CodePlan] = None, perm=None):
        """All device columns for one fact batch: fact planes resident; ONE
        packed row-gather per adjacent dim serves every dim value/code plane
        plus the join-validity mask, inside ONE call of the traced
        provisioning program (_provision_program). Returns (dcols, combined
        group codes, None unless `codes` asks for the dictionary strategy's).
        With `perm` every plane comes back in group-sorted row order (the
        locally-dense aggregation layout) at no extra per-batch gathers.

        The host's part is the look-ups that find the arrays (every one a
        cache hit on a repeat query) and the layout key; no array is touched
        outside the program. Over a mesh (set_mesh) `bucket` is the batch's
        padded rows over all shards (bucket_for) and what comes back is
        row-sharded: every device ran the program on its shard."""
        with profile_span("join.gather", "device", planes=len(needed)):
            return self._provision(batch, bucket, needed, codes, perm)

    def _provision(self, batch, bucket: int, needed: Sequence[str],
                   codes: Optional[_CodePlan], perm):
        spec = self.spec
        gb_cols, radices, cap, fact_code_planes = codes or _CodePlan((), (), 0, {})
        adj_of: Dict[str, int] = {}
        mats, idxs, ok_rows, windows, layouts, lines = [], [], [], [], [], []
        ndev = self.mesh_devices
        # rows of a device's share that read one window: a segment's
        window = self.window_rows(bucket)
        unwindowed = 0
        for a, adj in enumerate(self._adjacent()):
            adj_of[adj.name] = a
            didx, span = self.dev_idx(batch, adj.name, bucket, perm=perm)
            idxs.append(didx)
            pack, layout, code_layout, wide = \
                self.packed_plane(adj, needed, gb_cols) or (None, {}, {}, {})
            mat, ok_row = self.query_pack(adj, pack)
            mats.append(mat)
            ok_rows.append(ok_row)
            # a window as long as the segment serves it where the matched
            # rows lie that close and the pack is longer than one window
            windows.append(mat is not None and span is not None
                           and span < window < mat.shape[1])
            # the whole of a pack longer than a window: a fact not ordered by
            # this dimension's key
            whole = mat is not None and not windows[-1] and mat.shape[1] > window
            lines.append(mat.shape[0] if whole and self._gathers_lines(mat) else 0)
            if lines[-1]:
                mats[-1] = self._lines_of(adj, mat)
            unwindowed += whole
            layouts.append((layout, code_layout, wide))

        dcols: Dict[str, dev.DCol] = {}
        if perm is None:
            # the batch's own columns as they are: one request for all
            own = [name for name in needed if spec.col_side.get(name) == "fact"
                   and name not in spec.fact_synthetic]
            if self.mesh is None:
                dcols, _codes = batch_planes(batch, own, bucket, True)
            else:
                with profile_span("join.shard", "device", devices=ndev,
                                  planes=len(own), bucket=bucket):
                    dcols, _codes = batch_planes(batch, own, bucket, True,
                                                 self.mesh)
        columns = []
        for name in needed:
            side = spec.col_side.get(name)
            if side == "fact":
                if name in spec.fact_synthetic:
                    plane = self._fact_membership_plane(batch, bucket, name)
                    if perm is not None:
                        plane = self._permuted_membership(batch, bucket, name,
                                                          perm)
                    dcols[name] = plane
                elif perm is not None:
                    dcols[name] = self._permuted_fact_plane(
                        batch.get_column(name), bucket, perm)
                continue
            if name == "__join_ok__" or side is None:
                continue
            a = adj_of[self._root_of(side)]
            layout, _code_layout, wide = layouts[a]
            if name in wide:        # digit rows, then the validity row
                columns.append((name, a, tuple(wide[name][:-1]), wide[name][-1]))
            else:
                vi, mi = layout[name]
                columns.append((name, a, (vi,), mi))

        code_cols = []
        fact_codes = []
        for name, radix in zip(gb_cols, radices):
            side = spec.col_side.get(name)
            if side == "fact":
                code_cols.append((-1, len(fact_codes), radix))
                fact_codes.append(fact_code_planes[name])
            else:
                a = adj_of[self._root_of(side)]
                code_cols.append((a, layouts[a][1][name], radix))

        prog = _provision_program(_ProvisionLayout(
            tuple(ok_rows), tuple(windows), tuple(columns), tuple(code_cols), cap,
            ndev, window if window < bucket // ndev else 0,
            tuple(lines) if any(lines) else ()))
        gathered, combined = prog(tuple(mats), tuple(idxs), tuple(fact_codes))
        counters.bump("join_provision_calls")
        if any(windows):
            counters.bump("join_window_gathers", windows.count(True))
        # the complement: a fact not ordered by this dimension's key reads the
        # whole of a pack that is longer than a window
        if unwindowed:
            counters.bump("join_unwindowed_gathers", unwindowed)
        dcols.update(gathered)
        return dcols, combined

    def device_cols(self, batch, bucket: int, needed: Sequence[str]) -> Dict[str, dev.DCol]:
        dcols, _codes = self.provision(batch, bucket, needed)
        return dcols


# ======================================================================================
# runs: grouped + ungrouped over joined columns
# ======================================================================================


class _FactorizedCodes:
    """Cached host factorize of the joined group keys: dense ids, the
    gathered key Series, and per-group first-occurrence rows. The device
    codes plane, the group-sorted permutation layout (locally-dense path),
    key tuples and sort-rank planes all materialize lazily (a TopN run
    touches only K winners out of possibly millions of groups, and the
    permuted path never uploads the unpermuted codes plane at all)."""

    def __init__(self, cap: int, group_ids: np.ndarray, n: int, bucket: int,
                 key_series, first_idx: np.ndarray):
        self.cap = cap
        self.group_ids = group_ids
        self.n = n
        self.bucket = bucket
        self.key_series = key_series          # gathered to fact length
        self.first_idx = first_idx            # group -> first fact row
        self._dcodes = None
        self._perm = None
        self._perm_dev = None
        self._full_rows = None
        self._rank_planes: Dict[int, object] = {}

    def device_nbytes(self) -> int:
        """Residency-manager accounting hook: device planes here materialize
        LAZILY after the entry is stored, so the manager re-measures on every
        cache hit via this hook."""
        from ..device.residency import device_nbytes

        lazy = [self._dcodes, self._perm_dev,
                list(self._rank_planes.values())]
        if self._perm is not None:
            lazy.extend(self._perm[1:])  # local codes + seg_lo device arrays
        return device_nbytes(lazy)

    @property
    def dcodes(self):
        if self._dcodes is None:
            codes = np.full(self.bucket, self.cap, dtype=np.int32)
            codes[:self.n] = self.group_ids
            self._dcodes = jnp.asarray(codes)
        return self._dcodes

    def perm_layout(self):
        """(pperm np, pperm device, local_codes device, seg_lo device)."""
        if self._perm is None:
            from .grouped_stage import build_permuted_layout

            pperm, local, seg_lo = build_permuted_layout(
                self.group_ids, self.n, self.bucket)
            self._perm = (pperm, local, seg_lo)
            self._perm_dev = jnp.asarray(pperm)
        pperm, local, seg_lo = self._perm
        return pperm, self._perm_dev, local, seg_lo

    @property
    def num_groups(self) -> int:
        return len(self.first_idx)

    def rows_for(self, gids) -> List[tuple]:
        """Key tuples for the given group ids (vectorized takes)."""
        gids = np.asarray(gids, dtype=np.int64)
        take = self.first_idx[gids]
        return list(zip(*[s.take(take).to_pylist() for s in self.key_series])) \
            if len(gids) else []

    def full_rows(self) -> List[tuple]:
        if self._full_rows is None:
            self._full_rows = self.rows_for(np.arange(self.num_groups))
        return self._full_rows

    def rank_plane(self, key_index: int):
        """f32[cap] device plane: each group's ORDER RANK for one key column
        (rank of its value in the column's natural ascending order, computed
        on host where any dtype sorts exactly; nulls rank last and carry a
        separate validity plane). Cached per key column."""
        if key_index not in self._rank_planes:
            s_first = self.key_series[key_index].take(self.first_idx)
            n = len(s_first)
            rank, valid = _dense_ranks(s_first)
            plane = np.full(self.cap, float(self.cap), dtype=np.float32)
            plane[:n] = rank.astype(np.float32)
            vplane = np.zeros(self.cap, dtype=bool)
            vplane[:n] = valid
            self._rank_planes[key_index] = (jnp.asarray(plane),
                                            jnp.asarray(vplane))
        return self._rank_planes[key_index]


class _LazyKeyRows:
    """List-like view over _FactorizedCodes key tuples (index + bulk)."""

    def __init__(self, fc: _FactorizedCodes):
        self.fc = fc

    def __len__(self) -> int:
        return self.fc.num_groups

    def __getitem__(self, g: int) -> tuple:
        return self.fc.rows_for([g])[0]

    def rows_for(self, gids) -> List[tuple]:
        return self.fc.rows_for(gids)


def _joined_stage_schema(spec: JoinAggSpec) -> Schema:
    return Schema(list(spec.schema.fields) + [Field("__join_ok__", DataType.bool())])


def _with_join_ok(predicate: Optional[Expression]) -> Expression:
    ok = ColumnRef("__join_ok__")
    return ok if predicate is None else (predicate & ok)


def _fact_dictionary(s) -> tuple:
    """(values, K) of the dictionary of a fact column's rows, kept on the
    ROWS (series_keyed: the residency manager's lineage), not on the object:
    Series._dict_codes lives on the Series, and a range of a resident table
    is a new object every query, so a join grouped by a fact column (TPC-H
    q12: `l_shipmode`) encoded every range anew on the host every query,
    1.15 s of a 1.26 s q12 at SF10 (PERF.md, PR 49). The codes themselves
    are the resident plane (cached_dict_code_plane), made from the same
    encoding where it has to be built."""
    return series_keyed(s, ("factdict",), (), lambda: tuple(s.dict_codes()[1:]))


def _dict_code_product(ctx: _JoinContext, batch, gb_cols) -> Optional[int]:
    """Product of per-column dictionary cardinalities (host, cached), or
    None when a groupby column cannot dictionary-encode."""
    total = 1
    for name in gb_cols:
        side = ctx.spec.col_side.get(name)
        try:
            if side == "fact":
                _v, k = _fact_dictionary(batch.get_column(name))
            else:
                _c, _v, k = ctx._dim_source(side, name).dict_codes()
        except Exception:  # lint: ignore[broad-except] -- estimate only; caller treats None as unknown
            return None
        total *= max(k, 1)
    return total


def _groupby_columns(stage: GroupedAggStage) -> List[str]:
    """The joined schema's column each group-by expression names."""
    return [(g.child if isinstance(g, Alias) else g)._name for g in stage.groupby]


def note_join_mesh_dispatch(n_devices: int, stage_noted: bool = False) -> None:
    """One join dispatch whose fact rows were sharded over `n_devices` > 1
    local devices (`stage_noted`: the stage's own run has counted it as a
    mesh dispatch already)."""
    counters.bump("device_join_mesh_batches")
    counters.bump("device_join_mesh_shards", n_devices)
    if not stage_noted:
        note_mesh_dispatch(n_devices)


class DeviceJoinGroupedRun(GroupedAggRun):
    """GroupedAggRun over gather-joined columns: same jitted programs, same
    finalize/merge — only column provisioning and group codes differ. With
    `mesh_devices` > 1 a dispatch's fact rows are sharded over that many
    local devices, each runs the one chip's programs on its shard
    (_JoinContext.set_mesh, GroupedAggStage._program_for) and the result
    holds one table a shard, merged on the host like successive batches'.
    The sharded dispatch takes the dictionary strategy's group codes only
    (sharded_join_reason: the executor asks before it makes the run)."""

    # group-count ceiling for the non-TopN grouped path: the full cap-sized
    # table is fetched at finalize, so cap is bounded by d2h budget, not
    # compute (TopN-fused runs raise this — they fetch K rows)
    max_segments = 1 << 16

    def __init__(self, stage: GroupedAggStage, ctx: _JoinContext,
                 mesh_devices: int = 1):
        super().__init__(stage, _join_stage_literals(ctx.spec), mesh_devices)
        self.ctx = ctx
        ctx.set_mesh(self.mesh_devices)

    # TopN runs force the host-factorize path (dense first-occurrence ids
    # double as the stable tie-break and feed the rank planes)
    force_host_codes = False

    def feed_batch(self, batch) -> None:
        """One fact batch through the fused program.

        Group-code strategy (VERDICT r4 next #1): per-column dictionary codes
        radix-combined on device while the code PRODUCT stays under the
        matmul ceiling; otherwise the joined key rows factorize on host
        (true group count — correlated brand x brand_id products collapse),
        riding the matmul table below 4096 groups and the host-permuted
        locally-dense reduction above it. All host work and uploads are
        series_keyed-cached, so reps pay only gathers + the program.
        """
        stage = self.stage
        n = batch.num_rows
        if n == 0:
            return
        ndev, mesh = self.mesh_devices, self.ctx.mesh
        bucket = self.ctx.bucket_for(n)
        needed = list(stage._input_cols) + ["__join_ok__"]
        gb_cols = _groupby_columns(stage)

        total = None
        if not self.force_host_codes:
            with profile_span("join.codes", "host", strategy="dict",
                              step="product"):
                total = _dict_code_product(self.ctx, batch, gb_cols)
        with profile_span("device.dispatch", "device", op="join_agg",
                          rows=n, bucket=bucket):
            if total is not None and 0 < total <= min(self.max_segments,
                                                      MAX_MATMUL_SEGMENTS):
                with profile_span("join.codes", "host", strategy="dict") as sp:
                    decode, codes = self._dict_code_plan(batch, n, bucket,
                                                         gb_cols)
                    if sp is not None:
                        sp.args["cap"] = decode.cap
                dcols, decode.dcodes = self.ctx.provision(batch, bucket, needed,
                                                          codes=codes)
                decode.shards = ndev
                prog, form = stage._program_for(decode.cap, mesh_devices=ndev)
                mask = device_row_mask(n, bucket, mesh)
                lit_args = self.literals.args((self._row_offset,))
                with profile_span("device.launch", "device", op="join_agg",
                                  cap=decode.cap, reduce=form, devices=ndev):
                    out = prog(dcols, decode.dcodes, mask, lit_args)
                count_reduce(form)
            elif mesh is not None:
                raise DeviceFallback(
                    "the sharded join dispatch takes dictionary group codes only")
            else:
                with profile_span("join.codes", "host", strategy="host") as sp:
                    decode = self._host_factorized_codes(batch, n, bucket)
                    if sp is not None:
                        sp.args["cap"] = decode.cap
                        if decode.permuted:
                            sp.args["strategy"] = "host_permuted"
                if decode.permuted:
                    if stage._sct_specs or stage._use_f64:
                        # statically incompatible with the local-dense program:
                        # bail BEFORE dispatching the packed gathers
                        raise DeviceFallback(
                            "local-dense path cannot serve 64-bit scatter "
                            "extremes / f64-exact stages")
                    _pp, pdev, _l, _s = decode.fact_codes.perm_layout()
                    dcols, _ = self.ctx.provision(batch, bucket, needed,
                                                  perm=(decode.pperm, pdev))
                    prog = stage._jit_local(decode.cap)
                    mask = device_row_mask(n, bucket)
                    # (the host supplies this layout's first rows: the
                    # program reads no row offset)
                    lit_args = self.literals.args((self._row_offset,))
                    with profile_span("device.launch", "device",
                                      op="join_agg_local", cap=decode.cap):
                        out = prog(dcols, decode.local_codes, decode.seg_lo,
                                   mask, lit_args)
                else:
                    dcols, _ = self.ctx.provision(batch, bucket, needed)
                    prog, form = stage._program_for(decode.cap)
                    mask = device_row_mask(n, bucket)
                    lit_args = self.literals.args((self._row_offset,))
                    with profile_span("device.launch", "device", op="join_agg",
                                      cap=decode.cap, reduce=form):
                        out = prog(dcols, decode.dcodes, mask, lit_args)
                    count_reduce(form)
        decode.row_offset = float(self._row_offset)
        self._row_offset += n
        self._pending.append((out, decode))
        counters.bump("device_grouped_batches")
        counters.bump("device_join_batches")
        if ndev > 1:
            note_join_mesh_dispatch(ndev)

    def _dict_code_plan(self, batch, n: int, bucket: int, gb_cols):
        """The host's side of the dictionary strategy: each group-by column's
        dictionary and radix, and the resident code planes of the fact-side
        columns (dim codes ride the packed row-gather). Returns the _Decode,
        still without its codes, and the _CodePlan from which the
        provisioning program radix-combines the planes on the device."""
        ctx = self.ctx
        spec = ctx.spec
        dicts = []           # (values, K) per group-by column
        fact_codes: Dict[str, object] = {}
        for name in gb_cols:
            side = spec.col_side.get(name)
            if side == "fact":
                s = batch.get_column(name)
                values, k = _fact_dictionary(s)
                fact_codes[name] = cached_dict_code_plane(
                    s, lambda s=s: s.dict_codes()[0], n, bucket, ctx.mesh)
            else:
                _codes, values, k = ctx._dim_source(side, name).dict_codes()
            dicts.append((values, k))
        radices = []
        mult = 1
        for _, k in reversed(dicts):
            radices.append(mult)
            mult *= max(k, 1)
        radices.reverse()
        cap = _pad_groups(mult)
        return (_Decode(cap=cap, dcodes=None, dicts=dicts, radices=radices,
                        key_rows=None),
                _CodePlan(tuple(gb_cols), tuple(radices), cap, fact_codes))

    def _host_factorized_codes(self, batch, n: int, bucket: int) -> _Decode:
        """Joined-key group codes via host factorize over the static join
        indices. Returns dense codes (cap = padded TRUE group count) and
        first-occurrence key tuples. All host arrays + the device codes plane
        are series_keyed-cached; phantom groups from join-miss rows carry
        rows=0 and are dropped at finalize."""
        ctx = self.ctx
        spec = ctx.spec
        idxs = ctx.indices_for(batch)
        from ..core.series import Series

        key_cols = []    # per groupby col: (side, source Series)
        for g in self.stage.groupby:
            node = g.child if isinstance(g, Alias) else g
            name = node._name
            side = spec.col_side.get(name)
            if side == "fact":
                key_cols.append(("fact", batch.get_column(name)))
            else:
                dim_b = ctx.batches[side]
                src = ctx.syn_series[side][name] if name.startswith("__syn_") \
                    else dim_b.get_column(name)
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
                else:
                    idx = idxs[side]
                    if len(s) == 0:
                        series.append(Series.from_pylist([None] * n, s.name,
                                                         dtype=s.dtype))
                        miss_marks.append(np.ones(n, dtype=bool))
                    else:
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
            num_groups = len(first_idx)
            key_series = series[:len(key_cols)]
            cap = _pad_groups(max(num_groups, 1))
            return _FactorizedCodes(cap, group_ids.astype(np.int64, copy=False),
                                    n, bucket, key_series, first_idx)

        fc = series_keyed(
            anchor,
            ("jfact", bucket) + tuple(repr(g) for g in self.stage.groupby),
            deps, build)
        if fc.cap > self.max_segments:
            raise DeviceFallback(
                f"joined group count {fc.cap} exceeds the "
                f"{'TopN' if self.max_segments > (1 << 16) else 'full-fetch'} "
                f"ceiling {self.max_segments}")
        if fc.cap > MAX_MATMUL_SEGMENTS:
            # locally-dense path: host-permuted rows, no codes-plane upload
            pperm, _pdev, local, seg_lo = fc.perm_layout()
            return _Decode(cap=fc.cap, dcodes=None, dicts=None, radices=None,
                           key_rows=_LazyKeyRows(fc), fact_codes=fc,
                           local_codes=local, seg_lo=seg_lo,
                           host_firsts=np.asarray(fc.first_idx, np.float64),
                           pperm=pperm)
        return _Decode(cap=fc.cap, dcodes=fc.dcodes, dicts=None, radices=None,
                       key_rows=_LazyKeyRows(fc), fact_codes=fc)


# What the two TopN ceilings bound. A run whose group ids hold for one batch
# (host factorization, DeviceJoinTopNRun's one-batch form) builds a table of
# its batch's padded group count: TOPN_MAX_SEGMENTS bounds that table and the
# device sort over it. A run whose ids hold for the whole run (a dimension's
# rows) builds ONE set of tables of the dimension's padded row count, whatever
# the number of batches: TOPN_RUN_MAX_SEGMENTS bounds, by HBM, the ids A CHIP
# combines and selects over at the run's end (at 2^25 ids a sum's two float32
# planes are 268 MB, and q3's three sums, its first-row table and the
# select's operands come to about 1.7 GB). One chip selects over the whole
# table; over a mesh of N every chip adds its shard's rows into a table of
# its own of all the ids (40 bytes an id for q3 while it accumulates, 2.7 GB
# at 2^26: the sums' pairs, the first rows and the dispatches' float32
# partial a plane, which is dropped before the select; 28 bytes then), the chips
# exchange slices at the end and each selects over ids / N, so the ceiling
# is held to that share. Neither ceiling bounds a fetch: both forms bring
# back K rows (a chip).
TOPN_MAX_SEGMENTS = 1 << 22
TOPN_RUN_MAX_SEGMENTS = 1 << 25


@dataclass
class TopNSpec:
    """ORDER BY ... LIMIT lowering for the fused device program.

    keys: (kind, index, descending, nulls_first) per sort column — kind "agg"
    indexes spec.aggregations (the plane is computed on device from the group
    tables), kind "group" indexes spec.groupby (the plane is a host-computed
    order-rank, exact for any dtype including strings)."""
    keys: List[Tuple[str, int, bool, bool]]
    limit: int
    offset: int


def _agg_sort_plane(stage: GroupedAggStage, mm_col, out, agg_idx: int):
    """(value f64[cap], valid bool[cap]) ordering plane for one aggregation,
    computed ON DEVICE from the group tables (mirrors
    grouped_stage.results_from_tables; f64 is ample for ordering). `mm_col(j)`
    is the matmul table's plane j; `out` holds the "ext" and "sct" tables."""
    slots = stage._agg_slots[agg_idx]
    _name, agg = stage.aggs[agg_idx]
    count_all = agg.op == "count" and agg.params.get("mode", "valid") == "all"
    cnt = mm_col(0) if count_all else mm_col(slots["count"][1])
    if agg.op == "count":
        return cnt, jnp.ones(cnt.shape, dtype=bool)
    valid = cnt > 0
    if agg.op in ("sum", "mean"):
        sl = slots["sum"]
        if sl[0] == "imm":
            _k, base, nd, lo = sl
            s = jnp.zeros(cnt.shape, dtype=jnp.float64)
            for k in range(nd):
                s = s + mm_col(base + k) * float(1 << (8 * k))
            s = s + float(lo) * cnt
        elif sl[0] == "mm":
            s = mm_col(sl[1])
        else:
            s = out["sct"][sl[1]].astype(jnp.float64)
        return (s / jnp.maximum(cnt, 1.0) if agg.op == "mean" else s), valid
    sl = slots[agg.op]
    plane = out["ext"][sl[1]] if sl[0] == "ext" else out["sct"][sl[1]]
    return plane.astype(jnp.float64), valid


def _dense_ranks(s) -> Tuple[np.ndarray, np.ndarray]:
    """(int64 rank of each row's value in the column's natural ascending
    order, validity), computed on the host where any dtype sorts exactly.
    DENSE: equal values MUST share a rank, or ties would never reach the next
    sort key. A null row's rank is 0 and means nothing."""
    n = len(s)
    valid = s.validity_numpy()
    rank = np.zeros(n, dtype=np.int64)
    dense = None
    try:
        vals = s.to_numpy()
        if vals.dtype.kind in "biufM":
            _u, inv = np.unique(vals[valid], return_inverse=True)
            dense = inv
    except Exception:  # lint: ignore[broad-except] -- falls back to python comparison
        dense = None
    if dense is None:  # strings/objects: python comparison
        arr = s.to_pylist()
        vv = [arr[i] for i in range(n) if valid[i]]
        order = {v: r for r, v in enumerate(sorted(set(vv)))}
        dense = np.asarray([order[v] for v in vv], dtype=np.int64)
    rank[valid] = dense
    return rank, valid


# rows a block of the select holds: each block is sorted on its own and gives
# its first K on, level after level, so a table of 2^24 ids is 65,536 sorts of
# 256, then 2,560 and 100 of them, then one of 1,000 rows, not one sort of
# 2^24. Short blocks for the chip's compiler: a five-operand sort of blocks of
# 4,096 took it 70-100 s, of 256 a third of that (PR 38)
_SELECT_BLOCK = 256


def select_top(operands: tuple, num_keys: int, k: int) -> tuple:
    """The first `k` rows of `operands` in the order of a stable multi-key
    sort on the first `num_keys` of them (jax.lax.sort's), found without
    sorting the whole: a row among the first k of the whole is among the
    first k of its block, so blocks are sorted alone (one sort along the
    minor axis), each hands on its first k, and the survivors are sorted."""
    n = operands[0].shape[0]
    block = _SELECT_BLOCK
    while block < 4 * k:
        block *= 2
    while n >= 2 * block and n % block == 0:
        rows = n // block
        operands = jax.lax.sort(tuple(o.reshape(rows, block) for o in operands),
                                dimension=1, num_keys=num_keys)
        operands = tuple(o[:, :k].reshape(-1) for o in operands)
        n = rows * k
    operands = jax.lax.sort(tuple(operands), num_keys=num_keys)
    return tuple(o[:k] for o in operands)


class RunWideGroups(NamedTuple):
    """A group-id space that holds for a whole run: the rows of one dimension
    (`dim`), whose unique join key is among the group-by columns while every
    other group-by column is `dim`'s own or a dimension's chained from it. A
    fact row's group id is then `dim`'s row index for it, which the join
    resolves anyway (_JoinContext.dev_idx): RAW row indices, not ranks among
    the rows that pass `dim`'s filters, so the tables are as long as the
    dimension is, padded (orders at SF10: 2^24 ids), and no batch is ever
    factorized on the host."""
    dim: DimSpec
    cols: tuple     # per group-by column: (dimension holding it, its column there)


def run_wide_groups(spec: JoinAggSpec) -> Tuple[Optional[RunWideGroups], str]:
    """(the run-wide id space of `spec`'s group-by, "") or (None, why not)."""
    by_name = {d.name: d for d in spec.dims}
    names = []
    for g in spec.groupby:
        node = g.child if isinstance(g, Alias) else g
        names.append(node._name)
    sides = [spec.col_side.get(c) for c in names]
    if any(s not in by_name for s in sides):
        return None, "a group-by column is the fact's"

    def under(dname: str, root: str) -> bool:
        while dname != root:
            parent = by_name[dname].parent[0]
            if parent == "fact":
                return False
            dname = parent
        return True

    for d in spec.dims:
        # the key itself, or the column it was joined on (q10 groups by
        # o_custkey, which the join made equal to customer's key)
        keyed = [(c, s) for c, s in zip(names, sides)
                 if (s == d.name and c == d.key_col)
                 or (s == d.parent[0] and c == d.parent[1])]
        if not keyed:
            continue
        cols = tuple((d.name, d.key_col) if (c, s) in keyed else (s, c)
                     for c, s in zip(names, sides))
        if all(under(s, d.name) for s, _c in cols):
            return RunWideGroups(d, cols), ""
    return None, "no dimension's key with its own columns spans the group-by"


def topn_run_wide(ctx: _JoinContext, stage: GroupedAggStage,
                  mesh_devices: int = 1
                  ) -> Tuple[Optional[RunWideGroups], int, str]:
    """(id space, its tables' length, "") where a fused TopN over `ctx` keeps
    run-wide tables, else (None, 0, why it is held to one fact batch). The
    run and the placement decision both ask here, so what is priced is what
    runs. Over `mesh_devices` > 1 a chip selects over its share of the ids,
    and the ceiling is held to that."""
    groups, why = run_wide_groups(ctx.spec)
    if groups is None:
        return None, 0, why
    why = stage.run_wide_reason()
    if why:
        return None, 0, why
    cap = pad_bucket(max(ctx.batches[groups.dim.name].num_rows, 1))
    if cap // max(mesh_devices, 1) > TOPN_RUN_MAX_SEGMENTS:
        return None, 0, (f"the dimension's {cap} padded rows are over the "
                         f"run-wide table ceiling {TOPN_RUN_MAX_SEGMENTS}")
    return groups, cap, ""


def host_ids_reason(ctx: _JoinContext, stage, grouped: bool, topn: bool,
                    batch, mesh_devices: int = 1) -> str:
    """Why a join over `ctx` makes its group ids on the host a batch at a
    time ("" where it does not): a grouped aggregate whose group-by does not
    dictionary-encode under the matmul ceiling (host-factorized codes, the
    locally dense layout: a permutation and a table as long as the batch's
    own groups), and a fused TopN whose ids hold for one batch only. Such a
    run keeps today's dispatch: it is not sharded over a mesh
    (sharded_join_reason), and over a resident fact its coalescer flushes a
    bucket at a time, not DISPATCH_SEGMENTS (executor._run_device_join).
    `batch` is a fact batch (the dictionaries of fact-side group columns are
    read from it)."""
    if not grouped:
        return ""
    if topn:
        return topn_run_wide(ctx, stage, mesh_devices)[2]
    total = _dict_code_product(ctx, batch, _groupby_columns(stage))
    if total is None or not 0 < total <= min(DeviceJoinGroupedRun.max_segments,
                                             MAX_MATMUL_SEGMENTS):
        return "the group codes need a host factorization of every batch"
    return ""


def sharded_join_reason(ctx: _JoinContext, stage, grouped: bool, topn: bool,
                        batch, mesh_devices: int) -> str:
    """Why a join over `ctx` cannot run as the one chip's dispatch on every
    shard of a mesh of `mesh_devices` ("" where it can: the runs of this file
    then take `mesh_devices`). What is declined needs ids made on the host a
    batch at a time, which the sharded dispatch never makes
    (host_ids_reason). And a join under a forced Pallas hash probe
    (pallas_mode "on"): that kernel probes a whole batch on one chip. A
    declined join is not over the mesh: it runs on one chip, with this
    reason in the rejection log (executor._run_device_join)."""
    from ..config import execution_config

    if getattr(execution_config(), "pallas_mode", "auto") == "on":
        return "a forced Pallas hash probe runs on one chip"
    return host_ids_reason(ctx, stage, grouped, topn, batch, mesh_devices)


class DeviceJoinTopNRun(DeviceJoinGroupedRun):
    """Join + grouped aggregate + ORDER BY + LIMIT as one device pipeline:
    the group tables never leave the device — a selection over the table-long
    planes picks the K winners and ONLY their rows are fetched. This is what
    makes orderkey-cardinality groupbys (TPC-H q3/q10: millions of groups)
    device-viable: the full-table d2h that rules out the plain grouped path
    shrinks to K rows.

    Two forms, by the group-by (run_wide_groups). Where it spans one
    dimension's key space the ids are that dimension's rows and hold for the
    run: the fact may come in any number of batches, every dispatch adds its
    batch into ONE set of tables kept on the device
    (GroupedAggStage._build_run_wide), and no batch is factorized. Any other
    group-by keeps the older form: ids from the host factorization of ONE
    batch (dense ids in first-occurrence order double as the stable
    tie-break), a second batch raises DeviceFallback.

    With `mesh_devices` > 1 (the run-wide form only) a dispatch's fact rows
    are sharded over that many local devices and every chip adds its shard
    into tables of its own, as long as the one chip's. At the finalize the
    chips exchange the tables by slices of the ids (an all_to_all over
    MESH_AXIS: a chip receives every chip's rows of ITS slice and adds them
    up), each selects its slice's first K, and the host merges the K rows a
    chip by the sort operands the chips sorted on, first-row positions
    included, so ties fall as on one chip."""

    max_segments = TOPN_MAX_SEGMENTS
    force_host_codes = True

    def __init__(self, stage: GroupedAggStage, ctx: _JoinContext, topn: TopNSpec,
                 mesh_devices: int = 1):
        super().__init__(stage, ctx, mesh_devices)
        self.topn = topn
        self.groups, self._cap, why = topn_run_wide(ctx, stage, self.mesh_devices)
        # why this run is held to one fact batch ("" where it is not)
        self.one_batch_reason = why
        self._tables = None
        self._batches = 0

    @property
    def run_wide(self) -> bool:
        return self.groups is not None

    def feed_batch(self, batch) -> None:
        if self.run_wide:
            return self._feed_run_wide(batch)
        if batch.num_rows and (self._pending or self.mesh_devices > 1):
            # bail BEFORE dispatching work the finalize would throw away (the
            # one-batch form is one chip's: sharded_join_reason)
            raise DeviceFallback(
                "device TopN holds this group-by to a single fact batch: "
                + self.one_batch_reason)
        super().feed_batch(batch)
        counters.bump("device_join_topn_batches")

    def _feed_run_wide(self, batch) -> None:
        """One fact batch into the run's tables: the dimension's index plane
        of the batch IS the ids (a cache hit on a repeat query), so the
        host's part is the look-ups and two launches, whatever the batch's
        length: over a resident fact it is DISPATCH_SEGMENTS morsels glued
        (a device's share of it, over a mesh), which the two programs walk a
        segment at a time (_provision_program, GroupedAggStage._build_run_wide)."""
        stage, ctx = self.stage, self.ctx
        n = batch.num_rows
        if n == 0:
            return
        ndev = self.mesh_devices
        bucket = ctx.bucket_for(n)
        needed = list(stage._input_cols) + ["__join_ok__"]
        with profile_span("device.dispatch", "device", op="join_topn",
                          rows=n, bucket=bucket):
            with profile_span("join.codes", "host", strategy="dim_rows",
                              cap=self._cap):
                gid, _span = ctx.dev_idx(batch, self.groups.dim.name, bucket)
            dcols, _ = ctx.provision(batch, bucket, needed)
            prog = stage._jit_run_wide(self._cap, ndev, ctx.segment_rows)
            mask = device_row_mask(n, bucket, ctx.mesh)
            lit_args = self.literals.args((self._row_offset,))
            if self._tables is None:
                # once a run, on the dispatching thread
                with profile_span("join.tables", "device", cap=self._cap,
                                  devices=ndev) as sp:
                    self._tables = stage.run_wide_tables(self._cap, ndev)
                    nbytes = sum(int(x.nbytes) for x in
                                 jax.tree_util.tree_leaves(self._tables))
                    counters.bump("device_topn_table_bytes", nbytes)
                    if sp is not None:
                        sp.args["bytes"] = nbytes
            with profile_span("device.launch", "device", op="join_topn",
                              cap=self._cap, reduce="run_wide", devices=ndev):
                self._tables = prog(self._tables, dcols, gid, mask, lit_args)
        self._row_offset += n
        self._batches += 1
        counters.bump("device_grouped_batches")
        counters.bump("device_join_batches")
        counters.bump("device_join_topn_batches")
        if ndev > 1:
            note_join_mesh_dispatch(ndev)

    def finalize_topn(self):
        """(key_rows, agg_results) for the K winners, in final output order."""
        with profile_span("stage.finalize", "host", op="join_topn") as sp:
            key_rows, results = self._finalize_run_wide() if self.run_wide \
                else self._finalize_topn()
            if sp is not None:
                sp.args["groups"] = len(key_rows)
            return key_rows, results

    def _empty(self):
        counters.bump("device_stage_runs")
        return [], [(np.empty(0), np.empty(0, dtype=bool))
                    for _ in self.stage.aggs]

    # ---- the run-wide form ---------------------------------------------------------
    def _group_series(self, index: int):
        """Group-by column `index` as a Series over the rows of the id
        space's dimension (its own column, or a chained dimension's gathered
        through the chain on the host, dimension-long, once)."""
        ctx, root = self.ctx, self.groups.dim
        dname, col = self.groups.cols[index]
        src = ctx._dim_source(dname, col)
        if dname == root.name:
            return src
        chain = []
        d = next(dd for dd in ctx.dims if dd.name == dname)
        while d.name != root.name:
            chain.append(d)
            d = next(dd for dd in ctx.dims if dd.name == d.parent[0])
        idxs = tuple(ctx.dim_space_idx(child) for child in reversed(chain))

        def build():
            rows = np.arange(ctx.batches[root.name].num_rows, dtype=np.int64)
            ok = np.ones(len(rows), dtype=bool)
            for idx in idxs:
                step = idx[np.clip(rows, 0, max(len(idx) - 1, 0))] if len(idx) \
                    else np.full(len(rows), -1, dtype=np.int64)
                ok &= step >= 0
                rows = np.where(ok, step, 0)
            if len(src) == 0:
                from ..core.series import Series

                return Series.from_pylist([None] * len(rows), src.name, dtype=src.dtype)
            # a row the chain does not reach joins nothing: its value is never read
            return src.take(rows)

        return series_keyed(src, ("jtopn_col", root.name, root.key_col, dname),
                            idxs, build)

    def _rank_plane(self, index: int, desc: bool, nulls_first: bool):
        """int32[cap] device plane: each id's place in the order of group-by
        column `index` (dense ranks, negated for a descending key; nulls and
        the padding at the end their `nulls_first` asks for). Built once a
        dimension column and direction, resident after."""
        cap = self._cap
        s = self._group_series(index)
        mesh = self.ctx.mesh

        def build():
            rank, valid = _dense_ranks(s)
            if desc:
                rank = -rank
            edge = np.int64(-(1 << 30) if nulls_first else (1 << 30))
            plane = np.full(cap, edge, dtype=np.int32)
            plane[:len(rank)] = np.where(valid, rank, edge).astype(np.int32)
            # over a mesh: a chip holds the ranks of the ids it selects over
            return jnp.asarray(plane) if mesh is None else shard_rows(mesh, plane, cap)

        return series_keyed(s, ("jtopn_rank", cap, desc, nulls_first)
                            + self.ctx._mesh_key(), (), build)

    def _select_program(self, k: int):
        """The jitted finalize of the run-wide form: the sort operands from
        the tables, the selection, and the K winners' rows."""
        stage, cap, keys = self.stage, self._cap, tuple(self.topn.keys)
        ndev = self.mesh_devices
        key = ("topn_select", cap, k, keys) + self.ctx._mesh_key()
        if key in stage._jitted:
            return stage._jitted[key]

        def operands_of(mm_col, first, ranks):
            """The select's sort operands over one span of ids, in order."""
            present = mm_col(0) > 0
            operands = [jnp.where(present, 0, 1).astype(jnp.int32)]
            ranks = list(ranks)
            for kind, idx, desc, nf in keys:
                if kind == "group":
                    operands.append(ranks.pop(0))
                    continue
                v, valid = _agg_sort_plane(stage, mm_col, None, idx)
                if desc:
                    v = -v
                operands.append(jnp.where(valid, v, -jnp.inf if nf else jnp.inf))
            # ties: the order the groups were first seen in, as the host
            # engine's stable sort over its first-occurrence output
            operands.append(first)
            return present, operands

        def select(tables, ranks):
            note_program_trace()
            hi, lo, first = tables["hi"], tables["lo"], tables["first"][:cap]

            def mm_col(j):
                # a sum is two float32 planes (grouped_stage._build_run_wide)
                return hi[j][:cap].astype(jnp.float64) + lo[j][:cap].astype(jnp.float64)

            present, operands = operands_of(mm_col, first, ranks)
            gid = jnp.arange(cap, dtype=jnp.int32)
            top = select_top(tuple(operands) + (gid,), len(operands), k)[-1]
            rows = [hi[j][top].astype(jnp.float64) + lo[j][top].astype(jnp.float64)
                    for j in range(len(hi))]
            return top, jnp.stack(rows, axis=-1), present[top], tables["dense"]

        part = cap // ndev      # the ids a chip of the mesh combines and selects over

        def combine_and_select(tables, ranks):
            """On every chip of the mesh: its own tables in, its slice's K out."""
            note_program_trace()

            def mine(x):
                # [every chip, my slice of the ids]: each chip's rows of it
                return jax.lax.all_to_all(x[:cap].reshape(ndev, part), MESH_AXIS, 0, 0)

            hi = [mine(h) for h in tables["hi"]]
            lo = [mine(l) for l in tables["lo"]]
            first = jnp.min(mine(tables["first"]), axis=0)
            # a chip's share of a sum is a float32 pair: the pairs add up in
            # float64, as the one chip's select reads its own pair
            cols = [(h.astype(jnp.float64) + l.astype(jnp.float64)).sum(axis=0)
                    for h, l in zip(hi, lo)]
            present, operands = operands_of(lambda j: cols[j], first, ranks)
            base = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * part
            gid = base + jnp.arange(part, dtype=jnp.int32)
            chosen = select_top(tuple(operands) + (gid,), len(operands), min(k, part))
            top = chosen[-1]
            rows = jnp.stack([c[top - base] for c in cols], axis=-1)
            return top, rows, present[top - base], tuple(chosen[:-1]), tables["dense"]

        if ndev > 1:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            select = shard_map(combine_and_select, mesh=self.ctx.mesh,
                               in_specs=(P(MESH_AXIS), P(MESH_AXIS)),
                               out_specs=P(MESH_AXIS), check_vma=False)
        stage._jitted[key] = jax.jit(select)
        return stage._jitted[key]

    def _finalize_run_wide(self):
        stage = self.stage
        tables, self._tables = self._tables, None
        batches, self._batches = self._batches, 0
        self._row_offset = 0
        if tables is None:
            return self._empty()
        k_eff = min(self.topn.offset + self.topn.limit, self._cap)
        ranks = tuple(self._rank_plane(idx, desc, nf)
                      for kind, idx, desc, nf in self.topn.keys if kind == "group")
        ndev = self.mesh_devices
        fetched_rows = int(k_eff)
        with profile_span("join.topn_select", "device", cap=self._cap,
                          rows=int(k_eff), batches=batches) as sp:
            # the select programs take the leaves they were compiled for (25-28
            # s each on a machine's first process: their text stays as it was);
            # what the accumulate program counted beside them rides the same fetch
            counted = {n: tables.pop(n) for n in ("compact", "ordered", "folds")}
            # (the dispatches' partial is all zeros and the select's memory now)
            del tables["part"]
            if ndev == 1:
                fetch = self._select_program(k_eff)(tables, ranks)
            else:
                # what crosses between the chips: of every chip's tables, the
                # slices of the ids the other chips select over
                moved = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(
                    (tables["hi"], tables["lo"], tables["first"]))) * (ndev - 1) // ndev
                with profile_span("join.combine", "device", devices=ndev,
                                  cap=self._cap, bytes=moved):
                    # (the wait is the fetch's anyway: it is counted here, with
                    # the collective and each chip's select)
                    fetch = jax.block_until_ready(
                        self._select_program(k_eff)(tables, ranks))
                counters.bump("device_topn_combine_bytes", moved)
            with profile_span("device.d2h", "device", op="join_topn",
                              rows=int(k_eff)):
                fetch, counted = jax.device_get((fetch, counted))
            if ndev == 1:
                gids, mm_rows, present_rows, dense = fetch
            else:
                # K rows a chip, each chip's in its own order: merged by the
                # operands the chips sorted on (np.lexsort's last key leads)
                gids, mm_rows, present_rows, operands, dense = fetch
                fetched_rows = len(gids)
                order = np.lexsort(tuple(reversed(operands)))[:k_eff]
                gids, mm_rows, present_rows = (
                    np.asarray(x)[order] for x in (gids, mm_rows, present_rows))
            # (a count a chip)
            compact_batches, ordered_batches, folds = (
                int(np.sum(counted[n])) // ndev for n in ("compact", "ordered", "folds"))
            if sp is not None:
                sp.args["dense_batches"] = int(np.sum(dense)) // ndev
                sp.args["compact_batches"] = compact_batches
                sp.args["ordered_batches"] = ordered_batches
                sp.args["folds"] = folds
        del tables
        counters.bump("join_topn_compact_batches", compact_batches)
        counters.bump("join_topn_ordered_batches", ordered_batches)
        counters.bump("join_topn_folds", folds)
        counters.bump("device_stage_runs")
        counters.bump("device_topn_runs")
        counters.bump("device_topn_fetched_rows", fetched_rows)

        off = self.topn.offset
        keep = np.asarray(present_rows)[off:]
        gids = np.asarray(gids)[off:][keep].astype(np.int64)
        mm_rows = np.asarray(mm_rows, dtype=np.float64)[off:][keep]
        from .grouped_stage import results_from_tables

        key_cols = [self._group_series(i).take(gids).to_pylist()
                    for i in range(len(self.groups.cols))]
        key_rows = list(zip(*key_cols)) if len(gids) else []
        results = results_from_tables(stage, mm_rows, [np.zeros(len(gids))], [])
        return key_rows, results

    # ---- the one-batch form --------------------------------------------------------
    def _finalize_topn(self):
        stage = self.stage
        pending, self._pending = self._pending, []
        self._row_offset = 0
        if not pending:
            return self._empty()
        if len(pending) > 1:
            raise DeviceFallback(
                "device TopN path requires a single fact batch")
        out, decode = pending[0]
        fc = decode.fact_codes
        if fc is None:
            raise DeviceFallback("device TopN needs host-factorized codes")
        cap = decode.cap
        k_eff = min(self.topn.offset + self.topn.limit, cap)

        mm = out["mm"]
        with profile_span("join.topn_select", "device", cap=cap, rows=int(k_eff),
                          batches=1):
            present = mm[:, 0] > 0
            operands = [jnp.where(present, 0.0, 1.0).astype(jnp.float32)]
            for kind, idx, desc, nf in self.topn.keys:
                if kind == "agg":
                    v, valid = _agg_sort_plane(stage, lambda j: mm[:, j], out, idx)
                else:
                    v, valid = fc.rank_plane(idx)
                    v = v.astype(jnp.float64)
                if desc:
                    v = -v
                v = jnp.where(valid, v, -jnp.inf if nf else jnp.inf)
                operands.append(v)
            gid = jnp.arange(cap, dtype=jnp.int32)
            sorted_ops = jax.lax.sort(tuple(operands) + (gid,),
                                      num_keys=len(operands) + 1)
            top = sorted_ops[-1][:k_eff]
            fetch = (top, mm[top],
                     tuple(e[top] for e in out["ext"]),
                     tuple(s[top] for s in out["sct"]),
                     present[top])
            with profile_span("device.d2h", "device", op="join_topn", rows=int(k_eff)):
                gids, mm_rows, ext_rows, sct_rows, present_rows = jax.device_get(fetch)
        counters.bump("device_stage_runs")
        counters.bump("device_topn_runs")
        counters.bump("device_topn_fetched_rows", int(k_eff))

        off = self.topn.offset
        keep = np.asarray(present_rows)[off:]
        gids = np.asarray(gids)[off:][keep]
        mm_rows = np.asarray(mm_rows, dtype=np.float64)[off:][keep]
        ext_rows = [np.asarray(e, dtype=np.float64)[off:][keep]
                    for e in ext_rows]
        sct_rows = [np.asarray(s)[off:][keep] for s in sct_rows]
        from .grouped_stage import results_from_tables

        key_rows = fc.rows_for(gids)
        results = results_from_tables(stage, mm_rows, ext_rows, sct_rows)
        return key_rows, results


def try_capture_join_topn(plan):
    """Match TopN <- [pure-column Project]* <- Aggregate <- star-join tree.

    Returns (JoinAggSpec, TopNSpec, out_map) or None; out_map maps each output
    column of the TopN schema to ("agg"|"group", index) for final assembly.
    Reference contrast: the host engine runs sinks/top_n.rs over the
    aggregate's output stream — here the whole tail fuses into the join+agg
    device program and only K rows come back."""
    from ..plan import logical as lp

    projections: List[Dict[str, str]] = []
    src = plan.input
    for _ in range(4):
        if isinstance(src, lp.Project):
            mapping: Dict[str, str] = {}
            for p in src.projection:
                inner = p.child if isinstance(p, Alias) else p
                if not isinstance(inner, ColumnRef):
                    return None
                mapping[p.name()] = inner._name
            projections.append(mapping)
            src = src.input
        else:
            break
    if not isinstance(src, lp.Aggregate) or not src.groupby:
        return None
    jspec = try_capture_join_agg(src)
    if jspec is None:
        return None

    def resolve(name: str) -> str:
        for m in projections:  # outermost first
            name = m.get(name, name)
        return name

    agg_names = [a.name() for a in jspec.aggregations]
    gb_names = [g.name() for g in jspec.groupby]
    keys: List[Tuple[str, int, bool, bool]] = []
    for e, desc, nf in zip(plan.sort_by, plan.descending, plan.nulls_first):
        node = e.child if isinstance(e, Alias) else e
        if not isinstance(node, ColumnRef):
            return None
        nm = resolve(node._name)
        if nm in agg_names:
            keys.append(("agg", agg_names.index(nm), bool(desc), bool(nf)))
        elif nm in gb_names:
            keys.append(("group", gb_names.index(nm), bool(desc), bool(nf)))
        else:
            return None
    if plan.limit < 0 or plan.limit + plan.offset > 4096:
        return None
    out_map: List[Tuple[str, int]] = []
    for f in plan.schema:
        nm = resolve(f.name)
        if nm in agg_names:
            out_map.append(("agg", agg_names.index(nm)))
        elif nm in gb_names:
            out_map.append(("group", gb_names.index(nm)))
        else:
            return None
    return jspec, TopNSpec(keys, plan.limit, plan.offset), out_map


class DeviceJoinUngroupedRun(FilterAggRun):
    """FilterAggRun over gather-joined columns; with `mesh_devices` > 1 a
    dispatch's fact rows are sharded over that many local devices
    (_JoinContext.set_mesh) and the partials come back one a shard."""

    def __init__(self, stage: FilterAggStage, ctx: _JoinContext,
                 mesh_devices: int = 1):
        super().__init__(stage, _join_stage_literals(ctx.spec), mesh_devices)
        self.ctx = ctx
        ctx.set_mesh(self.mesh_devices)

    def feed_batch(self, batch) -> None:
        n = batch.num_rows
        if n == 0:
            return
        bucket = self.ctx.bucket_for(n)
        # one `device.dispatch` a join dispatch, the provisioning (`join.*`)
        # and the launch inside it, as the grouped run's: the readers count a
        # join's dispatches by the `device.dispatch` spans that hold a `join.*`
        with profile_span("device.dispatch", "device", op="join_filter_agg",
                          rows=n, bucket=bucket):
            dcols = self.ctx.device_cols(
                batch, bucket, list(self.stage._input_cols) + ["__join_ok__"])
            self._launch(dcols, n, bucket, self.ctx.mesh)
        counters.bump("device_join_batches")
        if self.mesh_devices > 1:
            note_join_mesh_dispatch(self.mesh_devices, stage_noted=True)   # (by _run)


_JOINED_CARD_SAMPLE = 65536


def estimate_joined_cardinality(ctx: _JoinContext, batch, groupby) -> int:
    """Sampled cardinality of the joined group key: a STRIDED sample (clustered
    keys — orderkey-sorted facts — would saturate a head sample) of the key
    tuples gathered through the real join indices; extrapolated proportionally
    when near-saturated (can then only over-estimate, which biases toward the
    safe reject). Cached per (key series, idx) identity."""
    n = batch.num_rows
    m = min(n, _JOINED_CARD_SAMPLE)
    if m == 0:
        return 1
    idxs = ctx.indices_for(batch)
    spec = ctx.spec

    sources = []          # (side, series) per groupby col
    for g in groupby:
        node = g.child if isinstance(g, Alias) else g
        name = node._name
        side = spec.col_side.get(name)
        if side == "fact":
            sources.append(("fact", batch.get_column(name)))
        else:
            src = ctx.syn_series[side][name] if name.startswith("__syn_") \
                else ctx.batches[side].get_column(name)
            sources.append((side, src))

    anchor = ctx.fact_anchor(batch)
    deps = tuple(s for _sd, s in sources) + tuple(
        idxs[sd] for sd, _s in sources if sd != "fact")

    def build():
        # true even spread over [0, n): arange's integer stride degenerates to
        # a head sample for n < 2m, exactly the clustered-key case to avoid
        take_rows = np.unique(np.linspace(0, n - 1, m).astype(np.int64))
        cols = []
        for side, s in sources:
            if side == "fact":
                cols.append(s.take(take_rows).to_pylist())
            else:
                idx = idxs[side][take_rows]
                if len(s) == 0:
                    cols.append([None] * len(take_rows))
                else:
                    safe = np.clip(idx, 0, len(s) - 1)
                    vals = s.take(safe).to_pylist()
                    cols.append([v if i >= 0 else None
                                 for v, i in zip(vals, idx)])
        k = len(set(zip(*cols))) if cols else 1
        if n > len(take_rows) and k > len(take_rows) // 2:
            k = max(k, int(k * n / len(take_rows)))
        return max(k, 1)

    return series_keyed(anchor,
                        ("jcard",) + tuple(repr(g) for g in groupby),
                        deps, build)


def _join_stage_literals(spec: JoinAggSpec) -> tuple:
    """The literal values of one execution of build_join_stage(spec)'s
    stage: what its runs pass to the stage's programs."""
    from .stage import stage_literals

    return stage_literals(_with_join_ok(spec.predicate), spec.aggregations)


def build_join_stage(spec: JoinAggSpec):
    """(stage, grouped) with __join_ok__ folded into the predicate."""
    schema = _joined_stage_schema(spec)
    predicate = _with_join_ok(spec.predicate)
    if spec.groupby:
        stage = try_build_grouped_agg_stage(schema, predicate, spec.groupby,
                                            spec.aggregations)
        return stage, True
    from .stage import try_build_filter_agg_stage

    stage = try_build_filter_agg_stage(schema, predicate, spec.aggregations)
    return stage, False
