"""Device grouped-aggregation stage: chunked segment reduction without scatters.

The TPU answer to hash-table grouped aggregation (reference:
src/daft-local-execution/src/sinks/grouped_aggregate.rs). Design, driven by
how a TPU behaves (see ops/costmodel.py):

- **Reduction = masked sums or a matmul, not scatter.** TPU scatter-adds
  serialize. Rows are processed in chunks under ``lax.scan``; a chunk's f32
  [groups x planes] table is, for a handful of groups, one masked sum a cell
  on the VPU ("select"), and for hundreds to 4,096 a one-hot [chunk x groups]
  matrix times the value planes on the MXU ("matmul"; _reduce_form chooses
  from the group capacity). Per-chunk f32 partial tables are combined into an
  f64 accumulator, bounding float error to one chunk (~1e-6 relative) while
  keeping all heavy work in f32 (TPU f64 is software-emulated).
- **Each resident plane is read once.** The loop runs over tiles of the
  *input* planes (views of the resident arrays, 128 rows to a line as the
  chip tiles them); predicate, agg children, segment ids and planes are
  evaluated for the tile inside the step that reduces them, so no array of
  the bucket's length but the inputs exists (_build; measured in PERF.md §6,
  PR 31: q1 at SF10 56 ms -> 9 ms of device time).
- **Group codes come from per-column dictionaries, not per-query factorize.**
  When the group keys are plain columns, each key column is dictionary-encoded
  once per Series (cached — resident tables never re-factorize; see
  Series.dict_codes) and the combined segment id ``c0*K1 + c1`` is computed on
  device. Arbitrary key expressions fall back to per-batch host factorize.
- **min/max = chunked masked broadcasts** (no scatter): per chunk,
  ``where(onehot, v, ±inf).min(axis=rows)``; int/temporal extremes accumulate
  in f64 (exact to 2^53), floats in f32. The first row of a group (the
  stream's group order) is the least int32 position, widened at [groups].
- **Integer sums are exact**: 8-bit digit planes whose chunk partials stay
  under 2^24 (_classify_planes); 64-bit extremes keep the one scatter left
  (rare in practice and priced by the cost model).
- **One fetch per run.** feed_batch only *dispatches* (async); every per-batch
  result stays on device until finalize(), which fetches all pending tables in
  a single device_get, so the run pays the d2h round trip exactly once.

- **Several chips run the same program.** A run started with
  ``mesh_devices`` > 1 shards each batch's planes by rows over a mesh of
  local devices (stage.over_shards); every shard runs the program above at
  its own bucket and returns its [groups x planes] partial; finalize merges
  the shards' tables on the host like those of successive batches. No
  collective, no second implementation (_over_mesh).

Static shapes: rows pad to power-of-two buckets, the group table pads to a
power-of-two capacity, with one trash segment for filtered/padding rows. The
jit cache is bounded by O(log rows · log groups) per stage structure.

Like ops/stage.py, the compiled program (GroupedAggStage, cached process-wide)
is separated from per-run accumulator state (GroupedAggRun via start_run()), so
failed or interrupted runs can never corrupt subsequent runs of the same query.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from ..expressions.expressions import AggExpr, Alias, ColumnRef, Expression
from ..observability.runtime_stats import profile_span
from ..schema import Schema
from . import counters
from . import device_eval as dev
from .stage import (MESH_AXIS, _LiteralBinding, batch_planes, compile_stage_exprs,
                    device_aggs, device_row_mask, local_mesh, mesh_total, note_mesh_dispatch,
                    note_program_trace, over_shards, pad_bucket, shard_rows,
                    stage_cache_key, stage_structure, unwrap_aggs)

_MIN_GROUP_CAP = 8
# segment-count ceiling for the matmul path: beyond this the one-hot FLOPs and
# chunk materialization outgrow the win (high-cardinality groupbys go host-side
# via the cost model)
MAX_MATMUL_SEGMENTS = 4096
# sort-based segmented-reduction path ceiling (argsort + segmented scan):
# far past the matmul ceiling; bounded by device memory for the cap-sized
# output tables, not FLOPs
MAX_SORT_SEGMENTS = 1 << 20


class DeviceFallback(Exception):
    """Raised (before any device dispatch) when a stage's runtime shape is
    outside the device kernel's envelope; the executor reruns on host."""


def _pad_groups(g: int) -> int:
    c = _MIN_GROUP_CAP
    while c < g:
        c <<= 1
    return c


_F64_EXACT_KINDS = frozenset({"int8", "int16", "int32", "uint8", "uint16",
                              "uint32", "date", "bool"})


def _f64_exact_dtype(dt) -> bool:
    """True when every value of this dtype is exactly representable in f64
    (so extreme-plane reductions cannot round): <= 32-bit ints, dates, bools."""
    return dt.kind in _F64_EXACT_KINDS


def _static_int_bounds(e) -> Optional[Tuple[int, int]]:
    """Static (lo, hi) value bounds of an integer expression, or None.

    Interval arithmetic over literals / if_else / + - * / casts — enough to
    prove the common CASE-WHEN-1-ELSE-0 aggregation shapes tiny so their
    bit-slice sum needs one digit plane instead of eight."""
    from ..expressions.expressions import (Alias, BinaryOp, Cast, IfElse,
                                           Literal)

    if isinstance(e, Alias):
        return _static_int_bounds(e.child)
    if isinstance(e, Cast):
        b = _static_int_bounds(e.child)
        if b is None:
            return None
        # a narrowing cast can WRAP at runtime, putting values outside the
        # child's bounds — only pass bounds through when they fit the target
        rng = {"int8": (-128, 127), "int16": (-32768, 32767),
               "int32": (-2**31, 2**31 - 1), "int64": (-2**63, 2**63 - 1),
               "uint8": (0, 255), "uint16": (0, 65535),
               "uint32": (0, 2**32 - 1), "uint64": (0, 2**64 - 1)}.get(
                   getattr(e.dtype, "kind", None))
        if rng is None or b[0] < rng[0] or b[1] > rng[1]:
            return None
        return b
    if isinstance(e, Literal):
        if isinstance(e.value, bool):
            return (int(e.value), int(e.value))
        if isinstance(e.value, int):
            return (e.value, e.value)
        return None
    if isinstance(e, IfElse):
        a = _static_int_bounds(e.if_true)
        b = _static_int_bounds(e.if_false)
        if a is None or b is None:
            return None
        return (min(a[0], b[0]), max(a[1], b[1]))
    if isinstance(e, BinaryOp) and e.op in ("add", "sub", "mul"):
        a = _static_int_bounds(e.left)
        b = _static_int_bounds(e.right)
        if a is None or b is None:
            return None
        if e.op == "add":
            return (a[0] + b[0], a[1] + b[1])
        if e.op == "sub":
            return (a[0] - b[1], a[1] - b[0])
        corners = [x * y for x in a for y in b]
        return (min(corners), max(corners))
    return None


def _isum_digit(v, kind: str):
    """One 8-bit digit plane of an int sum (kind = "isum<k>:<lo>"): shift the
    offset int64 value and mask a byte. Arithmetic >> keeps two's complement,
    so with lo=0 the 8-digit sum reconstructs sum mod 2^64 exactly. Digit
    values are < 256, so f32 chunk partials stay exact."""
    head, lo = kind.split(":")
    k = int(head[len("isum"):])
    vi = jnp.round(v).astype(jnp.int64) if jnp.issubdtype(v.dtype, jnp.floating) \
        else v.astype(jnp.int64)
    u = vi - jnp.int64(int(lo))
    return ((u >> (8 * k)) & 255).astype(jnp.float32)


def resolve_key_series(batch, groupby, n: int):
    """Evaluate group-key expressions, resolving Alias(ColumnRef) to the
    underlying stored column so dictionary/device caches land on the
    long-lived Series rather than a per-eval rename() copy."""
    from ..expressions.eval import eval_expression, _broadcast

    out = []
    for e in groupby:
        node = e.child if isinstance(e, Alias) else e
        if isinstance(node, ColumnRef):
            s = batch.get_column(node._name)
        else:
            s = eval_expression(batch, e)
        if len(s) == 1 and n != 1:
            s = _broadcast(s, n)
        out.append(s)
    return out


_CARD_SAMPLE_ROWS = 8192


def _count_distinct(s) -> int:
    """Distinct values of a (small) Series, nulls one value: in Arrow where
    the column is Arrow's, so a key column of every morsel of a stream is
    not turned into Python objects to be counted."""
    if s._pyobjs is None:
        try:
            import pyarrow.compute as pc

            return int(pc.count_distinct(s.to_arrow(), mode="all").as_py())
        except Exception:  # lint: ignore[broad-except] -- a type Arrow cannot hash: count in Python
            pass
    return len(set(s.to_pylist()))


def estimate_key_cardinality(key_series) -> int:
    """Cheap lower-bound estimate of the combined group-key cardinality from the
    first _CARD_SAMPLE_ROWS rows (cached per Series). A sample can only
    under-count, so the dict path re-checks the exact product after encoding;
    the point here is to reject obviously high-cardinality keys (orderkey-like)
    BEFORE paying a full factorize + unique-value materialization."""
    total = 1
    for s in key_series:
        cached = getattr(s, "_dict_codes", None)
        if cached is not None:
            k = cached[2]
        else:
            head = s.head(_CARD_SAMPLE_ROWS)
            k = _count_distinct(head)
            if len(s) > _CARD_SAMPLE_ROWS and k > _CARD_SAMPLE_ROWS // 2:
                # sample is near-saturated: extrapolate proportionally
                k = max(k, int(k * (len(s) / _CARD_SAMPLE_ROWS)))
        total *= max(k, 1)
        if total > MAX_MATMUL_SEGMENTS * 16:
            return total
    return total


def _chunk_for(bucket: int, cap: int) -> int:
    """Rows whose f32 partial table is summed before the f64 combine: at most
    65,536 (an 8-bit digit's partial stays under 2^24, a float sum's rounding
    stays one chunk's), halved while a chunk's one-hot (chunk x cap+1 f32,
    which only the matmul form of _reduce_form builds) is over 32MB, never
    below 512 rows, never above the bucket."""
    c = 65536
    while c * (cap + 1) * 4 > (1 << 25) and c > 512:
        c >>= 1
    return min(c, bucket)


# group capacity up to which a chunk is reduced by one masked sum a group and
# plane on the VPU; above it by the one-hot product on the MXU. Measured on
# the chip over 2^24 rows (PERF.md §6, PR 31), select / matmul in ms: 11
# planes 2.9 / 10.3 at cap 8, 5.7 / 10.7 at 16, 7.9 / 11.1 at 32, 25.0 / 12.6
# at 64; 3 planes 1.8 / 3.3, 3.1 / 3.3, 4.6 / 4.1, 17.0 / 5.7
SELECT_MAX_GROUPS = 16
# chunks a loop step of the select form takes together: a step of one chunk
# costs q1 at SF10 51.8 ms, of four 13.2, of sixteen 8.5, of sixty-four 10.1
_SELECT_STEP_CHUNKS = 16
# rows to a line of a tile, as the chip lays a plane out
_LANES = 128


def _reduce_form(cap: int) -> str:
    """How a chunk's rows become its [cap, planes] table, from the group
    capacity alone: "select" (for each group, a masked sum of each plane: the
    VPU's work grows with cap x planes and no one-hot exists) for a handful
    of groups, "matmul" (one-hot [chunk, cap+1] times planes [chunk, P] at
    Precision.HIGHEST: the MXU's) for the rest up to MAX_MATMUL_SEGMENTS."""
    return "select" if cap <= SELECT_MAX_GROUPS else "matmul"


def count_reduce(form: str) -> None:
    """One dispatch of _build's program, counted under its reduce form."""
    if form == "select":
        counters.bump("device_grouped_reduce_select")
    elif form == "matmul":
        counters.bump("device_grouped_reduce_matmul")


def _counts_all(agg: AggExpr) -> bool:
    return agg.op == "count" and agg.params.get("mode", "valid") == "all"


class GroupedAggStage:
    """Compiled filter→grouped-agg program (immutable; see start_run()).

    Like FilterAggStage, compiled for the skeleton of the predicate and of
    the aggregates' inputs: their literals' values are arguments of every
    program (dev.LiteralSlots), and the expressions kept here serve for their
    structure alone. What of a value does set the structure is in the cache
    key (try_build_grouped_agg_stage): which inputs are the same expression
    (they share planes) and an integer sum's static bounds (its digit
    planes). The group keys are evaluated on the host, values and all."""

    def __init__(self, schema: Schema, predicate: Optional[Expression],
                 groupby: Sequence[Expression], aggs: Sequence[Tuple[str, AggExpr]]):
        self.schema = schema
        self.predicate = predicate
        self.groupby = list(groupby)
        self.aggs = list(aggs)
        self._jitted: Dict[Tuple[int, int], Callable] = {}
        self._input_cols = self._referenced_columns()
        # group keys qualify for the device dictionary path iff they are bare columns
        self.dict_keys = all(isinstance(g, ColumnRef) or
                             (isinstance(g, Alias) and isinstance(g.child, ColumnRef))
                             for g in groupby)
        # float min/max must be EXACT (downstream equality joins against the
        # aggregate — TPC-H Q2/Q15 shapes — would otherwise never match): such
        # stages run wholly in f64, trading the f32 fast path for host parity
        self._use_f64 = any(
            agg.op in ("min", "max")
            and agg.child.to_field(schema).dtype.is_floating()
            for _n, agg in self.aggs)
        self._slot_exprs = ([] if predicate is None else [predicate]) \
            + [agg.child for _n, agg in self.aggs]
        # a launch's row offset (the rows the run fed before it) rides with
        # the literals' values: one small transfer a launch for both
        self.slots = dev.LiteralSlots(
            self._slot_exprs, jnp.float64 if self._use_f64 else jnp.float32,
            run_values=1)
        self._classify_planes()

    def _classify_planes(self) -> None:
        """Assign each aggregation's partials to matmul / extreme / scatter slots.

        mm plane 0 is always the kept-row count ("rows"): it decides group
        existence and serves count(mode=all). Every agg also reads a
        valid-count plane (validity of the result = count > 0, matching host
        semantics). Planes that are the same by construction are reduced
        once: one count plane and one sum plane (or digit group) a distinct
        child expression, whatever the data; a spec names the first agg that
        asked, and every agg's slots point at the shared plane (TPC-H q1: 16
        planes -> 11).

        Integer sums ride the MXU as EXACT 8-bit bit-slice planes ("isum"):
        v mod 2^24 split into three 8-bit digits plus a negative-count plane,
        each digit's 64Ki-row chunk partial staying under 2^24 (f32-exact) and
        the f64 table accumulation exact below 2^53; the host recombines
        sum = d0 + 256*d1 + 65536*d2 - 2^24*negatives with Python ints. This
        replaces the i64 segment_sum scatter, MEASURED ~450ms per 8M-row plane
        on v5e (TPU scatters serialize; int64 is emulated) vs ~2ms of matmuls.
        In f64 mode a single f64 plane is already exact — no slicing. Integer
        extremes use f64 extreme planes (exact to 2^53 — and the f32 upload
        path quantizes past 2^24 anyway) instead of segment_min/max scatters.
        """
        self._mm_specs: List[Tuple[int, str]] = [(-1, "rows")]
        self._ext_specs: List[Tuple[int, str, bool]] = [(-1, "min", True)]  # first-row idx
        self._sct_specs: List[Tuple[int, str]] = []
        self._agg_slots: List[Dict[str, Tuple[str, int]]] = []
        shared: Dict[Tuple[str, str], tuple] = {}
        for i, (_name, agg) in enumerate(self.aggs):
            child_dt = agg.child.to_field(self.schema).dtype
            is_float = child_dt.is_floating()
            child = repr(agg.child)
            slots: Dict[str, Tuple[str, int]] = {}
            if _counts_all(agg):
                slots["count"] = ("mm", 0)
            else:
                if (child, "count") not in shared:
                    shared[child, "count"] = ("mm", len(self._mm_specs))
                    self._mm_specs.append((i, "count"))
                slots["count"] = shared[child, "count"]
            if agg.op in ("sum", "mean"):
                if (child, "sum") in shared:
                    pass
                elif is_float or child_dt.is_boolean() or self._use_f64:
                    shared[child, "sum"] = ("mm", len(self._mm_specs))
                    self._mm_specs.append((i, "sum"))
                else:
                    # exact int sum via bit-slice matmul planes (see above).
                    # Static expression bounds (CASE-of-literals etc.) shrink
                    # the digit count — the q12 shape needs ONE plane; unknown
                    # bounds use all 8 (sum mod 2^64 == true sum when it fits
                    # int64, so no sign-correction plane is needed).
                    bounds = _static_int_bounds(agg.child)
                    if bounds is not None:
                        lo, hi = bounds
                        nd = max(1, (max(hi - lo, 1).bit_length() + 7) // 8)
                    else:
                        lo, nd = 0, 8
                    shared[child, "sum"] = ("imm", len(self._mm_specs), nd, lo)
                    self._mm_specs.extend(
                        [(i, f"isum{k}:{lo}") for k in range(nd)])
                slots["sum"] = shared[child, "sum"]
            elif agg.op in ("min", "max"):
                if is_float or _f64_exact_dtype(child_dt):
                    # extremes ride the chunked broadcast path; f64 planes for
                    # <=32-bit ints/dates (f64 holds them exactly) and for
                    # _use_f64 float stages
                    slots[agg.op] = ("ext", len(self._ext_specs))
                    self._ext_specs.append((i, agg.op,
                                            self._use_f64 or not is_float))
                else:
                    # 64-bit ints/timestamps can exceed 2^53: only the i64
                    # scatter keeps them exact (rare in analytics aggs; the
                    # cost model prices it)
                    slots[agg.op] = ("sct", len(self._sct_specs))
                    self._sct_specs.append((i, agg.op))
            self._agg_slots.append(slots)

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

    def start_run(self, literals: Sequence = (), mesh_devices: int = 1) -> "GroupedAggRun":
        """A fresh accumulator for one execution, whose literal values are
        `literals` (stage.stage_literals of that execution's predicate and
        aggregates); with `mesh_devices` > 1 its dispatches shard each
        batch's rows over that many local devices."""
        return GroupedAggRun(self, literals, mesh_devices)

    def _chunk_planes(self, cap: int, fdt, radices: Tuple[int, ...]) -> Callable:
        """The plane evaluator of _build's loop: for a tile of rows (the
        tile's view of every input), the segment ids with filtered and
        padding rows sent to `cap`, the mm planes in _mm_specs order, and a
        (values, mask) pair for each agg of _ext_specs and _sct_specs. Each
        distinct child expression is evaluated once a tile. With `radices`,
        `codes` is the key columns' dictionary-code planes and the segment id
        their radix sum."""
        pred_fn, fns = compile_stage_exprs(self, fdt)
        # inputs that are the same expression, values and all, are evaluated
        # once, through the first's slots (which inputs are is in the cache key)
        child_key = [repr(agg.child) for _name, agg in self.aggs]
        child_fns: Dict[str, Callable] = {}
        for key, fn in zip(child_key, fns):
            child_fns.setdefault(key, fn)
        mm_specs, ext_specs, sct_specs = self._mm_specs, self._ext_specs, self._sct_specs

        def planes(cols: Dict[str, dev.DCol], codes, row_mask, lits):
            if pred_fn is not None:
                pv, pm = pred_fn(cols, lits)
                keep = pv.astype(bool) & pm & row_mask
            else:
                keep = row_mask
            if radices:
                codes = sum(c * np.int32(r) for c, r in zip(codes, radices))
            seg = jnp.where(keep, codes, cap).astype(jnp.int32)
            seen: Dict[str, tuple] = {}

            def child(agg_idx: int):
                key = child_key[agg_idx]
                if key not in seen:
                    v, m = child_fns[key](cols, lits)
                    v = jnp.broadcast_to(v, jnp.shape(seg))
                    seen[key] = (v, dev._broadcast_valid(v, m) & keep)
                return seen[key]

            mm = []
            for agg_idx, kind in mm_specs:
                if kind == "rows":
                    mm.append(keep.astype(fdt))
                elif kind == "count":
                    mm.append(child(agg_idx)[1].astype(fdt))
                elif kind.startswith("isum"):
                    v, mask = child(agg_idx)
                    mm.append(jnp.where(mask, _isum_digit(v, kind), 0.0)
                              .astype(fdt))
                else:  # float/bool sum
                    v, mask = child(agg_idx)
                    mm.append(jnp.where(mask, v.astype(fdt), 0.0))
            ext = [child(agg_idx) for agg_idx, _op, _f64 in ext_specs[1:]]
            sct = [child(agg_idx) for agg_idx, _kind in sct_specs]
            return seg, mm, ext, sct

        return planes

    def _build(self, cap: int, form: Optional[str] = None,
               radices: Tuple[int, ...] = (), mesh=None) -> Callable:
        """The one-hot tier's program (cap <= MAX_MATMUL_SEGMENTS): ONE loop
        over tiles of the input planes, taken as views of the resident arrays.
        A step evaluates predicate, agg children and planes for its tile only
        (_chunk_planes) and reduces them at once, so no array of `bucket` rows
        but the inputs exists. Each chunk of _chunk_for rows gives an f32
        [cap, P] partial, combined in f64 in the carry; the first-row index is
        the least int32 position of a group's kept rows, widened to f64 (and
        offset by the stream position) at [cap] after the loop; int64 scatter
        slots accumulate exactly a tile at a time.

        How a tile's rows become its table is _reduce_form's choice (`form`
        overrides it for tests): "select" takes _SELECT_STEP_CHUNKS chunks a
        step as a [chunks, rows] tile and reduces along the rows; "matmul"
        takes one chunk a step and contracts its one-hot on the MXU.

        `codes` is the segment-id plane or, with `radices` (the dictionary
        route), the tuple of the key columns' code planes, combined a tile at
        a time like every other input.

        Over `mesh` every device runs this program on its shard of the rows
        (_over_mesh): the loop, its tiles and the [cap, P] partial are one
        chip's at the shard's bucket."""
        fdt = jnp.float64 if self._use_f64 else jnp.float32
        planes_of = self._chunk_planes(cap, fdt, radices)
        ext_specs, sct_specs = self._ext_specs[1:], self._sct_specs
        n_mm = len(self._mm_specs)
        form = form or _reduce_form(cap)
        none = jnp.iinfo(jnp.int32).max  # "no kept row": past every position
        i64 = jnp.iinfo(jnp.int64)
        sct_ident = {"sum": 0, "min": i64.max, "max": i64.min}
        sct_fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                  "max": jax.ops.segment_max}

        def ext_big(op, use_f64):
            return jnp.asarray(jnp.inf if op == "min" else -jnp.inf,
                               jnp.float64 if use_f64 else jnp.float32)

        slots = self.slots

        def stage(cols: Dict[str, dev.DCol], codes, row_mask: jnp.ndarray,
                  lit_args, rows_before=0.0):
            note_program_trace()
            lits = slots.unpack(lit_args)
            row_offset = _row_offset(slots, lit_args, rows_before)
            bucket = row_mask.shape[0]
            chunk = _chunk_for(bucket, cap)
            n_chunks = bucket // chunk
            # rows lie 128 to a line, as the chip tiles them, so a tile is a
            # view of a resident plane and not a copy in another layout
            lanes = min(_LANES, chunk)
            sub = min(_SELECT_STEP_CHUNKS, n_chunks) if form == "select" else 1
            tile = (sub, chunk // lanes, lanes)
            n_steps = n_chunks // sub
            # position of a row in its chunk
            local = (jax.lax.broadcasted_iota(jnp.int32, tile, 1) * lanes
                     + jax.lax.broadcasted_iota(jnp.int32, tile, 2))

            def view(x):
                return x.reshape((n_steps,) + tile)

            def body(carry, xs):
                acc_mm, acc_first, acc_ext, acc_sct = carry
                step, ccols, ccodes, cmask = xs
                seg, mm, ext, sct = planes_of(ccols, ccodes, cmask, lits)
                if form == "select":
                    hits = [seg == g for g in range(cap)]

                    def per_group(x, fill, red):
                        """[cap, sub]: `red` over each chunk's rows of a group."""
                        return jnp.stack([red(jnp.where(hit, x, fill), axis=(1, 2))
                                          for hit in hits])

                    # [cap, P, sub] f32 partials, one a chunk
                    part = jnp.stack([per_group(p, 0.0, jnp.sum) for p in mm], axis=1)
                    acc_mm = acc_mm + part.astype(jnp.float64).sum(axis=-1)
                    first = per_group(local, chunk, jnp.min)
                    base = (step * sub + jnp.arange(sub, dtype=jnp.int32)) * chunk
                    first = jnp.min(jnp.where(first < chunk, first + base, none),
                                    axis=1)

                    def extreme(v, big, red):
                        return red(per_group(v, big, red), axis=1)
                else:
                    # the one-hot keeps the column of the filtered rows: with
                    # a power of two of columns the product ran 2.5 to 5
                    # times slower on the chip (cap 128 to 512; PERF.md §6)
                    oh = seg.reshape(chunk)[:, None] \
                        == jnp.arange(cap + 1, dtype=jnp.int32)[None, :]
                    v = jnp.stack([p.reshape(chunk) for p in mm], axis=-1)
                    # HIGHEST: the MXU's default rounds its inputs to bf16
                    part = jnp.matmul(oh.astype(v.dtype).T, v,
                                      precision=jax.lax.Precision.HIGHEST)
                    acc_mm = acc_mm + part[:cap].astype(jnp.float64)
                    oh = oh[:, :cap]

                    def extreme(x, fill, red):
                        """[cap]: `red` over the chunk's rows of a group."""
                        return red(jnp.where(oh, x.reshape(chunk)[:, None], fill),
                                   axis=0)

                    first = extreme(local, chunk, jnp.min)
                    first = jnp.where(first < chunk, first + step * chunk, none)
                new_ext = []
                for (_i, op, use_f64), (v, mask), acc in zip(ext_specs, ext, acc_ext):
                    big = ext_big(op, use_f64)
                    v = jnp.where(mask, v.astype(big.dtype), big)
                    new_ext.append(jnp.minimum(acc, extreme(v, big, jnp.min))
                                   if op == "min" else
                                   jnp.maximum(acc, extreme(v, big, jnp.max)))
                acc_first = jnp.minimum(acc_first, first)
                # exact int64 partials: the remaining scatters (priced by the
                # cost model), a tile at a time
                new_sct = []
                for (_i, kind), (v, mask), acc in zip(sct_specs, sct, acc_sct):
                    sv = jnp.where(mask, v.astype(jnp.int64),
                                   jnp.asarray(sct_ident[kind], jnp.int64))
                    t = sct_fn[kind](sv.reshape(-1), seg.reshape(-1),
                                     num_segments=cap + 1)[:cap]
                    new_sct.append(acc + t if kind == "sum" else
                                   jnp.minimum(acc, t) if kind == "min" else
                                   jnp.maximum(acc, t))
                return (acc_mm, acc_first, tuple(new_ext), tuple(new_sct)), None

            carry0 = (
                jnp.zeros((cap, n_mm), jnp.float64),
                jnp.full((cap,), none, jnp.int32),
                tuple(jnp.full((cap,), b, b.dtype)
                      for b in (ext_big(op, f) for _i, op, f in ext_specs)),
                tuple(jnp.full((cap,), sct_ident[kind], jnp.int64)
                      for _i, kind in sct_specs))
            xs = (jnp.arange(n_steps, dtype=jnp.int32),
                  {name: (view(v), view(m)) for name, (v, m) in cols.items()},
                  jax.tree_util.tree_map(view, codes), view(row_mask))
            (acc_mm, acc_first, acc_ext, acc_sct), _ = jax.lax.scan(
                body, carry0, xs)
            # group order is first occurrence in the stream: the position in
            # this batch, offset by the rows fed before it (+inf = no row)
            first = jnp.where(acc_first < none,
                              acc_first.astype(jnp.float64) + row_offset, jnp.inf)
            return {"mm": acc_mm, "ext": (first,) + acc_ext, "sct": acc_sct}

        return jax.jit(_over_mesh(stage, mesh))

    def _build_sorted(self, cap: int, mesh=None) -> Callable:
        """High-cardinality path (cap > MAX_MATMUL_SEGMENTS): sort-based
        segmented reduction instead of one-hot matmuls. All ops are
        XLA-native and scatter-free — argsort the segment ids, reduce runs
        with a segmented associative scan (flags reset the accumulator at
        segment boundaries, so sums never suffer global-prefix cancellation),
        and read each segment's total at its end position via searchsorted.
        O(n log n + G) — lifts the r3 VERDICT's 4096-group device ceiling to
        MAX_SORT_SEGMENTS."""
        fdt = jnp.float64 if self._use_f64 else jnp.float32
        pred_fn, fns = compile_stage_exprs(self, fdt)
        child_fns = [(fn, _counts_all(agg)) for fn, (_name, agg) in zip(fns, self.aggs)]
        slots = self.slots

        mm_specs, ext_specs, sct_specs = self._mm_specs, self._ext_specs, self._sct_specs

        def stage(cols: Dict[str, dev.DCol], codes: jnp.ndarray,
                  row_mask: jnp.ndarray, lit_args, rows_before=0.0):
            note_program_trace()
            lits = slots.unpack(lit_args)
            row_offset = _row_offset(slots, lit_args, rows_before)
            bucket = codes.shape[0]
            if pred_fn is not None:
                pv, pm = pred_fn(cols, lits)
                keep = pv.astype(bool) & pm & row_mask
            else:
                keep = row_mask
            seg = jnp.where(keep, codes, cap).astype(jnp.int32)

            evaluated = []
            for fn, count_all in child_fns:
                v, m = fn(cols, lits)
                v = v + jnp.zeros(jnp.shape(seg), dtype=v.dtype) if jnp.shape(v) != jnp.shape(seg) else v
                mask = keep if count_all else dev._broadcast_valid(v, m) & keep
                evaluated.append((v, mask))

            order = jnp.argsort(seg)
            sseg = seg[order]
            flags = jnp.concatenate([jnp.ones((1,), bool), sseg[1:] != sseg[:-1]])
            targets = jnp.arange(cap, dtype=sseg.dtype)
            starts = jnp.searchsorted(sseg, targets, side="left")
            ends = jnp.searchsorted(sseg, targets, side="right")
            sizes = ends - starts
            end_idx = jnp.clip(ends - 1, 0, bucket - 1)

            def seg_reduce(vals, op):
                def comb(a, b):
                    fa, va = a
                    fb, vb = b
                    return (fa | fb, jnp.where(fb, vb, op(va, vb)))

                _f, run = jax.lax.associative_scan(comb, (flags, vals))
                return run[end_idx]

            # mm planes: f64 segmented sums (matches the matmul path's combine)
            mm_cols = []
            for agg_idx, kind in mm_specs:
                if kind == "rows":
                    plane = keep.astype(fdt)
                elif kind == "count":
                    plane = evaluated[agg_idx][1].astype(fdt)
                elif kind.startswith("isum"):
                    v, mask = evaluated[agg_idx]
                    plane = jnp.where(mask, _isum_digit(v, kind), 0.0).astype(fdt)
                else:
                    v, mask = evaluated[agg_idx]
                    plane = jnp.where(mask, v.astype(fdt), 0.0)
                red = seg_reduce(plane[order].astype(jnp.float64), jnp.add)
                mm_cols.append(jnp.where(sizes > 0, red, 0.0))
            acc_mm = jnp.stack(mm_cols, axis=-1) if mm_cols \
                else jnp.zeros((cap, 0), jnp.float64)

            exts = []
            for (agg_idx, op, use_f64) in ext_specs:
                dt = jnp.float64 if use_f64 else jnp.float32
                big = jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dt)
                if agg_idx < 0:
                    v = jnp.arange(bucket, dtype=jnp.float64) + row_offset
                    mask = keep
                else:
                    v, mask = evaluated[agg_idx]
                plane = jnp.where(mask, v.astype(dt), big)
                red = seg_reduce(plane[order],
                                 jnp.minimum if op == "min" else jnp.maximum)
                exts.append(red)

            scts = []
            for agg_idx, kind in sct_specs:
                v, mask = evaluated[agg_idx]
                if kind == "sum":
                    sv = jnp.where(mask, v.astype(jnp.int64), jnp.zeros((), jnp.int64))
                    red = seg_reduce(sv[order], jnp.add)
                    scts.append(jnp.where(sizes > 0, red, 0))
                else:
                    info = jnp.iinfo(jnp.int64)
                    ident = info.max if kind == "min" else info.min
                    sv = jnp.where(mask, v.astype(jnp.int64), jnp.asarray(ident, jnp.int64))
                    red = seg_reduce(sv[order],
                                     jnp.minimum if kind == "min" else jnp.maximum)
                    scts.append(red)

            return {"mm": acc_mm, "ext": tuple(exts), "sct": tuple(scts)}

        return jax.jit(_over_mesh(stage, mesh))

    def _program_for(self, cap: int, rows: int = 0,
                     radices: Tuple[int, ...] = (),
                     mesh_devices: int = 1) -> Tuple[Callable, str]:
        """The jitted program that serves `cap` groups, and how it reduces:
        "pallas" (the kernel tier, when its gate admits the shape), "select"
        or "matmul" (_build's two forms, up to MAX_MATMUL_SEGMENTS), "sort".
        Only _build's program takes the key columns' code planes and their
        `radices` in place of the segment ids. With `mesh_devices` > 1 the
        program is the same one run on every shard of that many devices (the
        XLA tiers only: the kernel tier stays a single chip's)."""
        interp = self._pallas_gate(cap, rows) if mesh_devices <= 1 else None
        if interp is not None:
            key, form = ("pallas", cap), "pallas"
        elif cap <= MAX_MATMUL_SEGMENTS:
            key = (cap, radices) if radices else cap
            form = _reduce_form(cap)
        else:
            key, form = cap, "sort"
        if mesh_devices > 1:
            key = (key, "mesh", mesh_devices)
        if key not in self._jitted:
            mesh = local_mesh(mesh_devices)
            self._jitted[key] = (
                self._build_pallas(cap, interpret=interp) if form == "pallas"
                else self._build_sorted(cap, mesh) if form == "sort"
                else self._build(cap, radices=radices, mesh=mesh))
        return self._jitted[key], form

    def _pallas_eligible(self) -> bool:
        """Exactness contract for the Pallas tier (ops/pallas_kernels.py):
        sum planes accumulate in f32 — exact only for small-integer planes
        (rows/count/digit sums) — so raw float/bool sums and f64-exact mode
        (float min/max stages) keep the XLA tiers. Integer extremes — the
        f64 ext planes AND the int64 scatter slots — are now served exactly
        by segment_extreme_int64's refined hi/lo digit planes (exact over
        the FULL int64 range, parity-pinned past 2^53 in tests), so they no
        longer disqualify a stage."""
        if self._use_f64:
            return False
        for _idx, kind in self._mm_specs:
            if not (kind in ("rows", "count") or kind.startswith("isum")):
                return False
        for _idx, kind in self._sct_specs:
            if kind not in ("min", "max"):
                return False
        return True

    def _pallas_gate(self, cap: int, rows: int = 0) -> Optional[bool]:
        """Decide whether `cap` dispatches on the Pallas tier. Returns the
        kernel's `interpret` flag when it should (True = CPU interpreter,
        for off-silicon parity tests under DAFT_TPU_PALLAS=on), None when
        the XLA tiers serve this cap."""
        from ..config import execution_config

        mode = getattr(execution_config(), "pallas_mode", "auto")
        if mode == "off" or not self._pallas_eligible():
            return None
        from .pallas_kernels import MAX_PALLAS_BUCKET, PALLAS_MAX_SEGMENTS

        if cap > PALLAS_MAX_SEGMENTS or pad_bucket(rows) >= MAX_PALLAS_BUCKET:
            return None
        on_tpu = jax.default_backend() == "tpu"
        if mode == "on":
            return not on_tpu
        # auto: real silicon only, past the one-hot matmul ceiling, and only
        # when the calibrated kernel rate beats the sort tier for this shape
        if not on_tpu or cap <= MAX_MATMUL_SEGMENTS:
            return None
        from . import costmodel as cm

        cal = cm.calibrate()
        r = max(rows, 1)
        n_mm, n_ext = len(self._mm_specs), len(self._ext_specs)
        pallas = cm.device_grouped_pallas_cost(cal, r, 0, n_mm, n_ext, cap, 0)
        sort = cm.device_grouped_sort_cost(cal, r, 0, n_mm + n_ext, 0)
        return False if pallas.total < sort.total else None

    def _build_pallas(self, cap: int, interpret: bool) -> Callable:
        """Pallas blocked segment-reduce tier: same output contract as
        _build/_build_sorted ({"mm","ext","sct"}), compute routed through
        ops/pallas_kernels.py. Only built for stages passing
        _pallas_eligible(), so every plane is f32-exact: digit/count sums
        combine in f64 across kernel windows, float extremes are
        order-independent, and the first-row index rides an f32 plane
        (exact while bucket < 2^24 — _pallas_gate keeps larger buckets on
        the XLA tiers, and the trace refuses one that slips through)."""
        from . import pallas_kernels as pk

        fdt = jnp.float32
        pred_fn, fns = compile_stage_exprs(self, fdt)
        child_fns = [(fn, _counts_all(agg)) for fn, (_name, agg) in zip(fns, self.aggs)]
        slots = self.slots

        mm_specs, ext_specs = self._mm_specs, self._ext_specs
        sct_specs = self._sct_specs

        def stage(cols: Dict[str, dev.DCol], codes: jnp.ndarray,
                  row_mask: jnp.ndarray, lit_args, rows_before=0.0):
            note_program_trace()
            lits = slots.unpack(lit_args)
            row_offset = _row_offset(slots, lit_args, rows_before)
            bucket = codes.shape[0]
            if bucket >= pk.MAX_PALLAS_BUCKET:
                raise ValueError(
                    f"pallas tier: bucket {bucket} exceeds f32-exact "
                    f"first-row-index range {pk.MAX_PALLAS_BUCKET}")
            if pred_fn is not None:
                pv, pm = pred_fn(cols, lits)
                keep = pv.astype(bool) & pm & row_mask
            else:
                keep = row_mask
            seg = jnp.where(keep, codes, cap).astype(jnp.int32)

            evaluated = []
            for fn, count_all in child_fns:
                v, m = fn(cols, lits)
                v = v + jnp.zeros(jnp.shape(seg), dtype=v.dtype) \
                    if jnp.shape(v) != jnp.shape(seg) else v
                mask = keep if count_all else dev._broadcast_valid(v, m) & keep
                evaluated.append((v, mask))

            planes = []
            for agg_idx, kind in mm_specs:
                if kind == "rows":
                    planes.append(keep.astype(jnp.float32))
                elif kind == "count":
                    planes.append(evaluated[agg_idx][1].astype(jnp.float32))
                else:  # isum digit — _pallas_eligible admits nothing else
                    v, mask = evaluated[agg_idx]
                    planes.append(jnp.where(mask, _isum_digit(v, kind), 0.0)
                                  .astype(jnp.float32))

            # extreme planes grouped by op for the two kernel launches; the
            # first-row index (slot 0) rides the min family as a LOCAL f32
            # arange — row_offset folds back in f64 after the kernel
            min_slots, max_slots = [], []
            min_planes, max_planes = [], []
            int_ext = []    # (slot, agg_idx, op): exact-int64 extreme family
            for slot, (agg_idx, op, use_f64) in enumerate(ext_specs):
                if agg_idx < 0:
                    v = jnp.arange(bucket, dtype=jnp.float32)
                    mask = keep
                elif use_f64:
                    # integer extreme (f64 plane on the XLA tier): served by
                    # the refined hi/lo digit-plane kernel below — a single
                    # f32 plane would quantize values past 2^24
                    int_ext.append((slot, agg_idx, op))
                    continue
                else:
                    v, mask = evaluated[agg_idx]
                    v = v.astype(jnp.float32)
                big = jnp.float32(jnp.inf if op == "min" else -jnp.inf)
                plane = jnp.where(mask, v, big)
                if op == "min":
                    min_slots.append(slot)
                    min_planes.append(plane)
                else:
                    max_slots.append(slot)
                    max_planes.append(plane)

            acc_mm = pk.segment_sum_planes_windowed(
                jnp.stack(planes, axis=-1), seg, cap, interpret=interpret)
            ext_out: List = [None] * len(ext_specs)
            if min_planes:
                mins = pk.segment_extreme_planes(
                    jnp.stack(min_planes, axis=-1), seg, cap, "min",
                    interpret=interpret)
                for j, slot in enumerate(min_slots):
                    ext_out[slot] = mins[:, j]
            if max_planes:
                maxs = pk.segment_extreme_planes(
                    jnp.stack(max_planes, axis=-1), seg, cap, "max",
                    interpret=interpret)
                for j, slot in enumerate(max_slots):
                    ext_out[slot] = maxs[:, j]
            # slot 0 back to the global f64 index contract (+inf = empty group)
            r0 = ext_out[0]
            ext_out[0] = jnp.where(jnp.isfinite(r0),
                                   r0.astype(jnp.float64) + row_offset,
                                   jnp.inf)
            # exact-int64 families: integer ext planes decode back to the f64
            # plane contract (±inf = empty group), int64 scatter slots keep
            # their native int64 identity-fill contract — both bit-match the
            # XLA tier's segment_min/max outputs including values past 2^53
            for slot, agg_idx, op in int_ext:
                v, mask = evaluated[agg_idx]
                vals, nonempty = pk.segment_extreme_int64(
                    v.astype(jnp.int64), mask, seg, cap, op,
                    interpret=interpret)
                big = jnp.float64(jnp.inf if op == "min" else -jnp.inf)
                ext_out[slot] = jnp.where(nonempty, vals.astype(jnp.float64),
                                          big)
            scts = []
            for agg_idx, kind in sct_specs:
                v, mask = evaluated[agg_idx]
                vals, _nonempty = pk.segment_extreme_int64(
                    v.astype(jnp.int64), mask, seg, cap, kind,
                    interpret=interpret)
                scts.append(vals)

            return {"mm": acc_mm, "ext": tuple(ext_out), "sct": tuple(scts)}

        return jax.jit(stage)

    def _jit_local(self, cap: int) -> Callable:
        key = ("local", cap)
        if key not in self._jitted:
            self._jitted[key] = self._build_local_dense(cap)
        return self._jitted[key]

    def _build_local_dense(self, cap: int) -> Callable:
        """High-cardinality path over HOST-GROUP-SORTED rows: locally-dense
        one-hot matmuls (measured 122ms for 8M rows -> 2M segments on v5e).

        The host factorize already yields dense group ids; sorting rows by id
        on the host (cached, and folded into the static gather indices so the
        packed dim gathers emit rows pre-sorted) makes every CHUNK_LOCAL-row
        chunk span a CONTIGUOUS id range of width < CHUNK_LOCAL. Each chunk
        then reduces through a [chunk x chunk] one-hot matmul on the MXU and
        accumulates into the global table with one dynamic-slice add. No
        device sort, no scatter, no associative scan — the three ops measured
        catastrophically slow (or minutes-to-compile) on real v5e at 8M rows.
        Exactness matches the matmul path: digit planes for int sums, f64
        accumulators, f64 extreme planes.
        """
        fdt = jnp.float64 if self._use_f64 else jnp.float32
        pred_fn, fns = compile_stage_exprs(self, fdt)
        child_fns = [(fn, _counts_all(agg)) for fn, (_name, agg) in zip(fns, self.aggs)]
        slots = self.slots
        mm_specs = self._mm_specs
        ext_specs = self._ext_specs[1:]  # first-row index comes from the host
        if self._sct_specs:
            raise DeviceFallback(
                "local-dense path cannot serve 64-bit scatter extremes")
        if self._use_f64:
            raise DeviceFallback(
                "local-dense path does not run in f64-exact mode")

        def stage(cols: Dict[str, dev.DCol], local_codes: jnp.ndarray,
                  seg_lo: jnp.ndarray, row_mask: jnp.ndarray, lit_args):
            note_program_trace()
            lits = slots.unpack(lit_args)
            bucket = local_codes.shape[0]
            chunk = min(CHUNK_LOCAL, bucket)
            n_chunks = bucket // chunk
            if pred_fn is not None:
                pv, pm = pred_fn(cols, lits)
                keep = pv.astype(bool) & pm & row_mask
            else:
                keep = row_mask
            lc = jnp.where(keep, local_codes, chunk).astype(jnp.int32)

            evaluated = []
            for fn, count_all in child_fns:
                v, m = fn(cols, lits)
                v = v + jnp.zeros(jnp.shape(lc), dtype=v.dtype) \
                    if jnp.shape(v) != jnp.shape(lc) else v
                mask = keep if count_all else dev._broadcast_valid(v, m) & keep
                evaluated.append((v, mask))

            planes = []
            for agg_idx, kind in mm_specs:
                if kind == "rows":
                    planes.append(keep.astype(jnp.float32))
                elif kind == "count":
                    planes.append(evaluated[agg_idx][1].astype(jnp.float32))
                elif kind.startswith("isum"):
                    v, mask = evaluated[agg_idx]
                    planes.append(jnp.where(mask, _isum_digit(v, kind), 0.0))
                else:
                    v, mask = evaluated[agg_idx]
                    planes.append(jnp.where(mask, v.astype(jnp.float32), 0.0))

            ext_planes = []
            for agg_idx, op, use_f64 in ext_specs:
                dt = jnp.float64 if use_f64 else jnp.float32
                big = jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dt)
                v, mask = evaluated[agg_idx]
                ext_planes.append(jnp.where(mask, v.astype(dt), big))

            P = len(planes)
            lr = lc.reshape(n_chunks, chunk)
            mm_xs = jnp.stack(planes, -1).reshape(n_chunks, chunk, P)
            ext_xs = tuple(p.reshape(n_chunks, chunk) for p in ext_planes)
            acc_mm0 = jnp.zeros((cap + chunk, P), jnp.float64)
            acc_ext0 = tuple(
                jnp.full((cap + chunk,), jnp.inf if op == "min" else -jnp.inf,
                         dtype=jnp.float64 if use_f64 else jnp.float32)
                for _i, op, use_f64 in ext_specs)

            def body(carry, xs):
                acc_mm, acc_ext = carry
                s, v, lo = xs[0], xs[1], xs[2]
                ext_ch = xs[3:]
                # one-hot over the chunk's LOCAL id range; masked rows carry
                # lc == chunk and match no column
                oh = s[:, None] == jnp.arange(chunk, dtype=jnp.int32)[None, :]
                # HIGHEST: TPU matmuls default to bf16 inputs, which quantizes float
                # value planes (~4e-4 relative, observed on q3 revenue sums); the
                # 3-pass f32 mode keeps sums within f32 of the host
                lt = jnp.matmul(oh.astype(jnp.float32).T, v,
                                precision=jax.lax.Precision.HIGHEST).astype(jnp.float64)
                zero = jnp.int32(0)
                cur = jax.lax.dynamic_slice(acc_mm, (lo, zero), (chunk, P))
                acc_mm = jax.lax.dynamic_update_slice(acc_mm, cur + lt, (lo, zero))
                new_ext = []
                for (spec, ev_ch, acc) in zip(ext_specs, ext_ch, acc_ext):
                    _i, op, use_f64 = spec
                    dt = jnp.float64 if use_f64 else jnp.float32
                    big = jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dt)
                    w = jnp.where(oh, ev_ch[:, None].astype(dt), big)
                    red = jnp.min(w, axis=0) if op == "min" else jnp.max(w, axis=0)
                    cur_e = jax.lax.dynamic_slice(acc, (lo,), (chunk,))
                    comb = jnp.minimum(cur_e, red) if op == "min" \
                        else jnp.maximum(cur_e, red)
                    new_ext.append(jax.lax.dynamic_update_slice(acc, comb, (lo,)))
                return (acc_mm, tuple(new_ext)), None

            (acc_mm, acc_ext), _ = jax.lax.scan(
                body, (acc_mm0, acc_ext0), (lr, mm_xs, seg_lo) + ext_xs)
            # first-row-index slot placeholder (host supplies real firsts)
            firsts = jnp.zeros((cap,), jnp.float64)
            return {"mm": acc_mm[:cap],
                    "ext": (firsts,) + tuple(a[:cap] for a in acc_ext),
                    "sct": ()}

        return jax.jit(stage)

    def run_wide_reason(self) -> Optional[str]:
        """Why this stage's partials cannot be kept as run-wide tables
        (_build_run_wide), or None where they can: the tables hold the matmul
        planes alone (sums, counts, means)."""
        if self._sct_specs:
            return "the stage needs 64-bit scatter extremes"
        if self._use_f64:
            return "the stage computes in float64"
        if len(self._ext_specs) > 1:
            return "the stage has a min or max"
        return None

    def run_wide_tables(self, cap: int, mesh_devices: int = 1) -> dict:
        """The empty tables of one run over `cap` group ids (_build_run_wide).
        With `mesh_devices` > 1 every device of the mesh holds a set of its
        own, as long as the one chip's: each array is the devices' tables end
        to end, row-sharded, made on the devices (no host array that long)."""
        length = cap + min(CHUNK_LOCAL, cap)
        n_mm = len(self._mm_specs)

        def empty(ndev: int = 1):
            zeros = tuple(jnp.zeros(ndev * length, jnp.float32) for _ in range(n_mm))
            return {"hi": zeros, "lo": tuple(jnp.zeros_like(z) for z in zeros),
                    "first": jnp.full(ndev * length, _NO_ROW, jnp.int32),
                    # a dispatch's sparse segments' float32 partial: all zeros
                    # between dispatches (_build_run_wide folds it and zeroes it)
                    "part": tuple(jnp.zeros_like(z) for z in zeros),
                    **{count: jnp.zeros((ndev,) if ndev > 1 else (), jnp.int32)
                       for count in _RUN_WIDE_COUNTS}}

        # ONE launch makes every leaf (a leaf made by itself is a launch of
        # its own on the dispatching thread, 0.8 ms on a v5e's host)
        key = ("run_wide_tables", cap, mesh_devices)
        if key not in self._jitted:
            if mesh_devices <= 1:
                self._jitted[key] = jax.jit(empty)
            else:
                from jax.sharding import NamedSharding, PartitionSpec

                self._jitted[key] = jax.jit(
                    lambda: empty(mesh_devices), out_shardings=NamedSharding(
                        local_mesh(mesh_devices), PartitionSpec(MESH_AXIS)))
        return self._jitted[key]()

    def _jit_run_wide(self, cap: int, mesh_devices: int = 1,
                      segment: int = 0) -> Callable:
        key = ("run_wide", cap) if mesh_devices <= 1 \
            else ("run_wide", cap, "mesh", mesh_devices)
        if segment:
            key += ("segment", segment)
        if key not in self._jitted:
            self._jitted[key] = self._build_run_wide(
                cap, local_mesh(mesh_devices), segment)
        return self._jitted[key]

    def _build_run_wide(self, cap: int, mesh=None, segment: int = 0) -> Callable:
        """One batch into the tables of a whole run: the program of a run
        whose group ids mean the same in every batch (device_join's fused
        TopN: a fact row's id is a dimension's row). The tables are the
        program's first argument and its result, donated, so a run of any
        number of dispatches holds one set, each `cap` ids long (and a chunk
        of slack, so a chunk's window never needs clamping): for each matmul
        plane of _mm_specs a sum as TWO float32 planes, `hi` + `lo` (a
        double-single: about 48 bits, each addition an error-free two-sum),
        the int32 position in the run's stream of each id's first kept row
        (the order the host engine's stable sort leaves ties in), one float32
        partial a plane for a dispatch's sparse segments (below; all zeros
        between dispatches), and the counts of _RUN_WIDE_COUNTS. Not float64
        planes: the chip keeps a float64 array as two float32 ones INSIDE a
        program only, and converts the whole of it at the program's entry and
        exit (on a v5e 4-5 ms a dispatch for three planes of 2^24 ids,
        whatever the batch added: PR 38's chip run).

        A dispatch adds up in one of three forms, by what its rows are, seen
        on the device: where every chunk of CHUNK_LOCAL rows holds ids within
        CHUNK_LOCAL of each other (a fact sorted by the dimension's key, as
        lineitem by order: the locally dense layout of _build_local_dense
        with no host permutation), a chunk's float32 planes are contracted
        with its one-hot on the MXU and added to its window of the tables.
        The one-hot is never built whole: an id of the window is two digits
        (DENSE_DIGITS), so a row costs the SUM of the digits' ranges in
        compares and selects and not their product (_digit_product). Where,
        besides, the kept ids of every chunk of the segment never decrease
        in stream order (the same sorted fact), an id's first row is the one
        whose id exceeds every kept id before it, exactly one row an id, and
        its position rides the same product as a sum of one term; a dense
        segment whose ids are in no order keeps the masked minimum over the
        whole one-hot for its first rows. Both verdicts are the segment's
        own, from its ids, on the device; the tables count the segments of
        each.
        Any other segment scatter-adds its float32 rows into the DISPATCH's
        partial, a float32 table a plane that the segment loop carries beside
        the sums, and touches neither `hi` nor `lo`. A scatter on the chip
        costs by its index count, dropped indices included (0.89 ms for a
        bucket's 131,072, 0.07 for 8,192: PERF.md, PR 42), so a segment that
        keeps at most a bucket's 1 / COMPACT_SHARE of its rows (q10: 1.3%)
        first compacts them in stream order (_compact_kept) and scatters
        those; one that keeps more scatters the whole bucket. After the last
        segment a dispatch that wrote the partial folds it into the sums,
        ONE table-long two-sum a plane a dispatch (24 bytes an id, for up to
        eight segments that change at most K of millions of ids each), and
        writes it back as zeros, which is what the next dispatch finds. A dispatch whose segments were all
        dense (every one of q3's) wrote nothing and folds nothing: it pays
        no stream over the tables at all. So a dispatch's partial is float32
        (the rows of an id that a dispatch's sparse segments hold meet in
        float32 before they are widened) and the run's sum wider, as the
        merge on the host was; the tables count the dense, the ordered and
        the compacted segments and the dispatches that folded.

        A dispatch is one or more SEGMENTS of `segment` rows (0: the whole
        bucket is one): a join over a resident fact sends DISPATCH_SEGMENTS
        morsels' rows at once, so that the host's path a dispatch is paid
        once for all of them, and the program walks them a segment at a time
        (a loop that carries the donated tables and stops after the last
        segment that holds a row, so a tail's padding costs nothing). The
        segment is the unit of everything above but the fold: its form is
        chosen from its own rows, and the compaction, whose compares grow
        with the square of what it compacts, and every temporary stay a
        morsel's size whatever the dispatch's.

        Over `mesh` every device runs this program on its shard of the
        batch's rows and adds into tables of its own (run_wide_tables): ids
        are the dimension's rows whichever shard a fact row fell in, so a
        chip's tables are as long as the one chip's, and a row's position in
        the run's stream counts the rows of the shards before its own. What
        the chips added is combined at the run's end (device_join's select)."""
        reason = self.run_wide_reason()
        if reason is not None:
            raise DeviceFallback("run-wide tables: " + reason)
        planes_of = self._chunk_planes(cap, jnp.float32, ())
        slots = self.slots
        n_mm = len(self._mm_specs)

        def one_segment(acc, cols: Dict[str, dev.DCol], gid: jnp.ndarray,
                        row_mask: jnp.ndarray, offset, lits):
            """`acc` ((hi, lo, first, part), whether the dispatch wrote `part`,
            the _SEGMENT_COUNTS) with one segment's rows
            added, the segment's first row at `offset` of the run's stream."""
            sums, wrote, counts = acc
            bucket = gid.shape[0]
            chunk = min(CHUNK_LOCAL, bucket, cap)
            n_chunks = bucket // chunk
            # filtered, padding and unjoined rows carry the id `cap`
            seg, mm, _ext, _sct = planes_of(cols, gid, row_mask, lits)
            seg = jnp.where(seg < 0, cap, seg)
            kept = seg < cap
            pos = jnp.arange(bucket, dtype=jnp.int32) + offset
            g = seg.reshape(n_chunks, chunk)
            lo = jnp.min(g, axis=1)                          # cap: nothing kept
            top = jnp.max(jnp.where(g < cap, g, -1), axis=1)
            dense = jnp.all(top - lo < chunk)
            lo = jnp.minimum(lo, cap - 1)
            vals = jnp.stack(mm, axis=-1)                    # [bucket, P] f32

            def untouched(table):
                # a table the dense form has no use for, handed on through ONE
                # element written as it is: a branch that returns its parameter
                # itself is given a copy of the whole table by the chip's
                # compiler, a dense segment (three planes of 2^24 ids a segment
                # of q3's; tests/test_chip_compile.py reads the compiled text
                # for it)
                return table.at[0].add(0.0)

            def dense_form(acc):
                local = jnp.where(g < cap, g - lo[:, None], chunk)
                # a float32 as three bfloat16 terms (8 + 8 + 8 bits of it):
                # the one-hot is exact in bfloat16, so ONE pass of the MXU
                # over the three gives what Precision.HIGHEST takes six for
                terms = _bfloat16_terms(vals).reshape(n_chunks, chunk, 3 * n_mm)
                # the greatest kept id among the rows before, chunk by chunk
                # (a row that is not kept carries `cap`, over every kept id)
                before = _max_before(jnp.where(g < cap, g, -1))
                ordered = jnp.all(g >= before)
                # where the kept ids never decrease, a kept row is its id's
                # first exactly where its id exceeds every kept id before it:
                # ONE row an id, so the row's number in the chunk comes
                # through the product as a sum of one term, in two
                # bfloat16-exact digits (a chunk has at most CHUNK_LOCAL = 64
                # x 64 rows) and a presence flag; in a segment in no order
                # the three columns are zeros and say "no row"
                is_first = ordered & (g < cap) & (g > before)
                row = jnp.arange(chunk, dtype=jnp.int32)
                marks = jnp.stack(
                    [jnp.where(is_first, d, 0) for d in (1, row >> 6, row & 63)],
                    axis=-1).astype(jnp.bfloat16)
                rows_at = pos.reshape(n_chunks, chunk)

                def firsts_by_minimum():
                    # ids in no order: each id's least position among the
                    # chunk's rows that hold it, over the whole one-hot (made
                    # apart from the tables: a loop of its own that carried
                    # them had the chip's compiler copy each table whole, a
                    # chunk: 78 ms a segment; PERF.md, PR 47)
                    ids = jnp.arange(chunk, dtype=jnp.int32)

                    def body(_, xs):
                        s, p = xs
                        return None, jnp.min(
                            jnp.where(s[:, None] == ids[None, :], p[:, None], _NO_ROW), axis=0)

                    return jax.lax.scan(body, None, (local, rows_at))[1]

                unordered_firsts = jax.lax.cond(
                    ordered, lambda: jnp.full((n_chunks, chunk), _NO_ROW, jnp.int32),
                    firsts_by_minimum)

                def add_chunk(carry, xs):
                    c_hi, c_lo, c_first = carry
                    s, v, p, other, at = xs
                    prod = _digit_product(s, v)
                    new_hi, new_lo = [], []
                    for k in range(n_mm):
                        h, l = _two_sum_add(
                            jax.lax.dynamic_slice(c_hi[k], (at,), (chunk,)),
                            jax.lax.dynamic_slice(c_lo[k], (at,), (chunk,)),
                            prod[k] + prod[n_mm + k] + prod[2 * n_mm + k])
                        new_hi.append(jax.lax.dynamic_update_slice(c_hi[k], h, (at,)))
                        new_lo.append(jax.lax.dynamic_update_slice(c_lo[k], l, (at,)))
                    here, high, low = prod[3 * n_mm:]
                    first = jnp.where(
                        here > 0, p + (high * 64 + low).astype(jnp.int32), other)
                    cur = jax.lax.dynamic_slice(c_first, (at,), (chunk,))
                    c_first = jax.lax.dynamic_update_slice(
                        c_first, jnp.minimum(cur, first), (at,))
                    return (tuple(new_hi), tuple(new_lo), c_first), None

                # (two chunks a step: 0.357 -> 0.302 ms a segment on a v5e, four
                # read the same; PERF.md, PR 47)
                return jax.lax.scan(
                    add_chunk, acc[:3],
                    (local, jnp.concatenate([terms, marks], axis=-1), rows_at[:, 0],
                     unordered_firsts, lo),
                    unroll=min(2, n_chunks))[0] + (tuple(untouched(p) for p in acc[3]), ordered)

            # the sparse forms add into the dispatch's partial and touch
            # neither hi nor lo: the table-long two-sum is the dispatch's, once
            def scatter_form(acc):
                acc_hi, acc_lo, acc_first, part = acc
                at = jnp.where(kept, seg, acc_first.shape[0])   # out of range: dropped
                return (acc_hi, acc_lo, acc_first.at[at].min(pos, mode="drop"),
                        tuple(p.at[at].add(vals[:, k], mode="drop")
                              for k, p in enumerate(part)))

            # (a bucket is a power of two from 512 up: whole lines of lanes)
            n_compact = bucket // COMPACT_SHARE

            def compact_form(acc):
                acc_hi, acc_lo, acc_first, part = acc
                src, ids, rows = _compact_kept(
                    seg, [vals[:, k] for k in range(n_mm)], cap, n_compact)
                at = jnp.where(src < bucket, ids, acc_first.shape[0])   # an empty slot: dropped
                return (acc_hi, acc_lo, acc_first.at[at].min(offset + src, mode="drop"),
                        tuple(p.at[at].add(rows[k], mode="drop")
                              for k, p in enumerate(part)))

            def sparse_forms(acc):
                # (nothing of the compaction is computed for a dense segment,
                # nothing of the ids' order for a sparse one)
                few = jnp.sum(kept, dtype=jnp.int32) <= n_compact
                return jax.lax.cond(few, compact_form, scatter_form, acc) \
                    + (jnp.bool_(False), few)

            *sums, ordered, compacted = jax.lax.cond(
                dense, lambda acc: dense_form(acc) + (jnp.bool_(False),), sparse_forms,
                sums)
            # (_SEGMENT_COUNTS' order)
            return tuple(sums), wrote | ~dense, tuple(
                n + took.astype(jnp.int32)
                for n, took in zip(counts, (dense, compacted, ordered)))

        def stage(tables, cols: Dict[str, dev.DCol], gid: jnp.ndarray,
                  row_mask: jnp.ndarray, lit_args, rows_before=0):
            note_program_trace()
            lits = slots.unpack(lit_args)
            offset = slots.run_value(lit_args, 0).astype(jnp.int32) + rows_before
            bucket = gid.shape[0]
            rows = min(segment or bucket, bucket)
            acc = (tuple(tables[leaf] for leaf in ("hi", "lo", "first", "part")),
                   jnp.bool_(False), tuple(tables[n] for n in _SEGMENT_COUNTS))
            if rows == bucket:
                acc = one_segment(acc, cols, gid, row_mask, offset, lits)
            else:
                # the mask's set rows come first: the segments past them are padding
                live = (jnp.sum(row_mask, dtype=jnp.int32) + (rows - 1)) // rows

                def walk(i, acc):
                    # a segment is a stretch of each plane as it lies (as rows
                    # of a [segments, rows] view the chip would tile eight
                    # segments into one another: a copy of every plane, and
                    # strided reads of it)
                    c, g, m = jax.tree_util.tree_map(
                        lambda x: jax.lax.dynamic_slice_in_dim(x, i * rows, rows),
                        (cols, gid, row_mask))
                    return one_segment(acc, c, g, m, offset + i * rows, lits)

                acc = jax.lax.fori_loop(0, live, walk, acc)
            (hi, lo, first, part), wrote, counts = acc

            def fold(sums):
                # ONE table-long two-sum a plane a dispatch, and the partial
                # left all zeros for the next
                hi, lo, part = sums
                new_hi, new_lo = zip(*(_two_sum_add(h, l, p) for h, l, p in zip(hi, lo, part)))
                return new_hi, new_lo, tuple(jnp.zeros_like(p) for p in part)

            # a dispatch whose segments were all dense (every one of q3's)
            # wrote no partial and folds none: no stream over the tables
            hi, lo, part = jax.lax.cond(wrote, fold, lambda sums: sums, (hi, lo, part))
            return dict(zip(("hi", "lo", "first", "part") + _RUN_WIDE_COUNTS,
                            (hi, lo, first, part) + counts
                            + (tables["folds"] + wrote.astype(jnp.int32),)))

        if mesh is None:
            return jax.jit(stage, donate_argnums=0)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def on_shard(tables, cols, gid, row_mask, lit_args):
            before = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * gid.shape[0]
            return stage(tables, cols, gid, row_mask, lit_args, before)

        # the literals' values and the row offset go whole to every shard
        return jax.jit(shard_map(
            on_shard, mesh=mesh, in_specs=(P(MESH_AXIS),) * 4 + (P(),),
            out_specs=P(MESH_AXIS), check_vma=False), donate_argnums=0)


def _two_sum_add(hi, lo, x):
    """(hi, lo) + x for a double-single sum and a float32 addend, with the
    rounding error of hi + x kept in lo (Knuth's two-sum) and the pair
    renormalized so that hi is the sum rounded to float32."""
    s = hi + x
    back = s - hi
    lo = lo + ((hi - (s - back)) + (x - back))
    new_hi = s + lo
    # an infinite or NaN sum stays what it is (its error term would be a NaN)
    ok = jnp.isfinite(s)
    return jnp.where(ok, new_hi, s), jnp.where(ok, lo - (new_hi - s), 0.0)


# what a run-wide dispatch's tables count beside their sums: the segments
# that took the dense form, those whose kept rows were compacted before their
# scatters, the dense ones whose first rows rode the product, and the
# dispatches that folded a partial (the select programs read "dense" alone;
# device_join pops the others, and the partial, before the select)
_SEGMENT_COUNTS = ("dense", "compact", "ordered")
_RUN_WIDE_COUNTS = _SEGMENT_COUNTS + ("folds",)


def _digit_product(local: jnp.ndarray, terms: jnp.ndarray) -> jnp.ndarray:
    """[chunk] local ids (chunk: no id) and [chunk, T] bfloat16 terms -> [T,
    chunk] float32: each id's sum of each term over the rows that hold it,
    as `one_hot(local).T @ terms` gives it, with no chunk x chunk one-hot:
    the id is two digits, id = a * low + b, and

        part[(t, a), b] = sum_r (A[r, a] * terms[r, t]) * B[r, b]

    is one product [T * high, chunk] x [chunk, low] whose rows are the ids'
    in order. A row costs high + low compares and T * high selects; the
    one-hots are exact in bfloat16, a one-hot times a term is that term, and
    the MXU adds in float32."""
    chunk, n_terms = terms.shape
    high, low = _digits_of(chunk)
    # (the id `chunk` has the digit `high`, which matches nothing)
    a = (local // low)[:, None] == jnp.arange(high, dtype=jnp.int32)[None, :]
    b = (local % low)[:, None] == jnp.arange(low, dtype=jnp.int32)[None, :]
    spread = jnp.where(a[:, None, :], terms[:, :, None], jnp.zeros((), terms.dtype))
    part = jax.lax.dot_general(
        spread.reshape(chunk, n_terms * high), b.astype(terms.dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return part.reshape(n_terms, chunk)


def _max_before(x: jnp.ndarray) -> jnp.ndarray:
    """int32[n, m] (values >= -1) -> [n, m]: the greatest value of the row's
    entries before each (-1 before the first). Shifted maxima in log2(m)
    steps: 0.007 ms for [32, 4096] on a v5e, where jax.lax.cummax read 0.019
    and an associative scan 0.032 (PERF.md, PR 47)."""
    m = x.shape[1]

    def shifted(y, by):
        return jnp.pad(y, ((0, 0), (by, 0)), constant_values=-1)[:, :m]

    out, by = shifted(x, 1), 1
    while by < m:
        out = jnp.maximum(out, shifted(out, by))
        by *= 2
    return out


# a run-wide segment that keeps at most a bucket's 1 / COMPACT_SHARE of its
# rows scatters those alone (_build_run_wide's compact form). Every compacted
# segment pays for the K = bucket / COMPACT_SHARE slots whatever it kept: the
# compaction and a scatter of K indices a plane and one for the first rows.
# The stream over the tables is the DISPATCH's, once for its eight segments.
# On a v5e a compacted segment of 131,072 rows reads 0.41 ms into 2^21 ids
# and 0.79 into 2^23, its four scatters 0.24 and 0.47 of it; K = 4,096 reads
# 0.25 and 1.30, and 16,384 and up grow with K (PERF.md, PR 42 and PR 50)
COMPACT_SHARE = 16

# Morsel-long buckets a device that ONE join dispatch over a resident fact
# covers (batching.coalesce_target_rows(resident_rows=...), which takes fewer
# of a fact shorter than two such dispatches: a dispatch is never the whole
# fact; the programs walk them as segments). The host's path a join dispatch is look-ups and two
# launches, 1.8-3.4 ms on a v5e's host whatever the rows behind it, against
# 0.74-1.54 ms of device time for a bucket of 131,072 rows: at one bucket a
# dispatch every template of the SF10 join cell waited on the host (PERF.md,
# PR 43, with the micro-benchmark that chose the value).
DISPATCH_SEGMENTS = 8


def _compact_kept(seg: jnp.ndarray, planes, cap: int, k: int):
    """The first `k` kept rows (seg < cap) of a bucket in stream order:
    (src, ids, rows), each [k]: the row's number in the bucket (the bucket's
    length where fewer rows are kept), its id, and its value in each of
    `planes`, bit for bit.

    No sort, no scan and no bucket-long scatter, which the chip punishes
    (_build_local_dense; jnp.nonzero(size=k) is such a scatter: 9.05 ms on
    the v5e for k = 8,192 of 131,072 rows, PERF.md PR 42). The bucket is read
    as lines of _LANES rows. A kept row's place among the kept rows is the
    kept rows of the lines before its own plus those of the lanes before it
    (counts of 0/1: a product with a triangle, exact in bfloat16). Slot j's
    line is the number of lines that end before place j (k x lines compares);
    the k lines of each plane are gathered whole, which the chip does fast
    where a gather of single values is bound by its index count (PERF.md, PR
    39), and the one lane whose place is j is kept by its bits, a sum over
    the other lanes' zeros in int32, as device_join._gather_rows picks a
    lane. A gather a plane: one gather of the planes stacked read three
    times slower (0.37 against 0.12 ms)."""
    lines = seg.shape[0] // _LANES
    kept = (seg < cap).reshape(lines, _LANES)
    count = jnp.sum(kept, axis=1, dtype=jnp.int32)
    line = jnp.arange(lines, dtype=jnp.int32)
    through = jnp.sum(jnp.where(line[None, :] <= line[:, None], count[None, :], 0),
                      axis=1, dtype=jnp.int32)
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    within = jnp.matmul(kept.astype(jnp.bfloat16),
                        (lane[:, None] < lane[None, :]).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32).astype(jnp.int32)
    place = jnp.where(kept, (through - count)[:, None] + within, -1)
    slot = jnp.arange(k, dtype=jnp.int32)
    at = jnp.minimum(jnp.sum(through[None, :] <= slot[:, None], axis=1, dtype=jnp.int32),
                     lines - 1)
    mine = place[at] == slot[:, None]

    def pick(plane):
        """int32[bucket] -> [k]: slot j's lane of its line (0 where there is none)."""
        return jnp.sum(jnp.where(mine, plane.reshape(lines, _LANES)[at], 0), axis=1,
                       dtype=jnp.int32)

    src = jnp.where(jnp.any(mine, axis=1),
                    at * _LANES + jnp.sum(jnp.where(mine, lane[None, :], 0), axis=1,
                                          dtype=jnp.int32),
                    seg.shape[0])
    rows = tuple(jax.lax.bitcast_convert_type(
        pick(jax.lax.bitcast_convert_type(p, jnp.int32)), jnp.float32) for p in planes)
    return src, pick(seg), rows


def _bfloat16_terms(vals: jnp.ndarray) -> jnp.ndarray:
    """[rows, P] float32 -> [rows, 3P] bfloat16: each value as the three
    bfloat16 terms that add up to it (to its 24 bits). The terms are cut
    with reduce_precision: the chip's compiler is allowed excess precision
    and drops a float32 -> bfloat16 -> float32 round trip, which left every
    value as its first term alone (8 bits: q3 read 1.1e-3 off at SF10, PR
    38's chip run)."""
    def cut(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    first = cut(vals)
    # an infinite value stays one term (inf - inf would make it a NaN)
    rest = jnp.where(jnp.isfinite(vals), vals - first, 0.0)
    second = cut(rest)
    return jnp.concatenate([first, second, rest - second], axis=-1).astype(jnp.bfloat16)


# "no kept row" in a run-wide first-row table: past every position
_NO_ROW = np.int32(np.iinfo(np.int32).max)


def _row_offset(slots: dev.LiteralSlots, lit_args, rows_before):
    """The f64 position of a dispatch's first row in its run's stream: the
    launch's run value (the rows fed before it), and over a mesh the rows of
    the shards before this one."""
    return slots.run_value(lit_args, 0).astype(jnp.float64) + rows_before


def _over_mesh(stage: Callable, mesh) -> Callable:
    """A grouped program of (cols, codes, row_mask, lit_args) as it runs: as
    it is on one chip, or over `mesh` on every shard (stage.over_shards; the
    literals' values and the row offset whole on each), each shard's
    first-row positions offset by the rows of the shards before it, so the
    groups' order is that of the whole batch."""
    if mesh is None:
        return stage

    def on_shard(cols, codes, row_mask, lit_args):
        before = jax.lax.axis_index(MESH_AXIS).astype(jnp.float64) \
            * row_mask.shape[0]
        return stage(cols, codes, row_mask, lit_args, before)

    return over_shards(on_shard, mesh, replicated_tail=1)


class GroupedAggRun:
    """Per-run accumulator. Dispatches stay async; device tables are fetched in
    ONE device_get at finalize, then merged on the host (vectorized by slot).
    With `mesh_devices` > 1 a dispatch's rows are sharded over that many local
    devices and its result holds one table a shard, merged like the tables of
    successive batches. `literals` are the values of this execution's
    literals; every launch passes them to the program."""

    def __init__(self, stage: GroupedAggStage, literals: Sequence = (),
                 mesh_devices: int = 1):
        self.stage = stage
        self.mesh_devices = max(int(mesh_devices), 1)
        self.literals = _LiteralBinding(stage.slots, literals)
        # (device_out, decode) where decode resolves segment -> key tuple + presence
        self._pending: List[Tuple[dict, "_Decode"]] = []
        self._row_offset = 0

    def feed_batch(self, batch) -> None:
        stage = self.stage
        n = batch.num_rows
        if n == 0:
            return
        ndev = self.mesh_devices
        mesh = local_mesh(ndev)
        bucket = pad_bucket(n) if mesh is None else mesh_total(n, ndev)
        decode = self._codes_for(batch, n, bucket, mesh)
        decode.shards = ndev
        by_dict = decode.key_codes is not None
        prog, form = stage._program_for(
            decode.cap, n, tuple(decode.radices) if by_dict else (), ndev)
        with profile_span("device.h2d", "device", rows=n, bucket=bucket):
            # the keys' code planes come with the columns (a slot a Series)
            dcols, code_planes = batch_planes(
                batch, stage._input_cols, bucket, not stage._use_f64, mesh,
                key_codes=decode.key_codes or ())
        if not by_dict:
            codes = decode.dcodes
        elif form in ("select", "matmul"):
            codes = tuple(code_planes)
        else:  # the other tiers take the segment ids: combined here, eagerly
            codes = sum(c * r for c, r in zip(code_planes, decode.radices))
        with profile_span("device.dispatch", "device", op="grouped_agg",
                          rows=n, bucket=bucket, groups_cap=decode.cap):
            mask = device_row_mask(n, bucket, mesh)
            lit_args = self.literals.args((self._row_offset,))
            # a Pallas program that does not lower raises here: no tier
            # replaces it behind the caller's back
            with profile_span("device.launch", "device", op="grouped_agg",
                              cap=decode.cap, reduce=form, devices=ndev):
                out = prog(dcols, codes, mask, lit_args)
        if form == "pallas":
            counters.bump("pallas_dispatches")
        if ndev > 1:
            note_mesh_dispatch(ndev)
        count_reduce(form)
        self._row_offset += n
        self._pending.append((out, decode))
        counters.bump("device_grouped_batches")

    def _codes_for(self, batch, n: int, bucket: int, mesh=None) -> "_Decode":
        """Segment codes for one batch: device dictionary combine when the keys
        are plain columns with small combined cardinality, else host factorize.

        Raises DeviceFallback (before any device dispatch) when the group count
        exceeds the matmul segment ceiling — the executor reruns the whole
        stage on the host; the one-hot reduction must never see unbounded cap.
        """
        stage = self.stage
        key_series = resolve_key_series(batch, stage.groupby, n)

        if stage.dict_keys and estimate_key_cardinality(key_series) <= MAX_SORT_SEGMENTS:
            encoded = [s.dict_codes() for s in key_series]
            total = 1
            for _, _, k in encoded:
                total *= max(k, 1)
            if 0 < total <= MAX_SORT_SEGMENTS:
                cap = _pad_groups(total)
                radices = []
                mult = 1
                for _, _, k in reversed(encoded):
                    radices.append(mult)
                    mult *= max(k, 1)
                radices.reverse()
                # the segment id is the radix sum of the keys' code planes,
                # which feed_batch brings to the device with the batch's
                # columns: the one-hot tier's program takes it a tile at a time
                return _Decode(cap=cap, dcodes=None,
                               dicts=[(vals, k) for _, vals, k in encoded],
                               radices=radices, key_rows=None,
                               key_codes=[(s, codes) for s, (codes, _, _)
                                          in zip(key_series, encoded)])

        # fallback: host factorize of the full key rows for this batch (cached on
        # the batch so repeated queries over resident tables skip re-factorizing)
        gb_key = ("__group_codes__",) + tuple(str(e) for e in stage.groupby)
        cache = getattr(batch, "_stage_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(batch, "_stage_cache", cache)
        if gb_key in cache:
            group_ids, num_groups, key_rows = cache[gb_key]
        else:
            from ..core.kernels.groupby import make_groups

            first_idx, group_ids, _ = make_groups(key_series)
            num_groups = len(first_idx)
            key_rows = list(zip(*[s.take(first_idx).to_pylist() for s in key_series])) \
                if num_groups else []
            cache[gb_key] = (group_ids, num_groups, key_rows)
        cap = _pad_groups(max(num_groups, 1))
        if cap > MAX_SORT_SEGMENTS:
            raise DeviceFallback(
                f"grouped stage has {num_groups} groups > {MAX_SORT_SEGMENTS} "
                "sort-path segment ceiling")
        codes = np.full(bucket, cap, dtype=np.int32)
        codes[:n] = group_ids
        dcodes = jnp.asarray(codes) if mesh is None \
            else shard_rows(mesh, codes, bucket)
        return _Decode(cap=cap, dcodes=dcodes, dicts=None,
                       radices=None, key_rows=key_rows)

    def finalize(self):
        """Returns (key_rows, agg_results); agg_results[i] = (values, valid) arrays.

        ONE d2h fetch for all pending batch tables, then a vectorized host merge.
        Group order matches the host engine: first occurrence in the stream
        (reconstructed from the on-device first-row-index plane).
        """
        with profile_span("stage.finalize", "host", op="grouped_agg") as sp:
            key_rows, results = self._finalize()
            if sp is not None:
                sp.args["groups"] = len(key_rows)
            return key_rows, results

    def _finalize(self):
        stage = self.stage
        pending, self._pending = self._pending, []
        self._row_offset = 0
        counters.bump("device_stage_runs")
        if self.mesh_devices > 1:
            counters.bump("mesh_grouped_runs")
        if not pending:
            return [], [(np.empty(0), np.empty(0, dtype=bool)) for _ in stage.aggs]

        with profile_span("device.d2h", "device", op="grouped_agg",
                          batches=len(pending)):
            fetched = jax.device_get([out for out, _ in pending])  # one round trip

        # host merge across batches: key tuple -> slot, vectorized per table
        key_slot: Dict[tuple, int] = {}
        key_order: List[tuple] = []
        first_seen: List[float] = []
        n_mm = len(stage._mm_specs)
        mm_parts: List[np.ndarray] = []
        ext_parts: List[List[np.ndarray]] = []
        sct_parts: List[List[np.ndarray]] = []
        slot_maps: List[np.ndarray] = []

        tables = []  # (table, decode): a sharded dispatch gave one a shard
        for out, (_dev_out, decode) in zip(fetched, pending):
            if decode.shards > 1:
                tables += [(jax.tree_util.tree_map(lambda x, s=s: x[s], out), decode)
                           for s in range(decode.shards)]
            else:
                tables.append((out, decode))
        for out, decode in tables:
            mm = np.asarray(out["mm"])
            rows = mm[:, 0]
            present = np.flatnonzero(rows > 0)
            if decode.key_rows is None:
                keys = [decode.decode_key(int(g)) for g in present]
            elif hasattr(decode.key_rows, "rows_for"):
                keys = decode.key_rows.rows_for(present)  # one vectorized take
            else:
                keys = [decode.key_rows[g] for g in present]
            if decode.host_firsts is not None:
                firsts = (decode.host_firsts[present] + decode.row_offset
                          if len(present) else np.empty(0))
            else:
                firsts = np.asarray(out["ext"][0])[present] if len(present) \
                    else np.empty(0)
            slots = np.empty(len(present), dtype=np.int64)
            for j, key in enumerate(keys):
                slot = key_slot.get(key)
                if slot is None:
                    slot = len(key_order)
                    key_slot[key] = slot
                    key_order.append(key)
                    first_seen.append(float(firsts[j]) if len(firsts) else 0.0)
                else:
                    if len(firsts) and firsts[j] < first_seen[slot]:
                        first_seen[slot] = float(firsts[j])
                slots[j] = slot
            slot_maps.append(slots)
            mm_parts.append(mm[present])
            ext_parts.append([np.asarray(e)[present] for e in out["ext"]])
            sct_parts.append([np.asarray(s)[present] for s in out["sct"]])

        g = len(key_order)
        mm_acc = np.zeros((g, n_mm), dtype=np.float64)
        ext_acc = [np.full(g, np.inf if op == "min" else -np.inf)
                   for _, op, _ in stage._ext_specs]
        info = np.iinfo(np.int64)
        sct_acc = [
            np.full(g, 0 if kind == "sum" else (info.max if kind == "min" else info.min),
                    dtype=np.int64)
            for _, kind in stage._sct_specs
        ]
        for slots, mm, exts, scts in zip(slot_maps, mm_parts, ext_parts, sct_parts):
            np.add.at(mm_acc, slots, mm)
            for k, (spec, e) in enumerate(zip(stage._ext_specs, exts)):
                op = spec[1]
                if op == "min":
                    np.minimum.at(ext_acc[k], slots, e.astype(np.float64))
                else:
                    np.maximum.at(ext_acc[k], slots, e.astype(np.float64))
            for k, ((_idx, kind), s) in enumerate(zip(stage._sct_specs, scts)):
                if kind == "sum":
                    np.add.at(sct_acc[k], slots, s)
                elif kind == "min":
                    np.minimum.at(sct_acc[k], slots, s)
                else:
                    np.maximum.at(sct_acc[k], slots, s)

        # order groups by first occurrence (matches host groupby semantics)
        order = np.argsort(np.asarray(first_seen), kind="stable")
        inv = np.empty(g, dtype=np.int64)
        inv[order] = np.arange(g)
        key_rows = [key_order[i] for i in order]
        mm_acc = mm_acc[order]
        ext_acc = [e[order] for e in ext_acc]
        sct_acc = [s[order] for s in sct_acc]

        return key_rows, results_from_tables(stage, mm_acc, ext_acc, sct_acc)


def results_from_tables(stage: GroupedAggStage, mm_acc, ext_acc, sct_acc):
    """Per-agg (values, valid) arrays from accumulated plane tables — shared
    by the multi-batch finalize merge and the TopN winner-row path."""
    g = len(mm_acc)
    results = []
    for i, ((_name, agg), slots) in enumerate(zip(stage.aggs, stage._agg_slots)):
        op = agg.op
        cnt = mm_acc[:, slots["count"][1]]
        if op == "count":
            results.append((cnt.astype(np.int64), np.ones(g, dtype=bool)))
            continue
        valid = cnt > 0
        if op in ("sum", "mean"):
            if slots["sum"][0] == "imm":
                # recombine bit-slice digits in uint64 modular arithmetic
                # (digit totals are < 2^53 hence exact in the f64 table;
                # the 2^(8k) scale would overflow f64 exactness, and for
                # the 8-digit unbounded case the wrap mod 2^64 IS the
                # correct two's-complement sum)
                _k, base, nd, lo = slots["sum"]
                acc = np.zeros(g, dtype=np.uint64)
                for k in range(nd):
                    acc = acc + (mm_acc[:, base + k].astype(np.uint64)
                                 << np.uint64(8 * k))
                s_int = acc.view(np.int64) \
                    + np.int64(lo) * cnt.astype(np.int64)
                if op == "mean":
                    results.append((s_int.astype(np.float64)
                                    / np.maximum(cnt, 1), valid))
                else:
                    results.append((s_int, valid))
                continue
            kind, idx = slots["sum"]
            s = mm_acc[:, idx] if kind == "mm" else sct_acc[idx].astype(np.float64)
            if op == "mean":
                results.append((s / np.maximum(cnt, 1), valid))
            else:
                child_dt = agg.child.to_field(stage.schema).dtype
                if kind == "sct" and not child_dt.is_floating():
                    results.append((sct_acc[idx], valid))
                else:
                    results.append((s, valid))
        else:  # min / max
            kind, idx = slots[op]
            if kind == "sct":
                results.append((sct_acc[idx], valid))
            else:
                results.append((ext_acc[idx], valid))
    return results


CHUNK_LOCAL = 4096
# The two digits a local id of the dense form's window is addressed in: id =
# a * 128 + b, a in [0, 32). One segment's 32 chunks into tables of 2^24 ids
# on a v5e, three planes and the first rows through the product (PERF.md, PR
# 47's chip run; the whole one-hot and its masked minimum read 1.223 ms): 32 x
# 128 0.391 ms, 16 x 256 0.416, 64 x 64 0.485; with the terms on the low
# digit's side 0.486 / 0.638 / 0.444; the high digit outermost 0.693 / 0.545 /
# 1.068; 8 x 512 no better than 16 x 256 (0.305 against 0.297, sums alone,
# where 32 x 128 reads 0.279).
DENSE_DIGITS = (CHUNK_LOCAL // _LANES, _LANES)


def _digits_of(chunk: int):
    """(high, low): the two digits' ranges for a window of `chunk` ids."""
    low = min(DENSE_DIGITS[1], chunk)
    return chunk // low, low


def dense_row_cells(chunk: int, n_mm: int) -> int:
    """The compares and selects a row costs in the dense form with its first
    rows through the product (_digit_product over 3 * n_mm + 3 terms): what
    costmodel.device_join_topn_run_cost prices."""
    high, low = _digits_of(chunk)
    return high + low + (3 * n_mm + 3) * high


def build_permuted_layout(group_ids: np.ndarray, n: int, bucket: int):
    """Host side of the locally-dense reduction: rows sorted by dense group
    id. Returns (pperm, local_codes_dev, seg_lo_dev): pperm is the bucket-long
    row permutation (padding rows stay at the tail), local_codes are the
    per-row ids relative to their chunk's first id (each chunk of sorted dense
    ids spans < CHUNK_LOCAL distinct values), seg_lo the per-chunk base id.
    All uploads cached by the caller via series_keyed."""
    perm = np.argsort(group_ids, kind="stable")
    pperm = np.concatenate([perm, np.arange(n, bucket)]).astype(np.int32)
    chunk = min(CHUNK_LOCAL, bucket)
    codes_sorted = np.zeros(bucket, dtype=np.int64)
    codes_sorted[:n] = group_ids[perm]
    n_chunks = bucket // chunk
    seg_lo = codes_sorted.reshape(n_chunks, chunk)[:, 0].astype(np.int32)
    local = codes_sorted - np.repeat(seg_lo.astype(np.int64), chunk)
    # padding / masked rows are overridden to `chunk` in-program; clip keeps
    # the plane int32-safe either way
    local = np.clip(local, 0, chunk).astype(np.int32)
    import jax.numpy as _jnp

    return pperm, _jnp.asarray(local), _jnp.asarray(seg_lo)


class _Decode:
    """How to map a segment id back to its key tuple for one batch."""

    def __init__(self, cap: int, dcodes, dicts, radices, key_rows,
                 fact_codes=None, local_codes=None, seg_lo=None,
                 host_firsts=None, pperm=None, key_codes=None):
        self.cap = cap
        self.dcodes = dcodes        # segment-id plane (None with key_codes)
        # [(key Series, its rows' dictionary codes on the host)] (dict mode,
        # unjoined): their device planes travel with the batch's columns
        self.key_codes = key_codes
        self.dicts = dicts          # [(values, K)] per key column (dict mode)
        self.radices = radices
        self.key_rows = key_rows    # first-occurrence key tuples (host mode)
        self.fact_codes = fact_codes  # device_join._FactorizedCodes (lazy keys)
        # locally-dense (host-permuted) layout, set when cap > matmul ceiling
        self.local_codes = local_codes
        self.seg_lo = seg_lo
        self.host_firsts = host_firsts  # np first-occurrence row per group
        self.pperm = pperm              # np bucket-long row permutation
        self.row_offset = 0.0
        self.shards = 1                 # tables a dispatch's result holds

    @property
    def permuted(self) -> bool:
        return self.local_codes is not None

    def decode_key(self, seg: int) -> tuple:
        out = []
        for (values, _k), r in zip(self.dicts, self.radices):
            digit = seg // r
            seg = seg % r
            out.append(values[digit])
        return tuple(out)


_STAGE_CACHE: Dict[tuple, GroupedAggStage] = {}
# concurrent serving queries share this cache (PR 8 discipline)
_CACHE_LOCK = threading.Lock()


def grouped_stage_cache_key(schema: Schema, predicate: Optional[Expression],
                            groupby: Sequence[Expression],
                            agg_exprs: Sequence[Expression],
                            structure: Optional[Tuple[tuple, tuple]] = None) -> tuple:
    """stage_cache_key's skeletons and slot dtypes, and what of the values
    does set the program's structure: the group keys as they are (the host
    evaluates them), which aggregates' inputs are the same expression (they
    share their planes), and the inputs' static integer bounds (they size an
    exact integer sum's digit planes)."""
    inputs, bounds = [], []
    for _name, agg in unwrap_aggs(agg_exprs) or ():
        inputs.append(repr(agg.child))
        bounds.append(_static_int_bounds(agg.child))
    return stage_cache_key(schema, predicate, agg_exprs, structure=structure, static=(
        tuple(repr(g) for g in groupby),
        tuple(inputs.index(child) for child in inputs),
        tuple(bounds)))


def bind_grouped_agg_stage(schema: Schema, predicate: Optional[Expression],
                           groupby: Sequence[Expression],
                           agg_exprs: Sequence[Expression]
                           ) -> Optional[Tuple[GroupedAggStage, tuple]]:
    """(stage, literals) for filter+groupby+agg, or None if any piece
    doesn't qualify: stage.bind_filter_agg_stage's contract for the grouped
    stage. One walk of the expressions gives both.

    Group keys run host-side (factorize handles any dtype) or via cached
    per-column dictionaries, so they are unconstrained beyond being
    non-aggregate expressions. Stages (compiled programs only, no literal
    value) are cached by structure (grouped_stage_cache_key), so runs of a
    query with whatever literal values reuse the jitted executables. Run
    state and the values live in GroupedAggRun.
    """
    structure = stage_structure(predicate, agg_exprs)
    key = grouped_stage_cache_key(schema, predicate, groupby, agg_exprs, structure)
    stage = _STAGE_CACHE.get(key)
    if stage is None:
        if not groupby:
            return None
        if predicate is not None and not dev.is_device_evaluable(predicate, schema):
            return None
        aggs = device_aggs(schema, agg_exprs)
        if aggs is None:
            return None
        for g in groupby:
            for node in g.walk():
                if isinstance(node, AggExpr):
                    return None
        stage = GroupedAggStage(schema, predicate, groupby, aggs)
        with _CACHE_LOCK:
            _STAGE_CACHE[key] = stage
    return stage, structure[1]


def try_build_grouped_agg_stage(schema: Schema, predicate: Optional[Expression],
                                groupby: Sequence[Expression],
                                agg_exprs: Sequence[Expression]) -> Optional[GroupedAggStage]:
    """bind_grouped_agg_stage's stage alone: for whoever asks whether the
    device can run the shape, or prices it."""
    bound = bind_grouped_agg_stage(schema, predicate, groupby, agg_exprs)
    return None if bound is None else bound[0]
