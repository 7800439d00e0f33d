"""Mesh-sharded relational compute: the multi-chip execution path.

TPU-native replacement for the reference's distributed data movement
(reference: src/daft-distributed "Flotilla" + src/daft-shuffles Arrow-Flight
shuffle): within a mesh, repartition/aggregation exchange rides ICI via XLA
collectives (psum / all_gather) inside ONE jit program instead of host-side
shuffle services; cross-host DCN exchange reuses the same primitives through
jax.distributed.

Layout: rows are data-parallel sharded along the 'dp' mesh axis (each device
owns a contiguous row shard, padded with validity=False rows). Ungrouped
aggregation = local masked reduce + psum. Grouped aggregation = local
sort/unique + segment-reduce into a fixed-capacity group table, then an
all_gather table merge — an EXACT two-phase groupby whose 'shuffle' is one ICI
collective. Capacity is static (XLA needs static shapes); exceeding it is
reported via an overflow flag so the host can re-run with a larger table.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import threading

import numpy as np

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..expressions.expressions import AggExpr, Expression
from ..ops import device_eval as dev
from ..ops.stage import _decompose_agg, pad_bucket
from ..schema import Schema

# Sentinel key for invalid / padding rows: sorts after every real key.
_KEY_SENTINEL = np.iinfo(np.int64).max


_MESH_CACHE: Dict[Tuple[int, str], Mesh] = {}
# kernels are built from concurrent serving/executor threads (PR 8 discipline)
_CACHE_LOCK = threading.Lock()


def default_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    """1-D data-parallel mesh over the first `n_devices` local devices.

    Raises when more devices are requested than exist: silently building a
    smaller mesh from the slice (the pre-r7 behavior) made a forced
    `mesh_devices=N` config lie about its own width — callers that can
    degrade (the executor tier gate) must decide that themselves and count it
    (counters.mesh_unavailable_fallbacks)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(
            f"default_mesh: {n} devices requested but only {len(devs)} "
            f"available (jax.devices())")
    key = (n, axis)
    with _CACHE_LOCK:
        cached = _MESH_CACHE.get(key)
        if cached is None:
            cached = _MESH_CACHE[key] = Mesh(np.array(devs[:n]), (axis,))
    return cached


def shard_columns(mesh: Mesh, columns: Dict[str, Tuple[np.ndarray, np.ndarray]],
                  n: int, axis: str = "dp") -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """Pad host columns to a multiple of the mesh size and place them row-sharded."""
    n_dev = mesh.shape[axis]
    per = pad_bucket(max((n + n_dev - 1) // n_dev, 1))
    total = per * n_dev
    sharding = NamedSharding(mesh, P(axis))
    out = {}
    for name, (vals, valid) in columns.items():
        if len(vals) < total:
            pad = total - len(vals)
            vals = np.concatenate([vals, np.zeros(pad, dtype=vals.dtype)])
            valid = np.concatenate([valid, np.zeros(pad, dtype=bool)])
        out[name] = (jax.device_put(vals, sharding), jax.device_put(valid, sharding))
    return out


def shard_row_mask(mesh: Mesh, n: int, axis: str = "dp") -> jax.Array:
    """Row-sharded bool mask marking real rows (False on shard padding).

    Needed by count(mode=all): null values count, padding rows must not.
    """
    n_dev = mesh.shape[axis]
    per = pad_bucket(max((n + n_dev - 1) // n_dev, 1))
    total = per * n_dev
    mask = np.zeros(total, dtype=bool)
    mask[:n] = True
    return jax.device_put(mask, NamedSharding(mesh, P(axis)))


def sharded_filter_agg_step(mesh: Mesh, schema: Schema, predicate: Optional[Expression],
                            aggs: Sequence[Tuple[str, AggExpr]], axis: str = "dp") -> Callable:
    """Build a pjit'd distributed filter+ungrouped-agg step.

    Returns fn(cols, row_mask) -> {(name, partial_op): (value, valid)} with
    replicated outputs; row_mask (see shard_row_mask) marks real rows so shard
    padding never reaches an aggregate — count(mode=all) counts nulls, not padding.
    With row-sharded inputs, XLA lowers the reductions to per-shard partials plus a
    psum over ICI — no explicit collective code needed beyond the sharding contract.
    """
    pred_fn = dev.build_constant_device_expr(predicate, schema) if predicate is not None else None
    agg_specs = []
    for name, agg in aggs:
        child_fn = dev.build_constant_device_expr(agg.child, schema)
        count_all = agg.op == "count" and agg.params.get("mode", "valid") == "all"
        agg_specs.append((name, agg.op, count_all, child_fn))

    def step(cols, row_mask):
        if pred_fn is not None:
            pv, pm = pred_fn(cols)
            keep = pv.astype(bool) & pm & row_mask
        else:
            keep = row_mask
        out = {}
        for name, op, count_all, child_fn in agg_specs:
            v, m = child_fn(cols)
            m = dev._broadcast_valid(v, m) & keep
            if count_all:
                m = dev._broadcast_valid(v, keep)
            for partial_op in _decompose_agg(op):
                val, ok = dev.device_agg(partial_op, v, m)
                out[(name, partial_op)] = (val, ok)
        return out

    replicated = NamedSharding(mesh, P())
    return jax.jit(step, out_shardings=replicated)


# canonical masked segment reduce shared with the single-chip grouped stage
_segment_reduce = dev.segment_reduce


def _merge_op(op: str) -> str:
    """Reduce op used when merging per-shard partial tables."""
    return {"count": "sum", "sum": "sum", "min": "min", "max": "max"}[op]


def _pextreme(x: jnp.ndarray, axis: str, is_min: bool) -> jnp.ndarray:
    """pmin/pmax over the mesh axis. The chip's compiler lowers a 64-bit
    all-reduce for sums only ("Supported lowering only of Sum all reduce"),
    so 64-bit extremes all_gather the per-shard partials (n_dev values per
    cell) and reduce locally: the same exact answer, one collective."""
    if jnp.dtype(x.dtype).itemsize == 8:
        gathered = jax.lax.all_gather(x, axis)
        return jnp.min(gathered, axis=0) if is_min else jnp.max(gathered, axis=0)
    return (jax.lax.pmin if is_min else jax.lax.pmax)(x, axis)


_STEP_CACHE: Dict[tuple, Callable] = {}


def sharded_groupby_step(mesh: Mesh, agg_ops: Sequence[str], capacity: int,
                         axis: str = "dp") -> Callable:
    """EXACT distributed groupby-aggregate over int64 group keys.

    Each device: sort/unique its row shard's keys into a fixed-capacity group
    table (jnp.unique with static size) and segment-reduce values per group.
    Merge: all_gather the per-shard tables over the mesh axis and re-reduce —
    two-phase aggregation where the shuffle is one ICI collective. No hashing,
    no collisions: real keys are carried through both phases.

    agg_ops: per value-column ops from {sum, count, min, max, mean}.
    capacity: max distinct keys (static; XLA shape). Exceeding it sets the
    returned overflow flag (host should retry with a larger capacity).

    Returns fn(keys, key_valid, *[(values, valid) flattened]) ->
      (group_keys[capacity], group_valid[capacity], overflow_scalar,
       results: tuple of per-column (values[capacity], valid[capacity])).
    Rows with invalid keys (nulls / shard padding) are excluded.
    """
    ops = list(agg_ops)
    # memoize the compiled step: repeated groupbys at the same (mesh, ops,
    # capacity) reuse one jitted multi-device program instead of rebuilding a
    # fresh closure that can never cache-hit (Mesh is hashable by value)
    cache_key = (mesh, tuple(ops), capacity, axis)
    cached = _STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    cap1 = capacity + 1  # one extra slot so the sentinel never evicts a real key

    def _true_unique_count(sorted_keys: jnp.ndarray) -> jnp.ndarray:
        """Number of distinct non-sentinel keys in an ascending-sorted array."""
        real = sorted_keys != _KEY_SENTINEL
        first = jnp.concatenate([
            jnp.ones((1,), dtype=bool),
            sorted_keys[1:] != sorted_keys[:-1],
        ])
        return jnp.sum(first & real)

    def local(keys, key_valid, *flat):
        cols = [(flat[2 * i], flat[2 * i + 1]) for i in range(len(ops))]
        k = jnp.where(key_valid, keys.astype(jnp.int64), _KEY_SENTINEL)
        sorted_k = jnp.sort(k)
        local_nu = _true_unique_count(sorted_k)
        uk = jnp.unique(k, size=cap1, fill_value=_KEY_SENTINEL)
        seg = jnp.searchsorted(uk, k)

        # per-column partial tables; a "count" partial is always included so the
        # merge phase can null out groups whose values are all-null
        col_partials: List[List[str]] = []
        partial_tables = []
        for (v, m), op in zip(cols, ops):
            mask = dev._broadcast_valid(k, m) & key_valid
            partials = list(_decompose_agg(op))
            if "count" not in partials:
                partials.append("count")
            col_partials.append(partials)
            for partial in partials:
                partial_tables.append(_segment_reduce(partial, v, mask, seg, cap1))

        # merge phase: gather every shard's table, re-group by real key
        all_k = jax.lax.all_gather(uk, axis).reshape(-1)
        gathered = [jax.lax.all_gather(t, axis).reshape(-1) for t in partial_tables]
        fuk = jnp.unique(all_k, size=cap1, fill_value=_KEY_SENTINEL)
        fseg = jnp.searchsorted(fuk, all_k)

        idx = 0
        results = []
        src_valid = all_k != _KEY_SENTINEL
        for op, partials in zip(ops, col_partials):
            merged = {}
            for partial in partials:
                t = gathered[idx]
                idx += 1
                merged[partial] = _segment_reduce(
                    _merge_op(partial), t, src_valid, fseg, cap1
                )
            cnt = merged["count"]
            if op == "mean":
                val = merged["sum"] / jnp.maximum(cnt, 1)
                ok = cnt > 0
            elif op == "count":
                val = cnt
                ok = jnp.ones_like(cnt, dtype=bool)
            else:
                val = merged[op]
                ok = cnt > 0
            results.append((val[:capacity], ok[:capacity]))

        total_nu = _true_unique_count(jnp.sort(all_k))
        overflow = (
            _pextreme(local_nu, axis, is_min=False) > capacity
        ) | (total_nu > capacity)
        group_keys = fuk[:capacity]
        group_valid = group_keys != _KEY_SENTINEL
        return group_keys, group_valid, overflow, tuple(results)

    in_specs = tuple([P(axis), P(axis)] + [P(axis)] * (2 * len(ops)))
    out_specs = (P(), P(), P(), tuple((P(), P()) for _ in ops))
    step = jax.jit(_shard_map(local, mesh, in_specs, out_specs))
    with _CACHE_LOCK:
        _STEP_CACHE[cache_key] = step
    return step


def _shard_map(local, mesh: Mesh, in_specs, out_specs):
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def sharded_alltoall_repartition_step(mesh: Mesh, dtypes: Sequence,
                                      axis: str = "dp") -> Callable:
    """Intra-host repartition over ICI: each shard stable-sorts its rows by
    destination, packs them into per-destination bins, and ONE
    ``jax.lax.all_to_all`` routes every bin to its owner — the in-mesh
    replacement for the host shuffle's write-files/fetch round trip when the
    'workers' are co-located mesh shards (SURVEY §7's two-tier mapping:
    ICI inside the host, DCN/host shuffle across hosts).

    dtypes: one per exchanged plane (column values and validity planes are
    both planes here). Bins are padded to the full shard size S (worst case
    one destination receives everything), so each device holds an
    [n_dev, S]-shaped scratch per plane — an input-sized copy per device.
    The path is an EXPLICIT opt-in (executor._mesh_repart_eligible requires
    a forced mesh_devices width matching the partition count), not
    cost-gated: forced tiers run forced, like every other forced tier.

    Returns fn(dest, row_mask, *planes) ->
      (counts[n_dev*n_dev] int64, tuple of exchanged planes [n_dev*n_dev, S])
    where row-block ``d * n_dev + j`` of an exchanged plane holds source
    shard j's rows destined to partition d (first counts[d*n_dev+j] rows
    real, in original stream order — stable sort + contiguous row shards
    preserve it end to end).
    """
    n_dev = int(mesh.shape[axis])
    dtypes = tuple(dtypes)

    def local(dest, row_mask, *planes):
        S = dest.shape[0]
        counts, mats = _repart_sort_pack(dest, row_mask, planes, n_dev, S)
        outs = [jax.lax.all_to_all(m, axis, split_axis=0, concat_axis=0,
                                   tiled=True) for m in mats]
        cnt_x = jax.lax.all_to_all(counts.reshape(n_dev, 1), axis,
                                   split_axis=0, concat_axis=0, tiled=True)
        return cnt_x.reshape(n_dev), tuple(outs)

    in_specs = tuple([P(axis), P(axis)] + [P(axis)] * len(dtypes))
    out_specs = (P(axis), tuple(P(axis) for _ in dtypes))
    return jax.jit(_shard_map(local, mesh, in_specs, out_specs))


def _repart_sort_pack(dest, row_mask, planes, n_dev: int, S: int):
    """Shared local half of both repartition exchanges: stable-sort this
    shard's rows by destination and scatter them into per-destination bins.
    Returns (counts[n_dev] int64, one [n_dev, S] bin matrix per plane)."""
    d = jnp.where(row_mask, dest.astype(jnp.int64), n_dev)
    order = jnp.argsort(d)  # jax argsort lowers to a stable lax.sort
    d_sorted = d[order]
    valid_sorted = d_sorted < n_dev
    counts = _segment_reduce("count", d, d < n_dev,
                             jnp.minimum(d, n_dev), n_dev + 1)[:n_dev]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int64),
                               jnp.cumsum(counts)[:-1]])
    safe_bin = jnp.minimum(d_sorted, n_dev - 1)
    pos = jnp.arange(S, dtype=jnp.int64) - offsets[safe_bin]
    flat_idx = jnp.where(valid_sorted, safe_bin * S + pos, n_dev * S)
    mats = []
    for p in planes:
        sp = p[order]
        mat = jnp.zeros((n_dev * S,), dtype=p.dtype)
        mat = mat.at[flat_idx].set(sp, mode="drop")
        mats.append(mat.reshape(n_dev, S))
    return counts, mats


def _pack_words(mat: jnp.ndarray) -> jnp.ndarray:
    """[n_dev, S] plane of any device dtype -> [n_dev, W] uint32 words,
    bit-exact and invertible by _unpack_words: 64-bit types split into two
    words, <=32-bit types widen losslessly to one. 64-bit integers split by
    shift and truncate: the chip's compiler has no rewrite for a
    shape-changing 64-bit bitcast. It has none for f64 -> bits either, so on
    the chip the executor hands f64 planes over as their uint64 host view
    and this f64 branch serves other backends only."""
    dt = mat.dtype
    if dt.itemsize == 8 and jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(mat, jnp.uint32) \
            .reshape(mat.shape[0], -1)
    if dt.itemsize == 8:
        u = mat.astype(jnp.uint64)
        pair = jnp.stack([u.astype(jnp.uint32),
                          (u >> jnp.uint64(32)).astype(jnp.uint32)], axis=-1)
        return pair.reshape(mat.shape[0], -1)
    if dt == jnp.bool_:
        return mat.astype(jnp.uint32)
    if jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(mat.astype(jnp.float32),
                                            jnp.uint32)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return mat.astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(mat.astype(jnp.int32), jnp.uint32)


def _unpack_words(words: jnp.ndarray, dt, S: int) -> jnp.ndarray:
    """Inverse of _pack_words: [n_dev, W] uint32 back to an [n_dev, S] dt
    plane."""
    dt = jnp.dtype(dt)
    if dt.itemsize == 8:
        pair = words.reshape(words.shape[0], S, 2)
        if jnp.issubdtype(dt, jnp.floating):
            return jax.lax.bitcast_convert_type(pair, jnp.float64)
        u = pair[..., 0].astype(jnp.uint64) \
            | (pair[..., 1].astype(jnp.uint64) << jnp.uint64(32))
        return u.astype(dt)
    if dt == jnp.bool_:
        return words != 0
    if jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(words, jnp.float32).astype(dt)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return words.astype(dt)
    return jax.lax.bitcast_convert_type(words, jnp.int32).astype(dt)


def sharded_ring_repartition_step(mesh: Mesh, dtypes: Sequence,
                                  axis: str = "dp",
                                  interpret: bool = False) -> Callable:
    """The Pallas tier of the intra-host repartition: same contract and
    bit-identical results as sharded_alltoall_repartition_step, but the
    exchange is an IN-KERNEL ICI ring permute (ops/pallas_kernels.py
    ring_permute_bits — a pallas_call issuing per-step remote DMAs with
    send/recv semaphores) instead of a standalone jax.lax.all_to_all. The
    sort, the per-destination pack, the permute and the unpack all lower
    into ONE compiled program with ZERO separate mesh collective dispatches
    — every plane (and the counts) bitcast into a single [n_dev, W] uint32
    word buffer so the ring crosses the interconnect exactly once.

    Selected by the executor's repartition exchange under
    DAFT_TPU_PALLAS=on only (interpreted off the chip); `auto` keeps the
    all_to_all tier. A lowering failure raises.
    """
    n_dev = int(mesh.shape[axis])
    dtypes = tuple(dtypes)

    def local(dest, row_mask, *planes):
        from ..ops.pallas_kernels import ring_permute_bits

        S = dest.shape[0]
        counts, mats = _repart_sort_pack(dest, row_mask, planes, n_dev, S)
        words = [_pack_words(m) for m in mats]
        widths = [w.shape[1] for w in words]
        words.append(_pack_words(counts.reshape(n_dev, 1)))
        buf = jnp.concatenate(words, axis=1)
        out = ring_permute_bits(buf, axis, interpret=interpret)
        outs = []
        off = 0
        for dt, w in zip(dtypes, widths):
            outs.append(_unpack_words(out[:, off:off + w], dt, S))
            off += w
        cnt_x = _unpack_words(out[:, off:off + 2], np.int64, 1)
        return cnt_x.reshape(n_dev), tuple(outs)

    in_specs = tuple([P(axis), P(axis)] + [P(axis)] * len(dtypes))
    out_specs = (P(axis), tuple(P(axis) for _ in dtypes))
    return jax.jit(_shard_map(local, mesh, in_specs, out_specs))


def groupby_host(mesh: Mesh, keys: np.ndarray, key_valid: np.ndarray,
                 value_cols: Sequence[Tuple[np.ndarray, np.ndarray]],
                 agg_ops: Sequence[str], axis: str = "dp",
                 capacity: Optional[int] = None):
    """Host driver for sharded_groupby_step: shards inputs, retries on overflow.

    Returns (group_keys np.int64[g], per-col list of (values np, valid np)) with
    only real groups (overflow resolved by doubling capacity).
    """
    n = len(keys)
    keys = keys.astype(np.int64)
    if key_valid.any() and keys[key_valid].max() == _KEY_SENTINEL:
        raise ValueError(
            f"group key {_KEY_SENTINEL} (int64 max) is reserved as the null/padding "
            "sentinel on the device groupby path"
        )
    if capacity is None:
        capacity = max(int(2 ** np.ceil(np.log2(max(16, min(n, 4096))))), 16)
    cols = {"__key__": (keys, key_valid)}
    for i, (v, m) in enumerate(value_cols):
        cols[f"__v{i}__"] = (v, m)
    sharded = shard_columns(mesh, cols, n, axis=axis)
    flat = []
    for i in range(len(value_cols)):
        dv, dm = sharded[f"__v{i}__"]
        flat += [dv, dm]
    while True:
        step = sharded_groupby_step(mesh, agg_ops, capacity, axis=axis)
        gk, gv, overflow, results = step(
            sharded["__key__"][0], sharded["__key__"][1], *flat
        )
        if bool(np.asarray(overflow)):
            capacity *= 2
            continue
        gk = np.asarray(gk)
        gv = np.asarray(gv)
        keep = gv
        out_cols = [
            (np.asarray(v)[keep], np.asarray(ok)[keep]) for v, ok in results
        ]
        return gk[keep], out_cols
