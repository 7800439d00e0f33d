"""Pallas TPU kernels (SURVEY.md §7: custom kernels for the hot relational ops).

Three kernel families:

**Segment reduce** — the grouped-aggregation inner loop: accumulate value
planes into a (segments x planes) table keyed by per-row segment codes.
Instead of materializing one-hot matrices in HBM (the lax.scan formulation in
grouped_stage.py materializes chunk-sized one-hots per step), each kernel
builds its block's one-hot in VMEM and accumulates the block's partial into
the output across sequential grid steps, so HBM traffic per segment-column
block is: read planes once, read codes once, write the table once.
Entry points: segment_sum_planes (single-window parity anchor),
segment_sum_planes_windowed (the production tier: f32 window accumulation,
f64 cross-window combine outside the kernel but inside the same jit),
segment_extreme_planes (min/max), and segment_extreme_int64 (int extremes
past 2^53 via chained digit-plane refinement — three kernel launches glued
by in-jit XLA, exact over the full int64 domain).

**Hash probe** — the join inner loop: a VMEM-resident dim key table
(build_probe_table packs the dim key column into int32 hi/lo digit planes
plus a row-index payload plane) probed by every fact row with a grid-tiled
equality match on the VPU. hash_probe_index emits the fact->dim index plane
(bit-identical to device_join.unique_key_index), hash_probe_segment_sum
fuses probe + membership predicate + segment reduce into ONE kernel.

**ICI ring permute** — ring_permute_bits: an in-kernel all-to-all block
exchange (pallas_call with send/recv DMA semaphores, called inside
shard_map) so a mesh repartition and its consuming stage compile into one
program with zero standalone jax.lax.all_to_all dispatches
(parallel/distributed.sharded_ring_repartition_step).

Selected by grouped_stage._program_for / device_join / the executor's repartition
exchange when DAFT_TPU_PALLAS allows it (auto gates on the costmodel's
pallas_cell_rate / pallas_probe_cell_rate arms). Results are pinned by
interpret-mode tests; that the chip's compiler accepts each kernel at TPC-H
SF10 shapes is pinned by tests/test_chip_compile.py. A kernel that does not
lower raises to the caller: no tier silently replaces it.
"""

from __future__ import annotations

import functools

from ..utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# jax_enable_x64 traces a literal 0 in a BlockSpec index map as int64, which
# Mosaic refuses to legalize (func.return of (i32, i64)); grid indices are
# int32, so every constant block index is this int32 zero.
_Z = np.int32(0)

_BLOCK_ROWS = 1024
# f32 accumulation window: digit planes carry values <= 255, so a window
# partial tops out at 255 * 32768 = 8.3e6 < 2^24 and every window sum is
# f32-exact; the f64 cross-window combine then matches the XLA tiers bit
# for bit on the grouped stage's integer/count planes.
_WINDOW_ROWS = 32 * _BLOCK_ROWS
# segment-column tile: bounds the in-VMEM one-hot at BLOCK_ROWS x CAP_TILE
# f32 (= 8 MB at 2048) regardless of the total segment count.
_CAP_TILE = 2048
# ceiling for the Pallas tier: past this the table write-back dominates and
# the sort path wins outright; also bounds compile time for the tiled grid.
PALLAS_MAX_SEGMENTS = 1 << 17
# first-row indices ride an f32 plane inside the kernel; past 2^24 rows per
# bucket f32 cannot hold the index exactly, so the stage refuses at trace time
MAX_PALLAS_BUCKET = 1 << 24


def _row_block(n: int) -> int:
    """Row block size: buckets are power-of-two padded (>= 512), so
    min(_BLOCK_ROWS, n) always divides n."""
    b = min(_BLOCK_ROWS, n)
    assert n % b == 0, (n, b)
    return b


def _cap_tile(cap: int) -> int:
    t = min(_CAP_TILE, cap)
    assert cap % t == 0, (cap, t)
    return t


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def segment_sum_planes(planes: jnp.ndarray, codes: jnp.ndarray, cap: int,
                       interpret: bool = False) -> jnp.ndarray:
    """sum planes (N x P, f32) into segments (cap x P, f32) by codes (N, i32).

    N must be a multiple of the block size (the callers' quantized padding
    guarantees this); rows whose code is outside [0, cap) are dropped (the
    trash segment for filtered/padding rows). Single-window f32 accumulation —
    use segment_sum_planes_windowed when exactness past 2^24 matters.
    """
    n, p = planes.shape
    block = _row_block(n)
    grid = n // block

    def kernel(planes_ref, codes_ref, out_ref):
        step = pl.program_id(0)
        blk = planes_ref[...]                      # (BLOCK, P) in VMEM
        cds = codes_ref[...].astype(jnp.int32)     # (BLOCK, 1) — 2D for mosaic
        seg_ids = jax.lax.broadcasted_iota(jnp.int32, (block, cap), 1)
        oh = (cds == seg_ids).astype(jnp.float32)  # (BLOCK, cap)
        part = jax.lax.dot_general(                # (cap, P) on the MXU
            oh, blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(step == 0)
        def _init():
            out_ref[...] = part

        @pl.when(step != 0)
        def _acc():
            out_ref[...] += part

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block, p), lambda i: (i, _Z)),
            pl.BlockSpec((block, 1), lambda i: (i, _Z)),
        ],
        out_specs=pl.BlockSpec((cap, p), lambda i: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((cap, p), jnp.float32),
        interpret=interpret,
    )(planes, codes.reshape(-1, 1))


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def segment_sum_planes_windowed(planes: jnp.ndarray, codes: jnp.ndarray,
                                cap: int, interpret: bool = False) -> jnp.ndarray:
    """sum planes (N x P, f32) into segments (cap x P, f64) by codes (N, i32).

    The production tier behind grouped_stage._build_pallas: the grid tiles
    (window, segment-column, row-block); each (window, column) cell
    accumulates its row blocks in f32 VMEM — exact for the grouped stage's
    digit/count planes — and the per-window partials combine in f64 outside
    the kernel, inside this jit. Rows with codes outside [0, cap) are dropped.
    """
    n, p = planes.shape
    block = _row_block(n)
    blocks = n // block
    wnd = min(max(_WINDOW_ROWS // block, 1), blocks)  # row blocks per window
    n_windows = blocks // wnd
    tile = _cap_tile(cap)
    cap_tiles = cap // tile

    def kernel(planes_ref, codes_ref, out_ref):
        step = pl.program_id(2)
        ctile = pl.program_id(1)
        blk = planes_ref[...]                      # (BLOCK, P)
        cds = codes_ref[...].astype(jnp.int32)     # (BLOCK, 1)
        seg_ids = jax.lax.broadcasted_iota(jnp.int32, (block, tile), 1) \
            + ctile * tile
        oh = (cds == seg_ids).astype(jnp.float32)  # (BLOCK, tile)
        part = jax.lax.dot_general(                # (tile, P) on the MXU
            oh, blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(step == 0)
        def _init():
            out_ref[...] = part[None]

        @pl.when(step != 0)
        def _acc():
            out_ref[...] += part[None]

    parts = pl.pallas_call(
        kernel,
        grid=(n_windows, cap_tiles, wnd),
        in_specs=[
            pl.BlockSpec((block, p), lambda w, c, i: (w * wnd + i, _Z)),
            pl.BlockSpec((block, 1), lambda w, c, i: (w * wnd + i, _Z)),
        ],
        out_specs=pl.BlockSpec((1, tile, p), lambda w, c, i: (w, c, _Z)),
        out_shape=jax.ShapeDtypeStruct((n_windows, cap, p), jnp.float32),
        interpret=interpret,
    )(planes, codes.reshape(-1, 1))
    return parts.astype(jnp.float64).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("cap", "op", "interpret"))
def segment_extreme_planes(planes: jnp.ndarray, codes: jnp.ndarray, cap: int,
                           op: str, interpret: bool = False) -> jnp.ndarray:
    """min/max planes (N x Q, f32, identity-filled) into (cap x Q, f32).

    Masked-out rows must already carry the identity (+inf for min, -inf for
    max) — the kernel only routes by segment code; codes outside [0, cap)
    are dropped. Plane columns loop inside the kernel (Q is a handful), so
    the in-VMEM select buffer stays one (BLOCK x tile) slab.
    """
    assert op in ("min", "max"), op
    n, q = planes.shape
    block = _row_block(n)
    blocks = n // block
    tile = _cap_tile(cap)
    cap_tiles = cap // tile
    big = float("inf") if op == "min" else float("-inf")  # python scalar:
    # jnp constants captured from outside a pallas kernel are rejected

    def kernel(planes_ref, codes_ref, out_ref):
        step = pl.program_id(1)
        ctile = pl.program_id(0)
        blk = planes_ref[...]                      # (BLOCK, Q)
        cds = codes_ref[...].astype(jnp.int32)     # (BLOCK, 1)
        seg_ids = jax.lax.broadcasted_iota(jnp.int32, (block, tile), 1) \
            + ctile * tile
        oh = cds == seg_ids                        # (BLOCK, tile) bool
        cols = []
        for j in range(q):
            w = jnp.where(oh, blk[:, j][:, None], big)   # (BLOCK, tile)
            red = (jnp.min(w, axis=0, keepdims=True) if op == "min"
                   else jnp.max(w, axis=0, keepdims=True))  # (1, tile)
            cols.append(red)
        part = jnp.concatenate(cols, axis=0).T     # (tile, Q)

        @pl.when(step == 0)
        def _init():
            out_ref[...] = part

        @pl.when(step != 0)
        def _acc():
            cur = out_ref[...]
            out_ref[...] = (jnp.minimum(cur, part) if op == "min"
                            else jnp.maximum(cur, part))

    return pl.pallas_call(
        kernel,
        grid=(cap_tiles, blocks),
        in_specs=[
            pl.BlockSpec((block, q), lambda c, i: (i, _Z)),
            pl.BlockSpec((block, 1), lambda c, i: (i, _Z)),
        ],
        out_specs=pl.BlockSpec((tile, q), lambda c, i: (c, _Z)),
        out_shape=jax.ShapeDtypeStruct((cap, q), jnp.float32),
        interpret=interpret,
    )(planes, codes.reshape(-1, 1))


_I64_MIN = -(1 << 63)
_D24 = (1 << 24) - 1


@functools.partial(jax.jit, static_argnames=("cap", "op", "interpret"))
def segment_extreme_int64(vals: jnp.ndarray, mask: jnp.ndarray,
                          codes: jnp.ndarray, cap: int, op: str,
                          interpret: bool = False):
    """Exact int64 min/max by segment — past 2^53, where a single f64 plane
    quantizes. The order-preserving trick: XOR the sign bit maps int64 order
    onto uint64 order; three 24/24/16-bit digit planes of that unsigned view
    each fit f32 exactly, and a chained refinement (reduce the high digit,
    then reduce the next digit only over rows still tied with the running
    winner) recovers the exact extreme in three kernel launches glued by
    in-jit XLA. Returns (int64[cap] extremes, bool[cap] nonempty); empty
    segments carry the reduction identity (int64 max for min / min for max),
    matching the XLA scatter tier's segment_min/max fill.
    """
    assert op in ("min", "max"), op
    u = jax.lax.bitcast_convert_type(
        vals.astype(jnp.int64) ^ jnp.int64(_I64_MIN), jnp.uint64)
    digits = (
        (u >> jnp.uint64(48)).astype(jnp.float32),            # 16 bits
        ((u >> jnp.uint64(24)) & jnp.uint64(_D24)).astype(jnp.float32),
        (u & jnp.uint64(_D24)).astype(jnp.float32),
    )
    big = jnp.float32(jnp.inf if op == "min" else -jnp.inf)
    safe = jnp.clip(codes, 0, cap - 1)
    m = mask
    reduced = []
    for dplane in digits:
        plane = jnp.where(m, dplane, big)
        r = segment_extreme_planes(plane[:, None], codes, cap, op,
                                   interpret=interpret)[:, 0]
        reduced.append(r)
        # refine: only rows still tied with the per-segment winner compete
        # for the next (less significant) digit
        m = m & (dplane == r[safe])
    nonempty = jnp.isfinite(reduced[0])
    shifts = (48, 24, 0)
    acc = jnp.zeros(cap, dtype=jnp.uint64)
    for r, sh in zip(reduced, shifts):
        d = jnp.where(nonempty, r, 0.0).astype(jnp.uint64)
        acc = acc | (d << jnp.uint64(sh))
    out = jax.lax.bitcast_convert_type(acc, jnp.int64) ^ jnp.int64(_I64_MIN)
    info = jnp.iinfo(jnp.int64)
    ident = info.max if op == "min" else info.min
    return jnp.where(nonempty, out, jnp.int64(ident)), nonempty


# ---- hash-probe join kernels ---------------------------------------------------------
#
# The dim side of an equi-join becomes a device-resident "probe table": the
# key column split into int32 hi/lo digit planes (exact over the FULL int64
# domain — hi = k >> 32, lo = k & 0xffffffff) plus an f32 payload plane
# carrying row+1 (0 = empty slot, so misses sum to 0 and decode to idx -1).
# The kernel tiles the fact rows x table slots match matrix through VMEM:
# each (row-block x table-tile) cell is a VPU equality compare, and the
# matched payload reduces along the table axis. Probing is O(rows x slots) —
# brute force, but entirely vector-parallel and gather-free; the cost model's
# pallas_probe_cell_rate arm prices it against the XLA gather tier, so big
# dims keep the gather and small dims (the star-schema common case) fuse.

PROBE_SENTINEL = _I64_MIN  # marks empty table slots AND invalid fact rows
_PROBE_TILE = 2048


def build_probe_table(keys: "np.ndarray", valid: "np.ndarray" = None):
    """Host-side probe-table build from a dim key column.

    Returns (tbl_hi, tbl_lo, tbl_row): three (1, T) host arrays — int32 key
    digit planes and the f32 row+1 payload — with T the slot count padded to
    a power of two >= 128 (tileable by every _PROBE_TILE divisor). Invalid
    (null) dim keys and padding slots carry PROBE_SENTINEL digits with a 0
    payload, so nothing real ever matches them. Raises ValueError when valid
    keys collide (the caller maps this onto the same DeviceFallback as
    unique_key_index) or when the dim is too large for the f32 payload.
    """
    keys = np.asarray(keys, dtype=np.int64)
    n = len(keys)
    if valid is None:
        valid = np.ones(n, dtype=bool)
    if n >= MAX_PALLAS_BUCKET:
        raise ValueError(
            f"probe table: {n} dim rows exceed the f32 payload range")
    vk = keys[valid]
    if len(vk) and np.any(vk == PROBE_SENTINEL):
        raise ValueError("probe table: a dim key equals the empty-slot "
                         "sentinel (int64 min)")
    if len(np.unique(vk)) != len(vk):
        raise ValueError("probe table: dim keys are not unique")
    t = 128
    while t < n:
        t *= 2
    hi = np.full(t, PROBE_SENTINEL >> 32, dtype=np.int64)
    lo = np.zeros(t, dtype=np.int64)
    row = np.zeros(t, dtype=np.float32)
    hi[:n] = np.where(valid, keys >> 32, PROBE_SENTINEL >> 32)
    lo[:n] = np.where(valid, keys & 0xFFFFFFFF, 0)
    row[:n] = np.where(valid, np.arange(1, n + 1, dtype=np.float32), 0.0)
    # int32 digit planes: hi is the arithmetic high word, lo the raw low word
    return (hi.astype(np.int32).reshape(1, t),
            lo.astype(np.uint32).view(np.int32).reshape(1, t),
            row.reshape(1, t))


def probe_key_digits(vals: jnp.ndarray, valid: jnp.ndarray):
    """Fact-side (hi, lo) int32 digit planes; invalid rows get the sentinel's
    digits — they can only match zero-payload slots and decode to idx -1."""
    v = jnp.where(valid, vals.astype(jnp.int64), jnp.int64(PROBE_SENTINEL))
    hi = (v >> jnp.int64(32)).astype(jnp.int32)
    lo = jax.lax.convert_element_type(
        jax.lax.bitcast_convert_type(v, jnp.uint64) & jnp.uint64(0xFFFFFFFF),
        jnp.uint32)
    return hi, jax.lax.bitcast_convert_type(lo, jnp.int32)


def _probe_tbl_tile(t: int) -> int:
    tile = min(_PROBE_TILE, t)
    assert t % tile == 0, (t, tile)
    return tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_probe_index(fact_hi: jnp.ndarray, fact_lo: jnp.ndarray,
                     tbl_hi: jnp.ndarray, tbl_lo: jnp.ndarray,
                     tbl_row: jnp.ndarray, interpret: bool = False):
    """Probe fact key digits (N, i32 each) against a (1, T) table; returns
    the int32 fact->dim index plane (-1 = miss), bit-identical to the host
    unique_key_index. Each grid cell matches one (row-block x table-tile)
    slab in VMEM and accumulates the matched row+1 payload along the table
    axis; uniqueness of table keys means at most one tile contributes."""
    n = fact_hi.shape[0]
    block = _row_block(n)
    t = tbl_hi.shape[1]
    tile = _probe_tbl_tile(t)

    def kernel(fh_ref, fl_ref, th_ref, tl_ref, tr_ref, out_ref):
        step = pl.program_id(1)
        fh = fh_ref[...]                          # (BLOCK, 1)
        fl = fl_ref[...]
        th = th_ref[...]                          # (1, tile)
        tl = tl_ref[...]
        tr = tr_ref[...]
        match = (fh == th) & (fl == tl)           # (BLOCK, tile)
        part = jnp.sum(jnp.where(match, tr, 0.0), axis=1,
                       keepdims=True)             # (BLOCK, 1)

        @pl.when(step == 0)
        def _init():
            out_ref[...] = part

        @pl.when(step != 0)
        def _acc():
            out_ref[...] += part

    acc = pl.pallas_call(
        kernel,
        grid=(n // block, t // tile),
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
            pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(fact_hi.reshape(-1, 1), fact_lo.reshape(-1, 1), tbl_hi, tbl_lo, tbl_row)
    return acc.reshape(-1).astype(jnp.int32) - 1


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def hash_probe_segment_sum(fact_hi: jnp.ndarray, fact_lo: jnp.ndarray,
                           codes: jnp.ndarray,
                           tbl_hi: jnp.ndarray, tbl_lo: jnp.ndarray,
                           tbl_row: jnp.ndarray,
                           tbl_planes: jnp.ndarray, cap: int,
                           interpret: bool = False):
    """The fully fused join inner loop: probe + membership predicate +
    segment reduce in ONE kernel. Fact rows probe the (1, T) key table;
    matched rows gather the table's (T, P) f32 value planes via the match
    matrix on the MXU and accumulate them into a (cap, P+1) segment table by
    fact-side codes — column P is the match count (the membership predicate:
    a row that missed every slot contributes to no plane and no count).
    Returns (cap, P) gathered-value sums and (cap,) matched-row counts.
    f32 accumulation: exact for digit/count planes (the same contract as
    segment_sum_planes); misses/padding rows contribute exact zeros.
    """
    n = fact_hi.shape[0]
    block = _row_block(n)
    t = tbl_hi.shape[1]
    tile = _probe_tbl_tile(t)
    p = tbl_planes.shape[1]

    last_tile = t // tile - 1

    def kernel(fh_ref, fl_ref, codes_ref, th_ref, tl_ref, tr_ref, tp_ref,
               out_ref, gath_ref):
        row_blk = pl.program_id(0)
        step = pl.program_id(1)
        fh = fh_ref[...]                           # (BLOCK, 1)
        fl = fl_ref[...]
        # sentinel-digit fact rows (invalid keys) equal the padding slots'
        # digits, so real-slot membership rides the payload plane: only
        # slots with a nonzero row+1 payload count as hits
        match = ((fh == th_ref[...]) & (fl == tl_ref[...])
                 & (tr_ref[...] > 0.0))            # (BLOCK, tile)
        mf = match.astype(jnp.float32)
        part = jax.lax.dot_general(                # (BLOCK, P) on the MXU
            mf, tp_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        hit = jnp.sum(mf, axis=1, keepdims=True)   # (BLOCK, 1) membership

        @pl.when(step == 0)
        def _init():
            gath_ref[...] = jnp.concatenate([part, hit], axis=1)

        @pl.when(step != 0)
        def _acc():
            gath_ref[...] += jnp.concatenate([part, hit], axis=1)

        @pl.when((step == last_tile) & (row_blk == 0))
        def _reduce_first():
            out_ref[...] = _reduce(gath_ref, codes_ref)

        @pl.when((step == last_tile) & (row_blk != 0))
        def _reduce_rest():
            out_ref[...] += _reduce(gath_ref, codes_ref)

    def _reduce(gath_ref, codes_ref):
        g = gath_ref[...]                          # (BLOCK, P+1)
        member = g[:, p:p + 1] > 0.0               # membership predicate
        cds = codes_ref[...].astype(jnp.int32)     # (BLOCK, 1)
        # np.int32: a bare python int is traced as int64 under x64, and
        # Mosaic's i64->i32 convert recurses without end
        seg = jnp.where(member, cds, np.int32(cap))
        seg_ids = jax.lax.broadcasted_iota(jnp.int32, (block, cap), 1)
        oh = (seg == seg_ids).astype(jnp.float32)
        return jax.lax.dot_general(                # (cap, P+1)
            oh, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=(n // block, t // tile),
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
            pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
            pl.BlockSpec((block, 1), lambda i, c: (i, _Z)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
            pl.BlockSpec((1, tile), lambda i, c: (_Z, c)),
            pl.BlockSpec((tile, p), lambda i, c: (c, _Z)),
        ],
        out_specs=pl.BlockSpec((cap, p + 1), lambda i, c: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((cap, p + 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, p + 1), jnp.float32)],
        interpret=interpret,
    )(fact_hi.reshape(-1, 1), fact_lo.reshape(-1, 1), codes.reshape(-1, 1),
      tbl_hi, tbl_lo, tbl_row, tbl_planes)
    return out[:, :p], out[:, p]


# ---- in-kernel ICI ring permute ------------------------------------------------------

_RING_SLAB = 8 * 128  # one (sublane, lane) tile of 32-bit words


def ring_permute_bits(buf: jnp.ndarray, axis: str, interpret: bool = False):
    """All-to-all block exchange, in-kernel: must be called INSIDE a
    shard_map over `axis`. buf is each shard's (n_dev, W) uint32 send
    matrix (row d = my block for device d); the result's row j = source
    shard j's block for me — the same permutation jax.lax.all_to_all(...,
    split_axis=0, concat_axis=0) performs, but issued as per-step remote
    DMAs (send/recv semaphore pairs) from inside one pallas_call, so the
    surrounding program needs NO standalone collective dispatch. Step s
    sends block (me+s) mod n to that device; the matching receive from
    (me-s) mod n signals the same semaphore slot, so each step's wait pairs
    up symmetrically across the ring.
    """
    n_dev, w = buf.shape
    # blocks ride an untiled leading axis: slicing row d of a 2-D (n_dev, W)
    # buffer cuts the tiled sublane axis ("slice shape along dimension 0 must
    # be aligned to tiling"), so each block becomes a whole (8k, 128) slab
    wp = -(-w // _RING_SLAB) * _RING_SLAB
    buf = jnp.pad(buf, ((0, 0), (0, wp - w))).reshape(n_dev, wp // 128, 128)

    def kernel(buf_ref, out_ref, send_sem, recv_sem):
        # every index is int32: under x64 a python int slices a semaphore
        # array with an i64, which tpu.memref_slice refuses
        my_id = jax.lax.axis_index(axis)
        if not interpret:
            # co-launch barrier: no remote DMA may land before every peer's
            # kernel owns its output buffer
            barrier = pltpu.get_barrier_semaphore()
            for peer in range(n_dev):
                pltpu.semaphore_signal(
                    barrier, device_id=jnp.int32(peer),
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(barrier, n_dev)
        local = pltpu.make_async_copy(buf_ref.at[my_id], out_ref.at[my_id],
                                      send_sem.at[np.int32(n_dev - 1)])
        local.start()
        local.wait()
        for s in range(1, n_dev):
            dst = jax.lax.rem(my_id + jnp.int32(s), jnp.int32(n_dev))
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf_ref.at[dst],
                dst_ref=out_ref.at[my_id],
                send_sem=send_sem.at[np.int32(s - 1)],
                recv_sem=recv_sem.at[np.int32(s - 1)],
                device_id=dst,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdma.wait()

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(buf.shape, jnp.uint32),
        scratch_shapes=[pltpu.SemaphoreType.DMA((n_dev,)),
                        pltpu.SemaphoreType.DMA((n_dev,))],
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=interpret,
    )(buf).reshape(n_dev, wp)[:, :w]
