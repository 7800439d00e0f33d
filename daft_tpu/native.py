"""ctypes loader for the C++ host-kernel library (native/src/kernels.cpp).

Reference parity: the reference compiles its Rust core into the daft.daft
extension module; here the hot host kernels live in a C ABI shared library with
a numpy path for hosts without a toolchain. The library is a build product
outside git (daft_tpu/_native/): get_lib() builds it on first use where it is
missing or older than its source, build() builds it unconditionally, and
implementation() reports which path this process uses and why.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SO_PATH = os.path.join(_REPO_ROOT, "daft_tpu", "_native", "libdaft_native.so")


# why get_lib() serves None (the numpy paths are in use); "" while unknown
_WHY_NUMPY = ""


def build() -> None:
    """Compile native/src/kernels.cpp into daft_tpu/_native/ (the library is
    a build product outside git). Raises when there is no compiler or the
    compile fails. Call it before the first get_lib(): a loaded library must
    not be overwritten."""
    os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
         os.path.join(_REPO_ROOT, "native", "src", "kernels.cpp"), "-o", _SO_PATH],
        check=True, capture_output=True, timeout=300,
    )


def implementation() -> str:
    """Which host kernels this process uses: "native", or "numpy: <why>"."""
    return "native" if get_lib() is not None else f"numpy: {_WHY_NUMPY}"


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None on a host without a toolchain (every
    caller has a numpy path); implementation() says which and why."""
    global _LIB, _TRIED, _WHY_NUMPY
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("DAFT_TPU_DISABLE_NATIVE"):
        _WHY_NUMPY = "DAFT_TPU_DISABLE_NATIVE is set"
        return None
    src = os.path.join(_REPO_ROOT, "native", "src", "kernels.cpp")
    stale = (
        os.path.exists(_SO_PATH) and os.path.exists(src)
        and os.path.getmtime(src) > os.path.getmtime(_SO_PATH)
    )
    if not os.path.exists(_SO_PATH) or stale:
        try:
            build()
        except (OSError, subprocess.SubprocessError) as e:
            _WHY_NUMPY = f"build failed: {e}"
    if not os.path.exists(_SO_PATH):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        _WHY_NUMPY = f"load failed: {e}"
        return None
    i64p = ctypes.POINTER(ctypes.c_int64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)

    lib.xxhash64.restype = ctypes.c_uint64
    lib.xxhash64.argtypes = [u8p, ctypes.c_uint64, ctypes.c_uint64]
    lib.hash_binary_column.restype = None
    lib.hash_binary_column.argtypes = [u8p, i64p, ctypes.c_int64, ctypes.c_uint64, u64p]
    lib.hash_u64_column.restype = None
    lib.hash_u64_column.argtypes = [u64p, ctypes.c_int64, ctypes.c_uint64, u64p]
    lib.factorize_i64.restype = ctypes.c_int64
    lib.factorize_i64.argtypes = [i64p, ctypes.c_int64, i64p]
    lib.combine_factorize_i64.restype = ctypes.c_int64
    lib.combine_factorize_i64.argtypes = [i64p, i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.grouped_sum_f64.restype = None
    lib.grouped_sum_f64.argtypes = [i64p, f64p, u8p, ctypes.c_int64, ctypes.c_int64, f64p, i64p]
    lib.grouped_sum_i64.restype = None
    lib.grouped_sum_i64.argtypes = [i64p, i64p, u8p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.grouped_minmax_f64.restype = None
    lib.grouped_minmax_f64.argtypes = [i64p, f64p, u8p, ctypes.c_int64, ctypes.c_int64, f64p, f64p]
    lib.grouped_minmax_i64.restype = None
    lib.grouped_minmax_i64.argtypes = [i64p, i64p, u8p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.join_count.restype = ctypes.c_int64
    lib.join_count.argtypes = [i64p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.join_fill.restype = None
    lib.join_fill.argtypes = [i64p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64,
                              i64p, i64p, i64p, i64p]
    lib.probe_count.restype = ctypes.c_int64
    lib.probe_count.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.i64_pairmap_build.restype = None
    lib.i64_pairmap_build.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.i64_pairmap_lookup.restype = None
    lib.i64_pairmap_lookup.argtypes = [i64p, ctypes.c_int64, i64p, ctypes.c_int64, i64p]
    lib.probe_lookup_count_pair.restype = ctypes.c_int64
    lib.probe_lookup_count_pair.argtypes = [i64p, u8p, ctypes.c_int64, i64p,
                                            ctypes.c_int64, i64p, ctypes.c_int64,
                                            i64p, i64p]
    lib.probe_fill.restype = None
    lib.probe_fill.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p, i64p,
                               i64p, i64p]
    lib.bucket_build.restype = ctypes.c_int64
    lib.bucket_build.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.bool_mask_indices.restype = ctypes.c_int64
    lib.bool_mask_indices.argtypes = [u8p, u8p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.probe_unique_pair.restype = ctypes.c_int64
    lib.probe_unique_pair.argtypes = [i64p, u8p, ctypes.c_int64, i64p,
                                      ctypes.c_int64, i64p, i64p, i64p]
    lib.probe_unique_dense.restype = ctypes.c_int64
    lib.probe_unique_dense.argtypes = [i64p, u8p, ctypes.c_int64, ctypes.c_int64,
                                       ctypes.c_int64, i64p, i64p, i64p, i64p]
    lib.probe_lookup_count_hash.restype = ctypes.c_int64
    lib.probe_lookup_count_hash.argtypes = [i64p, u8p, ctypes.c_int64, i64p, i64p,
                                            ctypes.c_int64, i64p, ctypes.c_int64,
                                            i64p, i64p]
    lib.probe_lookup_count_dense.restype = ctypes.c_int64
    lib.probe_lookup_count_dense.argtypes = [i64p, u8p, ctypes.c_int64,
                                             ctypes.c_int64, ctypes.c_int64, i64p,
                                             ctypes.c_int64, i64p, i64p]
    lib.bucket_scatter.restype = None
    lib.bucket_scatter.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    _LIB = lib
    return _LIB


def _p(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def native_factorize(keys: np.ndarray) -> Optional[tuple]:
    """(codes, num_groups) in first-occurrence order, or None if lib unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    out = np.empty(len(keys), dtype=np.int64)
    g = lib.factorize_i64(_p(keys, ctypes.c_int64), len(keys), _p(out, ctypes.c_int64))
    return out, int(g)


def native_combine_factorize(a: np.ndarray, b: np.ndarray, b_domain: int) -> Optional[tuple]:
    lib = get_lib()
    if lib is None:
        return None
    a = np.ascontiguousarray(a, dtype=np.int64)
    b = np.ascontiguousarray(b, dtype=np.int64)
    out = np.empty(len(a), dtype=np.int64)
    g = lib.combine_factorize_i64(_p(a, ctypes.c_int64), _p(b, ctypes.c_int64),
                                  len(a), int(b_domain), _p(out, ctypes.c_int64))
    return out, int(g)


def native_join_counts(lcodes: np.ndarray, rcodes: np.ndarray, num_codes: int) -> Optional[np.ndarray]:
    """Per-left-row match counts only (semi/anti joins skip pair materialization)."""
    lib = get_lib()
    if lib is None:
        return None
    lcodes = np.ascontiguousarray(lcodes, dtype=np.int64)
    rcodes = np.ascontiguousarray(rcodes, dtype=np.int64)
    nl, nr = len(lcodes), len(rcodes)
    bucket_counts = np.empty(max(num_codes, 1), dtype=np.int64)
    l_match = np.empty(max(nl, 1), dtype=np.int64)
    lib.join_count(_p(lcodes, ctypes.c_int64), nl, _p(rcodes, ctypes.c_int64), nr,
                   num_codes, _p(bucket_counts, ctypes.c_int64), _p(l_match, ctypes.c_int64))
    return l_match[:nl]


def native_join_indices(lcodes: np.ndarray, rcodes: np.ndarray, num_codes: int) -> Optional[tuple]:
    """Inner-match pairs for compact codes: (l_idx, r_idx, l_match_counts)."""
    lib = get_lib()
    if lib is None:
        return None
    lcodes = np.ascontiguousarray(lcodes, dtype=np.int64)
    rcodes = np.ascontiguousarray(rcodes, dtype=np.int64)
    nl, nr = len(lcodes), len(rcodes)
    bucket_counts = np.empty(max(num_codes, 1), dtype=np.int64)
    l_match = np.empty(max(nl, 1), dtype=np.int64)
    total = lib.join_count(_p(lcodes, ctypes.c_int64), nl, _p(rcodes, ctypes.c_int64), nr,
                           num_codes, _p(bucket_counts, ctypes.c_int64), _p(l_match, ctypes.c_int64))
    offsets = np.concatenate([[0], np.cumsum(bucket_counts[:num_codes])[:-1]]).astype(np.int64) \
        if num_codes else np.zeros(1, np.int64)
    bucket_rows = np.empty(max(nr, 1), dtype=np.int64)
    out_l = np.empty(max(total, 1), dtype=np.int64)
    out_r = np.empty(max(total, 1), dtype=np.int64)
    lib.join_fill(_p(lcodes, ctypes.c_int64), nl, _p(rcodes, ctypes.c_int64), nr, num_codes,
                  _p(offsets, ctypes.c_int64), _p(bucket_rows, ctypes.c_int64),
                  _p(out_l, ctypes.c_int64), _p(out_r, ctypes.c_int64))
    return out_l[:total], out_r[:total], l_match[:nl]


def native_grouped_sum(gids: np.ndarray, vals: np.ndarray, valid: np.ndarray,
                       num_groups: int) -> Optional[tuple]:
    """(sums, counts) or None. vals must be float64 or int64."""
    lib = get_lib()
    if lib is None:
        return None
    gids = np.ascontiguousarray(gids, dtype=np.int64)
    valid8 = np.ascontiguousarray(valid, dtype=np.uint8)
    if vals.dtype == np.float64:
        vals = np.ascontiguousarray(vals)
        out = np.empty(num_groups, dtype=np.float64)
        cnt = np.empty(num_groups, dtype=np.int64)
        lib.grouped_sum_f64(_p(gids, ctypes.c_int64), _p(vals, ctypes.c_double),
                            _p(valid8, ctypes.c_uint8), len(gids), num_groups,
                            _p(out, ctypes.c_double), _p(cnt, ctypes.c_int64))
        return out, cnt
    if vals.dtype == np.int64:
        vals = np.ascontiguousarray(vals)
        out = np.empty(num_groups, dtype=np.int64)
        cnt = np.empty(num_groups, dtype=np.int64)
        lib.grouped_sum_i64(_p(gids, ctypes.c_int64), _p(vals, ctypes.c_int64),
                            _p(valid8, ctypes.c_uint8), len(gids), num_groups,
                            _p(out, ctypes.c_int64), _p(cnt, ctypes.c_int64))
        return out, cnt
    return None


def native_grouped_minmax(gids: np.ndarray, vals: np.ndarray, valid: np.ndarray,
                          num_groups: int) -> Optional[tuple]:
    lib = get_lib()
    if lib is None:
        return None
    gids = np.ascontiguousarray(gids, dtype=np.int64)
    valid8 = np.ascontiguousarray(valid, dtype=np.uint8)
    if vals.dtype == np.float64:
        vals = np.ascontiguousarray(vals)
        mn = np.empty(num_groups, dtype=np.float64)
        mx = np.empty(num_groups, dtype=np.float64)
        lib.grouped_minmax_f64(_p(gids, ctypes.c_int64), _p(vals, ctypes.c_double),
                               _p(valid8, ctypes.c_uint8), len(gids), num_groups,
                               _p(mn, ctypes.c_double), _p(mx, ctypes.c_double))
        return mn, mx
    if vals.dtype == np.int64:
        vals = np.ascontiguousarray(vals)
        mn = np.empty(num_groups, dtype=np.int64)
        mx = np.empty(num_groups, dtype=np.int64)
        lib.grouped_minmax_i64(_p(gids, ctypes.c_int64), _p(vals, ctypes.c_int64),
                               _p(valid8, ctypes.c_uint8), len(gids), num_groups,
                               _p(mn, ctypes.c_int64), _p(mx, ctypes.c_int64))
        return mn, mx
    return None


def native_probe(lcodes: np.ndarray, num_codes: int, bucket_offsets: np.ndarray,
                 bucket_counts: np.ndarray, bucket_rows: np.ndarray) -> Optional[tuple]:
    """Probe prebuilt join buckets: (l_idx, r_idx, l_match_counts) or None.
    Buckets are built once by kernels/join.py ProbeTable; this is the per-morsel
    lookup (all inputs read-only -> safe from concurrent pool threads)."""
    lib = get_lib()
    if lib is None:
        return None
    lcodes = np.ascontiguousarray(lcodes, dtype=np.int64)
    nl = len(lcodes)
    l_match = np.empty(max(nl, 1), dtype=np.int64)
    total = lib.probe_count(_p(lcodes, ctypes.c_int64), nl, int(num_codes),
                            _p(bucket_counts, ctypes.c_int64), _p(l_match, ctypes.c_int64))
    out_l = np.empty(max(total, 1), dtype=np.int64)
    out_r = np.empty(max(total, 1), dtype=np.int64)
    lib.probe_fill(_p(lcodes, ctypes.c_int64), nl, int(num_codes),
                   _p(bucket_offsets, ctypes.c_int64), _p(bucket_counts, ctypes.c_int64),
                   _p(bucket_rows, ctypes.c_int64), _p(out_l, ctypes.c_int64),
                   _p(out_r, ctypes.c_int64))
    return out_l[:total], out_r[:total], l_match[:nl]


def native_i64_map_build(keys: np.ndarray) -> Optional[tuple]:
    """Open-addressing hash map over unique int64 keys -> their positions, in
    an interleaved (key, val) pair layout so a probe touches ONE cache line.
    Returns (slots, cap) or None. Read-only after build, so lookups are safe
    from concurrent pool threads."""
    lib = get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    n = len(keys)
    cap = 1
    while cap < max(2 * n, 16):
        cap <<= 1
    slots = np.empty(2 * cap, dtype=np.int64)
    slots[1::2] = -1
    lib.i64_pairmap_build(_p(keys, ctypes.c_int64), n, cap, _p(slots, ctypes.c_int64))
    return slots, cap


def native_i64_map_lookup(slots: np.ndarray, cap: int,
                          vals: np.ndarray) -> Optional[np.ndarray]:
    """Positions of vals in the map's key set (-1 for absent), or None."""
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    out = np.empty(max(len(vals), 1), dtype=np.int64)
    lib.i64_pairmap_lookup(_p(slots, ctypes.c_int64), int(cap),
                           _p(vals, ctypes.c_int64), len(vals),
                           _p(out, ctypes.c_int64))
    return out[:len(vals)]


def native_bucket_build(codes: np.ndarray, num_codes: int) -> Optional[tuple]:
    """(counts, offsets, max_count) per joint code in one C pass — the
    ProbeTable build side of native_probe. codes < 0 are skipped.
    max_count == 1 signals unique build keys (direct-lookup joins legal).
    None if lib unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    codes = np.ascontiguousarray(codes, dtype=np.int64)
    g = max(int(num_codes), 1)
    counts = np.empty(g, dtype=np.int64)
    offsets = np.empty(g, dtype=np.int64)
    mx = lib.bucket_build(_p(codes, ctypes.c_int64), len(codes), g,
                          _p(counts, ctypes.c_int64), _p(offsets, ctypes.c_int64))
    return counts[:num_codes] if num_codes else counts[:0], \
        offsets[:num_codes] if num_codes else offsets[:0], int(mx)


def native_bucket_scatter(codes: np.ndarray, num_codes: int,
                          offsets: np.ndarray, total: int) -> Optional[np.ndarray]:
    """Stable counting-sort scatter of row ids into buckets (row order preserved
    within a bucket), or None. O(n + num_codes), replaces np.argsort."""
    lib = get_lib()
    if lib is None:
        return None
    codes = np.ascontiguousarray(codes, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    rows = np.empty(max(int(total), 1), dtype=np.int64)
    lib.bucket_scatter(_p(codes, ctypes.c_int64), len(codes), max(int(num_codes), 1),
                       _p(offsets, ctypes.c_int64), _p(rows, ctypes.c_int64))
    return rows[:total]


def native_probe_lookup_count(vals: np.ndarray, valid: Optional[np.ndarray],
                              lookup, bucket_counts: np.ndarray,
                              num_codes: int) -> Optional[tuple]:
    """Fused single-i64-key probe: value -> build joint code -> match count in
    one C pass. lookup is ProbeTable's ("dense", lo, hi) or ("hashmap", hm)
    descriptor. Returns (codes, l_match_counts, total) or None."""
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    vp = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vp = _p(valid, ctypes.c_uint8)
    codes = np.empty(max(n, 1), dtype=np.int64)
    l_match = np.empty(max(n, 1), dtype=np.int64)
    if lookup[0] == "dense":
        total = lib.probe_lookup_count_dense(
            _p(vals, ctypes.c_int64), vp, n, int(lookup[1]), int(lookup[2]),
            _p(bucket_counts, ctypes.c_int64), int(num_codes),
            _p(codes, ctypes.c_int64), _p(l_match, ctypes.c_int64))
    else:
        slots, cap = lookup[1]
        total = lib.probe_lookup_count_pair(
            _p(vals, ctypes.c_int64), vp, n, _p(slots, ctypes.c_int64), int(cap),
            _p(bucket_counts, ctypes.c_int64), int(num_codes),
            _p(codes, ctypes.c_int64), _p(l_match, ctypes.c_int64))
    return codes[:n], l_match[:n], int(total)


def native_probe_fill(codes: np.ndarray, num_codes: int, bucket_offsets: np.ndarray,
                      bucket_counts: np.ndarray, bucket_rows: np.ndarray,
                      total: int) -> Optional[tuple]:
    """probe_fill only (match total already known from the fused count pass)."""
    lib = get_lib()
    if lib is None:
        return None
    codes = np.ascontiguousarray(codes, dtype=np.int64)
    out_l = np.empty(max(total, 1), dtype=np.int64)
    out_r = np.empty(max(total, 1), dtype=np.int64)
    lib.probe_fill(_p(codes, ctypes.c_int64), len(codes), int(num_codes),
                   _p(bucket_offsets, ctypes.c_int64), _p(bucket_counts, ctypes.c_int64),
                   _p(bucket_rows, ctypes.c_int64), _p(out_l, ctypes.c_int64),
                   _p(out_r, ctypes.c_int64))
    return out_l[:total], out_r[:total]


def native_probe_unique(vals: np.ndarray, valid: Optional[np.ndarray],
                        direct) -> Optional[tuple]:
    """Unique-build-key probe: one random access per row. `direct` is
    ("pairmap", slots, cap) over value -> build row, or
    ("dense", lo, hi, row_of_code). Returns (ridx_full, matched_l, matched_r)
    or None if lib unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    vp = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vp = _p(valid, ctypes.c_uint8)
    ridx_full = np.empty(max(n, 1), dtype=np.int64)
    out_l = np.empty(max(n, 1), dtype=np.int64)
    out_r = np.empty(max(n, 1), dtype=np.int64)
    if direct[0] == "pairmap":
        m = lib.probe_unique_pair(_p(vals, ctypes.c_int64), vp, n,
                                  _p(direct[1], ctypes.c_int64), int(direct[2]),
                                  _p(ridx_full, ctypes.c_int64),
                                  _p(out_l, ctypes.c_int64), _p(out_r, ctypes.c_int64))
    else:
        m = lib.probe_unique_dense(_p(vals, ctypes.c_int64), vp, n,
                                   int(direct[1]), int(direct[2]),
                                   _p(direct[3], ctypes.c_int64),
                                   _p(ridx_full, ctypes.c_int64),
                                   _p(out_l, ctypes.c_int64), _p(out_r, ctypes.c_int64))
    return ridx_full[:n], out_l[:m], out_r[:m]


def native_mask_indices(arr) -> Optional[np.ndarray]:
    """Selection vector (int64 row indices) of a pyarrow BooleanArray in one
    word-wise C pass over the bitmaps; nulls drop. None if lib unavailable or
    the array isn't a plain boolean array."""
    import pyarrow as pa

    lib = get_lib()
    if lib is None:
        return None
    if isinstance(arr, pa.ChunkedArray):
        if arr.num_chunks == 1:
            arr = arr.chunk(0)
        else:
            arr = arr.combine_chunks()
    if not isinstance(arr, pa.BooleanArray):
        return None
    bufs = arr.buffers()
    if len(bufs) != 2 or bufs[1] is None:
        return None
    bits = ctypes.cast(bufs[1].address, ctypes.POINTER(ctypes.c_uint8))
    validity = ctypes.cast(bufs[0].address, ctypes.POINTER(ctypes.c_uint8)) \
        if bufs[0] is not None else None
    out = np.empty(max(len(arr), 1), dtype=np.int64)
    m = lib.bool_mask_indices(bits, validity, arr.offset, len(arr),
                              _p(out, ctypes.c_int64))
    return out[:m]
