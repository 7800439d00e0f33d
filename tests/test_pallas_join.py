"""Pallas join-probe kernel + in-kernel ICI ring permute (the r19 tentpole)
— interpret-mode parity for every new kernel family under the 8 forced host
devices from conftest.

Covers: hash_probe_index bit-identity vs a host dict probe (int64 past 2^53,
negative keys, null keys, misses), duplicate-key/sentinel probe-table
refusals, the fused probe+segment-sum kernel vs numpy, segment_extreme_int64
exactness past 2^53 (both ops, empty segments), the ring-permute repartition
step bit-identical to the classic all_to_all step, end-to-end device joins
through the probe kernel (single chip + mesh) with lowering-failure fallback
latch / exact host replay, the widened groupby eligibility (int64 extremes on
the kernel tier), the fused repartition's zero-standalone-all_to_all counter
assert, the Pallas what-if side on every join placement record (including
Pallas-ineligible stages), the device_join_pallas_cost arm, calibrate's
kernel-rate suggestions, and the DAFT_TPU_PALLAS=off no-import guard. Run
standalone via `make test-pallas`.
"""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.ops import counters
from daft_tpu.ops import pallas_kernels as pk


needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices — see conftest")

BIG = (1 << 53) + 11   # past f64's exact-integer range


# ---- kernel-level parity -----------------------------------------------------


def _host_probe(fact_keys, fact_valid, dim_keys, dim_valid):
    lut = {int(k): i for i, (k, v) in enumerate(zip(dim_keys, dim_valid)) if v}
    return np.array([lut.get(int(k), -1) if v else -1
                     for k, v in zip(fact_keys, fact_valid)], dtype=np.int32)


def test_hash_probe_index_matches_host_probe():
    rng = np.random.default_rng(0)
    n_dim = 300
    dim_keys = np.concatenate([
        rng.choice(10_000, n_dim - 100, replace=False).astype(np.int64),
        BIG + np.arange(50, dtype=np.int64),
        -(1 << 62) - np.arange(50, dtype=np.int64),
    ])
    dim_valid = np.ones(n_dim, dtype=bool)
    dim_valid[::41] = False            # null dim keys never match
    n = 4096
    fact_keys = dim_keys[rng.integers(0, n_dim, n)].copy()
    fact_keys[::7] += 1_000_000        # misses
    fact_valid = rng.random(n) > 0.1   # null fact keys
    tbl = pk.build_probe_table(dim_keys, dim_valid)
    fh, fl = pk.probe_key_digits(jnp.asarray(fact_keys),
                                 jnp.asarray(fact_valid))
    idx = np.asarray(pk.hash_probe_index(
        fh, fl, jnp.asarray(tbl[0]), jnp.asarray(tbl[1]), jnp.asarray(tbl[2]),
        interpret=True))
    expect = _host_probe(fact_keys, fact_valid, dim_keys, dim_valid)
    np.testing.assert_array_equal(idx, expect)


def test_probe_table_refuses_duplicates_and_sentinel():
    with pytest.raises(ValueError, match="not unique"):
        pk.build_probe_table(np.array([3, 7, 3], dtype=np.int64))
    with pytest.raises(ValueError, match="sentinel"):
        pk.build_probe_table(np.array([1, pk.PROBE_SENTINEL], dtype=np.int64))
    # a duplicate hidden behind a null mask is fine — nulls never match
    tbl = pk.build_probe_table(np.array([3, 7, 3], dtype=np.int64),
                               np.array([True, True, False]))
    assert tbl[0].shape == (1, 128)


def test_hash_probe_segment_sum_matches_numpy():
    rng = np.random.default_rng(1)
    n_dim, n, cap, p = 200, 4096, 64, 3
    dim_keys = np.concatenate([
        rng.choice(5_000, n_dim - 40, replace=False).astype(np.int64),
        BIG + np.arange(40, dtype=np.int64)])
    planes = rng.integers(0, 100, (n_dim, p)).astype(np.float32)
    fact_keys = dim_keys[rng.integers(0, n_dim, n)].copy()
    fact_keys[::5] = -9               # misses
    fact_valid = rng.random(n) > 0.15
    codes = rng.integers(0, cap, n).astype(np.int32)
    tbl = pk.build_probe_table(dim_keys)
    # pad the value planes to the table slot count (row i -> slot i)
    t = tbl[0].shape[1]
    tp = np.zeros((t, p), dtype=np.float32)
    tp[:n_dim] = planes
    fh, fl = pk.probe_key_digits(jnp.asarray(fact_keys),
                                 jnp.asarray(fact_valid))
    sums, counts = pk.hash_probe_segment_sum(
        fh, fl, jnp.asarray(codes), jnp.asarray(tbl[0]), jnp.asarray(tbl[1]),
        jnp.asarray(tbl[2]), jnp.asarray(tp), cap, interpret=True)
    exp_sums = np.zeros((cap, p), dtype=np.float64)
    exp_counts = np.zeros(cap, dtype=np.int64)
    lut = {int(k): i for i, k in enumerate(dim_keys)}
    for i in range(n):
        if not fact_valid[i]:
            continue
        row = lut.get(int(fact_keys[i]), -1)
        if row < 0:
            continue
        exp_sums[codes[i]] += planes[row]
        exp_counts[codes[i]] += 1
    np.testing.assert_array_equal(np.asarray(sums), exp_sums)
    np.testing.assert_array_equal(np.asarray(counts).astype(np.int64),
                                  exp_counts)


@pytest.mark.parametrize("op", ["min", "max"])
def test_segment_extreme_int64_exact_past_2_53(op):
    rng = np.random.default_rng(2)
    n, cap = 4096, 16
    vals = (1 << 62) + rng.integers(-1000, 1000, n) * (1 << 11)
    vals[::3] = -(1 << 61) - rng.integers(0, 1 << 20, n)[::3]
    mask = rng.random(n) > 0.2
    codes = rng.integers(0, cap - 2, n)   # segments cap-2, cap-1 stay empty
    out, nonempty = pk.segment_extreme_int64(
        jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(codes), cap, op,
        interpret=True)
    info = np.iinfo(np.int64)
    ident = info.max if op == "min" else info.min
    expect = np.full(cap, ident, dtype=np.int64)
    seen = np.zeros(cap, dtype=bool)
    red = np.minimum if op == "min" else np.maximum
    for v, m, c in zip(vals, mask, codes):
        if m:
            expect[c] = red(expect[c], v)
            seen[c] = True
    np.testing.assert_array_equal(np.asarray(out), expect)
    np.testing.assert_array_equal(np.asarray(nonempty), seen)


# ---- ring-permute repartition step -------------------------------------------


@needs_mesh
def test_ring_repartition_step_bit_identical_to_alltoall():
    from daft_tpu.parallel.distributed import (
        default_mesh, sharded_alltoall_repartition_step,
        sharded_ring_repartition_step)

    rng = np.random.default_rng(3)
    n_dev, S = 8, 512
    total = n_dev * S
    mesh = default_mesh(n_dev)
    dest = rng.integers(0, n_dev, total).astype(np.int64)
    row_mask = rng.random(total) > 0.1
    planes = (rng.standard_normal(total),                       # f64
              rng.random(total) > 0.5,                          # bool validity
              (1 << 62) + rng.integers(0, 1 << 20, total))      # int64
    dtypes = tuple(np.asarray(p).dtype for p in planes)
    classic = sharded_alltoall_repartition_step(mesh, dtypes)
    ring = sharded_ring_repartition_step(mesh, dtypes, interpret=True)
    c_counts, c_planes = classic(dest, row_mask, *planes)
    r_counts, r_planes = ring(dest, row_mask, *planes)
    np.testing.assert_array_equal(np.asarray(c_counts), np.asarray(r_counts))
    for cp, rp in zip(c_planes, r_planes):
        np.testing.assert_array_equal(np.asarray(cp), np.asarray(rp))


@needs_mesh
def test_fused_repartition_zero_alltoall_dispatches():
    """The acceptance assert: under pallas_mode=on the repartition + permute
    compile into one program — ZERO standalone all_to_all dispatches while
    the fused-permute counter attributes the exchange, partitions
    bit-identical to the host shuffle."""
    rng = np.random.default_rng(4)
    n = 16_000
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 997, n).tolist(),
        "v": (rng.random(n) * 100).tolist(),
        "w": [None if i % 17 == 0 else int(i % 31) for i in range(n)],
        "big": (2**53 + rng.integers(0, 1000, n)).tolist(),
    })
    with execution_config_ctx(device_mode="off"):
        host = df.repartition(8, col("k")).collect()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1, pallas_mode="on"):
        fused = df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches == 0
    assert counters.mesh_fused_permute_dispatches > 0

    from daft_tpu.core.recordbatch import RecordBatch

    def rows(p):
        bs = [b for b in p.batches if b.num_rows]
        if not bs:
            return {}
        b = bs[0] if len(bs) == 1 else RecordBatch.concat(bs)
        return {c: b.get_column(c).to_pylist() for c in ("k", "v", "w", "big")}

    for i, (a, b) in enumerate(zip(host._result, fused._result)):
        assert rows(a) == rows(b), f"partition {i} diverged"


@needs_mesh
def test_ring_permute_failure_latches_to_alltoall(monkeypatch):
    """A runtime lowering failure in the fused exchange reaches the caller:
    no latch, no all_to_all replay, no host-bucket replay."""
    from daft_tpu.parallel import distributed as dist

    def broken(*a, **k):
        raise RuntimeError("mosaic lowering failed (injected)")

    rng = np.random.default_rng(5)
    n = 8_000
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 97, n).tolist(),
        "v": (rng.random(n) * 10).tolist(),
    })
    monkeypatch.setattr(dist, "sharded_ring_repartition_step", broken)
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1, pallas_mode="on"):
        with pytest.raises(RuntimeError, match="mosaic lowering failed"):
            df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches == 0
    assert counters.mesh_fused_permute_dispatches == 0
    # auto never engages the kernel: the all_to_all tier serves the exchange
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches > 0


# ---- end-to-end device joins through the probe kernel ------------------------


def _star_tables():
    rng = np.random.default_rng(9)
    n = 6_000
    fact = daft_tpu.from_pydict({
        "f_k1": [int(x) if x % 37 else None for x in rng.integers(0, 200, n)],
        "f_k64": [int(BIG + (x % 150)) if x % 31 else None
                  for x in rng.integers(0, 10_000, n)],
        "f_v": rng.uniform(0, 100, n).tolist(),
        "f_q": rng.integers(1, 50, n).tolist(),
    }).collect()
    d1 = daft_tpu.from_pydict({
        "d1_k": list(range(200)),
        "d1_grp": [f"g{i % 7}" for i in range(200)],
        "d1_w": [float(i % 13) for i in range(200)],
        "d1_k2": [i % 40 for i in range(200)],
    }).collect()
    d2 = daft_tpu.from_pydict({
        "d2_k": list(range(40)),
        "d2_name": [f"n{i % 5}" for i in range(40)],
    }).collect()
    d64 = daft_tpu.from_pydict({
        "d64_k": [int(BIG + i) for i in range(150)],
        "d64_w": [float(i % 17) for i in range(150)],
    }).collect()
    return fact, d1, d2, d64


def _star_query(fact, d1, d2, d64):
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .join(d2, left_on="d1_k2", right_on="d2_k")
                .join(d64, left_on="f_k64", right_on="d64_k")
                .groupby("d1_grp", "d2_name")
                .agg(col("f_v").sum().alias("sv"),
                     col("d64_w").sum().alias("s64"),
                     col("f_q").count().alias("cq"))
                .sort("d1_grp", "d2_name").collect())


def _assert_close(host, dev):
    assert list(host.keys()) == list(dev.keys())
    for c in host:
        for a, b in zip(host[c], dev[c]):
            if isinstance(a, float):
                assert abs(a - b) <= 1e-6 * max(1.0, abs(a)), (c, a, b)
            else:
                assert a == b, (c, a, b)


def test_device_join_probe_end_to_end_parity():
    """Single-chip star join through hash_probe_index: fact-adjacent dims
    (int64 past 2^53 with nulls included) probe in-kernel, the chained dim
    keeps the host index path — results match the host, off-mode is
    bit-identical with zero probe dispatches."""
    fact, d1, d2, d64 = _star_tables()
    with execution_config_ctx(device_mode="off"):
        host = _star_query(fact, d1, d2, d64).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        dev = _star_query(fact, d1, d2, d64).to_pydict()
    snap = counters.snapshot()
    # two fact-adjacent dims (d1, d64) probe in-kernel; d2 chains off d1
    assert snap.get("pallas_probe_dispatches", 0) >= 2
    _assert_close(host, dev)
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="off"):
        dev2 = _star_query(fact, d1, d2, d64).to_pydict()
    assert counters.snapshot().get("pallas_probe_dispatches", 0) == 0
    assert dev2 == dev


def test_device_join_probe_failure_replays_on_host_tier(monkeypatch):
    """A probe kernel that fails at runtime reaches the caller: no latch,
    no host index-plane replay, no fallback counter."""
    def broken(*a, **k):
        raise RuntimeError("mosaic lowering failed (injected)")

    # patch the LIVE module: earlier no-import-guard tests pop the kernel
    # module from sys.modules, so device_join's function-local import may
    # bind a fresher object than this file's module-level `pk`
    import importlib

    pk_live = importlib.import_module("daft_tpu.ops.pallas_kernels")
    monkeypatch.setattr(pk_live, "hash_probe_index", broken)
    fact, d1, d2, d64 = _star_tables()
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        with pytest.raises(RuntimeError, match="mosaic lowering failed"):
            _star_query(fact, d1, d2, d64).to_pydict()
    assert counters.pallas_probe_dispatches == 0


# ---- widened groupby eligibility ---------------------------------------------


def test_widened_groupby_int64_extremes_parity():
    """int64 min/max (sct slots) and integer ext planes no longer disqualify
    a grouped stage from the kernel tier: exact at 1<<62 with nulls and
    negative extremes, off-mode bit-identical."""
    rng = np.random.default_rng(5)
    n = 6_000
    big = 1 << 62
    df = daft_tpu.from_pydict({
        "g": [f"k{i % 37}" for i in range(n)],
        "i64": [None if i % 23 == 0
                else int(big + rng.integers(-1000, 1000) * (1 << 11))
                for i in range(n)],
        "neg": [int(-(1 << 61) - x) for x in rng.integers(0, 1 << 20, n)],
        "i32": rng.integers(-(2**31) + 1, 2**31 - 1, n).tolist(),
        "q": rng.integers(0, 50, n).tolist(),
    }).collect()

    def q():
        return (df.groupby("g")
                .agg(col("i64").min().alias("mn64"),
                     col("i64").max().alias("mx64"),
                     col("neg").min().alias("mnneg"),
                     col("i32").min().alias("mn32"),
                     col("i32").max().alias("mx32"),
                     col("q").sum().alias("sq"))
                .sort("g").collect())

    with execution_config_ctx(device_mode="off"):
        host = q().to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        dev = q().to_pydict()
    assert counters.pallas_dispatches > 0
    assert host == dev
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="off"):
        dev2 = q().to_pydict()
    assert counters.pallas_dispatches == 0
    assert dev2 == host


# ---- placement ledger / cost model / calibrate -------------------------------


def test_join_records_carry_pallas_whatif(monkeypatch):
    """Every join decision records the Pallas arm's what-if breakdown —
    including when the kernel is ineligible (pallas_mode=off here): the
    PR 14 host-reject-keeps-mesh-what-if discipline, one tier further."""
    from daft_tpu.observability import placement as _placement

    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    fact, d1, d2, d64 = _star_tables()
    with _placement.query_scope() as scope:
        with execution_config_ctx(device_mode="on", pallas_mode="off"):
            _star_query(fact, d1, d2, d64).to_pydict()
    recs = [r for r in scope.to_dicts()
            if r.get("site") in ("join agg", "join topn")]
    assert recs, "no join placement records"
    carrying = [r for r in recs if r.get("pallas")]
    assert carrying, "join records lost the pallas what-if side"
    for r in carrying:
        assert "probe" in r["pallas"], r["pallas"]
        assert r["pallas"].get("total", 0) > 0
        # the arm is a what-if: never a chosen value of its own
        assert r.get("chosen") != "pallas"


def test_device_join_pallas_cost_terms():
    from daft_tpu.ops import costmodel as cm

    cal = cm.calibrate()
    c = cm.device_join_pallas_cost(cal, 100_000, 1 << 20, 1024, 2, 1, 1,
                                   512, 4096, 10_000)
    for term in ("probe", "compute", "factorize", "d2h"):
        assert c.terms.get(term, 0) > 0, (term, c.terms)
    # probe seconds scale with the padded table slots
    c2 = cm.device_join_pallas_cost(cal, 100_000, 1 << 20, 4096, 2, 1, 1,
                                    512, 4096, 10_000)
    assert c2.terms["probe"] > c.terms["probe"]
    assert c2.terms["compute"] == c.terms["compute"]


def test_calibrate_suggests_pallas_rates():
    """Ledger samples whose pallas arm won its gate drive the two kernel-rate
    suggestions; a sample whose arm lost contributes nothing."""
    from daft_tpu.tools.calibrate import suggest

    cal = {"pallas_cell_rate": 1e12, "pallas_probe_cell_rate": 2e12,
           "rtt_s": 0.0005, "h2d_bytes_per_s": 1e9, "d2h_bytes_per_s": 1e9}
    recs = []
    for _ in range(3):
        recs.append({   # grouped shape: compute residual 4x the prediction
            "site": "grouped agg", "chosen": "device", "rows": 100_000,
            "device": {"total": 0.01, "compute": 0.002},
            "pallas": {"total": 0.005, "compute": 0.001},
            "observed": {"dispatch": 0.0045, "dispatches": 1}})
        recs.append({   # join shape: probe residual 0.25x the prediction
            "site": "join agg", "chosen": "device", "rows": 100_000,
            "device": {"total": 0.02, "compute": 0.004},
            "pallas": {"total": 0.006, "probe": 0.002, "compute": 0.001},
            "observed": {"dispatch": 0.002, "dispatches": 1}})
    report = suggest(recs, cal)
    assert report["terms"]["pallas_compute"]["samples"] == 3
    assert report["terms"]["pallas_probe"]["samples"] == 3
    assert float(report["suggestions"]["DAFT_TPU_COST_PALLAS_RATE"]) \
        == pytest.approx(2.5e11)
    assert float(report["suggestions"]["DAFT_TPU_COST_PALLAS_PROBE_RATE"]) \
        == pytest.approx(8e12)
    # an arm that LOST its gate (what-if dwarfs the chosen tier) is not a
    # kernel observation
    lost = suggest([{
        "site": "grouped agg", "chosen": "device", "rows": 1,
        "device": {"total": 0.001, "compute": 0.0005},
        "pallas": {"total": 0.5, "compute": 0.4},
        "observed": {"dispatch": 0.001, "dispatches": 1}}], cal)
    assert "pallas_compute" not in lost["terms"]


def test_pallas_off_join_keeps_kernels_unimported():
    """The zero-overhead contract, extended to the join/repartition wiring:
    DAFT_TPU_PALLAS=off runs never import the kernel module (all new imports
    are gate-guarded and function-local)."""
    sys.modules.pop("daft_tpu.ops.pallas_kernels", None)
    fact, d1, _d2, _d64 = _star_tables()

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .groupby("d1_grp").agg(col("f_q").sum().alias("s"))
                .sort("d1_grp").collect())

    with execution_config_ctx(device_mode="on", pallas_mode="off"):
        q().to_pydict()
    assert "daft_tpu.ops.pallas_kernels" not in sys.modules, \
        "off-mode join imported the kernel module"
