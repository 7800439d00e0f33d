"""Star joins over a mesh of local devices (ops/device_join.py with
`mesh_devices` > 1: the single chip's join dispatch on every shard of the
fact) under 8 forced host devices.

Covers: 3-way identity (mesh vs single-chip vs host) for grouped and ungrouped
join shapes including int64 exactness and null group keys, dim-filter
visibility, repeat-query h2d-flat planes (including the filtered/unfiltered
slot-thrash regression), tiny-HBM-budget pin safety, the loud
forced-mesh-unavailable fallback, the three-tier cost decision with all three
CostBreakdowns in the placement ledger, the intra-host all_to_all repartition
(bit-identical partitions, zero shuffle wire bytes), the calibrate tool's
mesh-term suggestions, and the persistent-compile-cache knob. What the
sharded dispatch declines is in tests/test_device_join_mesh.py. Run
standalone via `make test-mesh`.
"""

import os

import numpy as np
import pytest

import jax

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability.metrics import registry
from daft_tpu.ops import counters


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices — see conftest")


N_FACT = 24_000
N_DIM = 60


@pytest.fixture(scope="module")
def star():
    """A star pair: fact with int64-overflow-scale values + a dim with a
    null-bearing group key and a filterable numeric column."""
    rng = np.random.default_rng(11)
    fact = daft_tpu.from_pydict({
        "fk": rng.integers(0, N_DIM + 5, N_FACT).tolist(),  # some join misses
        "qty": rng.integers(0, 50, N_FACT).tolist(),
        "price": [None if i % 19 == 0 else float(i % 977)
                  for i in range(N_FACT)],
        "big": (2**53 + rng.integers(0, 1000, N_FACT)).tolist(),
    })
    dim = daft_tpu.from_pydict({
        "dk": list(range(N_DIM)),
        "grp": [None if i % 13 == 0 else f"g{i % 7}" for i in range(N_DIM)],
        "weight": [float(i % 11) for i in range(N_DIM)],
        "flag": [i % 4 for i in range(N_DIM)],
    })
    return fact, dim


def _grouped_q(fact, dim):
    return (fact.join(dim, left_on="fk", right_on="dk")
            .where(col("flag") < 3)
            .groupby("grp")
            .agg(col("qty").sum().alias("s"),
                 col("big").sum().alias("bs"),
                 col("weight").mean().alias("mw"),
                 col("price").count().alias("c"),
                 col("qty").min().alias("lo"),
                 col("qty").max().alias("hi"))
            .sort("grp"))


def test_grouped_mesh_join_three_way_parity(star):
    """Grouped star join: mesh vs single-chip vs host identical, null group
    keys preserved, int64 sums exact, and the mesh counters prove the tier
    actually ran."""
    fact, dim = star
    with execution_config_ctx(device_mode="off"):
        host = _grouped_q(fact, dim).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        mesh = _grouped_q(fact, dim).to_pydict()
    assert counters.mesh_join_runs > 0, "mesh join tier never ran"
    assert counters.mesh_dispatches > 0
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=1,
                              device_min_rows=1):
        single = _grouped_q(fact, dim).to_pydict()
    assert counters.mesh_join_runs == 0, "mesh_devices=1 must stay single-chip"
    assert counters.device_join_batches > 0
    for out in (mesh, single):
        assert out["grp"] == host["grp"]      # incl. the None group
        assert out["c"] == host["c"]
        assert out["lo"] == host["lo"] and out["hi"] == host["hi"]
        np.testing.assert_allclose(np.array(out["mw"], dtype=float),
                                   np.array(host["mw"], dtype=float),
                                   rtol=1e-12)
    assert None in host["grp"], "fixture lost its null group key"
    # int64 exactness: native-dtype mesh reduce must match host bit-for-bit
    assert mesh["bs"] == host["bs"], "mesh int64 join sum not exact"
    assert mesh["s"] == host["s"]


def test_ungrouped_mesh_join_parity(star):
    fact, dim = star

    def q():
        return (fact.join(dim, left_on="fk", right_on="dk")
                .where(col("flag") < 2)
                .agg(col("qty").sum().alias("s"),
                     col("big").sum().alias("bs"),
                     col("price").count().alias("c"),
                     col("weight").mean().alias("m"),
                     col("qty").min().alias("lo"),
                     col("qty").max().alias("hi")))

    with execution_config_ctx(device_mode="off"):
        host = q().to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        mesh = q().to_pydict()
    assert counters.mesh_join_runs > 0
    assert mesh["s"] == host["s"] and mesh["bs"] == host["bs"]
    assert mesh["c"] == host["c"]
    assert mesh["lo"] == host["lo"] and mesh["hi"] == host["hi"]
    np.testing.assert_allclose(mesh["m"], host["m"], rtol=1e-12)


def test_repeat_join_queries_h2d_flat(star):
    """Interleaved repeats of a filtered grouped join and an unfiltered TopN
    join hit resident sharded/replicated planes with ZERO new h2d bytes —
    the filtered and unfiltered index planes must hold separate slots (a
    shared slot thrashes on alternation: the regression this pins)."""
    fact, dim = star

    def q_topn():
        return (fact.join(dim, left_on="fk", right_on="dk")
                .groupby("grp").agg(col("qty").sum().alias("s"))
                .sort("s", desc=True).limit(3))

    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        g1 = _grouped_q(fact, dim).to_pydict()
        t1 = q_topn().to_pydict()
        h1 = registry().get("hbm_h2d_bytes")
        g2 = _grouped_q(fact, dim).to_pydict()
        t2 = q_topn().to_pydict()
        h2 = registry().get("hbm_h2d_bytes")
    assert (g2, t2) == (g1, t1)
    assert h2 == h1, f"repeat mesh join re-uploaded {h2 - h1} bytes"


def test_mesh_join_pins_under_tiny_hbm_budget(star):
    """Planes built inside a mesh join pin via the executor's pin_scope: a
    budget far below the working set must not thrash them mid-run."""
    fact, dim = star
    with execution_config_ctx(device_mode="off"):
        host = _grouped_q(fact, dim).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1, hbm_budget_bytes=2048):
        mesh = _grouped_q(fact, dim).to_pydict()
    assert counters.mesh_join_runs > 0
    assert counters.hbm_pins > 0, "mesh join planes never pinned"
    assert mesh["grp"] == host["grp"] and mesh["s"] == host["s"]


def test_forced_mesh_unavailable_falls_back_loudly(star):
    """mesh_devices beyond the local device count: the join runs single-chip
    with the fallback counter bumped — never silently, never wrong."""
    fact, dim = star
    with execution_config_ctx(device_mode="off"):
        host = _grouped_q(fact, dim).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=64,
                              device_min_rows=1):
        out = _grouped_q(fact, dim).to_pydict()
    assert counters.mesh_unavailable_fallbacks > 0
    assert counters.mesh_join_runs == 0
    assert counters.device_join_batches > 0, "fallback must still run device"
    assert out["grp"] == host["grp"] and out["s"] == host["s"]


# ---- three-tier cost decision --------------------------------------------------------

_MESH_WINS_PINS = {
    "DAFT_TPU_COST_RTT": "0.0001", "DAFT_TPU_COST_H2D": "1e11",
    "DAFT_TPU_COST_D2H": "1e9", "DAFT_TPU_COST_MM_RATE": "1e8",
    "DAFT_TPU_COST_MM_CELL_RATE": "1e7", "DAFT_TPU_COST_HOST_AGG": "1e6",
    "DAFT_TPU_COST_HOST_FACT": "1e9", "DAFT_TPU_COST_HOST_PROBE": "1e6",
    "DAFT_TPU_COST_ICI": "1e12", "DAFT_TPU_COST_MESH_DISPATCH": "1e-5",
}


def test_auto_join_decision_prices_all_three_tiers(star, monkeypatch):
    """device_mode=auto on a (simulated) accelerator: the join decision's
    ledger record carries device AND host AND mesh CostBreakdowns, and under
    mesh-favoring calibration the mesh tier actually executes the join."""
    from daft_tpu.execution import executor
    from daft_tpu.observability import placement
    from daft_tpu.ops import costmodel

    fact, dim = star
    for k, v in _MESH_WINS_PINS.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    costmodel.reset_calibration()
    executor._DECISION_CACHE.clear()
    try:
        counters.reset()
        with execution_config_ctx(device_mode="auto", mesh_devices=0,
                                  device_min_rows=1):
            with placement.query_scope() as scope:
                mesh = _grouped_q(fact, dim).to_pydict()
        recs = [r for r in scope.to_dicts() if r.get("site") == "join agg"]
        assert recs, "no join placement record"
        rec = recs[0]
        assert rec["chosen"] == "mesh"
        for tier in ("device", "host", "mesh"):
            assert rec.get(tier, {}).get("total", 0) > 0, \
                f"{tier} CostBreakdown absent from the join decision"
        # the sharded dispatch's arm: the premium of spanning the devices and
        # the fetch of a partial table a shard (a grouped join runs no
        # collective, so it prices no ICI term; a fused TopN's combine does)
        assert "mesh_dispatch" in rec["mesh"] and "combine" in rec["mesh"]
        assert counters.mesh_join_runs > 0, "costed mesh verdict did not run"
        with execution_config_ctx(device_mode="off"):
            host = _grouped_q(fact, dim).to_pydict()
        assert mesh["grp"] == host["grp"] and mesh["s"] == host["s"]
    finally:
        costmodel.reset_calibration()
        executor._DECISION_CACHE.clear()


def test_auto_join_host_reject_still_prices_mesh_arm(star, monkeypatch):
    """When every device tier loses, the host verdict's record still shows
    what the mesh WOULD have cost — the what-if explain_placement needs."""
    from daft_tpu.execution import executor
    from daft_tpu.observability import placement
    from daft_tpu.ops import costmodel

    fact, dim = star
    hostile = dict(_MESH_WINS_PINS,
                   **{"DAFT_TPU_COST_RTT": "5.0",
                      "DAFT_TPU_COST_MESH_DISPATCH": "5.0",
                      "DAFT_TPU_COST_ICI": "1e3",
                      "DAFT_TPU_COST_HOST_AGG": "1e12",
                      "DAFT_TPU_COST_HOST_FACT": "1e12",
                      "DAFT_TPU_COST_HOST_PROBE": "1e12"})
    for k, v in hostile.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    costmodel.reset_calibration()
    executor._DECISION_CACHE.clear()
    try:
        counters.reset()
        with execution_config_ctx(device_mode="auto", mesh_devices=0,
                                  device_min_rows=1):
            with placement.query_scope() as scope:
                _grouped_q(fact, dim).to_pydict()
        recs = [r for r in scope.to_dicts() if r.get("site") == "join agg"]
        assert recs and recs[0]["chosen"] == "host"
        assert recs[0].get("mesh", {}).get("total", 0) > 0, \
            "host reject lost the mesh what-if breakdown"
        assert counters.mesh_join_runs == 0
    finally:
        costmodel.reset_calibration()
        executor._DECISION_CACHE.clear()


# ---- intra-host all_to_all repartition -----------------------------------------------

def test_alltoall_repartition_bit_identical_zero_wire_bytes(monkeypatch):
    """Hash repartition over ICI: partition contents AND row order match the
    host path exactly (nulls included), with zero shuffle wire bytes while
    the exchange moved real plane bytes — the co-located-worker wire drop.
    No plane crosses as f64: a TPU holds f64 as a pair of f32 and hands back
    other bits (seen on four chips, PR 22), so f64 columns ride uint64 views."""
    from daft_tpu.core.recordbatch import RecordBatch
    from daft_tpu.parallel import distributed as dist

    crossed = []
    real_step = dist.sharded_alltoall_repartition_step

    def recording_step(mesh, dtypes, *a, **k):
        crossed.extend(np.dtype(d) for d in dtypes)
        return real_step(mesh, dtypes, *a, **k)

    monkeypatch.setattr(dist, "sharded_alltoall_repartition_step",
                        recording_step)

    n = 80_000
    rng = np.random.default_rng(5)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 997, n).tolist(),
        "v": (rng.random(n) * 100).tolist(),
        "w": [None if i % 17 == 0 else int(i % 31) for i in range(n)],
    })
    with execution_config_ctx(device_mode="off"):
        host = df.repartition(8, col("k")).collect()
    counters.reset()
    wire0 = registry().get("shuffle_wire_bytes")
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        mesh = df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches > 0, "all_to_all never engaged"
    assert counters.mesh_alltoall_ici_bytes > 0
    assert registry().get("shuffle_wire_bytes") == wire0, \
        "co-located repartition wrote shuffle wire bytes"
    assert np.dtype(np.uint64) in crossed and np.dtype(np.float64) not in crossed

    def rows(p):
        bs = [b for b in p.batches if b.num_rows]
        if not bs:
            return {}
        b = bs[0] if len(bs) == 1 else RecordBatch.concat(bs)
        return {c: b.get_column(c).to_pylist() for c in ("k", "v", "w")}

    hp, mp = list(host._result), list(mesh._result)
    assert len(hp) == len(mp) == 8
    for i, (a, b) in enumerate(zip(hp, mp)):
        assert rows(a) == rows(b), f"partition {i} diverged"


def test_alltoall_repartition_stays_off_by_default():
    """Without the explicit mesh opt-in (mesh_devices defaults to auto) the
    repartition path must stay on host bucketing — and string columns must
    reject to host even when the mesh is forced."""
    df = daft_tpu.from_pydict({"k": list(range(1000)),
                               "s": [f"x{i}" for i in range(1000)]})
    counters.reset()
    with execution_config_ctx(device_mode="on", device_min_rows=1):
        df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches == 0
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              device_min_rows=1):
        out = df.repartition(8, col("k")).collect()
    assert counters.mesh_alltoall_dispatches == 0, \
        "string columns must not ride the device exchange"
    assert sum(p.num_rows for p in out._result) == 1000


# ---- satellites ----------------------------------------------------------------------

def test_calibrate_tool_suggests_mesh_terms():
    """Ledger samples from mesh-tier dispatches drive DAFT_TPU_COST_ICI /
    DAFT_TPU_COST_MESH_DISPATCH suggestions when observation and calibration
    disagree by more than the 2x contract."""
    from daft_tpu.tools.calibrate import suggest

    cal = {"rtt_s": 0.001, "h2d_bytes_per_s": 1e9, "d2h_bytes_per_s": 1e9,
           "ici_bytes_per_s": 4.5e10, "mesh_dispatch_s": 2e-3,
           "mm_plane_rows_per_s": 5e9, "mm_cell_rate": 5e10}
    records = [{
        "site": "join agg", "chosen": "mesh", "rows": 1_000_000,
        "mesh": {"total": 0.05, "compute": 0.001, "ici": 0.004,
                 "mesh_dispatch": 0.002},
        "observed": {"total": 0.2, "dispatch": 0.2, "dispatches": 1},
        "error_ratio": 4.0,
    } for _ in range(3)]
    report = suggest(records, cal)
    assert "DAFT_TPU_COST_MESH_DISPATCH" in report["suggestions"], report
    # observed premium floor = 0.2 - rtt(0.001) = 0.199s >> 2ms calibration
    assert float(report["suggestions"]["DAFT_TPU_COST_MESH_DISPATCH"]) \
        == pytest.approx(0.199, rel=1e-3)
    assert "ici" in report["terms"]
    assert "DAFT_TPU_COST_ICI" in report["suggestions"]


def test_compile_cache_knob_resolution(monkeypatch):
    """The compile cache is placed from outside: JAX_COMPILATION_CACHE_DIR
    wins and the program sets none in code; without it the cache is the one
    fixed .jax_cache/ at the checkout root. The DAFT_TPU_COMPILE_CACHE*
    knobs are gone."""
    import importlib
    import os

    import jax

    from daft_tpu.utils import jax_setup

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("DAFT_TPU_COMPILE_CACHE_DIR", "/tmp/x1")
        monkeypatch.setenv("DAFT_TPU_COMPILE_CACHE", "/tmp/legacy")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        importlib.reload(jax_setup)
        assert jax_setup.compile_cache_dir() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == jax_setup.compile_cache_dir()

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", None)
        importlib.reload(jax_setup)
        assert jax_setup.compile_cache_dir() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir is None, \
            "with the variable set, the program sets no directory in code"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_mesh_probe_static_on_cpu_backend():
    """The live ICI probe must not run on a forced-multi-device CPU host —
    its 'interconnect' is memcpy and would flip auto verdicts dishonestly;
    the static v5e terms hold instead."""
    from daft_tpu.ops.costmodel import (_STATIC_ICI_BPS,
                                        _STATIC_MESH_DISPATCH_S,
                                        _probe_mesh_terms)

    ici, meshd = _probe_mesh_terms(0.001)
    assert ici == _STATIC_ICI_BPS and meshd == _STATIC_MESH_DISPATCH_S


@pytest.mark.parametrize("grouped", [False, True], ids=["ungrouped", "grouped"])
@pytest.mark.parametrize("mesh_devices", [8, 1], ids=["mesh", "single-chip"])
def test_join_aggregate_input_literals_are_this_querys(star, grouped, mesh_devices):
    """The join tiers take a query's aggregates from the query: the
    aggregate stage that says whether the shape qualifies is kept under the
    skeleton (ops/stage.bind_filter_agg_stage), so its expressions are those
    of the FIRST query of the shape, and a tier that compiled values in (the
    fused mesh join steps did, until PR 44) must not read them there. Two
    queries of one skeleton,
    another literal inside the aggregates' inputs and in the predicate, each
    against the host's answer."""
    fact, dim = star

    def q(scale, bias, cut):
        j = (fact.join(dim, left_on="fk", right_on="dk")
             .where(col("weight") < cut))
        aggs = [(col("qty") * scale).sum().alias("s"),
                (col("weight") + bias).mean().alias("m"),
                (col("qty") + int(bias)).max().alias("hi")]
        if grouped:
            return j.groupby("grp").agg(*aggs).sort("grp")
        return j.agg(*aggs)

    values = [(2.0, 1.0, 9.0), (3.0, 5.0, 6.0), (2.0, 1.0, 9.0)]
    with execution_config_ctx(device_mode="off"):
        host = [q(*v).to_pydict() for v in values]
    assert host[0]["s"] != host[1]["s"] and host[0]["m"] != host[1]["m"]
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=mesh_devices,
                              device_min_rows=1):
        device = [q(*v).to_pydict() for v in values]
    if mesh_devices > 1:
        assert counters.mesh_join_runs == len(values), "mesh join tier never ran"
    else:
        assert counters.device_join_batches > 0 and counters.mesh_join_runs == 0
    for got, want in zip(device, host):
        assert got.get("grp") == want.get("grp")
        assert got["hi"] == want["hi"]
        np.testing.assert_allclose(np.array(got["s"], dtype=float),
                                   np.array(want["s"], dtype=float), rtol=1e-6)
        np.testing.assert_allclose(np.array(got["m"], dtype=float),
                                   np.array(want["m"], dtype=float), rtol=1e-6)
