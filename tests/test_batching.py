"""Adaptive batching + device dispatch coalescing (ISSUE 4).

Covers the acceptance criteria end to end on the CPU backend, no chip needed:

- Coalescing microbench: >= 8 morsels into one device agg stage dispatch as
  ONE coalesced super-batch (>= 2x fewer compiled dispatches than morsels
  consumed, mean bucket fill >= 0.5) with results BIT-IDENTICAL to the
  uncoalesced path, including the int64 exactness guarantees from PR 2.
- DynamicBatching converges: a synthetic operator with a throughput knee
  pulls the morsel size to within one pow2 step of the knee.
- Cost model: the measured-constant decision boundary flips with the link
  RTT, and the coalescing horizon flips a previously-rejected morsel shape
  to the device — asserted via the decision functions with pinned
  calibration constants, never wall clock.
- Zero-overhead guard: batching_mode="static" runs the host path with no
  strategy/coalescer allocation and no registry writes.
"""

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import ExecutionConfig, execution_config_ctx
from daft_tpu.core.recordbatch import RecordBatch
from daft_tpu.core.series import Series
from daft_tpu.datatype import DataType
from daft_tpu.execution.batching import (DynamicBatching,
                                         LatencyConstrainedBatching,
                                         StaticBatching,
                                         adaptive_morsel_stream)
from daft_tpu.ops import costmodel, counters
from daft_tpu.ops.grouped_stage import try_build_grouped_agg_stage
from daft_tpu.ops.stage import DispatchCoalescer, pad_bucket
from daft_tpu.schema import Schema


# ---------------------------------------------------------------------------
# Coalescing microbench (acceptance criterion)
# ---------------------------------------------------------------------------

def _morsel_batches(n_batches=8, rows=1024):
    """Morsels whose int64 values stress PR 2's exactness guarantees: sums
    near 2^53 via ~2^40 addends, min/max over magnitudes past 2^53 (the i64
    scatter path — f64 would round them)."""
    rng = np.random.default_rng(7)
    schema = Schema.from_pydict({"k": DataType.int64(), "v": DataType.int64(),
                                 "w": DataType.int64()})
    out = []
    for _ in range(n_batches):
        k = rng.integers(0, 8, rows)
        v = rng.integers(0, 1 << 40, rows)
        w = rng.integers(-(1 << 60), 1 << 60, rows) | 1  # odd: f64-inexact
        cols = [Series.from_numpy(k, "k", DataType.int64()),
                Series.from_numpy(v, "v", DataType.int64()),
                Series.from_numpy(w, "w", DataType.int64())]
        out.append(RecordBatch(schema, cols, rows))
    return schema, out


_AGGS = lambda: [col("v").sum().alias("s"), col("v").mean().alias("m"),  # noqa: E731
                 col("w").min().alias("lo"), col("w").max().alias("hi"),
                 col("v").count().alias("c")]


def test_coalescing_microbench_grouped_bit_identical():
    schema, batches = _morsel_batches(8, 1024)
    stage = try_build_grouped_agg_stage(schema, None, [col("k")], _AGGS())
    assert stage is not None

    counters.reset()
    run = stage.start_run()
    coal = DispatchCoalescer(run.feed_batch, target_rows=65536, latency_s=3600.0)
    for b in batches:
        coal.add(b)
    coal.close()
    keys_c, res_c = run.finalize()

    # >= 2x fewer compiled dispatches than morsels consumed
    assert counters.coalesce_morsels_in == 8
    assert counters.dispatch_coalesced * 2 <= counters.coalesce_morsels_in
    # mean bucket fill ratio >= 0.5 (8192 rows pad to exactly the 8192 bucket)
    fill = counters.bucket_fill_rows / counters.bucket_capacity_rows
    assert fill >= 0.5
    # each flush is exactly one compiled dispatch
    assert counters.device_grouped_batches == counters.dispatch_coalesced

    # uncoalesced reference: one dispatch per morsel
    run2 = stage.start_run()
    for b in batches:
        run2.feed_batch(b)
    keys_u, res_u = run2.finalize()

    assert keys_c == keys_u
    for (vc, okc), (vu, oku) in zip(res_c, res_u):
        assert np.array_equal(np.asarray(okc), np.asarray(oku))
        assert np.array_equal(np.asarray(vc), np.asarray(vu)), \
            "coalesced device results drifted from per-morsel dispatch"


def test_coalescing_end_to_end_device_agg():
    """Executor wiring: a multi-part stream into DeviceGroupedAgg coalesces
    (counters prove it) and matches the host path exactly on int64 sums."""
    rng = np.random.default_rng(3)

    def chunk():
        n = 1024
        return daft_tpu.from_pydict({
            "k": rng.integers(0, 6, n).tolist(),
            "v": rng.integers(0, 1 << 40, n).tolist(),
        })

    df = chunk()
    for _ in range(7):
        df = df.concat(chunk())

    def q(mode):
        with execution_config_ctx(device_mode=mode, batch_latency_ms=60_000.0):
            out = (df.groupby("k")
                   .agg(col("v").sum().alias("s"), col("v").count().alias("c"))
                   .sort("k").to_pydict())
        return out

    counters.reset()
    dev = q("on")
    assert counters.coalesce_morsels_in >= 8
    assert counters.dispatch_coalesced * 2 <= counters.coalesce_morsels_in
    assert counters.device_grouped_batches == counters.dispatch_coalesced
    # the fill gauge reached the registry (flows to QueryEnd.metrics/EXPLAIN)
    assert counters.snapshot().get("bucket_fill_ratio", 0) >= 0.5
    host = q("off")
    assert dev == host, "device+coalesced result differs from host"


def test_coalescer_latency_deadline_flushes_partial():
    """latency_s=0: every add is already past the deadline — morsels dispatch
    1:1 (the no-coalescing degenerate), proving the deadline path flushes
    partial super-batches instead of waiting for fill."""
    schema, batches = _morsel_batches(4, 256)
    stage = try_build_grouped_agg_stage(schema, None, [col("k")], _AGGS())
    counters.reset()
    run = stage.start_run()
    coal = DispatchCoalescer(run.feed_batch, target_rows=1 << 20, latency_s=0.0)
    for b in batches:
        coal.add(b)
    coal.close()
    run.finalize()
    assert counters.dispatch_coalesced == 4
    assert counters.coalesce_morsels_in == 4


def test_coalescer_fill_threshold_batches_pairs():
    schema, batches = _morsel_batches(8, 1024)
    fed = []
    coal = DispatchCoalescer(fed.append, target_rows=2048, latency_s=3600.0)
    for b in batches:
        coal.add(b)
    coal.close()
    assert len(fed) == 4  # pairs of 1024-row morsels
    assert all(b.num_rows == 2048 for b in fed)


def test_coalescer_single_batch_preserves_identity():
    """One pending batch flushes as the ORIGINAL object — batch-identity-keyed
    device caches (resident tables, device_join series_keyed slots) must
    survive coalescing."""
    schema, batches = _morsel_batches(1, 512)
    fed = []
    coal = DispatchCoalescer(fed.append, target_rows=1 << 20, latency_s=3600.0)
    coal.add(batches[0])
    coal.close()
    assert fed[0] is batches[0]
    coal.close()  # idempotent: nothing pending, nothing dispatched
    assert len(fed) == 1


# ---------------------------------------------------------------------------
# Batching strategies
# ---------------------------------------------------------------------------

def test_dynamic_batching_converges_to_knee():
    """Acceptance criterion: a synthetic operator whose throughput peaks at a
    knee pulls the morsel size from 16x above it to within one pow2 step,
    within a bounded number of morsels."""
    knee = 32 * 1024
    strat = DynamicBatching(initial=512 * 1024, min_rows=1024,
                            max_rows=8 * 1024 * 1024)
    counters.reset()

    def seconds(rows, size):
        # peaked throughput: fixed per-morsel overhead below the knee, cache
        # pressure above it — maximal exactly at size == knee
        rate = 2e8 / (knee / size + size / knee)
        return rows / rate

    sizes = []
    for _ in range(60):  # 3-sample aggregation => 20 climb decisions
        s = strat.current_size()
        sizes.append(s)
        strat.record(s, seconds(s, s))
    assert knee // 2 <= strat.current_size() <= knee * 2, sizes
    assert counters.morsel_resize > 0, "convergence never resized"


def test_dynamic_batching_noise_robust():
    """Contention jitter inside the deadband must not random-walk the size:
    flat true throughput with ±4% multiplicative noise (under the 5%
    deadband after 3-sample averaging) holds the ladder step."""
    strat = DynamicBatching(initial=64 * 1024, min_rows=1024,
                            max_rows=16 * 1024 * 1024)
    jitter = [1.0, 0.96, 1.04]
    i = 0
    start_sizes = set()
    for _ in range(30):
        s = strat.current_size()
        start_sizes.add(s)
        strat.record(s, s / (1e8 * jitter[i % 3]))
        i += 1
    # one probe step away from the initial size is allowed; no runaway
    assert strat.current_size() in (64 * 1024, 128 * 1024), start_sizes


def test_dynamic_batching_respects_bounds_and_deadband():
    strat = DynamicBatching(initial=4096, min_rows=4096, max_rows=8192)
    for _ in range(10):
        strat.record(strat.current_size(), 1.0)  # flat throughput
    assert 4096 <= strat.current_size() <= 8192


def test_dynamic_batching_honors_small_configured_initial():
    """A morsel_size_rows below the default floor must not be silently
    quadrupled up: the floor clamps to the configured initial."""
    strat = DynamicBatching(initial=1024)
    assert strat.current_size() == 1024


def test_latency_constrained_caps_slow_operator():
    strat = LatencyConstrainedBatching(0.01, initial=128 * 1024)
    strat.record(128 * 1024, 1.0)  # 131Ki rows/s observed -> ~1.3Ki rows/10ms
    assert strat.current_size() <= 2048
    fast = LatencyConstrainedBatching(0.01, initial=128 * 1024)
    fast.record(128 * 1024, 0.001)  # 1.3e8 rows/s: big morsels stay fine
    assert fast.current_size() >= 128 * 1024


def test_static_batching_is_fixed():
    s = StaticBatching(1000)
    s.record(10, 100.0)
    assert s.current_size() == 1000


def test_adaptive_morsel_stream_follows_strategy():
    from daft_tpu.core.micropartition import MicroPartition

    n = 100_000
    s = Series.from_numpy(np.arange(n), "a", DataType.int64())
    schema = Schema.from_pydict({"a": DataType.int64()})
    part = MicroPartition(schema, [RecordBatch(schema, [s], n)])
    strat = StaticBatching(10_000)
    out = list(adaptive_morsel_stream(iter([part]), strat))
    assert len(out) == 10
    assert sum(p.num_rows for p in out) == n


def test_adaptive_morsel_stream_resizes_mid_partition():
    """A resize recorded while a partition is being split must apply to the
    REMAINDER of that partition — a single-partition table is the common
    case, so per-partition-only consultation would make feedback a no-op."""
    from daft_tpu.core.micropartition import MicroPartition

    n = 64_000
    s = Series.from_numpy(np.arange(n), "a", DataType.int64())
    schema = Schema.from_pydict({"a": DataType.int64()})
    part = MicroPartition(schema, [RecordBatch(schema, [s], n)])

    class Shrinking:
        def __init__(self):
            self.sizes = [16_000, 16_000, 4_000]  # consulted per slice

        def current_size(self):
            return self.sizes.pop(0) if len(self.sizes) > 1 else self.sizes[0]

        def record(self, rows, seconds):
            pass

    got = [p.num_rows for p in adaptive_morsel_stream(iter([part]), Shrinking())]
    assert got[0] == 16_000 and 4_000 in got, got
    assert sum(got) == n


def test_adaptive_morsel_stream_merges_small_batches():
    """A 'grow' decision must be real even when the source emits fixed small
    batches: undersized batches group (zero-copy, multi-batch partitions)
    until they reach the current size."""
    from daft_tpu.core.micropartition import MicroPartition

    schema = Schema.from_pydict({"a": DataType.int64()})

    def part(rows):
        s = Series.from_numpy(np.arange(rows), "a", DataType.int64())
        return MicroPartition(schema, [RecordBatch(schema, [s], rows)])

    parts = [part(1024) for _ in range(8)]
    out = list(adaptive_morsel_stream(iter(parts), StaticBatching(4096)))
    assert [p.num_rows for p in out] == [4096, 4096]
    assert all(len(p.batches) == 4 for p in out)  # grouped, never concatenated
    # a trailing remainder still flushes at stream end
    out2 = list(adaptive_morsel_stream(iter([part(1024) for _ in range(5)]),
                                       StaticBatching(4096)))
    assert [p.num_rows for p in out2] == [4096, 1024]


def test_dynamic_mode_end_to_end_results_match_static():
    """Full pipeline under batching_mode=dynamic (forced pipeline so morsel
    fan-out actually runs): ordered results identical to static mode."""
    n = 50_000
    df = daft_tpu.from_pydict({"a": list(range(n)),
                               "b": [float(i % 97) for i in range(n)]})
    q = lambda d: d.where(col("a") % 3 == 0).select(  # noqa: E731
        col("a"), (col("b") * 2).alias("b2")).to_pydict()
    with execution_config_ctx(batching_mode="static"):
        want = q(df)
    with execution_config_ctx(batching_mode="dynamic", pipeline_mode="force",
                              morsel_size_rows=1024):
        got = q(df)
    assert got == want
    with execution_config_ctx(batching_mode="latency", pipeline_mode="force",
                              morsel_size_rows=1024, batch_latency_ms=5.0):
        got_lat = q(df)
    assert got_lat == want


# ---------------------------------------------------------------------------
# Cost model: decision boundary + coalescing horizon
# ---------------------------------------------------------------------------

def _cal(rtt: float) -> costmodel.Calibration:
    """Pinned calibration: measured v5e compute rates, parameterized link."""
    return costmodel.Calibration(
        rtt_s=rtt, h2d_bytes_per_s=1e9, d2h_bytes_per_s=2e6,
        mm_plane_rows_per_s=5e9, mm_cell_rate=5e10, scatter_rows_per_s=1e8,
        ext_cell_rate=5e9, host_agg_rate=1.5e8, host_factorize_rate=8e6,
        host_probe_rate=3e7)


def test_cost_decision_boundary_flips_with_measured_rtt():
    """Satellite: two calibration points straddling the device/host boundary.
    Same 200k-row filter+agg shape: a 1ms link picks the device, a 90ms link
    picks the host."""
    rows = 200_000
    fast, slow = _cal(0.001), _cal(0.090)
    host_fast = costmodel.host_agg_cost(fast, rows, 1, grouped=False,
                                        has_predicate=True)
    host_slow = costmodel.host_agg_cost(slow, rows, 1, grouped=False,
                                        has_predicate=True)
    assert host_fast == host_slow  # host price doesn't depend on the link
    assert costmodel.device_ungrouped_cost(fast, rows, 0, 1) < host_fast
    assert costmodel.device_ungrouped_cost(slow, rows, 0, 1) > host_slow


def test_coalescing_horizon_flips_rejected_shape_to_device():
    """Acceptance criterion: a 4096-row morsel stream of a grouped 4-agg query
    is a cost rejection at coalesce=1 (full RTT per half-empty bucket) and an
    honest device win once the coalescer covers 16 morsels per dispatch."""
    cal = _cal(0.005)
    rows = 4096
    host = costmodel.host_agg_cost(cal, rows, 4, grouped=True,
                                   has_predicate=False)
    kw = dict(n_mm=9, n_ext=1, n_sct=0, cap=64, factorize_rows=0)
    rejected = costmodel.device_grouped_cost(cal, rows, 0, **kw)
    horizon = costmodel.expected_coalesce_factor(rows, 65536)
    assert horizon == 16.0
    flipped = costmodel.device_grouped_cost(cal, rows, 0, coalesce=horizon, **kw)
    assert rejected > host, "shape must start as a cost rejection"
    assert flipped < host, "coalescing horizon failed to flip the decision"


def test_expected_coalesce_factor_properties():
    f = costmodel.expected_coalesce_factor
    assert f(4096, 65536) == 16.0
    assert f(65536, 65536) == 1.0       # bucket-filling morsels: no optimism
    assert f(200_000, 65536) == 1.0
    assert f(1, 1 << 30) == 64.0        # capped like device_amortize_runs
    assert f(0, 65536) == 1.0
    assert f(4096, 0) == 1.0            # coalescing disabled


def test_executor_coalesce_horizon_batch_granularity():
    """The real decision path: the horizon comes from the first partition's
    BATCH granularity (what the coalescer merges) capped by the observed
    batch count — a single-batch partition gets no optimism however small,
    and a many-small-batch partition engages at DEFAULT knobs."""
    from daft_tpu.core.micropartition import MicroPartition
    from daft_tpu.execution.executor import _coalesce_horizon

    schema, batches = _morsel_batches(8, 4096)
    multi = MicroPartition(schema, batches)        # 8 x 4096-row batches
    single = MicroPartition(schema, [batches[0]])  # one batch: can't coalesce
    with execution_config_ctx(batch_fill_target=0.5,
                              morsel_size_rows=128 * 1024):
        assert _coalesce_horizon([multi]) == 8.0  # min(65536/4096, 8 batches)
        assert _coalesce_horizon([single]) == 1.0
        # a peeked second partition widens the horizon to the morsels
        # actually OBSERVED — never past them (2 seen => at most 2x)
        assert _coalesce_horizon([single, single]) == 2.0
        assert _coalesce_horizon([multi, multi]) == 16.0
    with execution_config_ctx(batch_fill_target=0.0):
        assert _coalesce_horizon([multi]) == 1.0


# ---------------------------------------------------------------------------
# Zero-overhead guard + config validation (satellites)
# ---------------------------------------------------------------------------

def test_static_mode_zero_overhead_guard(monkeypatch):
    """Tier-1 guard: with batching_mode=static the host path must not
    allocate a strategy or coalescer, and must not touch the metrics
    registry — byte-identical behavior to the pre-batching engine."""
    from daft_tpu.execution import batching
    from daft_tpu.observability.metrics import registry
    from daft_tpu.ops import stage as stage_mod

    def _forbidden(*a, **k):
        raise AssertionError("batching machinery touched on the static host path")

    monkeypatch.setattr(batching.StaticBatching, "__init__", _forbidden)
    monkeypatch.setattr(batching.DynamicBatching, "__init__", _forbidden)
    monkeypatch.setattr(batching.LatencyConstrainedBatching, "__init__", _forbidden)
    monkeypatch.setattr(stage_mod.DispatchCoalescer, "__init__", _forbidden)

    before = registry().snapshot()
    df = daft_tpu.from_pydict({"a": list(range(2000)), "b": ["x", "y"] * 1000})
    with execution_config_ctx(batching_mode="static", device_mode="off"):
        out = (df.where(col("a") >= 1000)
               .groupby("b").agg(col("a").sum().alias("s")).to_pydict())
    assert len(out["b"]) == 2
    # a query counts its own wall time and nothing else
    assert set(registry().diff(before)) == {"query_wall_us"}, \
        "registry touched on the static path"


def test_agg_morsel_rows_unified_with_config():
    """Satellite: the partial-agg splitter's morsel size follows the config
    (was a hardcoded 256Ki drifting from the 128Ki default)."""
    from daft_tpu.execution.executor import _agg_morsel_rows

    assert _agg_morsel_rows() == ExecutionConfig().morsel_size_rows
    with execution_config_ctx(morsel_size_rows=4096):
        assert _agg_morsel_rows() == 4096


def test_batching_config_validation():
    with pytest.raises(ValueError, match="batching_mode"):
        ExecutionConfig(batching_mode="bogus")
    with pytest.raises(ValueError, match="batch_fill_target"):
        ExecutionConfig(batch_fill_target=1.5)
    with pytest.raises(ValueError, match="batch_fill_target"):
        ExecutionConfig(batch_fill_target=-0.1)
    with pytest.raises(ValueError, match="batch_latency_ms"):
        ExecutionConfig(batch_latency_ms=0.0)
    # 0 fill target is legal: it disables coalescing
    assert ExecutionConfig(batch_fill_target=0.0).batch_fill_target == 0.0
