"""Whole-stage device fusion (ops/region.py + the Pallas kernel tier).

The planner collapses a maximal Filter/Project chain under an Aggregate into
ONE fused device region — a single jit program priced jointly by the cost
model and dispatched behind the usual start_run()/feed_batch()/finalize()
contract. These tests pin the region's correctness contract:

- region vs unfused-per-operator device vs host: 3-way bit-identity
  (including int64 exactness past 2^53 and null group keys)
- a mid-region DeviceFallback reruns the ENTIRE buffered region on host,
  bit-identically
- the Pallas segment-reduce kernels match jax.ops.segment_* in interpret
  mode, and the DAFT_TPU_PALLAS=on end-to-end path matches the XLA tiers
- device_mode=off queries import neither the region module nor the Pallas
  tier and leave an empty device-counter registry diff (zero overhead)
"""

import sys

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability.metrics import registry
from daft_tpu.ops import counters


def _chain_query(d):
    """filter -> project -> groupby-agg: the canonical fused-region shape."""
    return (d.select(col("k"), (col("v") * 3).alias("w"), col("v"))
            .where(col("w") > -2400)
            .groupby("k")
            .agg(col("w").sum().alias("s"),
                 col("w").count().alias("c"),
                 col("v").min().alias("lo"))
            .sort("k"))


def _data(n=4096, null_keys=False, big=False):
    rng = np.random.default_rng(7)
    keys = rng.choice(["a", "b", "c", "d", None] if null_keys
                      else ["a", "b", "c", "d"], n).tolist()
    if big:
        # past 2^53: only the stage's int64 digit/scatter planes keep these
        # exact — any float round-trip would corrupt low bits
        base = (1 << 60) + 12345
        vals = [(base + int(i)) * (1 if i % 2 else -1) for i in range(n)]
    else:
        vals = rng.integers(-1000, 1000, n).tolist()
    return {"k": keys, "v": vals}


@pytest.mark.parametrize("null_keys", [False, True])
def test_region_three_way_bit_identity(null_keys):
    data = _data(null_keys=null_keys)
    with execution_config_ctx(device_mode="on", region_mode="on"):
        fused = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    with execution_config_ctx(device_mode="on", region_mode="off"):
        unfused = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    assert fused == unfused
    assert fused == host


def test_region_int64_exactness_past_2_53():
    data = _data(n=512, big=True)
    q = lambda d: (d.where(col("v") != 0).groupby("k")
                   .agg(col("v").sum().alias("s"), col("v").max().alias("hi"))
                   .sort("k"))
    with execution_config_ctx(device_mode="on", region_mode="on"):
        fused = q(daft_tpu.from_pydict(data)).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host = q(daft_tpu.from_pydict(data)).to_pydict()
    assert fused == host
    assert any(abs(v) > (1 << 53) for v in fused["hi"])


def test_region_attribution_counters_and_explain():
    data = _data()
    counters.reset()
    with execution_config_ctx(device_mode="on", region_mode="on"):
        report = _chain_query(daft_tpu.from_pydict(data)).explain_analyze()
    assert counters.device_region_dispatches > 0
    # project+filter+agg = 3 ops amortized over every region dispatch
    assert (counters.device_region_ops_fused
            == 3 * counters.device_region_dispatches)
    assert "fused region: 3 ops" in report
    assert "project" in report and "filter" in report


def test_region_fuses_fewer_dispatches_than_unfused():
    """The tentpole's perf claim at counter granularity: the fused region
    dispatches ONE device program where the unfused plan runs the chain as
    separate host operators feeding a bare-agg device stage."""
    data = _data()
    counters.reset()
    with execution_config_ctx(device_mode="on", region_mode="on"):
        fused = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    fused_d = counters.device_grouped_batches
    assert counters.device_region_dispatches == fused_d > 0
    counters.reset()
    with execution_config_ctx(device_mode="on", region_mode="off"):
        unfused = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    assert fused == unfused
    # legacy capture still serves the agg on device, but the region path must
    # not dispatch MORE often than it
    assert fused_d <= max(counters.device_grouped_batches, 1)


def test_mid_region_fallback_reruns_whole_region_on_host(monkeypatch):
    """A DeviceFallback AFTER batches were fed and buffered discards every
    partial device accumulation and replays the ENTIRE buffered region
    through the host operators, bit-identically."""
    from daft_tpu.ops import grouped_stage as gs

    data = _data()
    with execution_config_ctx(device_mode="off"):
        host = _chain_query(daft_tpu.from_pydict(data)).to_pydict()

    fed = {"n": 0}
    real_feed = gs.GroupedAggRun.feed_batch

    def feeding(self, batch):
        real_feed(self, batch)
        fed["n"] += 1

    def exploding_finalize(self):
        raise gs.DeviceFallback("injected mid-region failure")

    monkeypatch.setattr(gs.GroupedAggRun, "feed_batch", feeding)
    monkeypatch.setattr(gs.GroupedAggRun, "finalize", exploding_finalize)
    with execution_config_ctx(device_mode="on", region_mode="on"):
        out = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    assert fed["n"] > 0, "device region never accumulated before the fallback"
    assert out == host


# ======================================================================================
# Pallas kernel tier
# ======================================================================================

def test_pallas_windowed_sum_matches_segment_sum():
    import jax.numpy as jnp
    import jax.ops

    from daft_tpu.ops.pallas_kernels import segment_sum_planes_windowed

    rng = np.random.default_rng(1)
    N, P, CAP = 65536, 4, 4096
    planes = rng.integers(0, 256, (N, P)).astype(np.float32)  # digit planes
    codes = rng.integers(0, CAP + 1, N).astype(np.int32)      # CAP = trash
    out = np.asarray(segment_sum_planes_windowed(planes, codes, CAP,
                                                 interpret=True))
    ref = jax.ops.segment_sum(jnp.asarray(planes, jnp.float64),
                              jnp.asarray(codes), num_segments=CAP + 1)[:CAP]
    assert (out == np.asarray(ref)).all(), "windowed kernel is not bit-exact"


def test_pallas_extremes_match_segment_min_max():
    import jax.numpy as jnp
    import jax.ops

    from daft_tpu.ops.pallas_kernels import segment_extreme_planes

    rng = np.random.default_rng(2)
    N, Q, CAP = 8192, 3, 512
    planes = rng.normal(size=(N, Q)).astype(np.float32)
    codes = rng.integers(0, CAP + 1, N).astype(np.int32)
    mn = np.asarray(segment_extreme_planes(planes, codes, CAP, "min",
                                           interpret=True))
    mx = np.asarray(segment_extreme_planes(planes, codes, CAP, "max",
                                           interpret=True))
    jc = jnp.asarray(codes)
    ref_mn = jax.ops.segment_min(jnp.asarray(planes), jc,
                                 num_segments=CAP + 1)[:CAP]
    ref_mx = jax.ops.segment_max(jnp.asarray(planes), jc,
                                 num_segments=CAP + 1)[:CAP]
    # segment_min/max yield +/-inf fill for empty segments too (f32)
    assert (mn == np.asarray(ref_mn)).all()
    assert (mx == np.asarray(ref_mx)).all()


def test_pallas_end_to_end_parity_and_counters():
    """DAFT_TPU_PALLAS=on forces the kernel tier (interpret mode off-silicon);
    results must match the XLA tiers bit for bit and the dispatch counter
    must attribute the kernel runs."""
    rng = np.random.default_rng(3)
    n = 6000
    data = {"k": rng.integers(0, 300, n).tolist(),
            "v": rng.integers(-1000, 1000, n).tolist()}
    q = lambda d: (d.where(col("v") > -500).groupby("k")
                   .agg(col("v").sum().alias("s"),
                        col("v").count().alias("c"),
                        col("v").mean().alias("m"))
                   .sort("k"))
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        r_pallas = q(daft_tpu.from_pydict(data)).to_pydict()
    assert counters.pallas_dispatches > 0
    with execution_config_ctx(device_mode="on", pallas_mode="off"):
        r_xla = q(daft_tpu.from_pydict(data)).to_pydict()
    with execution_config_ctx(device_mode="off"):
        r_host = q(daft_tpu.from_pydict(data)).to_pydict()
    assert r_pallas == r_xla
    assert r_pallas == r_host


def test_pallas_lowering_failure_falls_back_to_xla(monkeypatch):
    """A kernel that fails to lower reaches the caller: no latch, no XLA
    replay, no fallback counter — a run that did not use the kernel it was
    told to use must not exit with right answers."""
    from daft_tpu.ops import pallas_kernels as pk

    def broken(*a, **k):
        raise RuntimeError("mosaic lowering failed (injected)")

    monkeypatch.setattr(pk, "segment_sum_planes_windowed", broken)
    rng = np.random.default_rng(4)
    data = {"k": rng.integers(0, 50, 2048).tolist(),
            "v": rng.integers(0, 100, 2048).tolist()}
    q = lambda d: (d.groupby("k").agg(col("v").sum().alias("s")).sort("k"))
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        with pytest.raises(RuntimeError, match="mosaic lowering failed"):
            q(daft_tpu.from_pydict(data)).to_pydict()
    assert counters.pallas_dispatches == 0


def test_pallas_ineligible_stages_stay_on_xla():
    """f64-exact stages (float min/max) must never route to the f32 kernel
    tier, even under DAFT_TPU_PALLAS=on."""
    rng = np.random.default_rng(5)
    data = {"k": rng.integers(0, 20, 1024).tolist(),
            "f": rng.normal(size=1024).tolist()}
    q = lambda d: (d.groupby("k").agg(col("f").min().alias("lo"),
                                      col("f").sum().alias("s")).sort("k"))
    counters.reset()
    with execution_config_ctx(device_mode="on", pallas_mode="on"):
        out = q(daft_tpu.from_pydict(data)).to_pydict()
    assert counters.pallas_dispatches == 0
    # forcing the kernel tier changed nothing: ineligible stages keep the
    # exact XLA program (host comparison would only re-test the pre-existing
    # f32-vs-f64 device sum contract, not the gate)
    with execution_config_ctx(device_mode="on", pallas_mode="off"):
        xla = q(daft_tpu.from_pydict(data)).to_pydict()
    assert out == xla


def test_region_host_path_narrows_to_referenced_columns():
    """Absorbing a pruning Project moves the region's base BELOW it, so the
    raw stream is full-width; the executor must narrow to the referenced
    columns before the host path filters/buffers (the SF10 q1 regression: a
    wide never-referenced string column riding whole through filter/concat)."""
    from daft_tpu.execution.executor import _region_keep_columns
    from daft_tpu.plan import physical as pp
    from daft_tpu.plan.physical import translate

    n = 512
    data = {"k": [i % 7 for i in range(n)],
            "v": list(range(n)),
            "pad": ["x" * 64] * n}  # never referenced by the region
    q = lambda d: (d.select("k", "v", (col("v") * 2).alias("w"))
                   .where(col("w") > 4)
                   .groupby("k").agg(col("w").sum().alias("s"))
                   .sort("k"))
    with execution_config_ctx(device_mode="on", region_mode="on"):
        plan = translate(q(daft_tpu.from_pydict(data))._builder.optimize()._plan)
        node = next(nd for nd in plan.walk()
                    if isinstance(nd, pp.DeviceGroupedAgg))
        keep = _region_keep_columns(node, grouped=True)
        fused = q(daft_tpu.from_pydict(data)).to_pydict()
    assert "pad" in node.input.schema.column_names()  # base IS the wide table
    assert keep is not None and "pad" not in keep
    assert set(keep) == {"k", "v"}
    with execution_config_ctx(device_mode="off"):
        host = q(daft_tpu.from_pydict(data)).to_pydict()
    assert fused == host


# ======================================================================================
# Zero overhead when the device tier is off
# ======================================================================================

def test_zero_overhead_device_off():
    """device_mode=off queries import neither ops.region nor the Pallas tier
    and leave an empty device/pallas counter diff."""
    sys.modules.pop("daft_tpu.ops.region", None)
    sys.modules.pop("daft_tpu.ops.pallas_kernels", None)

    data = _data(n=256)
    counters.reset()
    before = registry().snapshot()
    with execution_config_ctx(device_mode="off"):
        out = _chain_query(daft_tpu.from_pydict(data)).to_pydict()
    assert len(out["k"]) > 0
    assert "daft_tpu.ops.region" not in sys.modules, \
        "host-only query imported the fused-region module"
    assert "daft_tpu.ops.pallas_kernels" not in sys.modules, \
        "host-only query imported the Pallas kernel tier"
    diff = {k: v for k, v in registry().diff(before).items() if v}
    assert not any(k.startswith(("device_", "pallas_")) for k in diff), diff
