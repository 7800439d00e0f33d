"""Process-wide host memory manager (daft_tpu/memory): ledger semantics,
budget resolution, shared admission across concurrent operators/queries,
pressure backpressure, and the zero-overhead guard for unbudgeted queries."""

import threading

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.memory import manager
from daft_tpu.memory.manager import system_ram_bytes
from daft_tpu.observability.metrics import registry


@pytest.fixture(autouse=True)
def _clean_manager():
    from daft_tpu import memory as mem

    mem.reset_counters()
    manager().clear()
    yield
    manager().clear()


def test_ledger_track_release_high_water():
    m = manager()
    m.track(1000)
    m.track(500)
    assert m.tracked_bytes() == 1500
    m.release(600)
    assert m.tracked_bytes() == 900
    assert m.high_water_bytes() == 1500
    snap = registry().snapshot()
    assert snap["host_bytes_tracked"] == 900.0
    assert snap["host_bytes_high_water"] == 1500.0
    m.release(10_000)  # over-release clamps at zero, never goes negative
    assert m.tracked_bytes() == 0


def test_limit_resolution_modes():
    m = manager()
    with execution_config_ctx(memory_limit_bytes=12345):
        assert m.limit_bytes() == 12345
    with execution_config_ctx(memory_limit_bytes=0):
        assert m.limit_bytes() == 0  # unbounded/untracked default
    with execution_config_ctx(memory_limit_bytes=-1, memory_fraction=0.5):
        auto = m.limit_bytes()
        ram = system_ram_bytes()
        if ram > 0:
            assert auto == int(ram * 0.5)
        else:
            assert auto == 0


def test_shared_budget_across_operators():
    """Two admission handles draw down ONE ledger: the second operator sees
    over-budget once the combined holdings cross the limit (the serving-tier
    'concurrent queries share one budget' satellite, at manager level)."""
    m = manager()
    with execution_config_ctx(memory_limit_bytes=1000):
        a = m.operator_budget()
        b = m.operator_budget()
        assert a.admit(600)
        assert not b.admit(600)  # ledger at 1200 > 1000: B must spill
        assert registry().get("host_over_budget_events") == 1
        b.release_all()
        assert m.tracked_bytes() == 600
        a.close()
        assert m.tracked_bytes() == 0


def test_inert_budget_when_unbudgeted():
    m = manager()
    with execution_config_ctx(memory_limit_bytes=0):
        b = m.operator_budget()
        assert b.admit(10**12)
        assert m.tracked_bytes() == 0  # nothing touched the ledger
        b.close()


def test_pressure_threshold_and_callbacks():
    m = manager()
    fired = []
    unsub = m.on_pressure(lambda tracked, limit: fired.append((tracked, limit)))
    with execution_config_ctx(memory_limit_bytes=1000, memory_pressure=0.8):
        m.track(700)
        assert not m.under_pressure()
        m.track(200)  # 900 >= 800: upward crossing fires once
        assert m.under_pressure()
        assert len(fired) == 1
        m.track(50)  # still in pressure: no re-fire
        assert len(fired) == 1
        m.release(900)  # 50 < 800: pressure clears
        assert not m.under_pressure()
        m.track(850)  # re-cross fires again
        assert len(fired) == 2
        unsub()
        m.release(900)
        m.track(900)
        assert len(fired) == 2


def test_wait_for_headroom_bounded_and_counted():
    m = manager()
    with execution_config_ctx(memory_limit_bytes=1000, memory_pressure=0.5):
        m.track(900)
        t = threading.Timer(0.05, lambda: m.release(900))
        t.start()
        stalled = m.wait_for_headroom(max_wait_s=5.0)
        t.join()
        assert 0.0 < stalled < 5.0  # woke on the release, not the deadline
        assert registry().get("scan_backpressure_stalls") == 1
        assert registry().get("scan_stall_ms") >= 1
        # pressure that never clears: returns at the bound (pacing, not a gate)
        m.track(900)
        stalled = m.wait_for_headroom(max_wait_s=0.05)
        assert stalled >= 0.05
        m.release(900)


def test_query_scope_observes_peak():
    m = manager()
    with execution_config_ctx(memory_limit_bytes=10_000):
        m.track(100)
        with m.query_scope() as scope:
            assert scope.peak_bytes() == 100  # pre-existing holdings count
            m.track(700)
            m.release(500)
            m.track(100)
        assert scope.peak_bytes() == 800
        m.release(400)
        assert scope.peak_bytes() == 800  # frozen after exit


def test_zero_overhead_unbudgeted_query():
    """Acceptance guard: an unbudgeted in-memory query allocates no
    manager/spill state and moves no counter but its own wall time."""
    import os

    from daft_tpu.memory import spill_root

    df = daft_tpu.from_pydict({
        "k": [i % 7 for i in range(10_000)],
        "v": [float(i) for i in range(10_000)],
    })

    def q():
        return df.groupby("k").agg(col("v").sum().alias("s")).sort("k")

    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        q().to_pydict()  # warm one run (pools, kernels)
        before = registry().snapshot()
        q().to_pydict()
        diff = registry().diff(before)
    # (its own wall time is the one counter a query moves)
    assert set(diff) == {"query_wall_us"}, f"unbudgeted query left a registry diff: {diff}"
    assert manager().tracked_bytes() == 0
    assert manager().high_water_bytes() == 0
    root = spill_root()
    if os.path.isdir(root):
        assert not [n for n in os.listdir(root) if f"{os.getpid()}_" in n]


def test_concurrent_queries_share_ledger_and_stay_exact():
    """Four concurrent spilling queries under one tiny shared budget: all
    bit-identical to the unbudgeted run, ledger drains to zero after."""
    rng = np.random.default_rng(3)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 50, 40_000).tolist(),
        "v": rng.uniform(0, 1, 40_000).tolist(),
    })

    def q():
        return (df.groupby("k").agg(col("v").sum().alias("s"))
                .sort("k").to_pydict())

    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        expected = q()
    results = [None] * 4
    errs = []
    with execution_config_ctx(memory_limit_bytes=128 * 1024, device_mode="off"):
        def run(i):
            try:
                results[i] = q()
            except Exception as e:  # noqa: BLE001 — surfaced via the errs assert
                errs.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs
    assert all(r == expected for r in results)
    assert registry().get("spill_batches") > 0
    assert manager().tracked_bytes() == 0, "a query leaked ledger bytes"
