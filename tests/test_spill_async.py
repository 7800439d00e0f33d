"""Async spill IO: overlapped writes with deferred-error surfacing,
prefetching readers, the carry-preserving merge's tier-1 microbench, the
.tmp-aware dead-pid sweep, and the zero-overhead guards (sync compat path
and unbudgeted queries must never touch the pool, the queue, or the new
counters)."""

import errno
import os
import time

import numpy as np
import pyarrow as pa
import pytest

import daft_tpu
from daft_tpu.config import execution_config, execution_config_ctx
from daft_tpu import memory as mem
from daft_tpu.observability.metrics import registry


@pytest.fixture(autouse=True)
def _clean():
    mem.reset_counters()
    mem.manager().clear()
    yield
    mem.manager().clear()


def _mixed_batch(n=4000):
    from daft_tpu.core.recordbatch import RecordBatch

    rng = np.random.default_rng(3)
    return RecordBatch.from_arrow(pa.table({
        "i": pa.array(rng.integers(-1000, 1000, size=n)),
        "f": pa.array(rng.standard_normal(n)),
        "s": pa.array([f"row-{x % 97}" for x in range(n)]),
        "b": pa.array((np.arange(n) % 3 == 0)),
        "maybe": pa.array([None if x % 7 == 0 else x for x in range(n)],
                          type=pa.int64()),
    }))


def test_async_round_trip_prefetch(tmp_path):
    """Async appends + prefetching read-back round-trip bit-identically
    across mixed dtypes; the prefetch high-water gauge never exceeds the
    configured depth; the cumulative/wall counter pairs both moved."""
    from daft_tpu.memory import SpillFile

    batch = _mixed_batch()
    with execution_config_ctx(memory_limit_bytes=1 << 24,
                              spill_io_threads=2, spill_prefetch_batches=2):
        f = SpillFile(batch.schema, spill_dir=str(tmp_path))
        for _ in range(6):
            f.append(batch)
        f.finish_async()  # publish rides the queue; read() joins below
        got = list(f.read())
    assert sum(b.num_rows for b in got) == 6 * batch.num_rows
    for col in ("i", "f", "s", "b", "maybe"):
        assert got[0].get_column(col).to_pylist() == \
            batch.get_column(col).to_pylist()
    assert registry().get("spill_write_seconds") > 0
    assert registry().get("spill_read_seconds") > 0
    assert registry().snapshot().get("spill_prefetch_inflight", 0) <= 2
    f.delete()
    assert not os.path.exists(f.path) and not os.path.exists(f._tmp)


def test_deferred_write_error_surfaces_and_cleans(tmp_path, monkeypatch):
    """A spill write that fails off-thread (ENOSPC at publish) surfaces as a
    RuntimeError at the next join point (finish/read/append), the ledger
    drops back to zero, and delete() leaves no artifacts behind."""
    from daft_tpu.memory import SpillFile
    from daft_tpu.memory import spill as spill_mod

    batch = _mixed_batch(1000)
    with execution_config_ctx(memory_limit_bytes=1 << 24,
                              spill_io_threads=2, spill_prefetch_batches=2):
        f = SpillFile(batch.schema, spill_dir=str(tmp_path))
        f.append(batch)

        def _enospc(src, dst):
            raise OSError(errno.ENOSPC, "No space left on device", dst)

        monkeypatch.setattr(spill_mod.os, "replace", _enospc)
        f.finish_async()  # the drainer hits ENOSPC publishing off-thread
        deadline = time.time() + 10
        while time.time() < deadline and f._io_err is None:
            time.sleep(0.01)
        assert f._io_err is not None, "drainer never surfaced the IO error"
        with pytest.raises(RuntimeError, match="deferred spill write failed"):
            f.finish()
        with pytest.raises(RuntimeError, match="deferred spill write failed"):
            f.append(batch)
        monkeypatch.undo()
        assert mem.manager().tracked_bytes() == 0, \
            "failed async spill leaked ledger bytes"
        f.delete()
    assert os.listdir(tmp_path) == [], "failed spill left artifacts behind"


def test_gc_sweeps_dead_pid_tmp_not_live(tmp_path):
    """The dead-pid sweep takes half-written .tmp names too (a killed writer
    never publishes them) while a LIVE process's .tmp survives — the
    fully-anchored artifact regex must not let a live writer's in-progress
    file be parsed as anything else."""
    from daft_tpu.memory import gc_stale_spills

    dead = None
    for pid in range(300_000, 300_064):
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            dead = pid
            break
        except OSError:
            continue
    if dead is None:
        pytest.skip("could not find a dead pid on this platform")
    root = tmp_path / "spillroot"
    root.mkdir()
    live_tmp = f"s{os.getpid()}_cafecafe01.arrow.tmp"
    (root / live_tmp).write_bytes(b"x")
    (root / f"s{dead}_deadbeef01.arrow.tmp").write_bytes(b"x")
    (root / f"s{dead}_deadbeef02.arrow").write_bytes(b"x")
    # names that merely RESEMBLE artifacts must never parse a pid out of a
    # prefix match (a bogus dead pid would delete a file we do not own)
    (root / f"s{dead}_deadbeef03.arrow.tmp.bak").write_bytes(b"x")
    removed = gc_stale_spills(str(root))
    assert removed == 2
    assert sorted(os.listdir(root)) == sorted(
        [live_tmp, f"s{dead}_deadbeef03.arrow.tmp.bak"])


def test_merge_microbench_tier1():
    """A synthetic sort forced through a >=32-run external merge under a
    tiny fixed budget is bit-identical to the in-memory sort, the
    carry-preserving merge keys each row once per level (far below the old
    re-argsort bound), and the prefetch high-water respects the knob."""
    rows = 80_000
    rng = np.random.default_rng(7)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, rows, size=rows),
        "g": rng.integers(0, 997, size=rows),
        "v": rng.standard_normal(rows),
    }).into_batches(max(rows // 64, 256)).collect()
    input_bytes = sum(p.size_bytes() for p in df.iter_partitions())
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        expected = df.sort(["k", "g"]).to_pydict()
    # ~48 runs: deep enough that the fan-in cascade (intermediate merges)
    # engages, so the sort-rows bound below exercises multi-level merging
    budget = max(input_bytes // 48, 48 << 10)
    before = registry().snapshot()
    with execution_config_ctx(memory_limit_bytes=budget, device_mode="off"):
        out = df.sort(["k", "g"]).to_pydict()
    diff = registry().diff(before)
    assert out == expected, "budgeted merge diverged from in-memory sort"
    runs = int(diff.get("spill_runs", 0))
    assert runs >= 32, f"expected a >=32-run cascade, got {runs}"
    merge_rows = int(diff.get("spill_merge_sort_rows", 0))
    # each row is keyed/argsorted at most once per merge level (cascade +
    # final), and single-source stretches skip the argsort entirely; the
    # old merge's bound was ~rows x fan-in (rows x runs // 2 here)
    levels = 1 + (1 if diff.get("spill_merge_passes", 0) else 0)
    assert 0 < merge_rows <= rows * (levels + 1), (
        f"spill_merge_sort_rows={merge_rows} outside the carry-preserving "
        f"bound for {rows} rows x {levels} merge level(s)")
    assert registry().snapshot().get("spill_prefetch_inflight", 0) <= \
        execution_config().spill_prefetch_batches


def test_sync_compat_path_touches_no_async_counters():
    """DAFT_TPU_SPILL_IO_THREADS=0 + PREFETCH=0 reproduces the synchronous
    path exactly: the run still spills and stays bit-identical, but none of
    the async-era counters (write/read cumulative+wall pairs, prefetch
    gauge) ever move."""
    rng = np.random.default_rng(11)
    n = 40_000
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, n, size=n),
        "v": rng.standard_normal(n),
    }).into_batches(1024).collect()
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        expected = df.sort(["k"]).to_pydict()
    before = registry().snapshot()
    with execution_config_ctx(memory_limit_bytes=64 << 10, device_mode="off",
                              spill_io_threads=0, spill_prefetch_batches=0):
        got = df.sort(["k"]).to_pydict()
    diff = registry().diff(before)
    assert got == expected
    assert diff.get("spill_bytes", 0) > 0, "budget never spilled"
    for name in ("spill_write_seconds", "spill_write_wall_seconds",
                 "spill_read_seconds", "spill_read_wall_seconds"):
        assert not diff.get(name), f"sync compat path moved {name}: {diff}"
    assert registry().snapshot().get("spill_prefetch_inflight", 0) == \
        before.get("spill_prefetch_inflight", 0)


def test_unbudgeted_query_touches_no_spill_state():
    """Zero-overhead guard: with no memory budget the whole spill subsystem
    stays cold — no spill counters move and no IO pool is created for the
    query's sake."""
    from daft_tpu.memory import spill as spill_mod

    rng = np.random.default_rng(13)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 1000, size=20_000),
        "v": rng.standard_normal(20_000),
    })
    pools_before = dict(spill_mod._POOLS)
    before = registry().snapshot()
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        df.sort(["k"]).to_pydict()
        df.groupby("k").agg(daft_tpu.col("v").sum()).to_pydict()
    diff = registry().diff(before)
    spilled = {k: v for k, v in diff.items() if k.startswith("spill_")}
    assert not spilled, f"unbudgeted query moved spill counters: {spilled}"
    assert spill_mod._POOLS == pools_before, \
        "unbudgeted query created a spill IO pool"
