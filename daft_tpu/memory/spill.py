"""Disk spill for out-of-core operators: compressed Arrow IPC files with a
crash-safe lifecycle and (optionally) overlapped IO.

Format: Arrow IPC *stream* files with per-message body compression — the
same wire format the shuffle writer uses (distributed/shuffle.py), governed
by ``DAFT_TPU_SPILL_COMPRESSION`` (none|lz4|zstd, default lz4). Readers
stream batch-by-batch; the codec travels in the IPC message headers, so
mixed-codec spill dirs decode fine.

IO overlap (``DAFT_TPU_SPILL_IO_THREADS``, default 2): ``SpillFile.append``
enqueues the batch into a bounded per-file queue and returns; compression +
disk writes drain on a small process-wide IO pool, so the producer keeps
computing while its spill lands on disk. The queue is byte-capped AND its
pending bytes are tracked in the host memory ledger while a budget is in
force — async spill cannot defeat the budget by parking batches in RAM.
``finish()`` joins the queue and surfaces any deferred IO error;
``finish_async()`` schedules close+publish behind the pending writes without
blocking the caller. ``read(prefetch=N)`` decodes ahead on the same pool
into a bounded queue (``DAFT_TPU_SPILL_PREFETCH_BATCHES`` per reader, capped
globally), so a k-way merge overlaps k decompress streams with merge
compute. ``spill_io_threads=0`` is the zero-overhead/compat guard: the
synchronous single-threaded spill path, byte-for-byte the pre-async code,
touching neither pool, queue, nor the overlap counters.

Lifecycle discipline:

- every artifact name carries the OWNING PID (``s<pid>_…`` files,
  ``g<pid>_…`` Grace directories) under one spill root
  (``DAFT_TPU_SPILL_DIR`` or ``<tmp>/daft_tpu_spill``);
- writers append to a ``.tmp`` name and ``os.replace`` into the final name
  on finish (tmp + atomic publish), so a half-written file is never
  mistaken for a complete one;
- operators delete their files in ``finally`` blocks, which the pipeline's
  cancellation propagation unwinds on the producer thread (pipeline.py
  spawn_stage closes abandoned generators) — query failure and cancellation
  both GC their spill state in-process; ``delete()`` also abandons queued
  async writes and releases their ledger bytes;
- artifacts orphaned by a KILLED process (no finally ran) are swept by
  ``gc_stale_spills()``: any artifact whose embedded pid is dead is removed,
  including its ``.tmp`` in-progress names (the name pattern is FULLY
  anchored, so a junk name can never parse as someone's pid). The sweep runs
  once per process, lazily, at the first spill — a crashed run's droppings
  survive at most until the next spilling process starts.

Attribution: spill_batches / spill_bytes (logical) / spill_wire_bytes
(on-disk) / spill_files / spill_runs / spill_merge_passes / spill_dirs_gced
counters in the process registry (observability/metrics.py), plus the async
overlap split (spill_write_seconds vs spill_write_wall_seconds,
spill_read_seconds vs spill_read_wall_seconds, spill_prefetch_inflight) so
spill activity reaches QueryEnd.metrics, EXPLAIN ANALYZE and /metrics.
"""

from __future__ import annotations

import os
import re
import shutil
import tempfile
import threading
import time
import uuid
from collections import deque
from typing import Callable, Iterator, List, Optional

import pyarrow as pa
import pyarrow.ipc as ipc

from ..core.recordbatch import RecordBatch
from ..core.series import Series
from ..observability.metrics import SPILL_COUNTER_NAMES, registry
from ..schema import Schema

_ATTR_TO_COUNTER = {"spills": "spill_batches", "spill_bytes": "spill_bytes"}


def __getattr__(name: str) -> int:
    # historical module attributes (memory.spills / memory.spill_bytes) as a
    # PEP 562 view over the registry — same pattern as ops/counters.py
    if name in _ATTR_TO_COUNTER:
        return registry().get(_ATTR_TO_COUNTER[name])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def reset_counters() -> None:
    from ..observability.metrics import MEMORY_COUNTER_NAMES

    registry().reset(SPILL_COUNTER_NAMES + MEMORY_COUNTER_NAMES)


def spill_root() -> str:
    """Base directory spill artifacts land under."""
    from ..config import execution_config

    d = execution_config().spill_dir
    return d or os.path.join(tempfile.gettempdir(), "daft_tpu_spill")


# ---- stale-artifact GC ---------------------------------------------------------------

_GC_LOCK = threading.Lock()
_GC_DONE = False

# s<pid>_<hex>.arrow files, g<pid>_<hex> Grace dirs, and their .tmp
# in-progress variants. FULLY anchored: a prefix-only match would let an
# unrelated name that merely starts like an artifact parse out a bogus pid
# (and a dead bogus pid would delete a file we do not own).
_ARTIFACT_RE = re.compile(r"^[sg](\d+)_[0-9a-f]+(?:\.arrow(?:\.tmp)?)?$")


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else
    except OSError:
        return True  # unknowable: never sweep what might be alive
    return True


def gc_stale_spills(root: Optional[str] = None) -> int:
    """Remove spill artifacts left behind by DEAD processes (pid parsed from
    the artifact name), INCLUDING their half-written ``.tmp`` names — a
    killed writer leaves its tmp behind and no finish() will ever publish
    it. Never touches a live process's files (published or .tmp). Returns
    the number of artifacts removed (also counted as spill_dirs_gced)."""
    root = root or spill_root()
    try:
        names = os.listdir(root)
    except OSError:
        return 0
    removed = 0
    for name in names:
        m = _ARTIFACT_RE.match(name)
        if m is None:
            continue
        pid = int(m.group(1))
        if pid == os.getpid() or _pid_alive(pid):
            continue
        path = os.path.join(root, name)
        try:
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            else:
                os.unlink(path)
            removed += 1
        except OSError:
            continue  # raced with another sweeper / fs trouble: leave it
    if removed:
        registry().inc("spill_dirs_gced", removed)
    return removed


def _gc_stale_once() -> None:
    global _GC_DONE
    with _GC_LOCK:
        if _GC_DONE:
            return
        _GC_DONE = True
    gc_stale_spills()


# ---- spill IO pool -------------------------------------------------------------------

_POOL_LOCK = threading.Lock()
_POOLS: dict = {}  # workers -> ThreadPoolExecutor (distinct knob values only)


def _io_pool(n: int):
    """The process-wide spill IO pool (created lazily at first async use).
    Keyed by size so a test overriding spill_io_threads gets a matching
    pool; real processes only ever create one."""
    with _POOL_LOCK:
        pool = _POOLS.get(n)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(max_workers=n,
                                      thread_name_prefix="daft-spill-io")
            _POOLS[n] = pool
        return pool


def _queue_cap_bytes() -> int:
    """Byte cap for one spill file's pending-write queue: enough to keep the
    IO threads fed, small against the host budget so queued-but-unwritten
    spill cannot hold a meaningful slice of the ledger."""
    from .manager import manager

    limit = manager().limit_bytes()
    cap = 64 << 20
    if limit > 0:
        cap = min(cap, max(limit // 8, 1 << 20))
    return cap


# ---- prefetching reader --------------------------------------------------------------

# Global allowance for read-ahead batches QUEUED BEYOND the first per reader:
# every reader may always hold one decoded batch (progress guarantee), extra
# depth draws from this shared pool so fan-in x depth cannot multiply.
_PF_LOCK = threading.Lock()
_PF_EXTRA = 0
_PF_EXTRA_CAP = 64


def _pf_take_extra() -> bool:
    global _PF_EXTRA
    with _PF_LOCK:
        if _PF_EXTRA >= _PF_EXTRA_CAP:
            return False
        _PF_EXTRA += 1
        return True


def _pf_give_extra() -> None:
    global _PF_EXTRA
    with _PF_LOCK:
        _PF_EXTRA = max(_PF_EXTRA - 1, 0)


_EOF = object()


class _Prefetcher:
    """Pump one iterator on the spill IO pool into a bounded queue.

    The pump task is INCREMENTAL: it decodes while the queue has space and
    returns otherwise (the consumer reschedules it on drain), so k starved
    readers can share a 2-thread pool without wedging it — a pump never
    blocks a pool thread on a full queue."""

    def __init__(self, factory: Callable[[], Iterator], depth: int, pool,
                 counters: bool = True):
        self._factory = factory
        self._depth = max(int(depth), 1)
        self._pool = pool
        self._counters = counters
        self._cond = threading.Condition(threading.Lock())
        self._q: deque = deque()  # (item, holds_extra_token)
        self._eof = False
        self._err: Optional[BaseException] = None
        self._closed = False
        self._running = False
        self._it: Optional[Iterator] = None
        self._hw = 0

    def _pump(self) -> None:
        try:
            if self._it is None:
                self._it = self._factory()
            while True:
                token = False
                with self._cond:
                    if (self._closed or self._eof or self._err is not None
                            or len(self._q) >= self._depth):
                        return
                    if self._q:
                        token = _pf_take_extra()
                        if not token:
                            return  # global read-ahead budget exhausted
                t0 = time.perf_counter()
                try:
                    item = next(self._it, _EOF)
                except BaseException as e:  # noqa: BLE001 — crossed to the consumer, re-raised there
                    if token:
                        _pf_give_extra()
                    with self._cond:
                        self._err = e
                    return
                if self._counters:
                    registry().inc("spill_read_seconds",
                                   time.perf_counter() - t0)
                with self._cond:
                    if item is _EOF:
                        if token:
                            _pf_give_extra()
                        self._eof = True
                        return
                    if self._closed:
                        if token:
                            _pf_give_extra()
                        return
                    self._q.append((item, token))
                    if len(self._q) > self._hw:
                        self._hw = len(self._q)
                        if self._counters:
                            registry().set_gauge_max("spill_prefetch_inflight",
                                                     float(self._hw))
        finally:
            with self._cond:
                self._running = False
                self._cond.notify_all()

    def _schedule_locked(self) -> None:
        if (not self._running and not self._eof and self._err is None
                and not self._closed and len(self._q) < self._depth):
            self._running = True
            self._pool.submit(self._pump)

    def __iter__(self):
        return self

    def __next__(self):
        t0 = None
        with self._cond:
            while True:
                if self._q:
                    item, token = self._q.popleft()
                    break
                if self._err is not None:
                    raise self._err
                if self._eof:
                    raise StopIteration
                self._schedule_locked()
                if t0 is None:
                    t0 = time.perf_counter()
                self._cond.wait(0.05)
            self._schedule_locked()  # top the queue back up
        if token:
            _pf_give_extra()
        if t0 is not None and self._counters:
            registry().inc("spill_read_wall_seconds",
                           time.perf_counter() - t0)
        return item

    def close(self) -> None:
        with self._cond:
            self._closed = True
            for _item, token in self._q:
                if token:
                    _pf_give_extra()
            self._q.clear()
            self._cond.notify_all()
            while self._running:  # pump unwinds at its next queue check
                self._cond.wait(0.05)
        it, self._it = self._it, None
        if it is not None and hasattr(it, "close"):
            it.close()  # generator close -> the decode stream's finally runs


def prefetch_iter(factory: Callable[[], Iterator], depth: int,
                  io_threads: Optional[int] = None,
                  counters: bool = True) -> Iterator:
    """Stream ``factory()`` with up to ``depth`` items decoded ahead on the
    spill IO pool; falls back to plain iteration when read-ahead is off
    (depth or the pool size resolve to 0). Shared by spill read-back,
    shuffle reduce reads, and budgeted parquet scans."""
    if io_threads is None:
        from ..config import execution_config

        io_threads = execution_config().spill_io_threads
    if depth <= 0 or io_threads <= 0:
        yield from factory()
        return
    pf = _Prefetcher(factory, depth, _io_pool(io_threads), counters=counters)
    try:
        yield from pf
    finally:
        pf.close()


# ---- spill files ---------------------------------------------------------------------


def _ipc_options(compression: Optional[str]) -> ipc.IpcWriteOptions:
    if compression is None:
        from ..config import execution_config

        compression = execution_config().spill_compression
    return ipc.IpcWriteOptions(
        compression=None if compression == "none" else compression)


_FINISH = object()  # queue sentinel: close + publish behind pending writes


class SpillFile:
    """One append-only compressed Arrow IPC spill file with streaming
    read-back, tmp + atomic-publish lifecycle, and (spill_io_threads > 0)
    asynchronous writes drained on the process-wide spill IO pool."""

    def __init__(self, schema: Schema, spill_dir: Optional[str] = None,
                 compression: Optional[str] = None):
        _gc_stale_once()
        self.schema = schema
        d = spill_dir or spill_root()
        os.makedirs(d, exist_ok=True)
        self.path = os.path.join(d, f"s{os.getpid()}_{uuid.uuid4().hex[:10]}.arrow")
        self._tmp = self.path + ".tmp"
        self._opts = _ipc_options(compression)
        self._writer = None
        self._published = False
        self.rows = 0
        self.bytes_written = 0  # logical Arrow bytes appended
        from ..config import execution_config

        cfg = execution_config()
        # snapshot at construction: one file never mixes sync and async writes
        self._io_threads = cfg.spill_io_threads
        self._prefetch = cfg.spill_prefetch_batches
        # async-write state, allocated lazily at the first async append
        self._cond: Optional[threading.Condition] = None
        self._q: Optional[deque] = None  # (table|_FINISH, nbytes, ledgered)
        self._pending_bytes = 0
        self._draining = False
        self._io_err: Optional[BaseException] = None

    # ---- write side ----------------------------------------------------------------

    def append(self, batch: RecordBatch) -> None:
        if batch.num_rows == 0:
            return
        if self._io_threads <= 0:
            # synchronous path: byte-for-byte the pre-async behavior (the
            # DAFT_TPU_SPILL_IO_THREADS=0 compat guard)
            table = batch.to_arrow()
            if self._writer is None:
                registry().inc("spill_files")
                self._writer = ipc.new_stream(self._tmp, table.schema,
                                              options=self._opts)
            self._writer.write_table(table)
            self.rows += batch.num_rows
            nb = batch.size_bytes()
            self.bytes_written += nb
            registry().inc("spill_batches")
            registry().inc("spill_bytes", nb)
            return
        self._append_async(batch)

    def _append_async(self, batch: RecordBatch) -> None:
        from .manager import manager

        table = batch.to_arrow()
        nb = batch.size_bytes()
        if self._cond is None:
            self._cond = threading.Condition(threading.Lock())
            self._q = deque()
        cap = _queue_cap_bytes()
        stalled = 0.0
        with self._cond:
            t0 = time.perf_counter() if self._pending_bytes >= cap else 0.0
            while (self._pending_bytes >= cap and self._q
                   and self._io_err is None):
                self._cond.wait(0.05)
            if t0:
                stalled = time.perf_counter() - t0
            if self._io_err is not None:
                err = self._io_err
                raise RuntimeError(
                    f"deferred spill write failed: {err}") from err
            ledgered = 0
            mgr = manager()
            if mgr.limit_bytes() > 0:
                # pending spill is still resident host memory: keep it on the
                # ledger until the IO thread lands it, so async spill cannot
                # defeat the budget by parking batches in the queue
                mgr.track(nb)
                ledgered = nb
            self._q.append((table, nb, ledgered))
            self._pending_bytes += nb
            if not self._draining:
                self._draining = True
                _io_pool(self._io_threads).submit(self._drain)
        self.rows += batch.num_rows
        self.bytes_written += nb
        registry().inc("spill_batches")
        registry().inc("spill_bytes", nb)
        if stalled:
            registry().inc("spill_write_wall_seconds", stalled)

    def _drain(self) -> None:
        """IO-pool task: write queued tables in append order. One drainer per
        file at a time (the _draining flag), so writes stay ordered; the
        head item is only popped after its write completes, keeping
        backpressure honest."""
        from ..observability.runtime_stats import profile_span

        from .manager import manager

        while True:
            with self._cond:
                if self._io_err is not None or not self._q:
                    self._draining = False
                    self._cond.notify_all()
                    return
                item, nb, ledgered = self._q[0]
            t0 = time.perf_counter()
            try:
                if item is _FINISH:
                    self._close_and_publish()
                else:
                    with profile_span("spill.write", "spill",
                                      rows=item.num_rows):
                        if self._writer is None:
                            registry().inc("spill_files")
                            self._writer = ipc.new_stream(
                                self._tmp, item.schema, options=self._opts)
                        self._writer.write_table(item)
            except BaseException as e:  # noqa: BLE001 — deferred to append/finish on the producer
                with self._cond:
                    self._io_err = e
                    release = ledgered
                    while self._q:
                        _i, _nb, led = self._q.popleft()
                        release += led if _i is not item else 0
                    self._pending_bytes = 0
                    self._draining = False
                    self._cond.notify_all()
                if release:
                    manager().release(release)
                return
            if item is not _FINISH:
                registry().inc("spill_write_seconds",
                               time.perf_counter() - t0)
            with self._cond:
                if self._q and self._q[0][0] is item:
                    self._q.popleft()
                    self._pending_bytes -= nb
                else:
                    ledgered = 0  # delete() raced us and already released
                self._cond.notify_all()
            if ledgered:
                manager().release(ledgered)

    def _join_queue(self) -> None:
        """Wait for the async queue to drain; surface any deferred IO error.
        The wait is producer wall time the writes actually cost."""
        if self._cond is None:
            return
        t0 = time.perf_counter()
        with self._cond:
            while self._draining or self._q:
                self._cond.wait(0.05)
            err = self._io_err
        waited = time.perf_counter() - t0
        if waited > 0.0005:
            registry().inc("spill_write_wall_seconds", waited)
        if err is not None:
            raise RuntimeError(f"deferred spill write failed: {err}") from err

    def _close_and_publish(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        if not self._published and os.path.exists(self._tmp):
            os.replace(self._tmp, self.path)
            self._published = True
            try:
                registry().inc("spill_wire_bytes", os.path.getsize(self.path))
            except OSError:
                pass  # the file vanished (concurrent delete): wire bytes stay advisory

    def finish(self) -> None:
        """Close the writer and atomically publish the file (joining any
        pending async writes first)."""
        self._join_queue()
        self._close_and_publish()

    def finish_async(self) -> None:
        """Schedule close+publish behind the pending async writes WITHOUT
        joining — the producer moves on (e.g. to sorting the next run) while
        this file's tail lands. A later finish()/read() joins and surfaces
        any deferred error. Synchronous files (io_threads=0) finish inline."""
        if self._cond is None:  # sync mode, or nothing was ever queued
            self.finish()
            return
        with self._cond:
            if self._io_err is None:
                self._q.append((_FINISH, 0, 0))
                if not self._draining:
                    self._draining = True
                    _io_pool(self._io_threads).submit(self._drain)

    # ---- read side -----------------------------------------------------------------

    def _decode_iter(self) -> Iterator[RecordBatch]:
        """Decode the published file batch-by-batch. The IPC stream carries
        ONE schema for all batches, so the arrow-schema comparison runs once
        and matching batches wrap zero-copy instead of paying a per-batch
        Table.from_batches + full cast."""
        try:
            target = self.schema.to_arrow()
        except ValueError:
            target = None  # python-object dtypes: always take the cast path
        fields = list(self.schema)
        with ipc.open_stream(self.path) as r:
            same: Optional[bool] = None
            for rb in r:
                if same is None:
                    same = target is not None and rb.schema.equals(target)
                if same:
                    cols = [Series.from_arrow(rb.column(i), f.name,
                                              dtype=f.dtype)
                            for i, f in enumerate(fields)]
                    yield RecordBatch(self.schema, cols, rb.num_rows)
                else:
                    yield RecordBatch.from_arrow(
                        pa.Table.from_batches([rb])).cast_to_schema(self.schema)

    def read(self, prefetch: Optional[int] = None) -> Iterator[RecordBatch]:
        """Stream batches back in append order, one at a time. With
        ``prefetch`` > 0 (default: the config knob when the IO pool is on),
        decode runs ahead on the spill IO pool into a bounded queue."""
        self.finish()
        if prefetch is None:
            prefetch = self._prefetch if self._io_threads > 0 else 0
        if self.rows == 0 or not os.path.exists(self.path):
            return
        if prefetch > 0 and self._io_threads > 0:
            from ..observability.runtime_stats import span_iter

            yield from span_iter(
                "spill.read", "spill",
                prefetch_iter(self._decode_iter, prefetch, self._io_threads))
        else:
            yield from self._decode_iter()

    # ---- lifecycle -----------------------------------------------------------------

    def delete(self) -> None:
        from .manager import manager

        if self._cond is not None:
            release = 0
            with self._cond:
                # abandon queued writes; keep the head if a drainer holds it
                # (it finishes that one write, then exits on the empty queue)
                while len(self._q) > (1 if self._draining else 0):
                    _item, nb, led = self._q.pop()
                    self._pending_bytes -= nb
                    release += led
                while self._draining:
                    self._cond.wait(0.05)
                while self._q:  # drainer exited between our two loops
                    _item, nb, led = self._q.popleft()
                    self._pending_bytes = max(self._pending_bytes - nb, 0)
                    release += led
                self._cond.notify_all()
            if release:
                manager().release(release)
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        for p in (self._tmp, self.path):
            try:
                os.unlink(p)
            except FileNotFoundError:
                pass


class SpillPartitions:
    """K hash-partitioned spill files (Grace partitioning for agg/join/dedup/
    window), grouped under one per-operator directory so failure cleanup and
    the dead-pid sweep are a single rmtree."""

    def __init__(self, schema: Schema, k: int, spill_dir: Optional[str] = None):
        _gc_stale_once()
        base = spill_dir or spill_root()
        self.dir = os.path.join(base, f"g{os.getpid()}_{uuid.uuid4().hex[:10]}")
        os.makedirs(self.dir, exist_ok=True)
        self.k = k
        self.files: List[SpillFile] = [SpillFile(schema, self.dir)
                                       for _ in range(k)]

    @property
    def bytes_written(self) -> int:
        return sum(f.bytes_written for f in self.files)

    def append_partitioned(self, batch: RecordBatch, key_exprs) -> None:
        """Fan one batch across the K partition files. With the async spill
        pool on, each append is an enqueue and the K compress+write legs
        overlap on the IO pool instead of running as k serial writes on the
        producer thread."""
        from ..expressions.eval import eval_expression

        keys = [eval_expression(batch, e) for e in key_exprs]
        for j, piece in enumerate(batch.partition_by_hash(keys, self.k)):
            if piece.num_rows:
                self.files[j].append(piece)

    def delete(self) -> None:
        for f in self.files:
            f.delete()
        shutil.rmtree(self.dir, ignore_errors=True)
