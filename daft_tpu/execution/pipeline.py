"""Pipeline-parallel execution primitives.

Reference parity: src/daft-local-execution/src/pipeline.rs:358 (every pipeline
node runs as its own concurrent task), src/daft-local-execution/src/channel.rs
(bounded channels with backpressure), and
src/daft-local-execution/src/intermediate_ops/intermediate_op.rs:45-59
(intermediate operators fan morsels across a shared worker pool).

Host parallelism on threads is real here: the hot kernels are numpy / pyarrow
/ the C++ extension / JAX dispatch, all of which release the GIL. Three
primitives:

- Channel / spawn_stage: run one operator's generator on a dedicated thread,
  pushing into a bounded queue. Backpressure = the bounded queue; cancellation
  (a downstream limit stops pulling, or the query errors) propagates upstream
  by closing the producer's generator, which unwinds its `finally` blocks
  (spill-file cleanup etc.) on the producer thread. Out-of-core interplay
  (daft_tpu/memory): the bounded channel caps MORSELS between stages, while
  the host memory ledger's pressure signal paces BYTES — a StreamingScan
  producer additionally stalls (bounded) while downstream blocking operators
  sit at the memory wall, so channel depth x morsel size can't outrun the
  process budget; and because cancellation unwinds producer `finally`
  blocks, an abandoned spilling query deletes its spill artifacts on the
  way out.
- pmap_stream: ordered morsel fan-out — submit fn(item, i) for a bounded
  window of in-flight items to the shared compute pool, yield results in input
  order (row order is part of the engine's semantics).
- morsels: split one oversized MicroPartition into zero-copy slices so a
  single in-memory partition still feeds the whole pool.
"""

from __future__ import annotations

import contextvars
import threading
import time
from collections import deque
from queue import Empty, Full, Queue
from typing import Callable, Iterator, List, Optional

from ..core.micropartition import MicroPartition


class StageCancelled(BaseException):
    """Raised inside a producer blocked on a closed channel. BaseException so
    user-level `except Exception` inside operator bodies can't swallow it."""


_SENTINEL = object()


class Channel:
    """Bounded single-producer/single-consumer channel with error and
    cancellation propagation.

    Stall attribution (`profile` = (StatsCollector, producer_node_id), set by
    spawn_stage only while a collector is active): time the producer spends
    blocked in put() on a FULL queue is downstream backpressure charged to
    the producer node; time a consumer spends blocked in get() on an EMPTY
    queue is upstream starvation charged to whatever node is active on the
    consumer thread. The unprofiled path is byte-for-byte the original —
    uncontended put/get never read a clock."""

    def __init__(self, maxsize: int = 4, profile=None):
        self._q: Queue = Queue(maxsize)
        self._cancel = threading.Event()
        self._err: Optional[BaseException] = None
        self._profile = profile

    # ---- producer side -----------------------------------------------------------
    def put(self, item) -> None:
        if self._profile is not None and not self._cancel.is_set():
            try:
                self._q.put_nowait(item)
                return
            except Full:
                pass
            t0 = time.perf_counter()
            self._put_blocking(item)
            collector, nid = self._profile
            collector.note_blocked(nid, time.perf_counter() - t0)
            return
        self._put_blocking(item)

    def _put_blocking(self, item) -> None:
        while True:
            if self._cancel.is_set():
                raise StageCancelled()
            try:
                self._q.put(item, timeout=0.05)
                return
            except Full:
                continue

    def close(self, err: Optional[BaseException] = None) -> None:
        self._err = err
        while True:
            if self._cancel.is_set():
                return
            try:
                self._q.put(_SENTINEL, timeout=0.05)
                return
            except Full:
                continue

    # ---- consumer side -----------------------------------------------------------
    def __iter__(self) -> Iterator:
        try:
            while True:
                if self._profile is None:
                    item = self._q.get()
                else:
                    try:
                        item = self._q.get_nowait()
                    except Empty:
                        t0 = time.perf_counter()
                        item = self._q.get()
                        # starvation lands on the CONSUMER's active node (the
                        # operator whose next() this wait happened inside)
                        self._profile[0].note_starve(time.perf_counter() - t0)
                if item is _SENTINEL:
                    if self._err is not None:
                        raise self._err
                    return
                yield item
        finally:
            # normal exhaustion, consumer abandonment (GeneratorExit), or error:
            # unblock and cancel the producer either way
            self._cancel.set()


def spawn_stage(gen: Iterator, maxsize: int = 4, node=None) -> Iterator:
    """Run `gen` on a dedicated stage thread; return a bounded-channel iterator
    over its output. The stage thread inherits the ambient stats collector
    (threading.local in observability.runtime_stats).

    `node` (the physical node whose generator this is) enables stall
    attribution on the channel while a collector is active: put-side
    backpressure is charged to this node, get-side starvation to the
    consumer. With no collector the channel runs unprofiled.

    The thread starts on the FIRST pull, not at call time: a plan that is
    built but never iterated (caller bails before next()) must not leak
    producer threads — the channel's cancel flag is only ever set by the
    consumer iterator, which would otherwise never run."""
    from ..device.residency import current_pin_observation, set_pin_observation
    from ..observability.placement import current_scope as _cur_pscope
    from ..observability.placement import set_scope as _set_pscope
    from ..observability.runtime_stats import current_collector, set_collector

    collector = current_collector()
    # serving admission calibration: device pin scopes open on THIS stage
    # thread, so the observing query's handle rides along like the collector
    pin_obs = current_pin_observation()
    # placement decisions fire on stage threads too: the query's placement
    # scope (explain_placement / per-query QueryEnd records) rides along so
    # concurrent queries' decisions never bleed into each other's scopes
    pscope = _cur_pscope()
    profile = (collector, collector.node_id(node)) \
        if collector is not None and node is not None else None
    ch = Channel(maxsize, profile=profile)

    def run():
        set_collector(collector)
        set_pin_observation(pin_obs)
        _set_pscope(pscope)
        err: Optional[BaseException] = None
        try:
            for item in gen:
                ch.put(item)
        except StageCancelled:
            pass
        except BaseException as e:  # noqa: BLE001 — must ferry to the consumer
            err = e
        finally:
            try:
                gen.close()  # unwind upstream finally blocks on this thread
            except BaseException:  # lint: ignore[broad-except] -- teardown: close() may re-raise
                pass  # the propagating error; ch.close(err) reports it
            ch.close(err)

    def consume():
        # a copy of the puller's context: the stage's spans hang under the
        # operator that started it
        threading.Thread(target=contextvars.copy_context().run, args=(run,),
                         daemon=True, name="daft-stage").start()
        yield from ch

    return consume()


def pmap_stream(stream: Iterator, fn: Callable, window: int = 0,
                strategy=None) -> Iterator:
    """Ordered parallel map over a stream: keep up to `window` fn(item, index)
    calls in flight on the shared compute pool, yielding results in input
    order. While the window is full this thread blocks on the OLDEST future,
    so upstream production, pool workers, and downstream consumption overlap.

    `strategy` (an execution.batching.BatchingStrategy): each morsel's rows
    and processing wall time are fed back via strategy.record() from the pool
    worker that ran it, closing the adaptive-batching feedback loop. None
    (static mode) adds nothing to the per-morsel path.

    While a SpanRecorder is installed (timeline profiling) every morsel's
    pool execution is additionally recorded as a "pipeline.morsel" span, and
    each morsel runs in a copy of the submitting context, so its spans hang
    under the operator that fanned it out (pool workers are foreign threads:
    they follow the process-global recorder slot).
    """
    from ..observability.runtime_stats import current_spans, profile_span
    from ..utils.pool import compute_pool

    pool = compute_pool()
    if window <= 0:
        window = pool._max_workers
    spans = current_spans()
    if strategy is not None:
        timed = fn

        def fn(item, i):  # noqa: F811 — timed wrapper around the caller's fn
            t0 = time.perf_counter()
            out = timed(item, i)
            strategy.record(item.num_rows, time.perf_counter() - t0)
            return out
    if spans is not None:
        traced = fn

        def fn(item, i):  # noqa: F811 — the morsel's span around the above
            with profile_span("pipeline.morsel", "compute", rows=item.num_rows):
                return traced(item, i)
    futs: deque = deque()
    try:
        for i, item in enumerate(stream):
            if spans is not None:
                futs.append(pool.submit(contextvars.copy_context().run,
                                        fn, item, i))
            else:
                futs.append(pool.submit(fn, item, i))
            if len(futs) >= window:
                yield futs.popleft().result()
        while futs:
            yield futs.popleft().result()
    finally:
        for f in futs:
            f.cancel()


def morsels(part: MicroPartition, morsel_rows: int) -> List[MicroPartition]:
    """Split one partition into ~morsel_rows zero-copy slices (arrow slicing)
    so a single large in-memory partition can fan out across the pool. Small
    partitions pass through untouched."""
    return list(iter_morsels(part, morsel_rows))


def iter_morsels(part: MicroPartition, morsel_rows: int) -> Iterator[MicroPartition]:
    """`morsels`, cut one at a time as the caller pulls: whoever needs a
    table's first morsels (the join driver's placement decision over a
    resident fact) makes no more of them."""
    if part.num_rows <= morsel_rows * 2 or not part.batches:
        yield part
        return
    for b, _cut in cut_batches(part, morsel_rows):
        if b.num_rows:
            yield MicroPartition(part.schema, [b])


def cut_batches(part: MicroPartition, morsel_rows: int, piece_rows: int = 0):
    """(batch, cut) over a partition's batches under the one rule of what is
    cut: a batch of more than two morsels, in a partition of more than two,
    goes as zero-copy slices of `piece_rows` rows (a morsel's by default; the
    join driver asks for a resident dispatch's length) with `cut` true; any
    other goes whole, as the object it is, with `cut` false."""
    step = piece_rows or morsel_rows
    small = part.num_rows <= morsel_rows * 2
    for b in part.batches:
        if small or b.num_rows <= morsel_rows * 2:
            yield b, False
            continue
        for s in range(0, b.num_rows, step):
            yield b.slice(s, min(s + step, b.num_rows)), True


def morsel_stream(stream: Iterator, morsel_rows: int) -> Iterator:
    for part in stream:
        yield from morsels(part, morsel_rows)
