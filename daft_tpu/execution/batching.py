"""Adaptive batching strategies: per-operator morsel sizing.

Reference parity: src/daft-local-execution/src/dynamic_batching/mod.rs — the
reference engine's `BatchingStrategy` trait with static / dynamic /
latency-constrained implementations, consulted by every intermediate operator
to pick how many rows one unit of work should carry.

Why morsel size matters here more than in the reference: this engine's device
stages pay a FIXED per-dispatch price (the compiled-program round trip; not
measured on this chip) and a power-of-two padding tax (a
half-empty bucket uploads and reduces padding rows that carry no data), while
host operators pay per-morsel pool-scheduling overhead. Too-small morsels
drown in fixed costs; too-big morsels lose pipeline overlap and blow the
cache. The knee between those regimes is workload-dependent — `DynamicBatching`
finds it from live throughput feedback instead of a config guess.

The strategies are consulted by `executor._map_op` (via
`adaptive_morsel_stream`) and fed by `pipeline.pmap_stream`, which times each
morsel's processing and calls `record()`. `StaticBatching` exists so the
strategy seam has a zero-feedback implementation; the executor's static mode
bypasses strategy allocation entirely (the tier-1 zero-overhead guarantee —
see tests/test_batching.py).

All strategies are thread-safe: `record()` runs on compute-pool worker
threads while `current_size()` is read from the morselizing stage thread.
"""

from __future__ import annotations

import threading
from typing import Iterator, Protocol, runtime_checkable


@runtime_checkable
class BatchingStrategy(Protocol):
    """One operator's morsel-size policy."""

    def current_size(self) -> int:
        """Rows the next morsel should carry."""
        ...

    def record(self, rows: int, seconds: float) -> None:
        """Feed back one processed morsel's size and wall time."""
        ...


def _pow2(n: int) -> int:
    """Largest power of two <= n (>= 1) — sizes move on a pow2 ladder so the
    device stages' padding buckets stay well-filled at every step."""
    return 1 << max(int(n).bit_length() - 1, 0)


class StaticBatching:
    """Fixed morsel size — today's behavior behind the strategy seam."""

    def __init__(self, rows: int):
        self._rows = max(int(rows), 1)

    def current_size(self) -> int:
        return self._rows

    def record(self, rows: int, seconds: float) -> None:  # noqa: ARG002
        return None


class DynamicBatching:
    """Throughput-feedback morsel sizing: hill-climb toward the knee.

    Samples aggregate per ladder step: a step's rows/sec is measured over
    SAMPLES_PER_STEP morsels (summed rows / summed seconds) before any
    decision, because a single morsel's wall time under full-pool
    concurrency varies with sibling-morsel contention far more than any
    honest deadband — deciding per morsel would random-walk the ladder on
    scheduling noise. Morsels whose size is outside [size/2, 2*size] of the
    current step (in-flight stragglers cut at an old size) don't attribute.

    An aggregated improvement keeps moving the size in the same direction
    (×2 / ÷2 on the pow2 ladder), a degradation reverses direction, and a
    change inside the deadband holds (converged). Below the knee, bigger
    morsels amortize fixed per-morsel costs so throughput rises with size;
    past it, cache pressure and lost overlap push it back down — so the
    climb settles within one ladder step of the knee (asserted by
    tests/test_batching.py::test_dynamic_batching_converges_to_knee).
    """

    #: relative throughput change below which the size holds
    DEADBAND = 0.05
    #: morsels measured per ladder step before a climb decision
    SAMPLES_PER_STEP = 3

    def __init__(self, initial: int, min_rows: int = 4096,
                 max_rows: int = 16 * 1024 * 1024):
        self._lock = threading.Lock()
        # the floor never exceeds the configured initial: a user asking for
        # 1Ki morsels (memory/latency bound) must not be silently quadrupled
        # to the default 4Ki floor before any feedback is even observed
        self._min = _pow2(max(min(min_rows, max(initial, 1)), 1))
        self._max = _pow2(max(max_rows, self._min))
        self._size = min(max(_pow2(initial), self._min), self._max)
        self._grow = True          # current climb direction
        self._prev_rate: float = 0.0
        self._acc_rows = 0
        self._acc_secs = 0.0
        self._acc_n = 0

    def current_size(self) -> int:
        with self._lock:
            return self._size

    def record(self, rows: int, seconds: float) -> None:
        if rows <= 0:
            return
        with self._lock:
            if not self._size // 2 <= rows <= self._size * 2:
                return  # straggler morsel cut at an old size: don't attribute
            self._acc_rows += rows
            self._acc_secs += seconds
            self._acc_n += 1
            if self._acc_n < self.SAMPLES_PER_STEP:
                return
            rate = self._acc_rows / max(self._acc_secs, 1e-9)
            self._acc_rows, self._acc_secs, self._acc_n = 0, 0.0, 0
            prev = self._prev_rate
            self._prev_rate = rate
            if prev <= 0.0:
                # first step establishes the baseline AND takes a probing
                # move — without it every later step would compare equal
                # sizes and the climb could never start
                if self._size >= self._max:
                    self._grow = False
            else:
                change = (rate - prev) / prev
                if abs(change) < self.DEADBAND:
                    return  # converged (for now) — hold the size
                if change < 0:
                    self._grow = not self._grow
            nxt = self._size * 2 if self._grow else self._size // 2
            nxt = min(max(nxt, self._min), self._max)
            if nxt != self._size:
                self._size = nxt
                from ..ops import counters

                counters.bump("morsel_resize")


class LatencyConstrainedBatching:
    """Cap morsel size so per-morsel processing stays under a latency target.

    Tracks an EMA of the observed processing rate and sizes the next morsel
    to `rate * target_seconds`, quantized to the pow2 ladder — a slow
    operator (UDF, cold IO) gets small responsive morsels, a fast one keeps
    large amortizing morsels, and downstream consumers (progress bars, LIMIT
    pulls, interactive sessions) see output at a bounded cadence.
    """

    #: EMA smoothing for the observed rows/sec
    ALPHA = 0.3

    def __init__(self, target_seconds: float, initial: int,
                 min_rows: int = 1024, max_rows: int = 16 * 1024 * 1024):
        self._lock = threading.Lock()
        self._target = max(float(target_seconds), 1e-4)
        # like DynamicBatching: the floor never exceeds the configured
        # initial, so a sub-1Ki morsel_size_rows is honored in latency mode
        self._min = _pow2(max(min(min_rows, max(initial, 1)), 1))
        self._max = _pow2(max(max_rows, self._min))
        self._size = min(max(_pow2(initial), self._min), self._max)
        self._rate: float = 0.0    # EMA rows/sec

    def current_size(self) -> int:
        with self._lock:
            return self._size

    def record(self, rows: int, seconds: float) -> None:
        if rows <= 0:
            return
        rate = rows / max(seconds, 1e-9)
        with self._lock:
            self._rate = rate if self._rate <= 0.0 else (
                self.ALPHA * rate + (1.0 - self.ALPHA) * self._rate)
            nxt = min(max(_pow2(int(self._rate * self._target) or 1),
                          self._min), self._max)
            if nxt != self._size:
                self._size = nxt
                from ..ops import counters

                counters.bump("morsel_resize")


def coalesce_target_rows(cfg, shards: int = 1, resident_rows: int = 0) -> int:
    """Flush threshold of the device dispatch coalescer: batch_fill_target of
    the power-of-two bucket at the configured morsel size; 0 = coalescing
    disabled. THE one definition — the executor's coalescer construction and
    the cost model's expected-horizon both read it, so the priced coalescing
    behavior never promises more than the behavior that actually runs. A
    dispatch whose rows are sharded over `shards` devices holds a bucket a
    shard: the threshold is reached when the last of them is
    batch_fill_target full.

    `resident_rows` (the rows of a resident table a JOIN reads as its fact):
    the length of a join dispatch over that table: of the ranges the join
    driver cuts of a table it reads directly (executor._feed_resident: the
    whole morsels that reach this threshold), and of the contiguous morsels
    a coalescer glues at no copy where the fact came through the pipeline
    (stage.DispatchCoalescer's resident target):
    resident_dispatch_segments buckets a shard, since the host's path a join
    dispatch (look-ups, two launches) costs the same whatever the rows behind
    it, and the join's programs walk a long dispatch a bucket at a time. The
    horizon a join's tiers are priced with is resident_dispatch_rows over a
    morsel, the same arithmetic (executor._run_device_join): the rows a
    dispatch is priced at are the rows a dispatch delivers."""
    if cfg.batch_fill_target <= 0:
        return 0
    from ..ops.stage import pad_bucket

    bucket = pad_bucket(cfg.morsel_size_rows)
    buckets = max(shards, 1)
    if resident_rows > 0:
        buckets *= resident_dispatch_segments(-(-resident_rows // (buckets * bucket)))
    return (buckets - 1) * bucket + int(cfg.batch_fill_target * bucket)


def resident_dispatch_rows(cfg, shards: int = 1, resident_rows: int = 0) -> int:
    """Rows of one join dispatch over a resident fact that is read as ranges
    of its table: the whole morsels that reach
    coalesce_target_rows(resident_rows=...), one morsel where the run takes no
    long dispatch (`resident_rows` 0) or coalescing is off. THE one
    definition: executor._feed_resident cuts the ranges by it and
    executor._run_device_join prices the join's tiers at it."""
    morsel = cfg.morsel_size_rows
    target = coalesce_target_rows(cfg, shards, resident_rows=resident_rows) \
        if resident_rows else 0
    return max(-(-target // morsel), 1) * morsel


def resident_dispatch_segments(fact_buckets: int) -> int:
    """Buckets a device that one join dispatch over a resident fact covers,
    the fact `fact_buckets` such buckets a device long:
    grouped_stage.DISPATCH_SEGMENTS, or for a shorter fact the largest power
    of two under its length. A dispatch is never the whole fact: the host's
    look-ups for the next dispatch run while the device works the last one,
    which a run of one dispatch has nothing to overlap with, and a fact of
    several morsels stays a run of several batches (what the run-wide forms
    are for, and what the join suites' warm-up checks ask of it). A power of
    two, so that a dispatch's bucket holds no segment of padding but the
    tail's."""
    from ..ops.grouped_stage import DISPATCH_SEGMENTS

    segments = 1
    while segments * 2 <= DISPATCH_SEGMENTS and segments * 2 < fact_buckets:
        segments *= 2
    return segments


def make_strategy(cfg) -> BatchingStrategy:
    """Strategy instance for one operator from the execution config. Called
    once per operator stream (each operator climbs independently — the knee
    of a string-heavy project differs from a float filter's)."""
    if cfg.batching_mode == "dynamic":
        return DynamicBatching(cfg.morsel_size_rows)
    if cfg.batching_mode == "latency":
        return LatencyConstrainedBatching(cfg.batch_latency_ms / 1e3,
                                          cfg.morsel_size_rows)
    return StaticBatching(cfg.morsel_size_rows)


def adaptive_morsel_stream(stream: Iterator, strategy: BatchingStrategy) -> Iterator:
    """morsel_stream that re-consults the strategy per MORSEL, both ways:

    - Oversized batches are sliced lazily as the consumer (pmap_stream)
      pulls, so a resize recorded by a pool worker applies to the remainder
      of the very partition being split — a single in-memory table arrives
      as ONE huge partition, so per-partition-only consultation would make
      feedback a no-op exactly where it matters.
    - Undersized batches accumulate (zero-copy — batches are grouped into
      one multi-batch MicroPartition, never concatenated) until they reach
      the current size, so a "grow" decision is real even when the source
      emits fixed small batches (parquet's 128Ki reader batches, tiny
      concat inputs) — without a merge path, growing past the source batch
      size would be a no-op that still reported morsel_resize.

    Row order is preserved: merged batches stay consecutive and flush before
    any later slice is emitted."""
    from ..core.micropartition import MicroPartition

    pending: list = []  # consecutive small batches awaiting one fan-out task
    pending_rows = 0
    schema = None

    def flush():
        nonlocal pending, pending_rows
        if pending:
            out = MicroPartition(schema, pending)
            pending, pending_rows = [], 0
            yield out

    for part in stream:
        schema = part.schema
        if part.num_rows == 0:
            yield from flush()
            yield part  # empty partitions pass through like morsel_stream
            continue
        for b in part.batches:
            if b.num_rows == 0:
                continue
            size = max(strategy.current_size(), 1)
            if b.num_rows > size * 2:
                yield from flush()
                s = 0
                while s < b.num_rows:
                    size = max(strategy.current_size(), 1)
                    yield MicroPartition(part.schema,
                                         [b.slice(s, min(s + size, b.num_rows))])
                    s += size
                continue
            # flush BEFORE a merge would overshoot 2x the current size:
            # emitted morsels stay within the strategy's attribution window
            # (DynamicBatching ignores out-of-window stragglers, so an
            # oversized merged morsel would never feed the climb)
            if pending_rows and pending_rows + b.num_rows > size * 2:
                yield from flush()
            pending.append(b)
            pending_rows += b.num_rows
            if pending_rows >= size:
                yield from flush()
    yield from flush()
