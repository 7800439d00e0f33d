"""HostMemoryManager: a process-wide byte ledger for out-of-core execution.

Mirrors the HBM ResidencyManager's design (device/residency.py) on the host
side: ONE authority that knows how many bytes the engine's memory-hungry
sites currently hold, with a budget resolved from config
(``DAFT_TPU_MEMORY_LIMIT``), per-operator admission handles, pressure
callbacks, and ``host_bytes_tracked`` / ``host_bytes_high_water`` gauges in
the process metrics registry so per-query deltas land in QueryEnd.metrics,
EXPLAIN ANALYZE and the Prometheus exposition.

Budget semantics (config.memory_limit_bytes):

- positive: that many bytes, shared by EVERY admitting site in the process —
  concurrent serving queries draw down one ledger instead of each believing
  it owns the whole budget;
- 0 (default): unbounded AND untracked — the zero-overhead contract: an
  unbudgeted query allocates no manager state, writes no gauges, and its
  operators run the plain in-memory paths;
- negative: auto — ``DAFT_TPU_MEMORY_FRACTION`` (default 0.6) of system RAM,
  probed once per process, the out-of-core mirror of the HBM auto budget.

Admission model: a blocking operator (agg/sort/join build/window) takes an
``operator_budget()`` handle and admits each buffered batch's bytes; once the
LEDGER crosses the budget the handle answers False and the operator switches
to its spilling strategy (daft_tpu/memory/spill.py), releasing its tracked
bytes as the buffers flush to disk. Streaming scans don't admit (they hold
one bounded window) but consult ``under_pressure()`` /
``wait_for_headroom()`` so a fast producer stalls — boundedly, never as a
correctness gate — while a downstream operator is at the wall.

Pressure: tracked >= ``DAFT_TPU_MEMORY_PRESSURE`` (default 0.8) of the
budget. ``on_pressure`` callbacks fire on each upward crossing (coarse
events only — one per crossing, never per batch admitted below the line).
All waits are bounded: the ledger drains when operators spill, and a
stalled producer resumes after ``max_wait`` even if it doesn't, so a
mis-sized budget degrades to throughput loss, not deadlock.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Callable, List, Optional

from ..observability.metrics import registry

# bounded pacing wait: long enough that a spilling operator usually drains
# the ledger first, short enough that a stuck ledger costs throughput only
_DEFAULT_MAX_WAIT_S = 0.25


class HostMemoryManager:
    """The process-wide host byte ledger (one per driver / worker process)."""

    def __init__(self) -> None:
        self._cond = threading.Condition(threading.Lock())
        self._tracked = 0
        self._high_water = 0
        self._auto_limit: Optional[int] = None
        self._pressure_cbs: List[Callable[[int, int], None]] = []
        self._in_pressure = False
        self._scopes: List["QueryMemoryScope"] = []

    # ---- budget resolution ---------------------------------------------------------
    def limit_bytes(self) -> int:
        """Effective host budget in bytes (0 = unbounded/untracked)."""
        from ..config import execution_config

        b = execution_config().memory_limit_bytes
        if b > 0:
            return b
        if b == 0:
            return 0
        if self._auto_limit is None:
            self._auto_limit = self._probe_auto_limit()
        return self._auto_limit

    def _probe_auto_limit(self) -> int:
        from ..config import execution_config

        ram = system_ram_bytes()
        if ram <= 0:
            return 0  # unprobeable platform: degrade to unbounded, loudly-documented
        return int(ram * execution_config().memory_fraction)

    # ---- ledger --------------------------------------------------------------------
    def track(self, nbytes: int) -> None:
        """Admit `nbytes` into the ledger (coarse events: one call per
        buffered batch / materialized scan task, never per row)."""
        if nbytes <= 0:
            return
        fire = None
        crossed = False
        with self._cond:
            self._tracked += nbytes
            if self._tracked > self._high_water:
                self._high_water = self._tracked
            for s in self._scopes:
                if self._tracked > s._peak:
                    s._peak = self._tracked
            registry().set_gauge("host_bytes_tracked", float(self._tracked))
            registry().set_gauge("host_bytes_high_water", float(self._high_water))
            # crossing detection is independent of callback registration:
            # the flight recorder must see pressure crossings even with no
            # on_pressure subscribers attached
            if not self._in_pressure and self._under_pressure_locked():
                self._in_pressure = True
                crossed = True
                if self._pressure_cbs:
                    fire = list(self._pressure_cbs)
            elif self._in_pressure and not self._under_pressure_locked():
                self._in_pressure = False
        if crossed:
            from ..observability import flight as _flight

            frec = _flight.recorder()
            if frec is not None:
                frec.note_pressure(self._tracked, self.limit_bytes())
        if fire:
            tracked, limit = self._tracked, self.limit_bytes()
            for cb in fire:
                try:
                    cb(tracked, limit)
                except Exception:
                    # a broken pressure callback must not fail the admit
                    registry().inc("subscriber_errors")

    def release(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._cond:
            self._tracked = max(self._tracked - nbytes, 0)
            registry().set_gauge("host_bytes_tracked", float(self._tracked))
            if self._in_pressure and not self._under_pressure_locked():
                self._in_pressure = False
            self._cond.notify_all()

    def tracked_bytes(self) -> int:
        with self._cond:
            return self._tracked

    def high_water_bytes(self) -> int:
        with self._cond:
            return self._high_water

    # ---- pressure ------------------------------------------------------------------
    def _pressure_threshold(self) -> int:
        from ..config import execution_config

        limit = self.limit_bytes()
        if limit <= 0:
            return 0
        return int(limit * execution_config().memory_pressure)

    def _under_pressure_locked(self) -> bool:
        t = self._pressure_threshold()
        return t > 0 and self._tracked >= t

    def under_pressure(self) -> bool:
        """True when tracked bytes sit at/over the pressure fraction of the
        budget — the signal streaming producers pace themselves against."""
        t = self._pressure_threshold()
        if t <= 0:
            return False
        with self._cond:
            return self._tracked >= t

    def wait_for_headroom(self, max_wait_s: float = _DEFAULT_MAX_WAIT_S) -> float:
        """Block while the ledger is under pressure, up to `max_wait_s`.

        Returns seconds actually stalled. Bounded by construction: this is
        producer PACING (a scan yielding to a spilling consumer), not an
        admission gate, so it can never deadlock a query whose budget is
        smaller than one operator's working set. Stalls are attributed via
        scan_backpressure_stalls / scan_stall_ms."""
        if not self.under_pressure():
            return 0.0
        import time

        t0 = time.perf_counter()
        deadline = t0 + max_wait_s
        with self._cond:
            while self._under_pressure_locked():
                now = time.perf_counter()
                if now >= deadline:
                    break
                self._cond.wait(min(0.02, deadline - now))
        stalled = time.perf_counter() - t0
        registry().inc("scan_backpressure_stalls")
        registry().inc("scan_stall_ms", max(int(stalled * 1000), 1))
        return stalled

    def on_pressure(self, cb: Callable[[int, int], None]) -> Callable[[], None]:
        """Register `cb(tracked_bytes, limit_bytes)`, fired once per upward
        crossing of the pressure threshold. Returns an unsubscribe callable."""
        with self._cond:
            self._pressure_cbs.append(cb)

        def _unsub() -> None:
            with self._cond:
                if cb in self._pressure_cbs:
                    self._pressure_cbs.remove(cb)

        return _unsub

    # ---- admission handles ---------------------------------------------------------
    def operator_budget(self) -> "LedgerBudget":
        """Admission handle for one memory-hungry operator instance. The
        returned handle is inert (no ledger/registry traffic) when no budget
        is in force — the zero-overhead path."""
        return LedgerBudget(self, self.limit_bytes())

    @contextlib.contextmanager
    def query_scope(self):
        """Per-query admission scope: bracket one query's execution to
        observe its ledger footprint — the peak tracked bytes while the
        scope was open (process-wide, so concurrent queries observe the
        shared peak, which is what admission sizing needs). Release safety
        does NOT depend on scopes: every operator budget releases in its own
        finally, unwound on failure/cancellation by the pipeline's
        generator-close propagation. Yields the handle (`peak_bytes()`)."""
        scope = QueryMemoryScope()
        with self._cond:
            self._scopes.append(scope)
            scope._peak = self._tracked
        try:
            yield scope
        finally:
            with self._cond:
                if scope in self._scopes:
                    self._scopes.remove(scope)

    def clear(self) -> None:
        """Drop ledger state (test hook). Does not reset registry counters —
        memory.reset_counters() owns those."""
        with self._cond:
            self._tracked = 0
            self._high_water = 0
            self._auto_limit = None
            self._in_pressure = False
            self._pressure_cbs.clear()
            self._scopes.clear()
            registry().set_gauge("host_bytes_tracked", 0.0)
            registry().set_gauge("host_bytes_high_water", 0.0)


class QueryMemoryScope:
    """Handle yielded by HostMemoryManager.query_scope(): the ledger peak
    observed while the scope was open (process-wide — concurrent queries see
    a shared peak, which is exactly what admission sizing needs)."""

    __slots__ = ("_peak",)

    def __init__(self) -> None:
        self._peak = 0

    def peak_bytes(self) -> int:
        return self._peak


class LedgerBudget:
    """Byte-accounting handle for one blocking-operator instance, drawn
    against the shared process ledger.

    ``admit`` answers True while the LEDGER stays within the budget — so two
    concurrent queries each buffering 60% of the limit both flip to their
    spill strategies instead of jointly holding 120%. With no budget in
    force (limit <= 0) the handle is pure arithmetic: no manager calls, no
    registry writes (the zero-overhead contract the tier-1 guard pins).

    The operator owns release: ``release_all()`` when buffered bytes flush
    to spill files, and unconditionally (via ``close()``/finally) when the
    operator finishes, so an abandoned or failed query cannot leak ledger
    bytes and throttle the rest of the process."""

    __slots__ = ("_mgr", "limit", "used", "_over_counted")

    def __init__(self, mgr: HostMemoryManager, limit: int):
        self._mgr = mgr
        self.limit = limit
        self.used = 0
        self._over_counted = False

    def admit(self, nbytes: int) -> bool:
        """Account nbytes; True while within budget."""
        self.used += nbytes
        if self.limit <= 0:
            return True
        self._mgr.track(nbytes)
        ok = self._mgr.tracked_bytes() <= self.limit
        if not ok and not self._over_counted:
            self._over_counted = True
            registry().inc("host_over_budget_events")
        return ok

    def release(self, nbytes: int) -> None:
        """Return `nbytes` (clamped to current holdings) to the ledger — the
        incremental form spill loops use as each buffered batch lands on
        disk, so the ledger never claims freedom the process doesn't have."""
        n = min(nbytes, self.used)
        if n <= 0:
            return
        self.used -= n
        if self.limit > 0:
            self._mgr.release(n)

    def release_all(self) -> None:
        if self.limit > 0 and self.used:
            self._mgr.release(self.used)
        self.used = 0

    def close(self) -> None:
        self.release_all()

    def __enter__(self) -> "LedgerBudget":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def system_ram_bytes() -> int:
    """Total physical RAM, or 0 when the platform doesn't expose it."""
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page = os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return 0
    if pages <= 0 or page <= 0:
        return 0
    return int(pages) * int(page)


_MANAGER = HostMemoryManager()


def manager() -> HostMemoryManager:
    """The process-wide host memory manager (one per driver / worker)."""
    return _MANAGER


def operator_budget() -> LedgerBudget:
    """Admission handle against the process ledger for one blocking operator."""
    return _MANAGER.operator_budget()
