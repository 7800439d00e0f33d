"""Scan planning abstractions.

Reference parity: src/daft-scan/src/scan_operator.rs:12 (ScanOperator trait),
src/daft-scan/src/lib.rs:346 (ScanTask), src/daft-scan/src/pushdowns.rs (Pushdowns).

A ScanOperator describes an external data source; the optimizer attaches Pushdowns
(column pruning, predicate, limit) and physical translation materializes ScanTasks —
each an independently-executable unit reading some files/byte-ranges and yielding
MicroPartitions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, List, Optional

from ..expressions import Expression
from ..schema import Schema


@dataclasses.dataclass
class Pushdowns:
    """Pushed-down hints a scan may exploit (all optional; scans may ignore filters/
    limits as long as they report whether they applied them exactly)."""

    columns: Optional[List[str]] = None
    filters: Optional[Expression] = None
    limit: Optional[int] = None

    def __repr__(self) -> str:
        parts = []
        if self.columns is not None:
            parts.append(f"columns={self.columns}")
        if self.filters is not None:
            parts.append(f"filters={self.filters}")
        if self.limit is not None:
            parts.append(f"limit={self.limit}")
        return "Pushdowns(" + ", ".join(parts) + ")"

    def is_empty(self) -> bool:
        return self.columns is None and self.filters is None and self.limit is None


@dataclasses.dataclass
class ScanTask:
    """One unit of scan work: a closure producing MicroPartitions plus metadata for
    scheduling/stats (reference ScanTask carries sources+pushdowns+size estimates)."""

    read: Callable[[], Iterator[Any]]  # yields MicroPartition
    schema: Schema
    size_bytes: Optional[int] = None
    num_rows: Optional[int] = None
    # True when the reader already applied the pushdown exactly (so the executor can
    # skip re-filtering / re-limiting).
    filters_applied: bool = False
    limit_applied: bool = False
    source_label: str = ""


# Runs of files smaller than this merge however wide the pool is: under it a
# thread more costs more than it buys. A task that is its own future costs
# 1.3-2.2 ms over the same files read inside a merged task (the pool hand-off
# and its wake-ups; 64 files of 100 rows), and a thread decodes 180-560 MB of
# file bytes a second (all 16 columns of lineitem, q1's 7), so halving a task
# of s bytes saves s / 2r and pays that: even at 0.5-2.5 MB. PERF.md section 6
# (PR 33) has the readings.
MERGE_FLOOR_BYTES = 4 * 1024 * 1024


def merge_small_tasks(tasks: List[ScanTask], target_bytes: int, window: int) -> List[ScanTask]:
    """Coalesce runs of small adjacent ScanTasks so a many-tiny-files source
    doesn't pay per-task scheduling/IO overhead, without narrowing the scan
    below the `window` of tasks it is run at (the merge half of scan split
    planning; io/parquet.py owns the split half). Runs merge toward the
    tasks' known bytes over `window`, at least MERGE_FLOOR_BYTES and at most
    `target_bytes` (scan_split_bytes): tasks only get smaller than under
    `target_bytes` alone.

    Only tasks with a KNOWN size merge, and only while every merged member
    agrees on filters_applied (a merged task must be re-filterable as one
    unit); limit-absorbing tasks never merge (the limit bookkeeping is
    per-task). Order is preserved — a merged task reads its members
    sequentially, so row order matches the unmerged plan exactly."""
    if target_bytes <= 0 or len(tasks) <= 1:
        return tasks
    known = sum(t.size_bytes for t in tasks if t.size_bytes is not None)
    target_bytes = min(target_bytes, max(known // window, MERGE_FLOOR_BYTES))

    out: List[ScanTask] = []
    group: List[ScanTask] = []
    group_bytes = 0

    def flush() -> None:
        nonlocal group, group_bytes
        if not group:
            return
        if len(group) == 1:
            out.append(group[0])
        else:
            members = list(group)

            def read_all(_members=members):
                for t in _members:
                    yield from t.read()

            rows = [t.num_rows for t in members]
            out.append(ScanTask(
                read=read_all,
                schema=members[0].schema,
                size_bytes=sum(t.size_bytes for t in members),
                num_rows=sum(rows) if all(r is not None for r in rows) else None,
                filters_applied=members[0].filters_applied,
                limit_applied=False,
                source_label=f"{members[0].source_label} (+{len(members) - 1} merged)",
            ))
            from ..observability.metrics import registry

            registry().inc("scan_tasks_merged", len(members) - 1)
        group, group_bytes = [], 0

    for t in tasks:
        mergeable = (t.size_bytes is not None and not t.limit_applied
                     and t.size_bytes < target_bytes)
        if not mergeable:
            flush()
            out.append(t)
            continue
        if group and (group_bytes + t.size_bytes > target_bytes
                      or group[0].filters_applied != t.filters_applied
                      or group[0].schema is not t.schema):
            flush()
        group.append(t)
        group_bytes += t.size_bytes
    flush()
    return out


class ScanOperator:
    """Base class for external sources (parquet/csv/json readers, Python DataSources)."""

    def name(self) -> str:
        return type(self).__name__

    def schema(self) -> Schema:
        raise NotImplementedError

    def can_absorb_select(self) -> bool:
        return False

    def can_absorb_filter(self) -> bool:
        return False

    def can_absorb_limit(self) -> bool:
        return False

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        raise NotImplementedError

    def approx_num_rows(self, pushdowns: Pushdowns) -> Optional[float]:
        return None
