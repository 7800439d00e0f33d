"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to what the metrics read.

`read_xplane` turns the file into plain events; everything after it works on
those, so the arithmetic is tested against a small recorded trace
(`tests/benchmark_harness/data/`). Times are seconds on the trace's own clock
(zero at the start of the profile).

What counts as "an operation ran on the device": an event on the `XLA Ops`
line of a `/device:TPU:<n>` plane. Where a plane has no such line, its
`XLA Modules` line (whole programs) stands in. Step and trace-me lines are
annotations, not work.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SYNC_EVENT = "bench.sync"
DEVICE_PLANE = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")
# host spans a gap can be charged to, as `profile_span` names them
GAP_SPANS = ("device.h2d", "device.dispatch", "device.d2h", "scan.stream")
OTHER = "host.other"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(path: str) -> dict:
    """{"device": {plane: {line: [(name, start_s, dur_s)]}}, "sync_s": float|None}.

    `sync_s` is when the `bench.sync` annotation (written by the harness as it
    reads the host's clock) began, on the trace's clock: it ties the program's
    spans, which carry `time.time()`, to the device events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, Dict[str, list]] = {}
    sync_s: Optional[float] = None
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            if is_device:
                device.setdefault(plane.name, {})[line.name] = events
            elif sync_s is None:
                sync_s = next((s for n, s, _d in events if n == SYNC_EVENT), None)
    return {"device": device, "sync_s": sync_s}


def device_ops(trace: dict) -> Dict[str, List[Tuple[str, float, float]]]:
    """{device plane: [(op name, start_s, dur_s)]} from the line that holds work."""
    out = {}
    for plane, lines in trace["device"].items():
        for name in OP_LINES:
            if lines.get(name):
                out[plane] = lines[name]
                break
        else:
            out[plane] = []
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_seconds(trace: dict, window: Interval) -> float:
    """Seconds inside `window` in which an operation ran, averaged over the
    device planes of the trace."""
    per_plane = [
        length(union(clip([(s, s + d) for _n, s, d in ops], *window)))
        for ops in device_ops(trace).values()]
    if not per_plane:
        raise ValueError("the trace has no device plane")
    return sum(per_plane) / len(per_plane)


def idle_share(trace: dict, window: Interval) -> float:
    return 1.0 - busy_seconds(trace, window) / (window[1] - window[0])


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """What `window` has left once the disjoint, sorted `busy` is taken out."""
    out, at = [], window[0]
    for a, b in clip(busy, *window):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def span_owners(spans: Sequence[Tuple[str, float, float]]) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted (start, end, name) stretches, each owned by the span of
    `GAP_SPANS` open over it; where several are, by the one that began last
    (the innermost)."""
    known = sorted((s for s in spans if s[0] in GAP_SPANS and s[2] > s[1]),
                   key=lambda s: s[1])
    cuts = sorted({t for _n, a, b in known for t in (a, b)})
    out, open_now, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(known) and known[k][1] <= a:
            open_now.append(known[k])
            k += 1
        open_now = [s for s in open_now if s[2] > a]
        if open_now:
            out.append((a, b, open_now[-1][0]))
    return out


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of idle time by the host span open in them.

    `spans` are (name, start_s, end_s) on the trace's clock. Each stretch of a
    gap goes to the span that owns it (`span_owners`); where none is open, to
    `host.other`."""
    owners = span_owners(spans)
    starts = [o[0] for o in owners]
    out: Dict[str, float] = {}
    for lo, hi in idle:
        owned = 0.0
        k = max(bisect.bisect_right(starts, lo) - 1, 0)
        while k < len(owners) and owners[k][0] < hi:
            a, b, name = owners[k]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                owned += part
            k += 1
        if hi - lo > owned:
            out[OTHER] = out.get(OTHER, 0.0) + (hi - lo - owned)
    return out


def top_ops(trace: dict, window: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """The device operations that took most time in `window`, summed by name
    over the device planes."""
    total: Dict[str, float] = {}
    for ops in device_ops(trace).values():
        for name, s, d in ops:
            lo, hi = max(s, window[0]), min(s + d, window[1])
            if hi > lo:
                total[name] = total.get(name, 0.0) + (hi - lo)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def busy_union(trace: dict) -> List[Interval]:
    """The disjoint, sorted intervals in which an operation ran on the first
    device plane (the cells that read it execution by execution are one-chip
    cells)."""
    ops = next(iter(device_ops(trace).values()), [])
    return union([(s, s + d) for _n, s, d in ops])


def busy_in(busy: Sequence[Interval], window: Interval) -> float:
    """Seconds of the disjoint, sorted `busy` that lie inside `window`."""
    k = max(bisect.bisect_right(busy, (window[0], float("inf"))) - 1, 0)
    total = 0.0
    while k < len(busy) and busy[k][0] < window[1]:
        total += max(0.0, min(busy[k][1], window[1]) - max(busy[k][0], window[0]))
        k += 1
    return total
