"""What the `mesh.*` readers share: the reduced trace (`xtrace.read_xplane`)
taken one device plane at a time. `xtrace`'s own reductions average over the
planes or read the first only, which is right for a one-chip cell."""

from __future__ import annotations

from typing import Dict, List

import xtrace as tr

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def busy_unions(trace: dict) -> Dict[str, List[tr.Interval]]:
    """{device plane: the disjoint, sorted intervals in which an operation ran
    on it}."""
    return {plane: tr.union([(s, s + d) for _n, s, d in ops])
            for plane, ops in tr.device_ops(trace).items()}


def busy_by_plane(trace: dict, window: tr.Interval) -> Dict[str, float]:
    """{device plane: seconds inside `window` in which an operation ran on it}."""
    return {plane: tr.length(tr.clip(busy, *window))
            for plane, busy in busy_unions(trace).items()}


def collective_seconds(trace: dict, plane: str, window: tr.Interval) -> float:
    """Seconds inside `window` in which a collective operation ran on `plane`."""
    ops = tr.device_ops(trace).get(plane, [])
    return tr.length(tr.union(tr.clip(
        [(s, s + d) for name, s, d in ops if any(c in name for c in COLLECTIVES)],
        *window)))
