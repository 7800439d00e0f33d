"""The benchmark's arithmetic: from executions to end-to-end metrics.

An execution is a dict {"template", "start", "end", "failed"} with `start` and
`end` in seconds from the start of the window (host clock). Every statistic is
taken over all the work and all the time of the window: nothing is trimmed.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Tuple[float, int]:
    """(the q-th percentile by linear interpolation, the sample count).

    The count goes with the value wherever it is printed: a p95 of twenty
    samples is a maximum, and the reader has to be able to see that."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo), len(xs)


def geomean(values: Iterable[float]) -> float:
    """Geometric mean of positive values."""
    vals = list(values)
    if not vals or any(v <= 0 for v in vals):
        raise ValueError(f"geomean needs positive values, got {vals}")
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def durations_ms(executions: List[dict]) -> Dict[str, List[float]]:
    """{template: [milliseconds of each execution that did not fail]}."""
    out: Dict[str, List[float]] = {}
    for e in executions:
        if not e["failed"]:
            out.setdefault(e["template"], []).append((e["end"] - e["start"]) * 1e3)
    return out


def query_ms_geomean(executions: List[dict], templates: Sequence[str]) -> float:
    """Geometric mean, over the cell's templates, of each template's median
    time in the window (the form of TPC-H's power metric). A template with no
    completed execution is an error, not a smaller mean."""
    by = durations_ms(executions)
    missing = [t for t in templates if t not in by]
    if missing:
        raise ValueError(f"no completed execution of {missing}")
    return geomean(statistics.median(by[t]) for t in templates)


def query_ms_p95(executions: List[dict]) -> Tuple[float, int]:
    """95th percentile over every completed execution in the window, and how
    many there were."""
    return percentile([ms for v in durations_ms(executions).values() for ms in v], 95)


def scan_rows_per_s(executions: List[dict], rows_read: Dict[str, int]) -> float:
    """Rows of the tables each completed execution read, summed, over the
    seconds from the start of the window to the last completion."""
    done = [e for e in executions if not e["failed"]]
    if not done:
        raise ValueError("no completed execution")
    return (sum(rows_read[e["template"]] for e in done)
            / max(e["end"] for e in done))
