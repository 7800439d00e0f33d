"""The comparison that decides `correct`: a template's answer against the
plain reference's.

Both are {column: list of Python values} with rows in the query's order.
Three numbers come out, each with a limit of its own:

- `shape`: 0 where the columns (names and order) and the row count agree,
  else 1. Limit 0.
- `exact_mismatches`: values that are not floats (integers, counts, strings,
  dates, nulls) and differ. Limit 0.
- `float_rel_gap`: the widest |got - ref| / max(1, |ref|) over the floats.
  Limit: the configuration's `float_rel_limit` for that template, each set
  from readings on the chip (PERF.md section 2).
"""

from __future__ import annotations

import math
from typing import Dict


def compare(ref: Dict[str, list], got: Dict[str, list]) -> Dict[str, float]:
    if list(ref) != list(got) or any(len(ref[c]) != len(got[c]) for c in ref):
        return {"shape": 1, "exact_mismatches": 0, "float_rel_gap": 0.0}
    exact, gap = 0, 0.0
    for c in ref:
        for a, b in zip(ref[c], got[c]):
            if isinstance(a, float) and isinstance(b, float):
                g = abs(a - b) / max(1.0, abs(a))
                # a NaN on one side only is a mismatch, not a gap of nan
                if math.isnan(g):
                    exact += not (math.isnan(a) and math.isnan(b))
                else:
                    gap = max(gap, g)
            elif a != b:
                exact += 1
    return {"shape": 0, "exact_mismatches": exact, "float_rel_gap": gap}


def limits(config: dict, template: str) -> Dict[str, float]:
    """The limits of one template's numbers; a template without a stated
    float limit is an error, not a default."""
    return {"shape": 0, "exact_mismatches": 0,
            "float_rel_gap": float(config["float_rel_limit"][template])}


def within(numbers: Dict[str, float], lim: Dict[str, float]) -> bool:
    return all(numbers[k] <= lim[k] for k in lim)
