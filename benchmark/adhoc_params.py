"""The substitution parameters of the `tpch_adhoc` suite: the specification's
domains, and one run's draws from them.

TPC-H rev 3 gives every query substitution parameters, which qgen draws afresh
for every stream of the throughput test. The two queries of the suite:

- Q1 (2.4.1.3): DELTA, a number of days in [60, 120]; the query keeps
  `l_shipdate <= date '1998-12-01' - DELTA days`.
- Q6 (2.4.6.3): DATE, the first of January of a year in [1993, 1997]; DISCOUNT
  in [0.02, 0.09]; QUANTITY, 24 or 25. The query keeps a year from DATE,
  `l_discount` between DISCOUNT - 0.01 and DISCOUNT + 0.01 and
  `l_quantity < QUANTITY`.

A run draws `DRAWS` distinct DELTAs and `DRAWS` distinct (DATE, DISCOUNT,
QUANTITY) tuples, uniformly and without replacement, from Python's generator
seeded by the run's `--seed` (not from qgen's: the configuration's `assumed`
says so). `datagen/tpch_adhoc.py` makes the draws when the harness asks it for
the tables; `queries/tpch_adhoc.py` and `reference/tpch_adhoc.py` read them
when a template runs. The three files import this module by name, so they
see one instance of it. It imports nothing of `daft_tpu`.

The discount bounds are decimal in the specification and are written rounded
to two places: `0.06 + 0.01` is 0.06999999999999999 in float64 and 0.07 in
float32, so unrounded a float64 reference drops the `l_discount = 0.07` rows
that a float32 plane keeps, for a reason that is not the program's.
"""

from __future__ import annotations

import datetime
import itertools
import random
from typing import NamedTuple, Optional, Tuple

DRAWS = 12
Q1_DELTAS = tuple(range(60, 121))
Q6_YEARS = tuple(range(1993, 1998))
Q6_DISCOUNTS = tuple(round(k / 100, 2) for k in range(2, 10))
Q6_QUANTITIES = (24, 25)
Q1_BASE = datetime.date(1998, 12, 1)


class Q1(NamedTuple):
    delta: int

    @property
    def cutoff(self) -> datetime.date:
        """`l_shipdate <= cutoff`."""
        return Q1_BASE - datetime.timedelta(days=self.delta)


class Q6(NamedTuple):
    year: int
    discount: float
    quantity: int

    @property
    def start(self) -> datetime.date:
        return datetime.date(self.year, 1, 1)

    @property
    def end(self) -> datetime.date:
        """`start <= l_shipdate < end`."""
        return datetime.date(self.year + 1, 1, 1)

    @property
    def low(self) -> float:
        return round(self.discount - 0.01, 2)

    @property
    def high(self) -> float:
        """`low <= l_discount <= high`."""
        return round(self.discount + 0.01, 2)


def draws(seed: int) -> Tuple[Tuple[Q1, ...], Tuple[Q6, ...]]:
    """The run's parameters: a function of the seed alone."""
    rng = random.Random(f"tpch_adhoc.{int(seed)}")
    q1 = tuple(Q1(d) for d in rng.sample(Q1_DELTAS, DRAWS))
    q6 = tuple(Q6(*t) for t in rng.sample(
        list(itertools.product(Q6_YEARS, Q6_DISCOUNTS, Q6_QUANTITIES)), DRAWS))
    return q1, q6


_current: Optional[Tuple[Tuple[Q1, ...], Tuple[Q6, ...]]] = None


def set_seed(seed: int) -> None:
    """Make this run's draws (`datagen/tpch_adhoc.py` does, from the seed the
    harness gives it)."""
    global _current
    _current = draws(seed)


def template_names() -> Tuple[str, ...]:
    return tuple(f"{q}.p{i:02d}" for q in ("q1", "q6") for i in range(DRAWS))


def of(template: str):
    """The parameters of one template (`q1.p03` -> the run's fourth Q1)."""
    if _current is None:
        raise RuntimeError(
            "adhoc_params: no draws yet; datagen/tpch_adhoc.generate (or set_seed) "
            "makes them from the run's seed")
    query, _, index = template.partition(".p")
    return _current[("q1", "q6").index(query)][int(index)]
