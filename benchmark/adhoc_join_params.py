"""The substitution parameters of the `tpch_adhoc_joins` suite: the
specification's domains, and one run's draws from them.

TPC-H rev 3 gives every query substitution parameters, which qgen draws afresh
for every stream of the throughput test. The three star joins of the suite:

- Q3 (2.4.3.3): SEGMENT, one of the five market segments of 4.2.2.13; DATE, a
  day in [1995-03-01, 1995-03-31]. The query keeps customers of SEGMENT,
  `o_orderdate < DATE` and `l_shipdate > DATE`.
- Q5 (2.4.5.3): REGION, one of the five `r_name` values; DATE, the first of
  January of a year in [1993, 1997]. The query keeps a year of orders from
  DATE.
- Q10 (2.4.10.3): DATE, the first day of a month from February 1993 to January
  1995 (24 values). The query keeps three months of orders from DATE.

A run draws `DRAWS` distinct (SEGMENT, DATE) pairs, `DRAWS` distinct (REGION,
DATE) pairs and `DRAWS` distinct months, uniformly and without replacement,
from Python's generator seeded by the run's `--seed` (not from qgen's: the
configuration's `assumed` says so): four, the power test's stream and the
three throughput streams the specification asks for at SF10 (5.3.4).
`datagen/tpch_adhoc_joins.py` makes the draws when the harness asks it for the
tables; `queries/tpch_adhoc_joins.py` and `reference/tpch_adhoc_joins.py` read
them when a template runs. The three files import this module by name, so
they see one instance of it. It imports nothing of `daft_tpu`.
"""

from __future__ import annotations

import datetime
import itertools
import random
from typing import NamedTuple, Optional, Tuple

DRAWS = 4
QUERIES = ("q3", "q5", "q10")
Q3_SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
Q3_DAYS = tuple(range(1, 32))                   # of March 1995
Q5_REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
Q5_YEARS = tuple(range(1993, 1998))
# (year, month) of the first day: February 1993 .. January 1995
Q10_MONTHS = tuple((1993 + (m - 1) // 12, (m - 1) % 12 + 1) for m in range(2, 26))


class Q3(NamedTuple):
    segment: str
    day: int

    @property
    def date(self) -> datetime.date:
        """`o_orderdate < date` and `l_shipdate > date`."""
        return datetime.date(1995, 3, self.day)


class Q5(NamedTuple):
    region: str
    year: int

    @property
    def start(self) -> datetime.date:
        return datetime.date(self.year, 1, 1)

    @property
    def end(self) -> datetime.date:
        """`start <= o_orderdate < end`."""
        return datetime.date(self.year + 1, 1, 1)


class Q10(NamedTuple):
    year: int
    month: int

    @property
    def start(self) -> datetime.date:
        return datetime.date(self.year, self.month, 1)

    @property
    def end(self) -> datetime.date:
        """`start <= o_orderdate < end`: three months on."""
        months = self.year * 12 + self.month - 1 + 3
        return datetime.date(months // 12, months % 12 + 1, 1)


Draws = Tuple[Tuple[Q3, ...], Tuple[Q5, ...], Tuple[Q10, ...]]


def draws(seed: int) -> Draws:
    """The run's parameters: a function of the seed alone."""
    rng = random.Random(f"tpch_adhoc_joins.{int(seed)}")
    q3 = tuple(Q3(*t) for t in rng.sample(
        list(itertools.product(Q3_SEGMENTS, Q3_DAYS)), DRAWS))
    q5 = tuple(Q5(*t) for t in rng.sample(
        list(itertools.product(Q5_REGIONS, Q5_YEARS)), DRAWS))
    q10 = tuple(Q10(*t) for t in rng.sample(Q10_MONTHS, DRAWS))
    return q3, q5, q10


_current: Optional[Draws] = None


def set_seed(seed: int) -> None:
    """Make this run's draws (`datagen/tpch_adhoc_joins.py` does, from the
    seed the harness gives it)."""
    global _current
    _current = draws(seed)


def template_names() -> Tuple[str, ...]:
    return tuple(f"{q}.p{i:02d}" for q in QUERIES for i in range(DRAWS))


def of(template: str):
    """The parameters of one template (`q3.p02` -> the run's third Q3)."""
    if _current is None:
        raise RuntimeError(
            "adhoc_join_params: no draws yet; datagen/tpch_adhoc_joins.generate (or "
            "set_seed) makes them from the run's seed")
    query, _, index = template.partition(".p")
    return _current[QUERIES.index(query)][int(index)]
