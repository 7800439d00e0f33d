"""Plain reference of the `tpch_adhoc_joins` suite: numpy in float64.

Q3, Q5 and Q10 as functions of their substitution parameters, written from
the specification's query definitions (2.4.3, 2.4.5, 2.4.10), not from the
program's plans, and given the run's draws (`adhoc_join_params`). Independent
of `daft_tpu`. The answer has the form `DataFrame.to_pydict()` gives.

A run asks for 12 answers, four a query, over one set of tables, so what does
not depend on a parameter is made once and kept between calls: the columns
read from the Arrow tables (float64 through `storage` where the control rounds
them to bfloat16), each line's revenue, and the rows the joins' keys lead to
(every join is a key lookup, a binary search in the sorted keys of the unique
side, never row by row). One set a `storage`, dropped when other tables come.
A parameter then costs comparisons and one weighted count over the fact.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict, Optional

import numpy as np
import pyarrow as pa

import adhoc_join_params

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch_joins10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch_joins10.py"))
_joins10 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_joins10)
_tpch = _joins10._tpch

to_bfloat16 = _tpch.to_bfloat16
_EPOCH, _date = _tpch._EPOCH, _tpch._date
# a join is a key lookup (a binary search in the sorted keys of the unique side)
_lookup, _strings = _joins10._lookup, _joins10._strings


def _days(d) -> int:
    return (d - _EPOCH).days


class _Star:
    """The tables of one run as numpy arrays, and what of the three queries no
    parameter changes, each made when first asked for and kept."""

    def __init__(self, tables: Dict[str, pa.Table], storage: Optional[Callable]):
        self.tables = tables
        self._readers = {n: _tpch._Reader(t, storage) for n, t in tables.items()}
        self._kept: Dict[str, object] = {}

    def reader(self, table: str):
        return self._readers[table]

    def kept(self, name: str, make: Callable[[], object]):
        if name not in self._kept:
            self._kept[name] = make()
        return self._kept[name]

    def num(self, table: str, column: str) -> np.ndarray:
        return self.kept(f"{table}.{column}", lambda: self._readers[table].num(column))

    def rows(self, name: str, keys: tuple, probe: tuple):
        """(row, found) of the table `keys[0]` for each value of the column
        `probe`: one join's lookup."""
        return self.kept(name, lambda: _lookup(self.num(*keys), self.num(*probe)))

    def revenue(self) -> np.ndarray:
        return self.kept("revenue", lambda: self.num("lineitem", "l_extendedprice")
                         * (1 - self.num("lineitem", "l_discount")))

    def line_order(self):
        return self.rows("line->order", ("orders", "o_orderkey"), ("lineitem", "l_orderkey"))

    def order_customer(self):
        return self.rows("order->customer", ("customer", "c_custkey"), ("orders", "o_custkey"))

    def customer_nation(self):
        return self.rows("customer->nation", ("nation", "n_nationkey"),
                         ("customer", "c_nationkey"))


_stars: Dict[Optional[Callable], _Star] = {}


def _star(tables: Dict[str, pa.Table], storage: Optional[Callable]) -> _Star:
    if any(kept.tables.get(n) is not t for kept in _stars.values()
           for n, t in tables.items()):
        _stars.clear()  # other tables: nothing of the last ones is held
    if storage not in _stars:
        _stars[storage] = _Star(tables, storage)
    return _stars[storage]


def _q3(s: _Star, p: adhoc_join_params.Q3) -> Dict[str, list]:
    """Shipping priority: the ten orders of SEGMENT's customers, placed before
    DATE, with the most revenue in lines shipped after it."""
    of_segment = s.reader("customer").is_in("c_mktsegment", [p.segment])
    date = _days(p.date)
    c_row, c_found = s.order_customer()
    o_date = s.num("orders", "o_orderdate")
    o_keep = (o_date < date) & c_found & of_segment[c_row]
    o_row, o_found = s.line_order()
    keep = o_found & (s.num("lineitem", "l_shipdate") > date)
    keep &= o_keep[o_row]
    at = o_row[keep]
    total = np.bincount(at, weights=s.revenue()[keep], minlength=len(o_date))
    groups = np.flatnonzero(np.bincount(at, minlength=len(o_date)))
    o_key = s.num("orders", "o_orderkey")
    # revenue descending, then the order's date, then (the groups' own order) its key
    top = groups[np.lexsort((o_key[groups], o_date[groups], -total[groups]))[:10]]
    return {"l_orderkey": o_key[top].tolist(),
            "revenue": total[top].tolist(),
            "o_orderdate": [_date(d) for d in o_date[top]],
            "o_shippriority": s.num("orders", "o_shippriority")[top].tolist()}


def _q5(s: _Star, p: adhoc_join_params.Q5) -> Dict[str, list]:
    """Local supplier volume: revenue by nation of REGION from a year's orders
    whose customer and supplier are of that same nation."""
    R, N = s.reader("region"), s.reader("nation")
    in_region = np.isin(s.num("nation", "n_regionkey"),
                        s.num("region", "r_regionkey")[R.is_in("r_name", [p.region])])
    n_row, n_found = s.customer_nation()
    c_keep = n_found & in_region[n_row]
    c_row, c_found = s.order_customer()
    o_date = s.num("orders", "o_orderdate")
    o_keep = (o_date >= _days(p.start)) & (o_date < _days(p.end)) & c_found & c_keep[c_row]
    o_row, o_found = s.line_order()
    s_row, s_found = s.rows("line->supplier", ("supplier", "s_suppkey"),
                            ("lineitem", "l_suppkey"))
    # the nation row of each line's customer (through its order), kept with the lookups
    line_nation = s.kept("line->nation", lambda: n_row[c_row[o_row]])
    keep = o_found & s_found
    keep &= o_keep[o_row]
    nation_key = s.num("nation", "n_nationkey")
    keep &= s.num("supplier", "s_nationkey")[s_row] == nation_key[line_nation]
    at = line_nation[keep]
    total = np.bincount(at, weights=s.revenue()[keep], minlength=len(nation_key))
    names = np.array(N.arrow("n_name").to_pylist(), dtype=object)
    groups = np.flatnonzero(np.bincount(at, minlength=len(nation_key)))
    groups = groups[np.argsort(names[groups], kind="stable")]      # the groups' own order
    order = groups[np.argsort(-total[groups], kind="stable")]
    return {"n_name": names[order].tolist(), "revenue": total[order].tolist()}


def _q10(s: _Star, p: adhoc_join_params.Q10) -> Dict[str, list]:
    """Returned item reporting: the 20 customers who lost most revenue on
    returned parts of orders of the quarter from DATE."""
    C, N = s.reader("customer"), s.reader("nation")
    returned = s.kept("returned", lambda: s.reader("lineitem").is_in("l_returnflag", ["R"]))
    o_date = s.num("orders", "o_orderdate")
    c_row, c_found = s.order_customer()
    n_row, n_found = s.customer_nation()
    # inner joins: a customer the table lacks, or one of no nation, drops out
    o_keep = (o_date >= _days(p.start)) & (o_date < _days(p.end)) & c_found & n_found[c_row]
    o_row, o_found = s.line_order()
    keep = returned & o_found
    keep &= o_keep[o_row]
    at = c_row[o_row[keep]]
    c_key = s.num("customer", "c_custkey")
    total = np.bincount(at, weights=s.revenue()[keep], minlength=len(c_key))
    groups = np.flatnonzero(np.bincount(at, minlength=len(c_key)))
    rows = groups[np.lexsort((c_key[groups], -total[groups]))[:20]]   # revenue descending, then the key
    return {"c_custkey": c_key[rows].tolist(),
            "c_name": _strings(C, "c_name", rows),
            "revenue": total[rows].tolist(),
            "c_acctbal": s.num("customer", "c_acctbal")[rows].tolist(),
            "n_name": _strings(N, "n_name", n_row[rows]),
            "c_address": _strings(C, "c_address", rows),
            "c_phone": _strings(C, "c_phone", rows),
            "c_comment": _strings(C, "c_comment", rows)}


_QUERIES = {"q3": _q3, "q5": _q5, "q10": _q10}


def answer_for(query: str, params, tables: Dict[str, pa.Table],
               storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to `query` ("q3", "q5", "q10") with the given
    parameters (an `adhoc_join_params.Q3`, `Q5` or `Q10`)."""
    return _QUERIES[query](_star(tables, storage), params)


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template (`q5.p01`: Q5 with the run's
    second (REGION, DATE)) over the Arrow tables."""
    return answer_for(template.partition(".")[0], adhoc_join_params.of(template),
                      tables, storage)
