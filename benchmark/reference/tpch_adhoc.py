"""Plain reference of the `tpch_adhoc` suite: numpy in float64.

`reference/tpch.py`'s `_q1` and `_q6` (the specification's query definitions,
not the program's plans) with the run's substitution parameters, read from
`adhoc_params`, in place of the validation values. Independent of `daft_tpu`.
The answer has the form `DataFrame.to_pydict()` gives.

A run asks for 24 answers over one `lineitem`, twelve a query, so the columns
read from the Arrow table (as float64 arrays, through `storage` where the
control rounds them to bfloat16) and q1's group codes are kept between calls:
one set a `storage`, dropped when another table comes.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict, Optional

import numpy as np
import pyarrow as pa

import adhoc_params

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

to_bfloat16 = _tpch.to_bfloat16
_EPOCH = _tpch._EPOCH


class _Columns:
    """`reference/tpch.py`'s reader over one table, keeping what it has read."""

    def __init__(self, table: pa.Table, storage: Optional[Callable]):
        self.table = table
        self._reader = _tpch._Reader(table, storage)
        self._kept: Dict[tuple, object] = {}

    def num(self, name: str) -> np.ndarray:
        if ("num", name) not in self._kept:
            self._kept["num", name] = self._reader.num(name)
        return self._kept["num", name]

    def codes(self, name: str):
        if ("codes", name) not in self._kept:
            self._kept["codes", name] = self._reader.codes(name)
        return self._kept["codes", name]

    def derived(self, name: str, make: Callable[[], np.ndarray]) -> np.ndarray:
        """An array made from the columns alone, kept like them."""
        if ("derived", name) not in self._kept:
            self._kept["derived", name] = make()
        return self._kept["derived", name]


_columns: Dict[Optional[Callable], _Columns] = {}


def _lineitem(tables: Dict[str, pa.Table], storage: Optional[Callable]) -> _Columns:
    table = tables["lineitem"]
    if any(kept.table is not table for kept in _columns.values()):
        _columns.clear()  # another table: nothing of the last one is held
    if storage not in _columns:
        _columns[storage] = _Columns(table, storage)
    return _columns[storage]


def _days(d) -> int:
    return (d - _EPOCH).days


def _q1(L: _Columns, p: adhoc_params.Q1) -> Dict[str, list]:
    """`reference/tpch.py`'s `_q1`, the rows a DELTA leaves out given the
    weight 0.0 instead of being taken out (the same sums in the same order,
    without six copies of 60 M rows a call), and what does not depend on the
    DELTA (the group of a row, the two products) kept with the columns."""
    rf, rf_names = L.codes("l_returnflag")
    ls, ls_names = L.codes("l_linestatus")
    n_groups = len(rf_names) * len(ls_names)
    group = L.derived("q1.group", lambda: rf * len(ls_names) + ls)
    disc_price = L.derived(
        "q1.disc_price", lambda: L.num("l_extendedprice") * (1 - L.num("l_discount")))
    charge = L.derived("q1.charge", lambda: disc_price * (1 + L.num("l_tax")))
    keep = (L.num("l_shipdate") <= _days(p.cutoff)).astype(np.float64)

    def total(x):
        return np.bincount(group, weights=x * keep, minlength=n_groups)

    count = np.rint(np.bincount(group, weights=keep, minlength=n_groups)).astype(np.int64)
    rows = sorted((rf_names[g // len(ls_names)], ls_names[g % len(ls_names)], g)
                  for g in range(n_groups) if count[g])
    idx = [g for _, _, g in rows]
    sums = {"sum_qty": total(L.num("l_quantity")),
            "sum_base_price": total(L.num("l_extendedprice")),
            "sum_disc_price": total(disc_price), "sum_charge": total(charge)}
    out = {"l_returnflag": [r for r, _, _ in rows],
           "l_linestatus": [s for _, s, _ in rows]}
    for name, v in sums.items():
        out[name] = v[idx].tolist()
    out["avg_qty"] = (sums["sum_qty"][idx] / count[idx]).tolist()
    out["avg_price"] = (sums["sum_base_price"][idx] / count[idx]).tolist()
    out["avg_disc"] = (total(L.num("l_discount"))[idx] / count[idx]).tolist()
    out["count_order"] = count[idx].tolist()
    return out


def _q6(L: _Columns, p: adhoc_params.Q6) -> Dict[str, list]:
    ship, disc, qty = L.num("l_shipdate"), L.num("l_discount"), L.num("l_quantity")
    keep = ((ship >= _days(p.start)) & (ship < _days(p.end))
            & (disc >= p.low) & (disc <= p.high) & (qty < p.quantity))
    return {"revenue": [float((L.num("l_extendedprice")[keep] * disc[keep]).sum())]}


_QUERIES = {"q1": _q1, "q6": _q6}


def answer_for(query: str, params, tables: Dict[str, pa.Table],
               storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to `query` ("q1", "q6") with the given
    parameters (an `adhoc_params.Q1` or `Q6`)."""
    return _QUERIES[query](_lineitem(tables, storage), params)


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template (`q1.p03`: Q1 with the run's
    fourth DELTA) over the Arrow tables."""
    return answer_for(template.partition(".")[0], adhoc_params.of(template),
                      tables, storage)
