"""Plain reference of the `tpch_joins10` suite: `reference/tpch.py`'s Q3 and
Q5, and Q10 and Q14 written here from the specification's text (2.4.10,
2.4.14) with its validation parameters, in numpy float64 on the Arrow tables.

Independent of `daft_tpu`, like every reference. Both new queries join by key
lookup (a binary search in the sorted keys of the unique side), never row by
row, so that they answer over 60 M `lineitem` rows in seconds. `storage`
rounds every floating-point column as it is read (`to_bfloat16` for the
control), `c_acctbal`, which Q10 only hands through, included.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

to_bfloat16 = _tpch.to_bfloat16
_Reader, _days = _tpch._Reader, _tpch._days


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """(row of `keys` equal to each probe value, whether there is one); `keys`
    are unique."""
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys, probe, sorter=order)
    at = order[np.minimum(at, len(keys) - 1)] if len(keys) else np.zeros(len(probe), np.int64)
    return at, (keys[at] == probe if len(keys) else np.zeros(len(probe), bool))


def _strings(reader: _Reader, name: str, rows: np.ndarray) -> list:
    return reader.arrow(name).combine_chunks().take(pa.array(rows)).to_pylist()


def _q10(t, storage):
    """Returned item reporting: the 20 customers who lost most revenue on
    returned parts of orders of the quarter from 1993-10-01."""
    C, O, L, N = (_Reader(t[n], storage) for n in ("customer", "orders", "lineitem", "nation"))
    o_date = O.num("o_orderdate")
    o_keep = (o_date >= _days(1993, 10, 1)) & (o_date < _days(1994, 1, 1))
    o_key, o_cust = O.num("o_orderkey")[o_keep], O.num("o_custkey")[o_keep]
    returned = L.is_in("l_returnflag", ["R"])
    at, hit = _lookup(o_key, L.num("l_orderkey")[returned])
    revenue = (L.num("l_extendedprice")[returned] * (1 - L.num("l_discount")[returned]))[hit]
    cust = o_cust[at[hit]]
    # inner joins: a customer the table lacks, or one of no nation, drops out
    c_row, c_hit = _lookup(C.num("c_custkey"), cust)
    _n_row, n_hit = _lookup(N.num("n_nationkey"), C.num("c_nationkey")[c_row])
    ok = c_hit & n_hit
    keys, inverse = np.unique(cust[ok], return_inverse=True)
    total = np.bincount(inverse, weights=revenue[ok], minlength=len(keys))
    top = np.lexsort((keys, -total))[:20]       # revenue descending, then the key
    rows, _ = _lookup(C.num("c_custkey"), keys[top])
    n_rows, _ = _lookup(N.num("n_nationkey"), C.num("c_nationkey")[rows])
    return {"c_custkey": keys[top].tolist(),
            "c_name": _strings(C, "c_name", rows),
            "revenue": total[top].tolist(),
            "c_acctbal": C.num("c_acctbal")[rows].tolist(),
            "n_name": _strings(N, "n_name", n_rows),
            "c_address": _strings(C, "c_address", rows),
            "c_phone": _strings(C, "c_phone", rows),
            "c_comment": _strings(C, "c_comment", rows)}


def _q14(t, storage):
    """Promotion effect: the share of September 1995's revenue that came from
    promotional parts, in per cent."""
    L, P = _Reader(t["lineitem"], storage), _Reader(t["part"], storage)
    ship = L.num("l_shipdate")
    keep = (ship >= _days(1995, 9, 1)) & (ship < _days(1995, 10, 1))
    at, hit = _lookup(P.num("p_partkey"), L.num("l_partkey")[keep])
    revenue = (L.num("l_extendedprice")[keep] * (1 - L.num("l_discount")[keep]))[hit]
    promo = pc.starts_with(P.arrow("p_type"), "PROMO").to_numpy(zero_copy_only=False)[at[hit]]
    total = float(revenue.sum())
    # SQL's division by a sum over no rows is null
    return {"promo_revenue": [100.0 * float(revenue[promo].sum()) / total if len(revenue) else None]}


TEMPLATES = {"q3": _tpch._q3, "q5": _tpch._q5, "q10": _q10, "q14": _q14}


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template over the Arrow tables."""
    return TEMPLATES[template](tables, storage)
