"""Plain reference of the `tpch_filtered_joins` suite: TPC-H's Q12, Q14 and
Q19 from the specification's text (2.4.12, 2.4.14, 2.4.19) with its validation
parameters, in pandas and numpy float64 on the Arrow tables.

Independent of `daft_tpu`, like every reference, and it stands alone: Q12 and
Q19 are stated as `reference/tpch.py` states them and Q14 as
`reference/tpch_joins10.py` does, copied here, not imported. `storage` rounds
every floating-point column as it is read (`to_bfloat16` for the control).
Q12 counts and has no float; Q14 is a ratio of two sums in which the control's
roundings cancel, so the control comes out not correct through Q19.
"""

from __future__ import annotations

import datetime
from typing import Callable, Dict, Optional

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc

_EPOCH = datetime.date(1970, 1, 1)


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float64 -> nearest bfloat16 (round to nearest even), returned as float64."""
    bits = x.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


class _Reader:
    """Columns of one Arrow table as numpy arrays (dates as days since 1970)."""

    def __init__(self, table: pa.Table, storage: Optional[Callable]):
        self._t, self._storage = table, storage

    def arrow(self, name: str) -> pa.ChunkedArray:
        return self._t.column(name)

    def num(self, name: str) -> np.ndarray:
        col = self._t.column(name)
        if pa.types.is_date32(col.type):
            return col.cast(pa.int32()).to_numpy()
        out = col.to_numpy()
        if out.dtype.kind == "f":
            out = out.astype(np.float64)
            if self._storage is not None:
                out = self._storage(out)
        return out

    def is_in(self, name: str, values) -> np.ndarray:
        return pc.is_in(self._t.column(name),
                        value_set=pa.array(values, pa.large_string())).to_numpy()


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """(row of `keys` equal to each probe value, whether there is one); `keys`
    are unique."""
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys, probe, sorter=order)
    at = order[np.minimum(at, len(keys) - 1)] if len(keys) else np.zeros(len(probe), np.int64)
    return at, (keys[at] == probe if len(keys) else np.zeros(len(probe), bool))


def _q12(t, storage):
    """Shipping modes and order priority: late lines of 1994 received by MAIL
    or SHIP, counted by the priority class of their order."""
    O, L = _Reader(t["orders"], storage), _Reader(t["lineitem"], storage)
    ship, commit, receipt = (L.num(c) for c in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    keep = (L.is_in("l_shipmode", ["MAIL", "SHIP"]) & (commit < receipt)
            & (ship < commit) & (receipt >= _days(1994, 1, 1))
            & (receipt < _days(1995, 1, 1)))
    mode = np.array(L.arrow("l_shipmode").filter(pa.array(keep)).to_pylist(), dtype=object)
    lines = pd.DataFrame({"o_orderkey": L.num("l_orderkey")[keep], "l_shipmode": mode})
    orders = pd.DataFrame({"o_orderkey": O.num("o_orderkey"),
                           "high": O.is_in("o_orderpriority", ["1-URGENT", "2-HIGH"])})
    j = lines.merge(orders, on="o_orderkey")
    j["high_line_count"] = j["high"].astype(np.int64)
    j["low_line_count"] = (~j["high"]).astype(np.int64)
    g = (j.groupby("l_shipmode", as_index=False)[["high_line_count", "low_line_count"]]
         .sum().sort_values("l_shipmode"))
    return {"l_shipmode": g["l_shipmode"].tolist(),
            "high_line_count": g["high_line_count"].tolist(),
            "low_line_count": g["low_line_count"].tolist()}


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


def _q19(t, storage):
    """Discounted revenue: lines delivered in person by air of three classes of
    brand, container, quantity and size."""
    L, P = _Reader(t["lineitem"], storage), _Reader(t["part"], storage)
    keep = (L.is_in("l_shipmode", ["AIR", "REG AIR"])
            & L.is_in("l_shipinstruct", ["DELIVER IN PERSON"]))
    lines = pd.DataFrame({"p_partkey": L.num("l_partkey")[keep],
                          "qty": L.num("l_quantity")[keep],
                          "revenue": L.num("l_extendedprice")[keep]
                          * (1 - L.num("l_discount")[keep])})
    size = P.num("p_size")
    arm = np.zeros(len(size), dtype=np.int8)
    arms = (("Brand#12", "SM", ("CASE", "BOX", "PACK", "PKG"), 5),
            ("Brand#23", "MED", ("BAG", "BOX", "PKG", "PACK"), 10),
            ("Brand#34", "LG", ("CASE", "BOX", "PACK", "PKG"), 15))
    for k, (brand, prefix, boxes, max_size) in enumerate(arms, 1):
        hit = (P.is_in("p_brand", [brand])
               & P.is_in("p_container", [f"{prefix} {b}" for b in boxes])
               & (size >= 1) & (size <= max_size))
        arm[hit] = k
    parts = pd.DataFrame({"p_partkey": P.num("p_partkey")[arm > 0], "arm": arm[arm > 0]})
    j = lines.merge(parts, on="p_partkey")
    lo = j["arm"].map({1: 1, 2: 10, 3: 20})
    ok = (j["qty"] >= lo) & (j["qty"] <= lo + 10)
    return {"revenue": [float(j["revenue"][ok].sum())]}


TEMPLATES = {"q12": _q12, "q14": _q14, "q19": _q19}


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template over the Arrow tables."""
    return TEMPLATES[template](tables, storage)
