"""Plain reference for the `tpch` templates: pandas and numpy in float64.

Independent of `daft_tpu`: it takes the Arrow tables the data generator made
and returns, for each template, the answer in the form `DataFrame.to_pydict()`
gives ({column: list of Python values}, rows in the query's order). Written
from the TPC-H specification's query definitions with its validation
parameters, not from the program's plans.

`storage` rounds every floating-point column as it is read. The benchmark
leaves it at None; the control (`benchmark/control.py`) passes `to_bfloat16`,
the precision a later PR would be tempted to store the device planes in, and
must come out as not correct.
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


def _date(days) -> datetime.date:
    return _EPOCH + datetime.timedelta(days=int(days))


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

    def codes(self, name: str):
        """(int codes, list of the distinct strings) of a string column."""
        d = self._t.column(name).combine_chunks().dictionary_encode()
        return d.indices.to_numpy(), d.dictionary.to_pylist()


def _q1(t, storage):
    L = _Reader(t["lineitem"], storage)
    keep = L.num("l_shipdate") <= _days(1998, 9, 2)
    rf, rf_names = L.codes("l_returnflag")
    ls, ls_names = L.codes("l_linestatus")
    group = (rf * len(ls_names) + ls)[keep]
    n_groups = len(rf_names) * len(ls_names)
    qty, price = L.num("l_quantity")[keep], L.num("l_extendedprice")[keep]
    disc, tax = L.num("l_discount")[keep], L.num("l_tax")[keep]
    disc_price = price * (1 - disc)

    def total(x):
        return np.bincount(group, weights=x, minlength=n_groups)

    count = np.bincount(group, minlength=n_groups)
    rows = sorted((rf_names[g // len(ls_names)], ls_names[g % len(ls_names)], g)
                  for g in range(n_groups) if count[g])
    idx = [g for _, _, g in rows]
    sums = {"sum_qty": total(qty), "sum_base_price": total(price),
            "sum_disc_price": total(disc_price),
            "sum_charge": total(disc_price * (1 + tax))}
    out = {"l_returnflag": [r for r, _, _ in rows],
           "l_linestatus": [s for _, s, _ in rows]}
    for name, v in sums.items():
        out[name] = v[idx].tolist()
    out["avg_qty"] = (sums["sum_qty"][idx] / count[idx]).tolist()
    out["avg_price"] = (sums["sum_base_price"][idx] / count[idx]).tolist()
    out["avg_disc"] = (total(disc)[idx] / count[idx]).tolist()
    out["count_order"] = count[idx].tolist()
    return out


def _q6(t, storage):
    L = _Reader(t["lineitem"], storage)
    ship, disc, qty = L.num("l_shipdate"), L.num("l_discount"), L.num("l_quantity")
    keep = ((ship >= _days(1994, 1, 1)) & (ship < _days(1995, 1, 1))
            & (disc >= 0.05) & (disc <= 0.07) & (qty < 24))
    return {"revenue": [float((L.num("l_extendedprice")[keep] * disc[keep]).sum())]}


def _revenue_lines(L: _Reader, keep: np.ndarray, extra=()) -> pd.DataFrame:
    cols = {"l_orderkey": L.num("l_orderkey")[keep],
            "revenue": L.num("l_extendedprice")[keep]
            * (1 - L.num("l_discount")[keep])}
    for name in extra:
        cols[name] = L.num(name)[keep]
    return pd.DataFrame(cols)


def _q3(t, storage):
    C, O, L = (_Reader(t[n], storage) for n in ("customer", "orders", "lineitem"))
    building = C.num("c_custkey")[C.is_in("c_mktsegment", ["BUILDING"])]
    o_date = O.num("o_orderdate")
    o_keep = (o_date < _days(1995, 3, 15)) & np.isin(O.num("o_custkey"), building)
    orders = pd.DataFrame({"l_orderkey": O.num("o_orderkey")[o_keep],
                           "o_orderdate": o_date[o_keep],
                           "o_shippriority": O.num("o_shippriority")[o_keep]})
    lines = _revenue_lines(L, L.num("l_shipdate") > _days(1995, 3, 15))
    g = (lines.merge(orders, on="l_orderkey")
         .groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False)
         ["revenue"].sum()
         .sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10))
    return {"l_orderkey": g["l_orderkey"].tolist(),
            "revenue": g["revenue"].tolist(),
            "o_orderdate": [_date(d) for d in g["o_orderdate"]],
            "o_shippriority": g["o_shippriority"].tolist()}


def _q5(t, storage):
    R, N, C, O, L, S = (_Reader(t[n], storage) for n in (
        "region", "nation", "customer", "orders", "lineitem", "supplier"))
    asia = R.num("r_regionkey")[R.is_in("r_name", ["ASIA"])]
    n_keep = np.isin(N.num("n_regionkey"), asia)
    nations = pd.DataFrame({"nationkey": N.num("n_nationkey")[n_keep],
                            "n_name": np.array(N.arrow("n_name").to_pylist(),
                                               dtype=object)[n_keep]})
    cust = pd.DataFrame({"o_custkey": C.num("c_custkey"),
                         "nationkey": C.num("c_nationkey")}).merge(nations, on="nationkey")
    o_date = O.num("o_orderdate")
    o_keep = (o_date >= _days(1994, 1, 1)) & (o_date < _days(1995, 1, 1))
    orders = pd.DataFrame({"l_orderkey": O.num("o_orderkey")[o_keep],
                           "o_custkey": O.num("o_custkey")[o_keep]}).merge(cust, on="o_custkey")
    lines = _revenue_lines(L, np.isin(L.num("l_orderkey"), orders["l_orderkey"].to_numpy()),
                           extra=("l_suppkey",))
    supp = pd.DataFrame({"l_suppkey": S.num("s_suppkey"),
                         "nationkey": S.num("s_nationkey")})
    g = (lines.merge(orders, on="l_orderkey")
         .merge(supp, on=["l_suppkey", "nationkey"])
         .groupby("n_name", as_index=False)["revenue"].sum()
         .sort_values("revenue", ascending=False, kind="stable"))
    return {"n_name": g["n_name"].tolist(), "revenue": g["revenue"].tolist()}


def _q12(t, storage):
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


def _q19(t, storage):
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


TEMPLATES = {"q1": _q1, "q3": _q3, "q5": _q5, "q6": _q6, "q12": _q12, "q19": _q19}


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template over the Arrow tables."""
    return TEMPLATES[template](tables, storage)
