"""TPC-H tables from a seed, for the benchmark.

A copy of `benchmarking/tpch/datagen.py` (the original is listed for deletion
under Open questions in PERF.md), changed in two ways and in no value domain:

- every table draws from a random stream of its own, keyed by the seed and
  the table's name, so a cell generates only the tables its templates read
  and gets the same rows as a cell that generates all eight;
- `lineitem` is made in 128 blocks of orders on a few threads, the per-order
  loop that built `l_linenumber` is one vectorised expression, and strings
  are made as `large_string`, the type the engine stores, so loading copies
  nothing.

Schema, row counts (lineitem ~= 6M x SF) and value domains follow the TPC-H
specification. This is not dbgen: the rows are not dbgen's rows, text columns
are short synthetic strings, and keys are dense.
"""

from __future__ import annotations

import datetime
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

TABLES = ("region", "nation", "part", "supplier", "partsupp", "customer",
          "orders", "lineitem")

EPOCH = datetime.date(1970, 1, 1)
D_1992 = (datetime.date(1992, 1, 1) - EPOCH).days
D_1998 = (datetime.date(1998, 12, 1) - EPOCH).days

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPES_P1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES_P2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES_P3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
COLORS = ["green", "blue", "red", "ivory", "forest", "lime", "navy"]
CONTAINERS_P1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINERS_P2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]


# the engine stores strings as large_string: made so, `from_arrow` copies nothing
_STR = pa.large_string()
# lineitem is made in this many blocks of orders, whatever the machine, so the
# rows do not depend on how many threads made them
_LINEITEM_BLOCKS = 128


def _rng(seed: int, stream: str, block: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(stream.encode()), block])


def _pick(rng, choices: Sequence[str], n: int, p=None) -> pa.Array:
    """Vectorised random choice: int codes + dictionary decode."""
    if p is None:
        codes = rng.integers(0, len(choices), n, dtype=np.int32)
    else:
        codes = rng.choice(len(choices), n, p=p).astype(np.int32)
    d = pa.DictionaryArray.from_arrays(pa.array(codes), pa.array(list(choices)))
    return d.cast(_STR)


def _istr(a) -> pa.Array:
    return pc.cast(pa.array(np.asarray(a)), _STR)


def _join(*parts) -> pa.Array:
    """Element-wise string concat; python str args broadcast as scalars."""
    parts = [pa.scalar(x, _STR) if isinstance(x, str) else x for x in parts]
    return pc.binary_join_element_wise(*parts, pa.scalar("", _STR))


def _maybe_prefix(rng, n: int, prob: float, prefix: str, body: pa.Array) -> pa.Array:
    mask = pa.array(rng.random(n) < prob)
    return _join(pc.if_else(mask, pa.scalar(prefix, _STR), pa.scalar("", _STR)), body)


def _phone(rng, n):
    return _join(_istr(rng.integers(10, 35, n)), "-",
                 _istr(rng.integers(100, 1000, n)), "-",
                 _istr(rng.integers(100, 1000, n)), "-",
                 _istr(rng.integers(1000, 10000, n)))


def sizes(sf: float) -> Dict[str, int]:
    """Rows of every table but lineitem (1 to 7 lines an order, from the seed)."""
    n_part = max(int(200_000 * sf), 20)
    return {"region": 5, "nation": 25, "part": n_part,
            "supplier": max(int(10_000 * sf), 5), "partsupp": n_part * 4,
            "customer": max(int(150_000 * sf), 15),
            "orders": max(int(1_500_000 * sf), 150)}


def _order_dates(seed: int, n_ord: int) -> np.ndarray:
    """o_orderdate, on a stream of its own: orders and lineitem both need it."""
    return _rng(seed, "o_orderdate").integers(
        D_1992, D_1998 - 151, n_ord).astype("int32")


def _region(seed, n):
    return pa.table({
        "r_regionkey": pa.array(range(5), pa.int64()),
        "r_name": REGIONS,
        "r_comment": [f"region {r}" for r in REGIONS],
    })


def _nation(seed, n):
    return pa.table({
        "n_nationkey": pa.array(range(25), pa.int64()),
        "n_name": [name for name, _ in NATIONS],
        "n_regionkey": pa.array([r for _, r in NATIONS], pa.int64()),
        "n_comment": [f"nation {name}" for name, _ in NATIONS],
    })


def _part(seed, n):
    rng = _rng(seed, "part")
    n_part = n["part"]
    return pa.table({
        "p_partkey": pa.array(np.arange(1, n_part + 1, dtype=np.int64)),
        "p_name": _join(_pick(rng, COLORS, n_part), " ",
                        _pick(rng, COLORS, n_part), " part ",
                        _istr(np.arange(1, n_part + 1))),
        "p_mfgr": _join("Manufacturer#", _istr(rng.integers(1, 6, n_part))),
        "p_brand": _join("Brand#", _istr(rng.integers(1, 6, n_part)),
                         _istr(rng.integers(1, 6, n_part))),
        "p_type": _join(_pick(rng, TYPES_P1, n_part), " ",
                        _pick(rng, TYPES_P2, n_part), " ",
                        _pick(rng, TYPES_P3, n_part)),
        "p_size": pa.array(rng.integers(1, 51, n_part, dtype=np.int32)),
        "p_container": _join(_pick(rng, CONTAINERS_P1, n_part), " ",
                             _pick(rng, CONTAINERS_P2, n_part)),
        "p_retailprice": pa.array(np.round(rng.uniform(900, 2000, n_part), 2)),
        "p_comment": _join("part comment ", _istr(np.arange(n_part))),
    })


def _supplier(seed, n):
    rng = _rng(seed, "supplier")
    n_supp = n["supplier"]
    return pa.table({
        "s_suppkey": pa.array(np.arange(1, n_supp + 1, dtype=np.int64)),
        "s_name": _join("Supplier#",
                        pc.utf8_lpad(_istr(np.arange(1, n_supp + 1)), 9, "0")),
        "s_address": _join("addr ", _istr(np.arange(n_supp))),
        "s_nationkey": pa.array(rng.integers(0, 25, n_supp), pa.int64()),
        "s_phone": _phone(rng, n_supp),
        "s_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n_supp), 2)),
        "s_comment": _maybe_prefix(
            rng, n_supp, 0.01, "Customer Complaints ",
            _join("supplier comment ", _istr(np.arange(n_supp)))),
    })


def _partsupp(seed, n):
    rng = _rng(seed, "partsupp")
    n_part, n_supp, n_psupp = n["part"], n["supplier"], n["partsupp"]
    ps_partkey = np.repeat(np.arange(1, n_part + 1), 4)
    ps_suppkey = ((ps_partkey + np.tile(np.arange(4), n_part)
                   * (n_supp // 4 + 1)) % n_supp) + 1
    return pa.table({
        "ps_partkey": pa.array(ps_partkey, pa.int64()),
        "ps_suppkey": pa.array(ps_suppkey, pa.int64()),
        "ps_availqty": pa.array(rng.integers(1, 10_000, n_psupp, dtype=np.int32)),
        "ps_supplycost": pa.array(np.round(rng.uniform(1.0, 1000.0, n_psupp), 2)),
        "ps_comment": _join("ps comment ", _istr(np.arange(n_psupp))),
    })


def _customer(seed, n):
    rng = _rng(seed, "customer")
    n_cust = n["customer"]
    return pa.table({
        "c_custkey": pa.array(np.arange(1, n_cust + 1, dtype=np.int64)),
        "c_name": _join("Customer#",
                        pc.utf8_lpad(_istr(np.arange(1, n_cust + 1)), 9, "0")),
        "c_address": _join("caddr ", _istr(np.arange(n_cust))),
        "c_nationkey": pa.array(rng.integers(0, 25, n_cust), pa.int64()),
        "c_phone": _phone(rng, n_cust),
        "c_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n_cust), 2)),
        "c_mktsegment": _pick(rng, SEGMENTS, n_cust),
        "c_comment": _join("customer comment ", _istr(np.arange(n_cust))),
    })


def _orders(seed, n):
    rng = _rng(seed, "orders")
    n_ord, n_cust = n["orders"], n["customer"]
    return pa.table({
        "o_orderkey": pa.array(np.arange(1, n_ord + 1, dtype=np.int64)),
        "o_custkey": pa.array(rng.integers(1, n_cust + 1, n_ord), pa.int64()),
        "o_orderstatus": _pick(rng, ["O", "F", "P"], n_ord, p=[0.49, 0.49, 0.02]),
        "o_totalprice": pa.array(np.round(rng.uniform(800, 500_000, n_ord), 2)),
        "o_orderdate": pa.array(_order_dates(seed, n_ord), pa.date32()),
        "o_orderpriority": _pick(rng, PRIORITIES, n_ord),
        "o_clerk": _join("Clerk#", pc.utf8_lpad(
            _istr(rng.integers(1, 1001, n_ord)), 9, "0")),
        "o_shippriority": pa.array(np.zeros(n_ord, dtype=np.int32)),
        "o_comment": _maybe_prefix(
            rng, n_ord, 0.02, "special requests ",
            _join("order comment ", _istr(np.arange(n_ord)))),
    })


def _lineitem_block(seed, n, block, order_lo, lines_per_order, order_dates, line_lo):
    rng = _rng(seed, "lineitem", block + 1)
    n_part, n_supp = n["part"], n["supplier"]
    n_line = int(lines_per_order.sum())
    first = np.cumsum(lines_per_order) - lines_per_order
    l_orderkey = np.repeat(
        np.arange(order_lo + 1, order_lo + 1 + len(lines_per_order)), lines_per_order)
    linenumber = np.arange(n_line) - np.repeat(first, lines_per_order) + 1
    l_orderdate = np.repeat(order_dates, lines_per_order)
    l_shipdate = l_orderdate + rng.integers(1, 122, n_line, dtype=np.int32)
    l_commitdate = l_orderdate + rng.integers(30, 91, n_line, dtype=np.int32)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n_line, dtype=np.int32)
    l_quantity = rng.integers(1, 51, n_line).astype(np.float64)
    l_extendedprice = np.round(l_quantity * rng.uniform(900, 2000, n_line) / 10, 2)
    return pa.table({
        "l_orderkey": pa.array(l_orderkey, pa.int64()),
        "l_partkey": pa.array(rng.integers(1, n_part + 1, n_line), pa.int64()),
        "l_suppkey": pa.array(rng.integers(1, n_supp + 1, n_line), pa.int64()),
        "l_linenumber": pa.array(linenumber.astype(np.int32)),
        "l_quantity": pa.array(l_quantity),
        "l_extendedprice": pa.array(l_extendedprice),
        "l_discount": pa.array(np.round(rng.uniform(0.0, 0.10, n_line), 2)),
        "l_tax": pa.array(np.round(rng.uniform(0.0, 0.08, n_line), 2)),
        "l_returnflag": _pick(rng, ["R", "A", "N"], n_line),
        "l_linestatus": _pick(rng, ["O", "F"], n_line),
        "l_shipdate": pa.array(l_shipdate, pa.date32()),
        "l_commitdate": pa.array(l_commitdate, pa.date32()),
        "l_receiptdate": pa.array(l_receiptdate, pa.date32()),
        "l_shipinstruct": _pick(rng, INSTRUCTIONS, n_line),
        "l_shipmode": _pick(rng, SHIPMODES, n_line),
        "l_comment": _join("line comment ",
                           _istr(np.arange(line_lo, line_lo + n_line))),
    })


def _lineitem(seed, n):
    n_ord = n["orders"]
    lines_per_order = _rng(seed, "lineitem").integers(1, 8, n_ord)
    order_dates = _order_dates(seed, n_ord)
    cuts = np.linspace(0, n_ord, _LINEITEM_BLOCKS + 1).astype(np.int64)
    line_cuts = np.concatenate([[0], np.cumsum(lines_per_order)])[cuts]
    # numpy's generators and Arrow's kernels release the interpreter lock
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        blocks = list(pool.map(
            lambda b: _lineitem_block(
                seed, n, b, int(cuts[b]), lines_per_order[cuts[b]:cuts[b + 1]],
                order_dates[cuts[b]:cuts[b + 1]], int(line_cuts[b])),
            range(_LINEITEM_BLOCKS)))
        names = blocks[0].column_names
        columns = list(pool.map(
            lambda c: pa.concat_arrays([b.column(c).chunk(0) for b in blocks]),
            names))
    return pa.table(columns, names=names)


_MAKERS = {"region": _region, "nation": _nation, "part": _part,
           "supplier": _supplier, "partsupp": _partsupp, "customer": _customer,
           "orders": _orders, "lineitem": _lineitem}


def generate(sf: float, seed: int, tables: Iterable[str] = TABLES) -> Dict[str, pa.Table]:
    """The named TPC-H tables at scale `sf` from `seed`, as Arrow tables."""
    n = sizes(sf)
    return {name: _MAKERS[name](seed, n) for name in tables}
