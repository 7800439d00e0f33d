"""TPC-H query templates as daft_tpu DataFrame programs, for the benchmark.

Copied from `benchmarking/tpch/queries.py` (the original is listed for
deletion under Open questions in PERF.md): the specification's queries with
its validation parameters, correlated subqueries written as joins. A traffic
file names templates of this suite; `TEMPLATES` maps each name to its program
and to the tables it reads, which fixes the rows an execution scans.

A template takes {table name: DataFrame} and returns a DataFrame. This file
uses only the program's public DataFrame API: it is the client's side.
"""

from __future__ import annotations

import datetime

from daft_tpu import col, lit


def _d(y, m, d):
    return lit(datetime.date(y, m, d))


def q1(t):
    L = t["lineitem"]
    return (
        L.where(col("l_shipdate") <= _d(1998, 9, 2))
        .groupby("l_returnflag", "l_linestatus")
        .agg(
            col("l_quantity").sum().alias("sum_qty"),
            col("l_extendedprice").sum().alias("sum_base_price"),
            (col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("sum_disc_price"),
            (col("l_extendedprice") * (1 - col("l_discount")) * (1 + col("l_tax"))).sum().alias("sum_charge"),
            col("l_quantity").mean().alias("avg_qty"),
            col("l_extendedprice").mean().alias("avg_price"),
            col("l_discount").mean().alias("avg_disc"),
            col("l_quantity").count().alias("count_order"),
        )
        .sort(["l_returnflag", "l_linestatus"])
    )


def q3(t):
    C, O, L = t["customer"], t["orders"], t["lineitem"]
    return (
        C.where(col("c_mktsegment") == "BUILDING")
        .join(O, left_on="c_custkey", right_on="o_custkey")
        .where(col("o_orderdate") < _d(1995, 3, 15))
        .join(L, left_on="o_orderkey", right_on="l_orderkey")
        .where(col("l_shipdate") > _d(1995, 3, 15))
        .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
        .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
        .sort(["revenue", "o_orderdate"], desc=[True, False])
        .limit(10)
    )


def q5(t):
    C, O, L, S, N, R = t["customer"], t["orders"], t["lineitem"], t["supplier"], t["nation"], t["region"]
    return (
        R.where(col("r_name") == "ASIA")
        .join(N, left_on="r_regionkey", right_on="n_regionkey")
        .join(C, left_on="n_nationkey", right_on="c_nationkey")
        .join(O, left_on="c_custkey", right_on="o_custkey")
        .where((col("o_orderdate") >= _d(1994, 1, 1)) & (col("o_orderdate") < _d(1995, 1, 1)))
        .join(L, left_on="o_orderkey", right_on="l_orderkey")
        # supplier must be in the same nation as the customer
        .join(S, left_on=["l_suppkey", "n_nationkey"], right_on=["s_suppkey", "s_nationkey"])
        .groupby("n_name")
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
        .sort("revenue", desc=True)
    )


def q6(t):
    L = t["lineitem"]
    return (
        L.where(
            (col("l_shipdate") >= _d(1994, 1, 1)) & (col("l_shipdate") < _d(1995, 1, 1))
            & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
            & (col("l_quantity") < 24)
        )
        .agg((col("l_extendedprice") * col("l_discount")).sum().alias("revenue"))
    )


def q12(t):
    O, L = t["orders"], t["lineitem"]
    high = col("o_orderpriority").is_in(["1-URGENT", "2-HIGH"])
    return (
        L.where(
            col("l_shipmode").is_in(["MAIL", "SHIP"])
            & (col("l_commitdate") < col("l_receiptdate"))
            & (col("l_shipdate") < col("l_commitdate"))
            & (col("l_receiptdate") >= _d(1994, 1, 1)) & (col("l_receiptdate") < _d(1995, 1, 1))
        )
        .join(O, left_on="l_orderkey", right_on="o_orderkey")
        .with_column("high_line", high.if_else(lit(1), lit(0)))
        .with_column("low_line", (~high).if_else(lit(1), lit(0)))
        .groupby("l_shipmode")
        .agg(col("high_line").sum().alias("high_line_count"),
             col("low_line").sum().alias("low_line_count"))
        .sort("l_shipmode")
    )


def q19(t):
    L, P = t["lineitem"], t["part"]
    joined = L.where(
        col("l_shipmode").is_in(["AIR", "REG AIR"])
        & (col("l_shipinstruct") == "DELIVER IN PERSON")
    ).join(P, left_on="l_partkey", right_on="p_partkey")
    sm = (col("p_brand") == "Brand#12") & col("p_container").is_in(
        ["SM CASE", "SM BOX", "SM PACK", "SM PKG"]
    ) & (col("l_quantity") >= 1) & (col("l_quantity") <= 11) & (col("p_size") <= 5)
    med = (col("p_brand") == "Brand#23") & col("p_container").is_in(
        ["MED BAG", "MED BOX", "MED PKG", "MED PACK"]
    ) & (col("l_quantity") >= 10) & (col("l_quantity") <= 20) & (col("p_size") <= 10)
    lg = (col("p_brand") == "Brand#34") & col("p_container").is_in(
        ["LG CASE", "LG BOX", "LG PACK", "LG PKG"]
    ) & (col("l_quantity") >= 20) & (col("l_quantity") <= 30) & (col("p_size") <= 15)
    return (
        joined.where((col("p_size") >= 1) & (sm | med | lg))
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
    )


# name -> the program, the tables it reads (their rows are what an execution
# scans) and, for a template that is one scan of the fact table, the columns
# of it that the scan has to read (the roofline's bytes: benchmark/scanbytes.py)
TEMPLATES = {
    "q1": {"program": q1, "tables": ("lineitem",),
           "scan_columns": ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                            "l_extendedprice", "l_discount", "l_tax")},
    "q3": {"program": q3, "tables": ("customer", "orders", "lineitem")},
    "q5": {"program": q5, "tables": ("region", "nation", "customer", "orders",
                                     "lineitem", "supplier")},
    "q6": {"program": q6, "tables": ("lineitem",),
           "scan_columns": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")},
    "q12": {"program": q12, "tables": ("orders", "lineitem")},
    "q19": {"program": q19, "tables": ("lineitem", "part")},
}
