"""The `tpch_joins10` suite: the specification's star-join queries Q3, Q5, Q10
and Q14 over tables that stay loaded at SF10 on one chip.

q3 and q5 are `queries/tpch.py`'s own, letter for letter; q10 and q14 are
copied from `benchmarking/tpch/queries.py` (the specification's text with its
validation parameters). Nothing here tells the program where to run a query:
the deployment (`configs/tpch-sf10-joins-1chip.json`) runs the shipped
defaults, and `auto` has to choose. What the suite adds is its own check, in
the manner of `queries/tpch_mesh.py` and `queries/tpch_adhoc.py`:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counters `device_join_topn_batches`, `device_topn_fetched_rows` and
  `device_topn_table_bytes`. A program without them takes a fused join TopN's
  fact as ONE batch only: at SF10 `lineitem` arrives as 458 batches, q3 and
  q10 fall back to per-batch group tables fetched whole, and the cell's
  per-layer metrics have nothing to read. That is the parent of the PR that
  added the cell: it fails at once and cleanly.
- On a TPU, when q3 or q10 is built for the second time (its first warm-up
  execution is then over), that execution has to have completed a fused TopN
  run (`device_topn_runs`) that took in more than one fact batch and fetched
  no more rows than the query's limit; else the suite prints why and exits 1,
  before the window. On any other backend (the tier-1 tests run the suite on
  the CPU, where `auto` never uses the device) nothing is checked.
"""

from __future__ import annotations

import datetime
import importlib.util
import os
import sys

from daft_tpu import col, lit

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

_COUNTERS = ("device_join_topn_batches", "device_topn_fetched_rows",
             "device_topn_table_bytes")
# the fused TopN templates and the rows their LIMIT allows a finalize to fetch
_TOPN_LIMIT = {"q3": 10, "q10": 20}


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_joins10.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counters() -> None:
    from daft_tpu.observability import metrics

    missing = [c for c in _COUNTERS if c not in metrics.DEVICE_COUNTER_NAMES]
    if missing:
        _refuse(f"the program does not declare the counter(s) {missing} "
                "(daft_tpu/observability/metrics.py): its fused join TopN takes a fact "
                "of one batch only, and tpch-sf10-joins-1chip is the deployment whose "
                "lineitem reaches q3 and q10 as 458 batches; the cell cannot run on it")


_require_the_counters()


def _d(y, m, d):
    return lit(datetime.date(y, m, d))


def q10(t):
    C, O, L, N = t["customer"], t["orders"], t["lineitem"], t["nation"]
    return (
        O.where((col("o_orderdate") >= _d(1993, 10, 1)) & (col("o_orderdate") < _d(1994, 1, 1)))
        .join(L.where(col("l_returnflag") == "R"), left_on="o_orderkey", right_on="l_orderkey")
        .join(C, left_on="o_custkey", right_on="c_custkey")
        .join(N, left_on="c_nationkey", right_on="n_nationkey")
        .groupby(col("o_custkey").alias("c_custkey"), "c_name", "c_acctbal", "c_phone",
                 "n_name", "c_address", "c_comment")
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
        .select("c_custkey", "c_name", "revenue", "c_acctbal", "n_name", "c_address",
                "c_phone", "c_comment")
        .sort(["revenue", "c_custkey"], desc=[True, False])
        .limit(20)
    )


def q14(t):
    L, P = t["lineitem"], t["part"]
    return (
        L.where((col("l_shipdate") >= _d(1995, 9, 1)) & (col("l_shipdate") < _d(1995, 10, 1)))
        .join(P, left_on="l_partkey", right_on="p_partkey")
        .with_column("revenue", col("l_extendedprice") * (1 - col("l_discount")))
        .with_column("promo", col("p_type").str.startswith("PROMO").if_else(col("revenue"), lit(0.0)))
        .agg(col("promo").sum().alias("promo_sum"), col("revenue").sum().alias("total_sum"))
        .select((lit(100.0) * col("promo_sum") / col("total_sum")).alias("promo_revenue"))
    )


_built = {}
_at_first_build = {}
_CHECKED = ("device_topn_runs", "device_join_topn_batches", "device_topn_fetched_rows")


def _topn_counts():
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return tuple(snap.get(c, 0) for c in _CHECKED)


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _require_the_fused_topn(name: str) -> None:
    """See the module's docstring: `name`'s first execution is over."""
    if not _on_tpu():
        return
    runs, batches, fetched = (now - then for now, then in
                              zip(_topn_counts(), _at_first_build[name]))
    if runs == 1 and batches > 1 and 0 < fetched <= _TOPN_LIMIT[name]:
        return
    _refuse(f"{name}'s first execution counted device_topn_runs {runs}, "
            f"device_join_topn_batches {batches} and device_topn_fetched_rows {fetched}: "
            f"it did not complete one fused TopN run over more than one fact batch that "
            f"fetched at most {_TOPN_LIMIT[name]} rows. This program does not keep the "
            "join's TopN on the device over a fact of many batches, which "
            "tpch-sf10-joins-1chip is the deployment of; the cell cannot run on it")


def _checked(name: str, query):
    def program(tables):
        _built[name] = _built.get(name, 0) + 1
        if _built[name] == 1:
            _at_first_build[name] = _topn_counts()
        elif _built[name] == 2:
            _require_the_fused_topn(name)
        return query(tables)

    program.__name__ = name
    return program


# name -> the program and the tables it reads (their rows are what an
# execution scans); `fact_columns` are the lineitem columns a template's join
# program references and `dims` the fact-adjacent dimensions it gathers from:
# what benchmark/joinbytes.py counts the least bytes of a dispatch from
TEMPLATES = {
    "q3": dict(_tpch.TEMPLATES["q3"], program=_checked("q3", _tpch.q3),
               fact_columns=("l_shipdate", "l_extendedprice", "l_discount"),
               gathered={"orders": 1}),
    "q5": dict(_tpch.TEMPLATES["q5"],
               fact_columns=("l_extendedprice", "l_discount"),
               gathered={"orders": 2, "supplier": 1}),
    "q10": {"program": _checked("q10", q10),
            "tables": ("customer", "orders", "lineitem", "nation"),
            "fact_columns": ("l_returnflag", "l_extendedprice", "l_discount"),
            "gathered": {"orders": 1}},
    "q14": {"program": q14, "tables": ("lineitem", "part"),
            "fact_columns": ("l_shipdate", "l_extendedprice", "l_discount"),
            "gathered": {"part": 1}},
}
