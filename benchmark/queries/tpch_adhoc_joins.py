"""The `tpch_adhoc_joins` suite: TPC-H's star joins Q3, Q5 and Q10 with
substitution parameters, over tables that stay loaded at SF10 on one chip.

`queries/tpch_joins10.py` runs the specification's *validation* parameters,
the same in every execution. This suite runs the three queries as an analyst
who changes the segment, the region or the quarter of a report sends them, and
as the specification's throughput streams do: each template is the text of
`tpch_joins10`'s q3, q5 or q10 with one draw of the query's substitution
parameters (`adhoc_join_params`) in place of the validation values, the draws
are made from the run's seed, and the window runs the 12 templates (4 draws a
query) in turn, so every execution of a query runs other values than the one
before it. The deployment is `configs/tpch-sf10-adhoc-joins-1chip.json`.

What the template-a-draw form cannot show is the first sight of a value: it
falls in warm-up, so in `setup_s`, not in the window. The suite makes up for
it with a check of its own, in the manner of `queries/tpch_adhoc.py` and
`queries/tpch_joins10.py`:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counters `hbm_literal_rebuilds`, `join_filter_program_traces` and
  `join_filter_literal_args`: a program that cannot say what a dimension
  filter's value cost it cannot show that it cost no rebuild and no trace.
  That is the parent of the PR that added the cell: it fails at once and
  cleanly.
- On a TPU, when a template is built for the second time (its first execution
  is then over), that first execution has to have dispatched its join on the
  device, kept q3's and q10's fused TopN run (one run over more than one fact
  batch that fetched no more rows than the query's limit) and, unless it was
  the first execution of its *query*, counted no `hbm_literal_rebuilds`, no
  `join_filter_program_traces`, no `join_provision_traces` and no
  `device_stage_program_traces`: a value is an argument, never a rebuilt slot
  or a traced program. Else the suite prints why and exits 1, before the
  window. On any other backend (the tier-1 tests run the suite on the CPU,
  where `auto` never uses the device) nothing is checked.

This file uses only the program's public DataFrame API: the client's side.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import adhoc_join_params
from daft_tpu import col, lit

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

_COUNTERS = ("hbm_literal_rebuilds", "join_filter_program_traces",
             "join_filter_literal_args")
# the fused TopN templates and the rows their LIMIT allows a finalize to fetch
_TOPN_LIMIT = {"q3": 10, "q10": 20}
# what a value may not move once its query has run
_PER_VALUE = ("hbm_literal_rebuilds", "join_filter_program_traces",
              "join_provision_traces", "device_stage_program_traces")
_CHECKED = ("device_join_batches", "device_topn_runs", "device_join_topn_batches",
            "device_topn_fetched_rows") + _PER_VALUE


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_adhoc_joins.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counters() -> None:
    from daft_tpu.observability import metrics

    missing = [c for c in _COUNTERS if c not in metrics.DEVICE_COUNTER_NAMES]
    if missing:
        _refuse(f"the program does not declare the counter(s) {missing} "
                "(daft_tpu/observability/metrics.py): it cannot say what a dimension "
                "filter's value cost it, and tpch-sf10-adhoc-joins-1chip is the deployment "
                "in which a star join with a new SEGMENT, REGION or DATE rebuilds no slot "
                "and traces no program; the cell cannot run on it")


_require_the_counters()


def q3(t, p: adhoc_join_params.Q3):
    """`queries/tpch.py`'s q3 with SEGMENT and DATE (in both comparisons)."""
    C, O, L = t["customer"], t["orders"], t["lineitem"]
    return (
        C.where(col("c_mktsegment") == p.segment)
        .join(O, left_on="c_custkey", right_on="o_custkey")
        .where(col("o_orderdate") < lit(p.date))
        .join(L, left_on="o_orderkey", right_on="l_orderkey")
        .where(col("l_shipdate") > lit(p.date))
        .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
        .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
        .sort(["revenue", "o_orderdate"], desc=[True, False])
        .limit(10)
    )


def q5(t, p: adhoc_join_params.Q5):
    """`queries/tpch.py`'s q5 with REGION and [DATE, DATE + 1 year)."""
    C, O, L, S, N, R = t["customer"], t["orders"], t["lineitem"], t["supplier"], t["nation"], t["region"]
    return (
        R.where(col("r_name") == p.region)
        .join(N, left_on="r_regionkey", right_on="n_regionkey")
        .join(C, left_on="n_nationkey", right_on="c_nationkey")
        .join(O, left_on="c_custkey", right_on="o_custkey")
        .where((col("o_orderdate") >= lit(p.start)) & (col("o_orderdate") < lit(p.end)))
        .join(L, left_on="o_orderkey", right_on="l_orderkey")
        # supplier must be in the same nation as the customer
        .join(S, left_on=["l_suppkey", "n_nationkey"], right_on=["s_suppkey", "s_nationkey"])
        .groupby("n_name")
        .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
        .sort("revenue", desc=True)
    )


def q10(t, p: adhoc_join_params.Q10):
    """`queries/tpch_joins10.py`'s q10 over [DATE, DATE + 3 months)."""
    C, O, L, N = t["customer"], t["orders"], t["lineitem"], t["nation"]
    return (
        O.where((col("o_orderdate") >= lit(p.start)) & (col("o_orderdate") < lit(p.end)))
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


_QUERIES = {"q3": q3, "q5": q5, "q10": q10}
_built = {}
_at_first_build = {}
_ran_first = set()   # queries one of whose templates has run


def _counts():
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return {c: snap.get(c, 0) for c in _CHECKED}


def _on_a_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _why_not(query: str, first_of_its_query: bool, grown: dict) -> str:
    """Why one first execution, whose counters grew by `grown`, is not this
    deployment's ("" where it is)."""
    if grown["device_join_batches"] <= 0:
        return "dispatched no join on the device (device_join_batches 0)"
    if query in _TOPN_LIMIT and not (
            grown["device_topn_runs"] == 1 and grown["device_join_topn_batches"] > 1
            and 0 < grown["device_topn_fetched_rows"] <= _TOPN_LIMIT[query]):
        return ("did not complete one fused TopN run over more than one fact batch that "
                f"fetched at most {_TOPN_LIMIT[query]} rows (device_topn_runs "
                f"{grown['device_topn_runs']}, device_join_topn_batches "
                f"{grown['device_join_topn_batches']}, device_topn_fetched_rows "
                f"{grown['device_topn_fetched_rows']})")
    moved = {c: grown[c] for c in _PER_VALUE if grown[c]}
    if moved and not first_of_its_query:
        return (f"counted {moved} after another {query} had run: this program rebuilds a "
                "slot or traces a program for a query's literal values")
    return ""


def _require_a_value_costs_nothing(name: str, query: str) -> None:
    """See the module's docstring: `name`'s first execution is over."""
    now = _counts()
    grown = {c: now[c] - _at_first_build[name][c] for c in _CHECKED}
    first_of_its_query = query not in _ran_first
    _ran_first.add(query)
    if not _on_a_tpu():
        return
    why = _why_not(query, first_of_its_query, grown)
    if why:
        _refuse(f"{name}'s first execution ({adhoc_join_params.of(name)}) {why}; "
                "tpch-sf10-adhoc-joins-1chip is the deployment whose analysts choose the "
                "segment, the region and the dates of a star join over a loaded "
                "warehouse; the cell cannot run on it")


def _template(name: str):
    query = name.partition(".")[0]

    def program(tables):
        _built[name] = _built.get(name, 0) + 1
        if _built[name] == 1:
            _at_first_build[name] = _counts()
        elif _built[name] == 2:
            _require_a_value_costs_nothing(name, query)
        return _QUERIES[query](tables, adhoc_join_params.of(name))

    program.__name__ = name.replace(".", "_")
    return program


# what benchmark/joinbytes.py counts the least bytes of a dispatch from
# (`queries/tpch_joins10.py`'s own declarations, a query's templates alike),
# and `filters`: fact-adjacent dimension -> the 4-byte planes its visibility
# program reads (benchmark/filterbytes.py: `o_orderdate`, and the segment's or
# the region's dictionary codes carried to `orders`' rows)
_SHAPES = {
    "q3": dict(_tpch.TEMPLATES["q3"],
               fact_columns=("l_shipdate", "l_extendedprice", "l_discount"),
               gathered={"orders": 1}, filters={"orders": 2}),
    "q5": dict(_tpch.TEMPLATES["q5"],
               fact_columns=("l_extendedprice", "l_discount"),
               gathered={"orders": 2, "supplier": 1}, filters={"orders": 2}),
    "q10": {"tables": ("customer", "orders", "lineitem", "nation"),
            "fact_columns": ("l_returnflag", "l_extendedprice", "l_discount"),
            "gathered": {"orders": 1}, "filters": {"orders": 1}},
}

TEMPLATES = {
    name: dict(_SHAPES[name.partition(".")[0]], program=_template(name))
    for name in adhoc_join_params.template_names()
}
