"""The `tpch_adhoc` suite: TPC-H's Q1 and Q6 with substitution parameters.

`queries/tpch.py` runs the specification's *validation* parameters, the same
in every execution. This suite runs the queries as a dashboard's viewers or
the specification's throughput test send them: each template is the text of
`queries/tpch.py`'s q1 or q6 with one draw of the query's substitution
parameters (`adhoc_params`) in place of the validation value, the draws are
made from the run's seed, and the window runs the 24 templates (12 draws a
query) in turn, so every execution runs other values than the one before it.
The deployment is `configs/tpch-sf10-adhoc-1chip.json`.

What the template-a-draw form cannot show is the first sight of a value: it
falls in warm-up, so in `setup_s`, not in the window. The suite makes up for
it with a check of its own, in the manner of `queries/tpch_mesh.py`:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counter `device_stage_program_traces`: a program that cannot say how many
  stage programs it traced cannot show that a new value traces none. That is
  the parent of the PR that added the cell: it fails at once and cleanly.
- On a TPU, when a template is built for the second time (its first execution
  is then over), that first execution has to have traced no stage program,
  unless it was the first execution of its *query* (the process's first q1
  or first q6 compiles the query's one program; with the templates in the
  traffic's order that is: no more traces than when the first template of the
  same query had finished its first execution). A program that traces a
  program a value does not run this deployment; the suite prints why and
  exits 1, before the window. On any other backend (the tier-1 tests run the
  suite on the CPU, where `auto` never uses the device) nothing is checked.

This file uses only the program's public DataFrame API: the client's side.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import adhoc_params
from daft_tpu import col, lit

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

_COUNTER = "device_stage_program_traces"


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_adhoc.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counter() -> None:
    from daft_tpu.observability import metrics

    if _COUNTER not in metrics.DEVICE_COUNTER_NAMES:
        _refuse(f"the program does not declare the counter {_COUNTER!r} "
                "(daft_tpu/observability/metrics.py): it cannot say how many stage "
                "programs it traced, and tpch-sf10-adhoc-1chip is the deployment in "
                "which a query with new literal values traces none; the cell cannot "
                "run on it")


_require_the_counter()


def q1(t, p: adhoc_params.Q1):
    """`queries/tpch.py`'s q1, `l_shipdate <= date '1998-12-01' - DELTA days`."""
    L = t["lineitem"]
    return (
        L.where(col("l_shipdate") <= lit(p.cutoff))
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


def q6(t, p: adhoc_params.Q6):
    """`queries/tpch.py`'s q6 over [DATE, DATE + 1 year), DISCOUNT -+ 0.01
    (written to two places, see `adhoc_params`) and `l_quantity < QUANTITY`."""
    L = t["lineitem"]
    return (
        L.where(
            (col("l_shipdate") >= lit(p.start)) & (col("l_shipdate") < lit(p.end))
            & (col("l_discount") >= p.low) & (col("l_discount") <= p.high)
            & (col("l_quantity") < p.quantity)
        )
        .agg((col("l_extendedprice") * col("l_discount")).sum().alias("revenue"))
    )


_QUERIES = {"q1": q1, "q6": q6}
_built = {}
_at_first_build = {}
_ran_first = set()   # queries one of whose templates has run


def _traces() -> int:
    from daft_tpu.ops import counters

    return counters.snapshot().get(_COUNTER, 0)


def _on_a_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _require_no_trace_for_a_value(name: str, query: str) -> None:
    """See the module's docstring: `name`'s first execution is over."""
    traced = _traces() - _at_first_build[name]
    first_of_its_query = query not in _ran_first
    _ran_first.add(query)
    if first_of_its_query or traced <= 0 or not _on_a_tpu():
        return
    _refuse(f"{name}'s first execution ({adhoc_params.of(name)}) traced {traced} stage "
            f"program(s) ({_COUNTER}) after another {query} had run: this program "
            "traces, and so compiles, a program for a query's literal values, and "
            "tpch-sf10-adhoc-1chip is the deployment whose viewers choose them; the "
            "cell cannot run on it")


def _template(name: str):
    query = name.partition(".")[0]

    def program(tables):
        _built[name] = _built.get(name, 0) + 1
        if _built[name] == 1:
            _at_first_build[name] = _traces()
        elif _built[name] == 2:
            _require_no_trace_for_a_value(name, query)
        return _QUERIES[query](tables, adhoc_params.of(name))

    program.__name__ = name.replace(".", "_")
    return program


TEMPLATES = {
    name: dict(_tpch.TEMPLATES[name.partition(".")[0]], program=_template(name))
    for name in adhoc_params.template_names()
}
