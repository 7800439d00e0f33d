"""The `tpch_filtered_joins` suite: the specification's filtered joins Q12,
Q14 and Q19 over tables that stay loaded at SF10 on one chip.

q12 and q19 are `queries/tpch.py`'s own and q14 is `queries/tpch_joins10.py`'s,
letter for letter (the specification's text with its validation parameters).
Nothing here tells the program where to run a query: the deployment
(`configs/tpch-sf10-filtered-joins-1chip.json`) runs the shipped defaults, and
`auto` has to choose. The three are the join shapes no other cell runs on the
device at a size that fills it: a global aggregate over a join (q14, q19), a
grouped join whose codes are a FACT column's dictionary (q12 groups by
`l_shipmode`), fact-side string membership planes (q12, q19), a synthetic
dimension column (q14's `p_type` prefix), a predicate hoisted out of a
disjunction that spans fact and dimension (q19), and a gather from a dimension
the fact is NOT ordered by: `l_partkey` is uniform over `part`'s 2,000,000
rows, so every dispatch of q14 and q19 reads the whole of `part`'s pack.

What the suite adds is its own check, in the manner of the other suites:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counters `join_priced_dispatch_rows` and `join_unwindowed_gathers`. A
  program without them prices a join dispatch over a resident fact at a
  horizon the run stopped delivering (one bucket where eight are dispatched),
  `auto` keeps q12 and q19 on the host tier, and the cell would measure the
  host's join. That is the parent of the PR that added the cell: it fails at
  once and cleanly.
- On a TPU, in warm-up: a template's first execution has to have dispatched
  its join on the device (`device_join_batches` > 0), and each of q14's and
  q19's dispatches has to have counted an unwindowed gather
  (`join_unwindowed_gathers` == `device_join_batches`); a template's second
  execution has to have counted no residency miss (`hbm_cache_misses`) and no
  traced program (`join_provision_traces`, `join_filter_program_traces`,
  `device_stage_program_traces`). Each is looked at when the next template is
  built (the last one's second execution at the window's first build); else
  the suite prints why and exits 1. On any other backend (the tier-1 tests
  run the suite on the CPU, where `auto` never uses the device) nothing is
  checked.

This file uses only the program's public DataFrame API: the client's side.
"""

from __future__ import annotations

import importlib.util
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))

_COUNTERS = ("join_priced_dispatch_rows", "join_unwindowed_gathers")
# the templates whose fact is not ordered by the dimension they gather from
_UNWINDOWED = ("q14", "q19")
# what a second execution may not move
_WARM = ("hbm_cache_misses", "join_provision_traces", "join_filter_program_traces",
         "device_stage_program_traces")
_CHECKED = ("device_join_batches", "join_unwindowed_gathers") + _WARM


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_filtered_joins.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counters() -> None:
    from daft_tpu.observability import metrics

    missing = [c for c in _COUNTERS if c not in metrics.DEVICE_COUNTER_NAMES]
    if missing:
        _refuse(f"the program does not declare the counter(s) {missing} "
                "(daft_tpu/observability/metrics.py): it prices a join dispatch over a "
                "resident fact at a horizon the run does not deliver, auto keeps q12 and "
                "q19 on the host tier, and tpch-sf10-filtered-joins-1chip is the "
                "deployment whose filtered joins run on the device; the cell cannot run "
                "on it")


_require_the_counters()


def _suite(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_queries_" + name, os.path.join(_HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_tpch = _suite("tpch")
_joins10 = _suite("tpch_joins10")

_QUERIES = {"q12": _tpch.q12, "q14": _joins10.q14, "q19": _tpch.q19}
_built = {}
_at_build = {}     # template -> the counters at each of its builds
_pending = []      # (template, execution number) still to be looked at


def _counts():
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return {c: snap.get(c, 0) for c in _CHECKED}


def _on_a_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _why_not(name: str, execution: int, grown: dict) -> str:
    """Why one warm-up execution, whose counters grew by `grown`, is not this
    deployment's ("" where it is)."""
    if execution == 1:
        batches = grown["device_join_batches"]
        if batches <= 0:
            return "dispatched no join on the device (device_join_batches 0)"
        if name in _UNWINDOWED and grown["join_unwindowed_gathers"] != batches:
            return (f"counted join_unwindowed_gathers {grown['join_unwindowed_gathers']} "
                    f"over device_join_batches {batches}: its dispatches did not each "
                    "gather from the whole of part's pack")
        return ""
    moved = {c: grown[c] for c in _WARM if grown[c]}
    if moved:
        return f"counted {moved}: a repeat missed a resident slot or traced a program"
    return ""


def _look_at_what_ran(now: dict) -> None:
    """See the module's docstring: the executions in `_pending` are over."""
    on_tpu = _on_a_tpu()
    while _pending:
        name, execution, before = _pending.pop(0)
        if not on_tpu:
            continue
        why = _why_not(name, execution, {c: now[c] - before[c] for c in _CHECKED})
        if why:
            _refuse(f"{name}'s warm-up execution {execution} {why}; "
                    "tpch-sf10-filtered-joins-1chip is the deployment whose filtered "
                    "joins run on the device over resident tables; the cell cannot run "
                    "on it")


def _checked(name: str):
    def program(tables):
        now = _counts()
        _look_at_what_ran(now)
        _built[name] = _built.get(name, 0) + 1
        if _built[name] <= 2:
            _pending.append((name, _built[name], now))
        return _QUERIES[name](tables)

    program.__name__ = name
    return program


# name -> the program and the tables it reads (their rows are what an
# execution scans). What benchmark/filteredjoinbytes.py counts the least bytes
# of a dispatch from: `fact_columns` are the lineitem value planes a
# template's join program reads, `fact_codes` its fact-side dictionary code
# planes (q12 groups by `l_shipmode`), `memberships` its fact-side string
# membership planes, `gathered` the fact-adjacent dimension and the columns
# of it the query reads (`o_orderpriority`; `p_type`; `p_brand`, `p_container`,
# `p_size`), `unwindowed` the dimensions whose whole pack a dispatch gathers
# from (the fact is not ordered by their key)
TEMPLATES = {
    "q12": {"program": _checked("q12"), "tables": ("orders", "lineitem"),
            "fact_columns": ("l_commitdate", "l_receiptdate", "l_shipdate"),
            "fact_codes": 1, "memberships": 1, "gathered": {"orders": 1}, "unwindowed": ()},
    "q14": {"program": _checked("q14"), "tables": ("lineitem", "part"),
            "fact_columns": ("l_shipdate", "l_extendedprice", "l_discount"),
            "fact_codes": 0, "memberships": 0, "gathered": {"part": 1},
            "unwindowed": ("part",)},
    "q19": {"program": _checked("q19"), "tables": ("lineitem", "part"),
            "fact_columns": ("l_quantity", "l_extendedprice", "l_discount"),
            "fact_codes": 0, "memberships": 2, "gathered": {"part": 3},
            "unwindowed": ("part",)},
}
