"""The `tpch_joins_mesh` suite: the specification's star-join queries Q3, Q5
and Q10 for a deployment whose `lineitem` is row-sharded over the four chips
of a host.

The templates are `queries/tpch_joins10.py`'s own, letter for letter (q3 and
q5 `queries/tpch.py`'s, q10 that file's), over the DataFrames the harness
collected: nothing here tells the program to use a mesh. The deployment
(`configs/tpch-sf30-joins-4chip.json`) runs the shipped defaults, and it is
the program's `auto` placement that has to spread 180 M fact rows over the
four chips and keep q3's and q10's group tables there. What the suite adds is
its own check that it did, in the manner of `queries/tpch_mesh.py` and
`queries/tpch_joins10.py`:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counters `device_join_mesh_batches`, `device_join_mesh_shards` and
  `device_topn_combine_bytes`. A program without them runs a join dispatch on
  one chip (or in a mesh tier whose TopN takes a fact of one batch): it
  cannot say how many devices a join dispatch spanned or what a run's
  cross-chip combine moved, and the cell's per-layer metrics read them. That
  is the parent of the PR that added the cell: it fails at once and cleanly.
- On a TPU host of four chips or more, when a template is built for the
  second time (its first warm-up execution is then over), that execution has
  to have dispatched joins (`device_join_batches`) every one of which spanned
  the four chips (`device_join_mesh_batches` equal to it,
  `device_join_mesh_shards` four times it); q3 and q10 have to have completed
  ONE fused TopN run (`device_topn_runs`) that took in more than one fact
  batch (`device_join_topn_batches`) and fetched no more than `CHIPS x K`
  rows (`device_topn_fetched_rows`: K a chip, merged on the host). Else the
  suite prints why and exits 1, before the window. On any other backend (the
  tier-1 tests run the suite on the CPU, where `auto` never uses the device)
  nothing is checked.
"""

from __future__ import annotations

import importlib.util
import os
import sys

CHIPS = 4
_COUNTERS = ("device_join_mesh_batches", "device_join_mesh_shards",
             "device_topn_combine_bytes")


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_joins_mesh.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counters() -> None:
    from daft_tpu.observability import metrics

    missing = [c for c in _COUNTERS if c not in metrics.DEVICE_COUNTER_NAMES]
    if missing:
        _refuse(f"the program does not declare the counter(s) {missing} "
                "(daft_tpu/observability/metrics.py): its join dispatch runs on one "
                "chip, and tpch-sf30-joins-4chip is the deployment whose lineitem is "
                "row-sharded over the four chips of the host, every join dispatch "
                "spanning them; the cell cannot run on it")


_require_the_counters()   # before the one-chip suite's own import-time check

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch_joins10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch_joins10.py"))
_j10 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_j10)

# the fused TopN templates and the rows their LIMIT allows ONE chip's select to
# hand back (a finalize fetches that many from each of the four)
_TOPN_LIMIT = dict(_j10._TOPN_LIMIT)
_QUERIES = {"q3": _j10._tpch.q3, "q5": _j10._tpch.q5, "q10": _j10.q10}
_CHECKED = ("device_join_batches", "device_join_mesh_batches", "device_join_mesh_shards",
            "device_topn_runs", "device_join_topn_batches", "device_topn_fetched_rows")

_built = {}
_at_first_build = {}


def _counts():
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return tuple(snap.get(c, 0) for c in _CHECKED)


def _on_four_chips() -> bool:
    import jax

    return jax.default_backend() == "tpu" and len(jax.devices()) >= CHIPS


def _why_not(name: str, counts: dict) -> str:
    """"" where `counts` (the counters' growth over `name`'s first execution)
    are those of the deployment, else what is wrong with them."""
    joins, spanned, shards = (counts[c] for c in _CHECKED[:3])
    if not (joins > 0 and spanned == joins and shards == CHIPS * joins):
        return (f"device_join_batches {joins}, device_join_mesh_batches {spanned} and "
                f"device_join_mesh_shards {shards}: its join dispatches did not each "
                f"span {CHIPS} chips")
    if name in _TOPN_LIMIT:
        runs, batches, fetched = (counts[c] for c in _CHECKED[3:])
        most = CHIPS * _TOPN_LIMIT[name]
        if not (runs == 1 and batches > 1 and 0 < fetched <= most):
            return (f"device_topn_runs {runs}, device_join_topn_batches {batches} and "
                    f"device_topn_fetched_rows {fetched}: it did not complete one fused "
                    f"TopN run over more than one fact batch that fetched at most "
                    f"{most} rows ({_TOPN_LIMIT[name]} a chip)")
    return ""


def _require_the_mesh_join(name: str) -> None:
    """See the module's docstring: `name`'s first execution is over."""
    if not _on_four_chips():
        return
    counts = {c: now - then for c, now, then in
              zip(_CHECKED, _counts(), _at_first_build[name])}
    why = _why_not(name, counts)
    if why:
        _refuse(f"{name}'s first execution counted {why}. This program keeps the join "
                "on one chip, on the host, or in a mesh tier that holds a TopN to one "
                "fact batch, and tpch-sf30-joins-4chip is the deployment whose lineitem "
                "is row-sharded over the four chips with the run's group tables "
                "combined there; the cell cannot run on it")


def _checked(name: str):
    query = _QUERIES[name]

    def program(tables):
        _built[name] = _built.get(name, 0) + 1
        if _built[name] == 1:
            _at_first_build[name] = _counts()
        elif _built[name] == 2:
            _require_the_mesh_join(name)
        return query(tables)

    program.__name__ = name
    return program


# name -> the program, the tables it reads, and what benchmark/joinbytes.py
# counts the least bytes of a dispatch from (`fact_columns`, `gathered`): the
# one-chip suite's declarations, the program its own
TEMPLATES = {name: dict(_j10.TEMPLATES[name], program=_checked(name))
             for name in ("q3", "q5", "q10")}
