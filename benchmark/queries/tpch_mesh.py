"""The `tpch_mesh` suite: `queries/tpch.py`'s q1 and q6, unchanged, for a
deployment whose `lineitem` is row-sharded over the four chips of a host.

The templates are the scan cell's own, letter for letter, over the DataFrames
the harness collected: nothing here tells the program to use a mesh. The
deployment (`configs/tpch-sf30-4chip.json`) runs the shipped defaults, and it
is the program's `auto` placement that has to spread 180 M rows over the four
chips. What the suite adds is its own check that it did, in the manner of
`queries/tpch_parquet.py`:

- When this file is imported (the harness does so before it makes any data)
  it exits 1, naming what is missing, if the program does not declare the
  counters `device_mesh_batches` and `device_mesh_shards`: a program without
  them cannot say how many devices a dispatch spanned, and the cell's
  per-layer metrics read them. That is the parent of the PR that added the
  cell: it fails at once and cleanly.
- On a TPU with four devices or more, when a template is built for the second
  time (its first warm-up execution is then over), that execution has to have
  counted at least one `device_mesh_batches` and four `device_mesh_shards`
  for each: every dispatch spanned the four chips. A program that keeps SF30
  on one chip (or on the host) does not run this deployment; the suite prints
  why and exits 1, before the window. On any other backend (the tier-1 tests
  run the suite on the CPU, where `auto` never uses the device) nothing is
  checked.
"""

from __future__ import annotations

import importlib.util
import os
import sys

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

CHIPS = 4
_COUNTERS = ("device_mesh_batches", "device_mesh_shards")


def _refuse(why: str) -> None:
    why = "benchmark/queries/tpch_mesh.py: " + why
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _require_the_counters() -> None:
    from daft_tpu.observability import metrics

    missing = [c for c in _COUNTERS if c not in metrics.DEVICE_COUNTER_NAMES]
    if missing:
        _refuse(f"the program does not declare the counter(s) {missing} "
                "(daft_tpu/observability/metrics.py): it cannot say how many devices a "
                "dispatch spanned, and tpch-sf30-4chip is the deployment in which every "
                "dispatch spans the four chips; the cell cannot run on it")


_require_the_counters()

_built = {}
_at_first_build = {}


def _mesh_counts():
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return tuple(snap.get(c, 0) for c in _COUNTERS)


def _on_four_chips() -> bool:
    import jax

    return jax.default_backend() == "tpu" and len(jax.devices()) >= CHIPS


def _require_the_mesh(name: str) -> None:
    """See the module's docstring: `name`'s first execution is over; on four
    chips every dispatch of it has to have spanned them."""
    if not _on_four_chips():
        return
    batches, shards = (now - then for now, then in
                       zip(_mesh_counts(), _at_first_build[name]))
    if batches > 0 and shards == CHIPS * batches:
        return
    _refuse(f"{name}'s first execution counted device_mesh_batches {batches} and "
            f"device_mesh_shards {shards}: its dispatches did not each span {CHIPS} "
            "chips. This program keeps lineitem on one chip or on the host, and "
            "tpch-sf30-4chip is the deployment whose lineitem is row-sharded over the "
            "four chips of the host; the cell cannot run on it")


def _checked(name: str):
    query = getattr(_tpch, name)

    def program(tables):
        _built[name] = _built.get(name, 0) + 1
        if _built[name] == 1:
            _at_first_build[name] = _mesh_counts()
        elif _built[name] == 2:
            _require_the_mesh(name)
        return query(tables)

    program.__name__ = name
    return program


TEMPLATES = {
    name: dict(_tpch.TEMPLATES[name], program=_checked(name))
    for name in ("q1", "q6")
}
