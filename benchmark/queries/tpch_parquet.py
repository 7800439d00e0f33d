"""The `tpch_parquet` suite: `queries/tpch.py`'s q1 and q6 over Parquet files.

How a deployment that loads its data differently enters the benchmark without
an edit to a file that is there: as a *suite* whose templates build their own
DataFrames. The harness hands every template the DataFrames it made with
`from_arrow(t).collect()` in set-up; a template of this suite ignores them,
builds `{"lineitem": daft_tpu.read_parquet(<the files>)}` and returns
`queries/tpch.py`'s own `q1` or `q6` over it, so the query text is the scan
cell's, letter for letter, and every execution plans the scan, reads and
decodes the files and uploads what the device tier needs. What the suite's
files share (where the generator put the files) lives in a plain module
under `benchmark/`, `parquet_store`, imported by name.

The harness's `from_arrow(t).collect()` of `lineitem` still happens in
set-up and is unused: at SF1 it costs `setup_s` about half a second and the
host 0.9 GB, touches no column and uploads nothing.

What the deployment asks of the program. `configs/tpch-sf1-parquet-1chip.json`
states that every execution uploads what the device tier needs, and the
benchmark admits no cell in which nothing ran on the device (a traced window
without a device operation has no device plane, and `xtrace.busy_seconds`
raises after the window). A program whose `auto` placement keeps the
streamed q1 on the host tier, as it did before PR 28, therefore does not run
this deployment. The suite says so itself, on a TPU, when q1 is built for
the second time (q1's first warm-up execution is then over): it exits 1
with the reason, before the window, instead of letting the run die in the
trace's reader after it. On another backend (the tier-1 tests run the suite
on the CPU, where `auto` never uses the device) nothing is checked.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import parquet_store

_spec = importlib.util.spec_from_file_location(
    "bench_queries_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

_DEVICE_BATCHES = ("device_grouped_batches", "device_stage_batches")
_built = {"q1": 0}
_batches_at_start = None


def _device_batches() -> int:
    from daft_tpu.ops import counters

    snap = counters.snapshot()
    return sum(snap.get(k, 0) for k in _DEVICE_BATCHES)


def _require_the_device_path() -> None:
    """See the module's docstring: q1's first execution is over; on a TPU it
    has to have dispatched on the device."""
    import jax

    if jax.default_backend() != "tpu" or _device_batches() > _batches_at_start:
        return
    why = ("benchmark/queries/tpch_parquet.py: q1 over read_parquet ran without a "
           "device dispatch: this program keeps a streamed scan on the host tier, and "
           "tpch-sf1-parquet-1chip is the deployment in which every execution uploads "
           "what the device tier needs; the cell cannot run on it")
    print(why, flush=True)
    print(why, file=sys.stderr, flush=True)
    raise SystemExit(1)


def _over_files(name: str):
    query = getattr(_tpch, name)

    def program(_collected):
        import daft_tpu as dt

        global _batches_at_start
        if name in _built:
            if _batches_at_start is None:
                _batches_at_start = _device_batches()
            _built[name] += 1
            if _built[name] == 2:
                _require_the_device_path()
        return query({"lineitem": dt.read_parquet(parquet_store.paths("lineitem"))})

    program.__name__ = name
    return program


TEMPLATES = {
    name: dict(_tpch.TEMPLATES[name], program=_over_files(name))
    for name in ("q1", "q6")
}
