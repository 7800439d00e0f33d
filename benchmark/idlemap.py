"""The device's idle seconds of a traced window, each put down to a layer of
the program: what the `idle.<layer>_ms` readers share.

`breakdown.idle_gaps` (`xtrace.attribute_gaps`) knows four span names and
calls the rest `host.other`. Here every idle stretch goes to the INNERMOST
span open over it, whatever its name (`spantree.owners` over all names, the
spans moved onto the trace's clock by `ctx["to_trace"]`), every span name
belongs to one layer of `PERF.md` section 3 (`LAYER_OF`), and idle time under
no span at all is the API's where it lies inside an execution (the DataFrame
calls that build the plan, `to_pydict`'s glue) and the client's where it lies
between two (the harness's own bookkeeping; a real client's think time). So
the layers' seconds add up to the idle seconds, with no remainder.

Idle means: no operation ran on ANY device plane of the trace. On one chip
that is `xtrace.gaps(ctx["busy"], ctx["window"])`, what `device.idle_share`
reads; on four chips a second in which one chip works is not idle, so the sum
is at most what the planes' mean (`device.idle_share` there) gives.

What the harness's sink does not keep limits the reading: a span has no
thread, so where spans of several threads overlap (the Parquet cell's scan
tasks and morsels) a stretch goes to the one that began last.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import spantree
import xtrace as tr

LAYERS = ("plan", "runner", "placement", "host_ops", "scan", "h2d", "dispatch",
          "launch", "d2h", "finalize", "api", "client")

# owners of idle time under no span: inside an execution, between executions
API, CLIENT = "(api)", "(client)"

# span name -> layer. A key that ends in "*" is a prefix; a name takes its
# exact key's layer, else its longest prefix's. `op.<Node>` names are the
# program's operators, whatever the node (`layer_of`).
LAYER_OF = {
    "plan.optimize": "plan", "plan.translate": "plan",
    "query": "runner", "query.open": "runner", "query.plan_key": "runner",
    "query.close": "runner",
    "placement.decide": "placement", "placement.calibrate": "placement",
    "op.*": "host_ops", "pipeline.morsel": "host_ops", "spill.*": "host_ops",
    "shuffle.*": "host_ops",
    "scan.plan": "scan", "scan.stream": "scan", "scan.decode": "scan",
    "load.from_arrow": "scan",
    "device.h2d": "h2d", "device.upload": "h2d", "device.upload.prepare": "h2d",
    "residency.build": "h2d", "series.dict_encode": "h2d",
    "series.fingerprint": "h2d", "device.coalesce_flush": "h2d",
    "device.dispatch": "dispatch", "device.literals": "dispatch",
    "join.range": "dispatch", "join.codes": "dispatch", "join.index": "dispatch",
    "join.gather": "dispatch", "join.membership": "dispatch",
    "join.filter": "dispatch", "join.shard": "dispatch", "join.tables": "dispatch",
    "join.pack_lines": "dispatch", "join.query_pack": "dispatch",
    "device.udf_*": "dispatch",
    "device.launch": "launch", "xla.trace": "launch", "xla.lower": "launch",
    "xla.compile": "launch",
    "device.d2h": "d2h",
    "stage.finalize": "finalize", "join.topn_select": "finalize",
    "join.combine": "finalize",
    "result.encode": "api", API: "api",
    CLIENT: "client",
}
_PREFIXES = sorted((k[:-1] for k in LAYER_OF if k.endswith("*")), key=len, reverse=True)


def layer_of(name: str) -> str:
    """The layer a span name belongs to. Raises KeyError for a name the table
    does not know: a span added later has to be given a layer, it cannot fall
    into a remainder (tests/benchmark_harness/test_bench_idlemap.py reads
    every name the program spells)."""
    if name in LAYER_OF:
        return LAYER_OF[name]
    for prefix in _PREFIXES:
        if name.startswith(prefix):
            return LAYER_OF[prefix + "*"]
    raise KeyError(f"idlemap.LAYER_OF has no layer for the span {name!r}")


def idle(ctx: dict) -> List[tr.Interval]:
    """The stretches of the window in which no operation ran on any device
    plane of the trace: disjoint, sorted, on the trace's clock."""
    planes = tr.device_ops(ctx["trace"])
    if len(planes) <= 1:
        busy = ctx["busy"]
    else:
        busy = tr.union([(s, s + d) for ops in planes.values() for _n, s, d in ops])
    return tr.gaps(busy, ctx["window"])


def by_owner(ctx: dict) -> Dict[str, float]:
    """{owner: idle seconds}: each idle stretch to the innermost span open
    over it, by name; where none is open, to `API` inside an execution's
    `unix_start`..`unix_end` and to `CLIENT` outside every one."""
    shift = ctx["to_trace"]
    owned = spantree.owners([(n, a + shift, b + shift) for n, a, b in ctx["spans"]])
    inside = tr.union([(e["unix_start"] + shift, e["unix_end"] + shift)
                       for e in ctx["executions"]])
    out: Dict[str, float] = {}

    def give(name: str, seconds: float) -> None:
        if seconds > 0:
            out[name] = out.get(name, 0.0) + seconds

    def bare(lo: float, hi: float) -> None:
        api = tr.length(tr.clip(inside, lo, hi))
        give(API, api)
        give(CLIENT, (hi - lo) - api)

    k = 0
    for lo, hi in idle(ctx):
        while k < len(owned) and owned[k][1] <= lo:
            k += 1
        at, j = lo, k
        while j < len(owned) and owned[j][0] < hi:
            a, b, name = owned[j]
            a, b = max(a, lo), min(b, hi)
            if a > at:
                bare(at, a)
            give(name, b - a)
            at = b
            j += 1
        if hi > at:
            bare(at, hi)
    return out


def layers(ctx: dict) -> Optional[Dict[str, float]]:
    """{layer: idle seconds} over `LAYERS`, every one present; their sum is
    `xtrace.length(idle(ctx))`. None where the run has no span tree. Kept in
    `ctx` once made: twelve readers ask for it."""
    if not spantree.has_tree(ctx["spans"]):
        return None
    if "idlemap.layers" not in ctx:
        out = dict.fromkeys(LAYERS, 0.0)
        for name, seconds in by_owner(ctx).items():
            out[layer_of(name)] += seconds
        ctx["idlemap.layers"] = out
    return ctx["idlemap.layers"]


def ms_per_execution(ctx: dict, layer: str) -> Optional[float]:
    """The layer's idle milliseconds an execution of the window (0.0 where it
    owned nothing); None where there is no span tree to read."""
    by_layer = layers(ctx)
    if by_layer is None:
        return None
    return 1e3 * by_layer[layer] / len(ctx["executions"])
