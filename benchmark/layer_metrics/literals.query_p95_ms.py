"""95th percentile of the ad-hoc cell's execution times in the traced window:
`query_ms.p95`'s arithmetic (`benchmark/arith.py`) over the executions the
traced run made (that end-to-end metric keeps its list of cells, and the
traced window is `trace_seconds` long, so this is a per-layer reading and no
end-to-end one). Every execution runs other literal values than the one
before it: a stall on a fresh value (a trace, a compile, a decision made
again) moves this long before it moves the geomean.

Source: the harness's clock around each execution. None where no execution
completed.
"""

import arith


def read(ctx):
    if all(e["failed"] for e in ctx["executions"]):
        return None
    return arith.query_ms_p95(ctx["executions"])[0]
