"""Share of the HBM roofline that the filtered-join programs reach.

For every execution of a template that declares its join: the least time the
chip could take to read what its dispatches need
(`benchmark/filteredjoinbytes.py`: fact value, code and membership planes, one
int32 index plane a fact-adjacent dimension, the gathered values of a
dimension the fact follows, and for a dimension it does not follow the rows of
the whole pack that hold them; each from the live device arrays' `nbytes`;
times the execution's join dispatches, from the `device_join_batches`
counter) over the peak HBM bandwidth of `peaks.json`, against the seconds in
which an operation ran on the device inside that execution, from the trace.
Summed over the window's executions before dividing. A floor, bound by memory
bandwidth; it cannot pass 100%. None where no join dispatched or no operation
ran on the device.
"""

import json

import filteredjoinbytes
import xtrace as tr


def read(ctx):
    arrays = filteredjoinbytes.live_arrays()
    least_s, device_s, nbytes_all = 0.0, 0.0, 0
    for e in ctx["executions"]:
        dispatches = e["counters"].get("device_join_batches", 0)
        if e["failed"] or not dispatches or not arrays:
            continue
        per_dispatch = filteredjoinbytes.dispatch_bytes(
            ctx["queries"][e["template"]], arrays, ctx["rows"])
        if per_dispatch is None:
            continue
        nbytes_all += dispatches * per_dispatch
        least_s += dispatches * per_dispatch / ctx["peaks"]["hbm_bytes_per_s"]
        device_s += tr.busy_in(ctx["busy"], (e["unix_start"] + ctx["to_trace"],
                                              e["unix_end"] + ctx["to_trace"]))
    if not device_s:
        return None
    print(json.dumps({"phase": "roofline", "filtered_join_least_bytes": nbytes_all,
                      "hbm_least_s": least_s, "bound": "hbm", "device_s": device_s}),
          flush=True)
    return 100.0 * least_s / device_s
