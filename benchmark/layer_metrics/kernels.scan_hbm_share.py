"""Share of the HBM roofline that the scan programs reach.

For every execution of a template that declares `scan_columns`: the least
time the chip could take to read those columns' resident planes once (their
bytes over the peak HBM bandwidth of `peaks.json`) over the seconds in which
an operation ran on the device inside that execution, from the trace. Summed
over the window's executions before dividing. The bytes come from the device
arrays' own `nbytes` (`benchmark/scanbytes.py`). These scans do a few operations
per value read, so the bound that applies is memory bandwidth, not compute:
the reader prints both least times.
"""

import json

import scanbytes
import xtrace as tr


def read(ctx):
    least_s, device_s, ops = 0.0, 0.0, 0.0
    for e in ctx["executions"]:
        columns = ctx["queries"][e["template"]].get("scan_columns")
        if not columns or e["failed"]:
            continue
        nbytes = scanbytes.scan_bytes(len(columns), ctx["big_arrays"])
        least_s += nbytes / ctx["peaks"]["hbm_bytes_per_s"]
        # a handful of compares, multiplies and adds per value read
        ops += 8.0 * nbytes / 4
        device_s += tr.busy_in(ctx["busy"], (e["unix_start"] + ctx["to_trace"],
                                              e["unix_end"] + ctx["to_trace"]))
    if not device_s:
        return None
    print(json.dumps({"phase": "roofline", "hbm_least_s": least_s,
                      "compute_least_s": ops / ctx["peaks"]["f32_flops_per_s"],
                      "bound": "hbm", "device_s": device_s}), flush=True)
    return 100.0 * least_s / device_s
