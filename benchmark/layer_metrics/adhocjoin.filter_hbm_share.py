"""Share of the HBM roofline that the join's visibility programs reach.

For every execution of a template that declares its filters (`filters`:
fact-adjacent dimension's table -> planes its visibility program reads): the least
time the chip could take to read those planes and write the verdict once
(`benchmark/filterbytes.py`, from the live device arrays' own `nbytes`) over
the peak HBM bandwidth of `peaks.json`, against the seconds of the visibility
programs' OWN device operations inside that execution: the trace's `XLA
Modules` events named `jit_join_filter_verdict`, which is what the program
calls the jitted function. Summed over the window's executions before
dividing. A floor (validity planes, 1 byte an item, are left out), bound by
memory bandwidth; it never divides by a window the operations may fall
outside of, so it cannot pass 100%. None where the trace has no such event
(a device whose trace does not name modules, or a program without the
visibility program).
"""

import json

import filterbytes
import joinbytes


def read(ctx):
    arrays = joinbytes.live_planes()
    least_s, device_s, nbytes_all = 0.0, 0.0, 0
    for e in ctx["executions"]:
        if e["failed"]:
            continue
        ran = filterbytes.program_seconds(ctx["trace"], (e["unix_start"] + ctx["to_trace"],
                                                         e["unix_end"] + ctx["to_trace"]))
        nbytes = filterbytes.least_bytes(ctx["queries"][e["template"]], arrays, ctx["rows"])
        if not ran or nbytes is None:
            continue
        nbytes_all += nbytes
        least_s += nbytes / ctx["peaks"]["hbm_bytes_per_s"]
        device_s += ran
    if not device_s:
        return None
    print(json.dumps({"phase": "roofline", "filter_least_bytes": nbytes_all,
                      "hbm_least_s": least_s, "bound": "hbm", "device_s": device_s}),
          flush=True)
    return 100.0 * least_s / device_s
