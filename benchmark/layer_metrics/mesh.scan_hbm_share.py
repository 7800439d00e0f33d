"""Share of the mesh's HBM roofline that the sharded scan programs reach.

`kernels.scan_hbm_share` for a table sharded over several chips. For every
execution of a template that declares `scan_columns`: the least time the
chips could take to read those columns' resident planes once, each chip its
own shard at its own peak bandwidth (columns x one value plane's global
`nbytes`, `benchmark/scanbytes.py`, over devices x the peak HBM bandwidth of
`peaks.json`), over the seconds in which an operation ran on the *busiest*
chip inside that execution: the chips work at once, and the slowest decides
when the answer is there. Summed over the window's executions before
dividing. `xtrace.busy_union` reads the first device plane only, so the
reader unions each plane itself (`benchmark/meshtrace.py`). The bound that
applies is memory bandwidth, not compute: the reader prints both least times.

Source: the `jax.profiler` trace. None where the trace has fewer than two
device planes, or nothing ran on them.
"""

import json

import meshtrace
import scanbytes
import xtrace as tr


def read(ctx):
    planes = meshtrace.busy_unions(ctx["trace"])
    if len(planes) < 2:
        return None
    least_s, device_s, ops = 0.0, 0.0, 0.0
    for e in ctx["executions"]:
        columns = ctx["queries"][e["template"]].get("scan_columns")
        if not columns or e["failed"]:
            continue
        nbytes = scanbytes.scan_bytes(len(columns), ctx["big_arrays"])
        least_s += nbytes / (len(planes) * ctx["peaks"]["hbm_bytes_per_s"])
        # a handful of compares, multiplies and adds per value read
        ops += 8.0 * nbytes / 4
        inside = (e["unix_start"] + ctx["to_trace"], e["unix_end"] + ctx["to_trace"])
        device_s += max(tr.busy_in(busy, inside) for busy in planes.values())
    if not device_s:
        return None
    print(json.dumps({"phase": "roofline", "devices": len(planes), "hbm_least_s": least_s,
                      "compute_least_s": ops / (len(planes) * ctx["peaks"]["f32_flops_per_s"]),
                      "bound": "hbm", "device_s": device_s}), flush=True)
    return 100.0 * least_s / device_s
