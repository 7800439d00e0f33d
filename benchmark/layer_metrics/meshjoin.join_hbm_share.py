"""Share of the mesh's HBM roofline that the sharded star-join programs reach.

`jointopn.join_hbm_share` for a fact sharded over several chips. For every
execution of a template that declares its join (`fact_columns`, `gathered`):
the least time the chips could take to read what its dispatches need, each
chip its own shard at its own peak bandwidth (`benchmark/joinbytes.py`'s
planes a dispatch x one batch-long plane's GLOBAL `nbytes`, from the live
device arrays, x the execution's join dispatches, from the
`device_join_batches` counter, over devices x the peak HBM bandwidth of
`peaks.json`), against the seconds in which an operation ran on the BUSIEST
chip inside that execution (`benchmark/meshtrace.py`, as
`mesh.scan_hbm_share` divides: the chips work at once, and the slowest
decides when the answer is there). Summed over the window's executions
before dividing. The bytes the run-wide tables' combine moves, and the tables
themselves, are left out, so the share is a floor, bound by memory bandwidth,
and cannot pass 100%.

Also prints, for whoever reads the run, the peak bytes in use of every chip
and the process's peak resident set.

Source: the `jax.profiler` trace. None where the trace has fewer than two
device planes, no join dispatch spanned them, or nothing ran on them.
"""

import json
import resource

import joinbytes
import meshtrace
import xtrace as tr


def _say_memory():
    import jax

    print(json.dumps({
        "phase": "memory",
        "peak_hbm_bytes_by_device": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()],
        "peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}),
        flush=True)


def read(ctx):
    planes = meshtrace.busy_unions(ctx["trace"])
    if len(planes) < 2:
        return None
    _say_memory()
    arrays = joinbytes.live_planes()
    least_s, device_s, nbytes_all = 0.0, 0.0, 0
    for e in ctx["executions"]:
        dispatches = e["counters"].get("device_join_batches", 0)
        if e["failed"] or not arrays or not dispatches \
                or not e["counters"].get("device_join_mesh_batches", 0):
            continue
        per_dispatch = joinbytes.dispatch_bytes(ctx["queries"][e["template"]], arrays)
        if per_dispatch is None:
            continue
        nbytes_all += dispatches * per_dispatch
        least_s += dispatches * per_dispatch / (len(planes) * ctx["peaks"]["hbm_bytes_per_s"])
        inside = (e["unix_start"] + ctx["to_trace"], e["unix_end"] + ctx["to_trace"])
        device_s += max(tr.busy_in(busy, inside) for busy in planes.values())
    if not device_s:
        return None
    print(json.dumps({"phase": "roofline", "devices": len(planes),
                      "join_least_bytes": nbytes_all, "hbm_least_s": least_s,
                      "bound": "hbm", "device_s": device_s}), flush=True)
    return 100.0 * least_s / device_s
