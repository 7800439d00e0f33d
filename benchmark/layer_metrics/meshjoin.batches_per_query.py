"""Join dispatches that spanned the mesh, per execution that made any: the
fact's rows over a dispatch's (a shard a chip of the one-chip cell's batch,
131,072 rows: 524,288 rows a dispatch, about 344 at SF30).

Source: the program's `device_join_mesh_batches` counter, read around each
execution. None where no join dispatch spanned more than one device, or from
a program without the counter.
"""


def read(ctx):
    per = [e["counters"].get("device_join_mesh_batches", 0) for e in ctx["executions"]]
    per = [n for n in per if n]
    return sum(per) / len(per) if per else None
