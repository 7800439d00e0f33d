"""Devices a JOIN dispatch spanned, averaged over the window's join
dispatches that spanned more than one: 4.0 where every one ran the single
chip's programs on a shard a chip.

Source: the program's `device_join_mesh_shards` and
`device_join_mesh_batches` counters, read around each execution. None where
no join dispatch spanned more than one device, or from a program without the
counters.
"""


def read(ctx):
    runs = ctx["executions"]
    batches = sum(e["counters"].get("device_join_mesh_batches", 0) for e in runs)
    if not batches:
        return None
    return sum(e["counters"].get("device_join_mesh_shards", 0) for e in runs) / batches
