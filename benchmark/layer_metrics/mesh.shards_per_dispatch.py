"""Devices a dispatch spanned, averaged over the window's dispatches.

Source: the program's `device_mesh_shards` and `device_mesh_batches`
counters (a dispatch whose rows were sharded over more than one device bumps
the second by one and the first by the devices), read around each execution.
None where no dispatch spanned more than one device: a one-chip run, or a
program that has no such counters.
"""


def read(ctx):
    runs = ctx["executions"]
    batches = sum(e["counters"].get("device_mesh_batches", 0) for e in runs)
    if not batches:
        return None
    return sum(e["counters"].get("device_mesh_shards", 0) for e in runs) / batches
