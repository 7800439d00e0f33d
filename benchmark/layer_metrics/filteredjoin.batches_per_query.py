"""Join dispatches an execution made on the device: the fact's ranges of
eight segments (58 at SF10: 57 of 2^20 rows and a tail).

Source: the program's `device_join_batches` counter, read around each
execution, over the window's executions. None where no join dispatched on the
device.
"""


def read(ctx):
    runs = ctx["executions"]
    total = sum(e["counters"].get("device_join_batches", 0) for e in runs)
    return total / len(runs) if total else None
