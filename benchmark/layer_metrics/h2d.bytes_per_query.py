"""Bytes uploaded to the device per execution in the window.

Source: the program's `hbm_h2d_bytes` counter, read around each execution.
Zero where every plane an execution needs stayed resident.
"""


def read(ctx):
    runs = ctx["executions"]
    return sum(e["counters"].get("hbm_h2d_bytes", 0) for e in runs) / len(runs)
