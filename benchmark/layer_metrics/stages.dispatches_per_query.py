"""Device dispatches per execution in the window.

Source: the program's `device_stage_batches` and `device_grouped_batches`
counters, read around each execution.
"""


def read(ctx):
    runs = ctx["executions"]
    return sum(e["counters"].get("device_stage_batches", 0)
               + e["counters"].get("device_grouped_batches", 0) for e in runs) / len(runs)
