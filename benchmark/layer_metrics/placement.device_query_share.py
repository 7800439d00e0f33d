"""Share of the window's executions that dispatched to the device at all.

Source: the program's `device_stage_batches` and `device_grouped_batches`
counters, read around each execution. Moves `query_ms.geomean`: what `auto`
chooses decides which tier's time the client sees.
"""


def read(ctx):
    runs = ctx["executions"]
    on_device = sum(1 for e in runs if e["counters"].get("device_stage_batches", 0)
                    + e["counters"].get("device_grouped_batches", 0) > 0)
    return 100.0 * on_device / len(runs)
