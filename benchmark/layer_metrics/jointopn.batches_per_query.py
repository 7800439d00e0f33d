"""Fact batches a fused join TopN run took in, averaged over the window's
runs: the fact's dispatch count where the group tables stay on the device
for the whole run (458 at SF10 with batches of 131,072 rows), 1 where the
fused program is held to one batch.

Source: the program's `device_join_topn_batches` and `device_topn_runs`
counters, read around each execution. None where no fused TopN run
completed, or from a program without the counters.
"""


def read(ctx):
    runs = sum(e["counters"].get("device_topn_runs", 0) for e in ctx["executions"])
    if not runs or not any("device_join_topn_batches" in e["counters"]
                           for e in ctx["executions"]):
        return None
    return sum(e["counters"].get("device_join_topn_batches", 0)
               for e in ctx["executions"]) / runs
