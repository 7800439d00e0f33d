"""Rows a fused join TopN run's finalize brought back from the device,
averaged over the window's runs: at most the query's LIMIT (10 for q3, 20 for
q10), whatever the number of groups in the tables.

Source: the program's `device_topn_fetched_rows` and `device_topn_runs`
counters, read around each execution. None where no fused TopN run
completed, or from a program without the counters.
"""


def read(ctx):
    runs = sum(e["counters"].get("device_topn_runs", 0) for e in ctx["executions"])
    if not runs or not any("device_topn_fetched_rows" in e["counters"]
                           for e in ctx["executions"]):
        return None
    return sum(e["counters"].get("device_topn_fetched_rows", 0)
               for e in ctx["executions"]) / runs
