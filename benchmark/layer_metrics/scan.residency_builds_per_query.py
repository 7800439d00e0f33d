"""Residency slots built per execution of a scan over files: every plane of
every morsel of a stream misses (its `Series` was decoded a moment ago and
dies with the query), so this counts the planes an execution uploads. The
same counter as `residency.misses_per_query`, which stays with the cells
whose tables are resident, where it reads 0.

Source: the program's `hbm_cache_misses` counter, read around each execution.
"""


def read(ctx):
    runs = ctx["executions"]
    return sum(e["counters"].get("hbm_cache_misses", 0) for e in runs) / len(runs)
