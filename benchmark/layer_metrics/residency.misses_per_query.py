"""Residency look-ups per execution that found nothing and built (and, for a
plane, uploaded) their value. Zero where everything an execution needs stayed
resident; the `residency.build` spans name the slot kinds behind a miss.

Source: the program's `hbm_cache_misses` counter, read around each execution.
"""


def read(ctx):
    runs = ctx["executions"]
    return sum(e["counters"].get("hbm_cache_misses", 0) for e in runs) / len(runs)
