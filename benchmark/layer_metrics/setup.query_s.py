"""Seconds of set-up spent inside queries: the load's collects and the two
warm-up executions of each template. The whole that the other `setup.*`
metrics are parts of; `setup_s` less this is the process's start, the
generator and the harness.

Source: the program's `query_wall_us` counter (the wall time of every query,
`NativeRunner._run_iter`), total less the window's executions
(`setup_counters.py`). None from a program without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, (sc.WALL,))
