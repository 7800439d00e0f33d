"""Seconds of set-up in the cost model's live probes (`costmodel.calibrate`:
round trip, upload and download rates, and on a host of several chips the
mesh terms), once a process.

Source: the program's `calibrate_us` counter, total less the window's
executions. None from a program without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, ("calibrate_us",))
