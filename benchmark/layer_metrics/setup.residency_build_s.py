"""Seconds of set-up in residency misses building their values, less the
uploads, dictionary encodes and program builds inside them, which count
themselves: a key column's code plane padded and put, a join's index and
packed planes.

Source: the program's `residency_build_us` counter, total less the window's
executions (a streamed scan builds in every query). None from a program
without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, ("residency_build_us",))
