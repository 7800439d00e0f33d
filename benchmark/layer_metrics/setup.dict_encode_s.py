"""Seconds of set-up dictionary-encoding key columns (`Series.dict_codes`),
summed over the threads that encoded. First touch's third part.

Source: the program's `dict_encode_us` counter, total less the window's
executions (the join cell's host tier encodes fresh slices in every query).
None from a program without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, ("dict_encode_us",))
