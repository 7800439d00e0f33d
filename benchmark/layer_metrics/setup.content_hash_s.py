"""Seconds of set-up hashing columns' content for their stable residency
keys (`Series.content_fingerprint`: every column once, at its first residency
miss): the price of an identity that only data arriving under a new object
(another process's copy, a table loaded twice) can use.

Source: the program's `content_hash_us` counter, total less the window's
executions. None from a program without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, ("content_hash_us",))
