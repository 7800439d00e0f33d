"""Milliseconds per execution in `result.encode`: concatenating the result's
partitions and converting them to what the client asked for.

Source: the program's spans (host clock). None from a program without them.
"""

import spantree


def read(ctx):
    return spantree.ms_per_execution(
        ctx, lambda spans: spantree.covered_seconds(spans, ("result.encode",)))
