"""Milliseconds per execution the host spends in `stage.finalize` outside
the `device.d2h` fetch inside it: merging the fetched tables, decoding group
keys, building the result columns.

Source: the program's spans (host clock). None from a program without them.
"""

import spantree


def read(ctx):
    return spantree.ms_per_execution(
        ctx, lambda spans: spantree.self_seconds(
            spans, ("stage.finalize", "device.d2h")).get("stage.finalize", 0.0))
