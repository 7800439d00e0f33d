"""Literal values an execution passes to its visibility programs: q3 two (the
segment's dictionary code and the order date), q5 three (two dates and the
region's code), q10 two dates.

Source: the program's `join_filter_literal_args` counter (the values passed,
summed over calls), read around each execution, over the window's executions.
None from a program without the counter.
"""


def read(ctx):
    from daft_tpu.ops import counters

    runs = ctx["executions"]
    if "join_filter_literal_args" not in counters.snapshot() or not runs:
        return None
    return sum(e["counters"].get("join_filter_literal_args", 0) for e in runs) / len(runs)
