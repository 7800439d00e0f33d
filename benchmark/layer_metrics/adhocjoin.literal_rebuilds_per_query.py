"""Residency slots an execution found under their key holding another query's
literal values, and therefore rebuilt in place: what a dimension filter's
SEGMENT, REGION or DATE costs where no program takes it as an argument (the
dimension's packed planes rebuilt whole, op by op, before this deployment's
PR). 0 where every value is a program's argument.

Source: the program's `hbm_literal_rebuilds` counter, read around each
execution, over the window's executions. None from a program without the
counter.
"""


def read(ctx):
    from daft_tpu.ops import counters

    runs = ctx["executions"]
    if "hbm_literal_rebuilds" not in counters.snapshot() or not runs:
        return None
    return sum(e["counters"].get("hbm_literal_rebuilds", 0) for e in runs) / len(runs)
