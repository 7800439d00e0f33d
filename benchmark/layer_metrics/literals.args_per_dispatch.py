"""Literal values passed to a stage program, per device dispatch in the
window: q6 passes five (two dates, two discounts, a quantity), q1 four (the
date and the three 1's of its aggregates).

Source: the program's `device_literal_args` counter (the values passed, summed
over launches) over its `device_stage_batches` and `device_grouped_batches`
counters, read around each execution. None from a program without the counter
or where nothing dispatched.
"""


def read(ctx):
    from daft_tpu.ops import counters

    if "device_literal_args" not in counters.snapshot():
        return None
    runs = ctx["executions"]
    dispatches = sum(e["counters"].get("device_stage_batches", 0)
                     + e["counters"].get("device_grouped_batches", 0) for e in runs)
    if not dispatches:
        return None
    return sum(e["counters"].get("device_literal_args", 0) for e in runs) / dispatches
