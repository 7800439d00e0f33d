"""Milliseconds a device dispatch spends binding the execution's literal
values: converting them (dates to days), packing their words into one uint32
array, the stage program's last argument, once a run; and, in a
filter-aggregate run on one device that dispatches a second time, putting
that array on the device for the run's later launches.

Source: the program's `device.literals` spans (host clock) in the window,
summed, over the window's device dispatches (`device_stage_batches` and
`device_grouped_batches`, read around each execution). None from a program
that has no such span or where nothing dispatched.
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "device.literals"]
    dispatches = sum(e["counters"].get("device_stage_batches", 0)
                     + e["counters"].get("device_grouped_batches", 0)
                     for e in ctx["executions"])
    return 1e3 * sum(durs) / dispatches if durs and dispatches else None
