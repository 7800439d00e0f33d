"""Rows a dispatch the join's chosen device arm was PRICED at over the rows a
dispatch was DELIVERED, over the window's executions that ran their join on
the device.

Priced: the program's `join_priced_dispatch_rows` counter, bumped once a
costed join decision by the rows a dispatch the chosen arm was priced at.
Delivered: the rows of the template's fact table (the largest it reads) over
the execution's `device_join_batches`. Both summed over the executions before
dividing. 1.0 where every dispatch is as long as the price assumed; a fact
whose last dispatch is short reads a little over it (58 dispatches of 2^20
rows over 60.0 M: 1.014). A program that prices one bucket and dispatches
eight reads 0.13.

Source: the program's counters, read around each execution. None where no
join was priced and dispatched on the device, or from a program without the
counter.
"""


def read(ctx):
    priced, delivered = 0.0, 0.0
    for e in ctx["executions"]:
        batches = e["counters"].get("device_join_batches", 0)
        rows = e["counters"].get("join_priced_dispatch_rows", 0)
        if e["failed"] or not batches or not rows:
            continue
        fact = max(ctx["rows"][t] for t in ctx["queries"][e["template"]]["tables"])
        priced += rows
        delivered += fact / batches
    return priced / delivered if delivered else None
