"""Milliseconds an execution spends making its filter verdicts: binding the
query's values (a string's dictionary code looked up, dates to days, one
small array), finding the resident planes and calling the visibility program,
once a filtered fact-adjacent dimension, inside the execution's first join
dispatch.

Source: the program's `join.filter` spans (host clock) in the window, summed,
over the window's executions. None from a program that has no such span.
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "join.filter"]
    return 1e3 * sum(durs) / len(ctx["executions"]) if durs else None
