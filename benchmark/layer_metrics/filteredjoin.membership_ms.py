"""Milliseconds an execution spends looking up its fact-side string membership
planes (q12's and q19's `l_shipmode IN`, q19's `l_shipinstruct =`): one
residency look-up a plane a dispatch, a hit on a repeat query.

Source: the program's `join.membership` spans (host clock) in the window,
summed, over the window's executions. None from a program that has no such
span.
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "join.membership"]
    return 1e3 * sum(durs) / len(ctx["executions"]) if durs else None
