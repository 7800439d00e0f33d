"""Milliseconds per execution in `scan.decode`: each pull of a reader's batch
iterator (read, decompress, decode, wrap as a morsel). The span is a leaf, so
its length is its self time; the tasks of a scan decode on several threads of
`compute_pool`, and their spans are summed, not merged: it is the work, not
the time the query waited for it.

Source: the program's spans (host clock). None from a program without them
(before PR 28), or where no execution decoded a file.
"""

import spantree

NAME = "scan.decode"


def seconds(ctx):
    """Summed length of the window's `scan.decode` spans; None without any."""
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == NAME]
    return sum(durs) if durs else None


def read(ctx):
    s = seconds(ctx)
    return None if s is None else 1e3 * s / len(ctx["executions"])
