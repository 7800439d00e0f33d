"""Mean length of the program's `join.combine` spans in the window: a fused
join TopN run's end on the chips (the all-to-all that hands every chip its
slice of each chip's group tables, their sum, and the chip's select over its
slice), waited for, inside `join.topn_select` and `stage.finalize`. The wait
covers every dispatch still in flight, as `jointopn.select_ms` says of the
one-chip select.

Source: the program's spans (host clock). None where no such span was
recorded (no run's tables were combined across chips, or the program has no
such span).
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "join.combine"]
    return 1e3 * sum(durs) / len(durs) if durs else None
