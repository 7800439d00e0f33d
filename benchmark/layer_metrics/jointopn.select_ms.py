"""Mean length of the program's `join.topn_select` spans in the window: the
fused join TopN's finalize on the device (the sort operands from the group
tables, the selection of the K winners) with the K-row fetch that waits for
it, inside `stage.finalize`. The fetch waits for every dispatch still in
flight, so a run whose dispatches outpace the chip shows its backlog here.

Source: the program's spans (host clock). None where no such span was
recorded (no fused TopN ran, or the program has no such span).
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "join.topn_select"]
    return 1e3 * sum(durs) / len(durs) if durs else None
