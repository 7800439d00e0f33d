"""Milliseconds per join dispatch inside `join.shard`: finding (in a warm
window) or building the batch's own fact planes laid out over the mesh, a
shard a chip, inside `device.dispatch`.

Source: the program's spans (host clock). None where no such span was
recorded (no dispatch spanned a mesh, or the program has no such span).
"""

import spantree


def read(ctx):
    spans = spantree.in_window(ctx["spans"], ctx["executions"])
    shard_s = sum(b - a for name, a, b in spans if name == "join.shard")
    dispatches = len(spantree.join_dispatches(spans))
    if not shard_s or not dispatches:
        return None
    return 1e3 * shard_s / dispatches
