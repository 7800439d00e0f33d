"""Milliseconds per join dispatch spent provisioning the joined columns:
`join.gather`, the packed dim planes, the row gathers on the device and the
plane assembly (`_JoinContext.provision` less the `join.index` inside it).

The window's self time of `join.gather` among the spans of the join dispatch
(`spantree.JOIN_PARTS`), over the number of `device.dispatch` spans that
hold a `join.*` span.

Source: the program's spans (host clock). None where no join dispatched.
"""

import spantree


def read(ctx):
    return spantree.join_part_ms(ctx, "join.gather")
