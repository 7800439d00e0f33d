"""Milliseconds per join dispatch spent probing the dimensions: `join.index`,
the host probe of each dim key and the padded index plane, both looked up in
the residency manager per probe Series.

The window's self time of `join.index` among the spans of the join dispatch
(`spantree.JOIN_PARTS`), over the number of `device.dispatch` spans that
hold a `join.*` span.

Source: the program's spans (host clock). None where no join dispatched.
"""

import spantree


def read(ctx):
    return spantree.join_part_ms(ctx, "join.index")
