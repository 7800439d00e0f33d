"""Milliseconds per join dispatch spent choosing and building group codes:
`join.codes`, the dictionary product before the dispatch, then the dictionary
combine or the host factorize inside it.

The window's self time of `join.codes` among the spans of the join dispatch
(`spantree.JOIN_PARTS`), over the number of `device.dispatch` spans that
hold a `join.*` span.

Source: the program's spans (host clock). None where no join dispatched.
"""

import spantree


def read(ctx):
    return spantree.join_part_ms(ctx, "join.codes")
