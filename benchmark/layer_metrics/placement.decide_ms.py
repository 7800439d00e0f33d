"""Milliseconds per execution in `placement.decide`: the cost model choosing
a tier (the peek of the first partition and the pricing). The deciders nest,
so overlapping spans count once.

Source: the program's spans (host clock). None from a program without them.
"""

import spantree


def read(ctx):
    return spantree.ms_per_execution(
        ctx, lambda spans: spantree.covered_seconds(spans, ("placement.decide",)))
