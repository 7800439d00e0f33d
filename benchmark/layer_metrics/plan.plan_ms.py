"""Milliseconds per execution in `plan.optimize` and `plan.translate`: the
logical optimizer and the translation to a physical plan, which every query
pays before anything executes.

Source: the program's spans (host clock). None from a program without them.
"""

import spantree

NAMES = ("plan.optimize", "plan.translate")


def read(ctx):
    return spantree.ms_per_execution(
        ctx, lambda spans: spantree.covered_seconds(spans, NAMES))
