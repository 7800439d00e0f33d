"""Milliseconds per execution that belong to the physical operators
themselves: the self time of the `op.*` spans, with every span opened inside
them (device stages, placement, residency, uploads) taken out. On the host
tier this is the host kernels; on the device tier, what the operator does
around its device stage.

Source: the program's spans (host clock). None from a program without them.
"""

import spantree


def read(ctx):
    def seconds(spans):
        own = spantree.self_seconds(spans)
        return sum(s for name, s in own.items() if name.startswith("op."))

    return spantree.ms_per_execution(ctx, seconds)
