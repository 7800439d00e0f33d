"""Share of the window's idle seconds (no operation running on the device)
over which no span of the program other than the `query` root is open: the
part of the device's wait that the program cannot name.

Source: the `jax.profiler` trace for the idle gaps, the program's spans moved
onto the trace's clock for what was open in them. None where the program
recorded no span.
"""

import spantree
import xtrace as tr


def read(ctx):
    named = [(a + ctx["to_trace"], b + ctx["to_trace"])
             for a, b in spantree.covered(ctx["spans"], lambda n: n != spantree.ROOT)]
    if not named:
        return None
    idle = tr.gaps(ctx["busy"], ctx["window"])
    idle_s = tr.length(idle)
    if not idle_s:
        return 0.0
    attributed = sum(tr.length(tr.clip(named, lo, hi)) for lo, hi in idle)
    return 100.0 * (idle_s - attributed) / idle_s
