"""Share of the traced window in which no operation ran on the device.

Source: the `jax.profiler` trace, reduced by `benchmark/xtrace.py`.
"""

import xtrace as tr


def read(ctx):
    return 100.0 * tr.idle_share(ctx["trace"], ctx["window"])
