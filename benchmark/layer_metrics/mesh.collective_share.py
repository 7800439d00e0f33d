"""Share of the busiest chip's busy seconds in the window that went to
collective operations (`all-reduce`, `all-gather`, `reduce-scatter`,
`collective-permute`, `all-to-all` in the operation's name). 0.0 where the
programs run no collective: the sharded stages combine their shards' partial
tables on the host.

Source: the `jax.profiler` trace. None where the trace has fewer than two
device planes.
"""

import meshtrace


def read(ctx):
    busy = meshtrace.busy_by_plane(ctx["trace"], ctx["window"])
    if len(busy) < 2 or not max(busy.values()):
        return None
    plane = max(busy, key=busy.get)
    return 100.0 * meshtrace.collective_seconds(ctx["trace"], plane, ctx["window"]) / busy[plane]
