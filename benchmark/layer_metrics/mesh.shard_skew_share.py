"""How unevenly the chips of the mesh were busy in the window: 1 - the least
over the greatest busy seconds among the device planes of the trace. 0% where
every chip worked as long as the others; a chip that idles while another
works its shard shows here (rows are sharded in order, so a predicate on a
column that follows the row order empties some shards).

Source: the `jax.profiler` trace, one plane a device. None where the trace
has fewer than two device planes.
"""

import json

import meshtrace


def read(ctx):
    busy = meshtrace.busy_by_plane(ctx["trace"], ctx["window"])
    if len(busy) < 2 or not max(busy.values()):
        return None
    print(json.dumps({"phase": "mesh", "busy_s_by_plane": busy}), flush=True)
    return 100.0 * (1.0 - min(busy.values()) / max(busy.values()))
