"""Mean length of the `device.launch` spans of a window whose dispatches
spanned several devices: the host's call of one program on every chip of the
mesh (the trace look-up and the enqueue on each device; the call is
asynchronous). The one-chip cells read the same span as `stages.launch_ms`.

Source: the program's spans (host clock), and its `device_mesh_batches`
counter to know that the launches were a mesh's. None where no dispatch
spanned more than one device.
"""

import spantree


def read(ctx):
    if not any(e["counters"].get("device_mesh_batches", 0) for e in ctx["executions"]):
        return None
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "device.launch"]
    return 1e3 * sum(durs) / len(durs) if durs else None
