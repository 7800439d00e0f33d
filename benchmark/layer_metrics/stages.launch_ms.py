"""Mean length of the program's `device.launch` spans in the window: the
call of the jitted program alone (the host's trace look-up and enqueue; the
call is asynchronous), inside `device.dispatch`.

Source: the program's spans (host clock). None where nothing launched.
"""

import spantree


def read(ctx):
    durs = [b - a for name, a, b in spantree.in_window(ctx["spans"], ctx["executions"])
            if name == "device.launch"]
    return 1e3 * sum(durs) / len(durs) if durs else None
