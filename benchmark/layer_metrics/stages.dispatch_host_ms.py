"""Mean length of the program's `device.dispatch` spans in the window.

Host clock (`time.time()` at both ends): the host's side of one dispatch,
never device time. Nothing to read where no execution dispatched.
"""


def read(ctx):
    lo = ctx["window"][0] - ctx["to_trace"]
    durs = [b - a for name, a, b in ctx["spans"] if name == "device.dispatch" and a >= lo]
    return 1e3 * sum(durs) / len(durs) if durs else None
