"""Milliseconds per execution the host spends bringing a stream's planes to
the device: the self time of `device.h2d` (the stage's walk over its input
columns) and `device.upload` (padding, the float32 cast and the `device_put`
of one column) among themselves and `residency.build`, whose own bookkeeping
is left out. A resident table pays this once, at first touch; a scan over
files pays it in every execution.

Source: the program's spans (host clock). None where nothing was uploaded.
"""

import spantree

PARTS = ("device.h2d", "residency.build", "device.upload")


def read(ctx):
    spans = spantree.in_window(ctx["spans"], ctx["executions"])
    if not any(name in ("device.h2d", "device.upload") for name, _a, _b in spans):
        return None
    own = spantree.self_seconds(spans, PARTS)
    return 1e3 * (own.get("device.h2d", 0.0) + own.get("device.upload", 0.0)) \
        / len(ctx["executions"])
