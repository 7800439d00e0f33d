"""Seconds of set-up putting prepared planes on the device: an upload's time
(`h2d_upload_us`) less its preparation (`h2d_prepare_us`, which lies inside
it). First touch's transfer part.

Source: those two counters, each total less the window's executions. None
from a program without either.
"""

import setup_counters as sc


def read(ctx):
    upload = sc.seconds_before_window(ctx, ("h2d_upload_us",))
    prepare = sc.seconds_before_window(ctx, ("h2d_prepare_us",))
    if upload is None or prepare is None:
        return None
    return upload - prepare
