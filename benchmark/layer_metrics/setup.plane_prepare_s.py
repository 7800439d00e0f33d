"""Seconds of set-up making the padded host planes of the columns uploaded:
Arrow to numpy, the float32 cast, the pad to the bucket, the validity plane.
First touch's host-only part.

Source: the program's `h2d_prepare_us` counter (`device.upload.prepare`),
total less the window's executions. None from a program without the counter.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, ("h2d_prepare_us",))
