"""Milliseconds an execution in which no operation ran on the device while the
innermost open span belonged to the call of a jitted program (`device.launch`,
and `xla.trace`, `xla.lower`, `xla.compile` where one is built).

One of the twelve `idle.*_ms` (`benchmark/idlemap.py`): together they are the
idle part of a mean execution, with no remainder. 0.0 where the layer owned no
idle time.

Source: the `jax.profiler` trace for the idle stretches, the program's spans
moved onto the trace's clock for who owned them. None from a program without
the span tree.
"""

import idlemap


def read(ctx):
    return idlemap.ms_per_execution(ctx, "launch")
