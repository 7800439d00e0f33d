"""Seconds of set-up building programs, by JAX's own reports: tracing Python
to jaxprs, lowering them to MLIR modules, and XLA's compilation or its
retrieval from the persistent cache (`compile.setup_compile_s` is the last
part alone, counted by the harness). Self times: a report that holds
another counts only what is left.

Source: the program's `jax_trace_us`, `jax_lower_us` and `xla_compile_us`
counters, total less the window's executions. None from a program without
them.
"""

import setup_counters as sc


def read(ctx):
    return sc.seconds_before_window(ctx, sc.BUILD)
