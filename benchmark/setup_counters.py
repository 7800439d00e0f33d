"""Set-up read from the program's cold counters.

The harness installs its span sink only at the window, so no span covers
set-up. What reaches a reader from there are the counters the program keeps
at its cold sites whether or not anything records (`timed_span(counter=...)`:
work that only a first execution does, counted in microseconds where it
happens) and `query_wall_us`, every query's wall time. A reader takes what
the process has counted, less what the window's executions added (the
harness's per-execution counter deltas): what is left was counted before the
window, in the load's collects and the warm-ups.

`NAMED` are the counters whose seconds `setup.unnamed_share` takes out of
`setup.query_s`. They are self times on a thread (a cold site counts its
extent less what the cold sites inside it counted: an upload inside a
residency build, a program built inside a calibration), so they add up;
`h2d_prepare_us` is a part of `h2d_upload_us` and is left out. A counter
summed over pool threads can exceed the wall time it ran in.
"""

WALL = "query_wall_us"
BUILD = ("jax_trace_us", "jax_lower_us", "xla_compile_us")
NAMED = ("h2d_upload_us", "dict_encode_us", "content_hash_us", "residency_build_us",
         "calibrate_us") + BUILD
# what coldreport.py prints the deltas of, phase by phase
COLD = ("h2d_prepare_us",) + NAMED + (WALL,)


def seconds_before_window(ctx, names):
    """Seconds the process counted under `names` (summed) before the window;
    None from a program without one of them."""
    from daft_tpu.ops import counters

    total = counters.snapshot()
    if not all(name in total for name in names):
        return None
    in_window = sum(e["counters"].get(name, 0) for e in ctx["executions"] for name in names)
    return (sum(total[name] for name in names) - in_window) / 1e6
