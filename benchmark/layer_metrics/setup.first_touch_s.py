"""Seconds of set-up the host spent on first touch: uploading column planes
and dictionary-encoding key columns, before the window.

Source: the program's `h2d_upload_us` and `dict_encode_us` counters: what the
process has counted, less what the window's executions added. The harness
installs its span sink only at the window, so set-up is read from counters.
None from a program without these counters.
"""

NAMES = ("h2d_upload_us", "dict_encode_us")


def read(ctx):
    from daft_tpu.ops import counters

    total = counters.snapshot()
    if not all(name in total for name in NAMES):
        return None
    in_window = sum(e["counters"].get(name, 0) for e in ctx["executions"] for name in NAMES)
    return (sum(total[name] for name in NAMES) - in_window) / 1e6
