"""Arrow bytes decoded per second of decoding: the window's
`scan_decoded_bytes` (the `nbytes` of every record batch pyarrow returned)
over the summed length of its `scan.decode` spans, so a thread's rate, not
the scan's.

Source: the program's counter, read around each execution, and its spans.
None from a program without them (before PR 28).
"""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_scan_decode_ms", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                         "scan.decode_ms.py"))
_decode = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_decode)


def read(ctx):
    decoded = sum(e["counters"].get("scan_decoded_bytes", 0) for e in ctx["executions"])
    seconds = _decode.seconds(ctx)
    if not decoded or not seconds:
        return None
    return decoded / seconds
