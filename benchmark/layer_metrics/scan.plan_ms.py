"""Milliseconds per execution in `scan.plan`: the glob in front of the scan
operator, the first footer (the schema) and `to_scan_tasks` (footers, zone
maps, the split by `scan_split_bytes`), which a query over files pays before
a byte is decoded.

Source: the program's spans (host clock). None from a program without them
(before PR 28), or where no execution scanned a file.
"""

import spantree

NAME = "scan.plan"


def read(ctx):
    spans = spantree.in_window(ctx["spans"], ctx["executions"])
    if not any(name == NAME for name, _a, _b in spans):
        return None
    return 1e3 * spantree.covered_seconds(spans, (NAME,)) / len(ctx["executions"])
