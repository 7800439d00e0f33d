"""Least bytes a join's visibility program moves, and the seconds its own
operations ran, for `layer_metrics/adhocjoin.filter_hbm_share.py`.

A visibility program makes one fact-adjacent dimension's filter verdict for a
query: it reads, once each, the planes its filters compare (the dimension's
own columns and its chained dimensions' columns carried into its row space: a
float32 or int32 plane a column) and the value-free verdict of the chain, and
writes the float32 verdict plane. A suite declares, per template, `filters`:
fact-adjacent dimension -> number of such 4-byte planes read. Each is as long
as the dimension padded, and that plane's `nbytes` is taken from the live
device arrays: the verdict itself is a float32 array of exactly that length
that no other table's planes share (the fact's batches are shorter, its
resident columns longer), so the commonest float32 or int32 one-dimensional
array whose length is the dimension's padded rows (the table's rows, which
the harness knows, padded to a power of two) is the plane. Validity planes and the chain's bool plane, 1 byte an
item, are left out: a floor.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import xtrace as tr

PROGRAM = "jit_join_filter_verdict"


def padded(rows: int) -> int:
    """A dimension's rows as the program pads them: the power of two at or
    above, 512 at least."""
    cap = 512
    while cap < rows:
        cap *= 2
    return cap


def least_bytes(template: dict, arrays: Sequence, rows: dict) -> Optional[int]:
    """Least bytes one execution's visibility programs move (`rows`: table ->
    its rows); None for a template that declares no `filters`, or where a
    dimension's planes are not on the device."""
    if "filters" not in template:
        return None
    total = 0
    for table, planes in template["filters"].items():
        want = 4 * padded(rows[table])
        if not any(n == want and len(shape) == 1 and dtype in ("float32", "int32")
                   for shape, dtype, n in arrays):
            return None
        total += (planes + 1) * want        # the planes read, the verdict written
    return total


def program_seconds(trace: dict, window: Tuple[float, float]) -> float:
    """Seconds inside `window` in which an operation of a visibility program
    ran on the first device plane: the `XLA Ops` events inside the `XLA
    Modules` events of the program's name (the module events themselves where
    the plane has no operations line). 0.0 where the trace names no such
    module."""
    for lines in trace["device"].values():
        modules = [(s, s + d) for name, s, d in lines.get("XLA Modules", ())
                   if name.startswith(PROGRAM)]
        mine = tr.clip(tr.union(modules), *window)
        if not mine:
            return 0.0
        ops = tr.union([(s, s + d) for _n, s, d in lines.get("XLA Ops", ())])
        if not ops:
            return tr.length(mine)
        return sum(tr.length(tr.clip(ops, lo, hi)) for lo, hi in mine)
    return 0.0
