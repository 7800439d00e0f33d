"""Least bytes a filtered-join template's device dispatches read, from the
device arrays' own sizes: `joinbytes.py`'s count for a fact that is NOT
ordered by the dimension it gathers from, and for fact-side code and
membership planes.

A dispatch cannot do with less than:

- the value plane of each fact column the template's join program reads and
  each fact-side dictionary code plane it groups by (`fact_columns`,
  `fact_codes`): a batch-long plane of 4-byte items each;
- each fact-side string membership plane (`memberships`): a batch-long plane
  of 1-byte items;
- one int32 index plane for each fact-adjacent dimension it joins;
- from a dimension the fact follows (its gather reads a batch-long window of
  the pack), the values gathered to the fact's length: a 4-byte plane each,
  as `joinbytes.py` counts them;
- from a dimension the fact does NOT follow (`unwindowed`), the rows of the
  dimension's packed matrix that hold the gathered values, WHOLE: a dispatch
  of 2^20 indices drawn uniformly over the dimension's rows leaves no part of
  them unread. A row's `nbytes` is the least `nbytes / rows` among the live
  two-dimensional device arrays of 4-byte items at least as long as the
  dimension (the packs are `[planes, padded rows]`).

Every size is taken from the live device arrays (`jax.live_arrays()`), as in
`scanbytes.plane_nbytes`: the commonest one-dimensional array of 4-byte (of
1-byte) items is a dispatch's plane. Validity planes, the pack's other rows
and what the aggregate writes are left out, so the bytes are a floor and the
roofline share is never flattered.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, Optional, Sequence

import numpy as np

import scanbytes


def live_arrays() -> list:
    """(shape, dtype, nbytes) of every live device array."""
    import jax

    return [(a.shape, str(a.dtype), a.nbytes) for a in jax.live_arrays() if a.ndim in (1, 2)]


def byte_plane_nbytes(arrays: Sequence) -> int:
    """`nbytes` of one batch-long plane of 1-byte items (a membership or a
    validity plane); 0 where none is resident."""
    sizes = Counter(n for shape, dtype, n in arrays
                    if len(shape) == 1 and np.dtype(dtype).itemsize == 1)
    return sizes.most_common(1)[0][0] if sizes else 0


def pack_row_nbytes(arrays: Sequence, dim_rows: int) -> Optional[int]:
    """`nbytes` of one row of the packed matrix of a dimension of `dim_rows`
    rows; None where no such matrix is resident."""
    rows = [n // shape[0] for shape, dtype, n in arrays
            if len(shape) == 2 and shape[0] and shape[1] >= dim_rows
            and np.dtype(dtype).itemsize == 4]
    return min(rows) if rows else None


def dispatch_bytes(template: dict, arrays: Sequence, rows: Dict[str, int]) -> Optional[int]:
    """Least bytes one dispatch of `template` reads, from what the suite
    declares; None for a template that declares no join or whose unwindowed
    dimension has no pack on the device."""
    if "fact_columns" not in template:
        return None
    planes = [a for a in arrays if len(a[0]) == 1]
    plane = scanbytes.plane_nbytes(planes)
    gathered = template.get("gathered", {})
    unwindowed = template.get("unwindowed", ())
    total = plane * (len(template["fact_columns"]) + template.get("fact_codes", 0)
                     + len(gathered))
    total += byte_plane_nbytes(planes) * template.get("memberships", 0)
    for dim, values in gathered.items():
        if dim not in unwindowed:
            total += plane * values
            continue
        row = pack_row_nbytes(arrays, rows[dim])
        if row is None:
            return None
        total += row * values
    return total
