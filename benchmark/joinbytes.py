"""Least bytes a star-join template's device dispatches read, from the device
arrays' own sizes.

A resident fact reaches the join program as batches (morsels) of equal
length. A dispatch cannot do with less than: the value plane of each fact
column the template references, one int32 index plane for each fact-adjacent
dimension it joins, and the dimension values it gathers to the fact's length
(a float32 row of the dimension's packed matrix a value, a group code or a
join-validity mark). Each is one plane of a batch's padded length and four
bytes an item, and that plane's `nbytes` is taken from the live device
arrays: the commonest one-dimensional array of 4-byte items is a batch's
plane, as in `scanbytes.plane_nbytes` (validity planes, 1 byte an item, are
left out, as are the tables the aggregate writes, so the bytes are a floor and
the roofline share is never flattered).
"""

from __future__ import annotations

from typing import Optional, Sequence

import scanbytes


def live_planes() -> list:
    """(shape, dtype, nbytes) of every live one-dimensional device array."""
    import jax

    return [(a.shape, str(a.dtype), a.nbytes) for a in jax.live_arrays() if a.ndim == 1]


def planes_per_dispatch(template: dict) -> Optional[int]:
    """Planes of a batch's length one dispatch of `template` reads at least,
    from what the suite declares (`fact_columns`, `gathered`: fact-adjacent
    dimension -> values gathered from it); None for a template that declares
    no join."""
    if "fact_columns" not in template:
        return None
    gathered = template.get("gathered", {})
    return len(template["fact_columns"]) + len(gathered) + sum(gathered.values())


def dispatch_bytes(template: dict, arrays: Sequence) -> Optional[int]:
    planes = planes_per_dispatch(template)
    return None if planes is None else planes * scanbytes.plane_nbytes(arrays)
