"""Bytes a scan has to read, from the device arrays' own sizes.

The program keeps each referenced column of a loaded table as a plane on the
device, padded to a bucket of rows. `arrays` is (shape, dtype, nbytes) of
every live device array at least as long as the cell's largest table, taken
with `jax.live_arrays()` once warm-up has made the planes resident. A value
plane is the commonest such array of 4-byte items or wider (f32 values, int32
dates and codes); validity planes (1 byte) are left out, so the bytes are a
floor and the roofline share is never flattered.
"""

from __future__ import annotations

from collections import Counter
from typing import Sequence, Tuple

import numpy as np

Array = Tuple[tuple, str, int]


def plane_nbytes(arrays: Sequence[Array]) -> int:
    """`nbytes` of one value plane."""
    sizes = Counter(n for _shape, dtype, n in arrays if np.dtype(dtype).itemsize >= 4)
    if not sizes:
        raise ValueError("no value plane is resident on the device")
    return sizes.most_common(1)[0][0]


def scan_bytes(n_columns: int, arrays: Sequence[Array]) -> int:
    """Least bytes one scan of `n_columns` resident columns reads."""
    return n_columns * plane_nbytes(arrays)
