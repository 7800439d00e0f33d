"""Milliseconds per join dispatch spent provisioning the joined columns (`join.gather`, with the `join.membership` look-ups and the first dispatch's `join.filter` inside it): `join.gather_ms`'s reader, as it is, for the filtered join cell (that metric's list of
cells cannot take the cell).

Source: as `join.gather_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("join.gather_ms")
