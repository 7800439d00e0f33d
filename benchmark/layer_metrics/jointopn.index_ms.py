"""Milliseconds per join dispatch spent finding the index planes (`join.index`) in the SF10 join cell: `join.index_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `join.index_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("join.index_ms")
