"""Milliseconds per join dispatch spent provisioning the joined columns (`join.gather`) in the SF10 join cell: `join.gather_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `join.gather_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("join.gather_ms")
