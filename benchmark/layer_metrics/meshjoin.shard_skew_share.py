"""How unevenly the four chips were busy in the window (rows are sharded in order, so a date cut on a fact sorted by order key empties some shards of a dispatch, not of the run) in the four-chip join cell: `mesh.shard_skew_share`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `mesh.shard_skew_share`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("mesh.shard_skew_share")
