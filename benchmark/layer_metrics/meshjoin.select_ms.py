"""Mean length of the `join.topn_select` spans (the run's finalize on the chips: the `join.combine` inside it and the fetch of K rows a chip) in the four-chip join cell: `jointopn.select_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `jointopn.select_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("jointopn.select_ms")
