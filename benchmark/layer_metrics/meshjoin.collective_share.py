"""Share of the busiest chip's busy seconds that went to collective operations (the run-wide tables' all-to-all at a TopN run's end: the benchmark's first collective) in the four-chip join cell: `mesh.collective_share`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `mesh.collective_share`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("mesh.collective_share")
