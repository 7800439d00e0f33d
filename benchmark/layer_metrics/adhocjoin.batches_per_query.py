"""Fact batches a fused join TopN run took in: `jointopn.batches_per_query`'s reader, as it is, for the ad-hoc join cell (that metric's list of
cells cannot take the cell).

Source: as `jointopn.batches_per_query`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("jointopn.batches_per_query")
