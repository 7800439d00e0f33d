"""Rows a fused join TopN run's finalize brought back, averaged over the window's runs (at most K a chip: 40 for q3, 80 for q10) in the four-chip join cell: `jointopn.fetched_rows_per_query`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `jointopn.fetched_rows_per_query`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("jointopn.fetched_rows_per_query")
