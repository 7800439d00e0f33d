"""Residency look-ups per execution that found nothing and built their value: `residency.misses_per_query`'s reader, as it is, for the filtered join cell (that metric's list of
cells cannot take the cell).

Source: as `residency.misses_per_query`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("residency.misses_per_query")
