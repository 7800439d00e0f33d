"""Mean length of the `join.topn_select` spans: `jointopn.select_ms`'s reader, as it is, for the ad-hoc join cell (that metric's list of
cells cannot take the cell).

Source: as `jointopn.select_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("jointopn.select_ms")
