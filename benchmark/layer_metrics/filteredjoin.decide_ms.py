"""Milliseconds per execution in `placement.decide`: `placement.decide_ms`'s reader, as it is, for the filtered join cell (that metric's list of
cells cannot take the cell).

Source: as `placement.decide_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("placement.decide_ms")
