"""Milliseconds per join dispatch spent on group codes (`join.codes`: the run-wide TopN's is the look-up of the dimension's index plane, no factorization) in the SF10 join cell: `join.codes_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `join.codes_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("join.codes_ms")
