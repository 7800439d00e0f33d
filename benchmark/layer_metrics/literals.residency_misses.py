"""Residency look-ups per execution of the ad-hoc cell that found nothing and
built their value: `residency.misses_per_query`'s reader, as it is (that
metric's list of cells cannot take the cell). 0 where a new literal value
leaves every plane resident; a residency slot keyed on a value would read 1
or more in every execution, since each runs other values than the one before.

Source: the program's `hbm_cache_misses` counter, read around each execution.
"""

import twin

read = twin.reader_of("residency.misses_per_query")
