"""Share of the ad-hoc cell's idle seconds (no operation running on the
device) over which no span of the program other than the `query` root is
open: `idle.unattributed_share`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: the `jax.profiler` trace for the idle gaps, the program's spans moved
onto the trace's clock. None where the program recorded no span.
"""

import twin

read = twin.reader_of("idle.unattributed_share")
