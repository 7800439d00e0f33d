"""Mean host milliseconds of a `device.dispatch` span (one join dispatch over the four chips: the look-ups of its sharded planes, the provisioning call and the launch) in the four-chip join cell: `stages.dispatch_host_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `stages.dispatch_host_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("stages.dispatch_host_ms")
