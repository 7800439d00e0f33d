"""Mean host milliseconds of a `device.dispatch` span in the SF10 join cell: `stages.dispatch_host_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `stages.dispatch_host_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("stages.dispatch_host_ms")
