"""Mean length of the `device.launch` spans in the SF10 join cell: `stages.launch_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `stages.launch_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("stages.launch_ms")
