"""Mean length of the program's `device.launch` spans in the window: `stages.launch_ms`'s reader, as it is, for the filtered join cell (that metric's list of
cells cannot take the cell).

Source: as `stages.launch_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("stages.launch_ms")
