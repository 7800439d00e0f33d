"""Mean length of the program's `device.launch` spans in the window of the
ad-hoc cell: `stages.launch_ms`'s reader, as it is (that metric's list of
cells cannot take the cell). The call of the jitted program alone: the
host's look-up of the compiled program, the transfer of whatever small host
array travels with the launch (a grouped run's literal values and row
offset) and the enqueue; the call is asynchronous.

Source: the program's spans (host clock). None where nothing launched.
"""

import twin

read = twin.reader_of("stages.launch_ms")
