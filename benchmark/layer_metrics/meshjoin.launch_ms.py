"""Mean length of the `device.launch` spans (the host's call of one program on every chip of the mesh) in the four-chip join cell: `mesh.launch_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `mesh.launch_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("mesh.launch_ms")
