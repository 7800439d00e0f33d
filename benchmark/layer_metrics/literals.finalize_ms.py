"""Milliseconds per execution the host spends in `stage.finalize` outside
its `device.d2h` fetch in the ad-hoc cell: `stages.finalize_ms`'s reader, as
it is (that metric's list of cells cannot take the cell).

Source: the program's spans (host clock). None from a program without them.
"""

import twin

read = twin.reader_of("stages.finalize_ms")
