"""Milliseconds per execution the host spends in `stage.finalize` outside its `device.d2h` fetch in the SF10 join cell: `stages.finalize_ms`'s reader, as it is (that metric's list of
cells cannot take the cell).

Source: as `stages.finalize_ms`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("stages.finalize_ms")
