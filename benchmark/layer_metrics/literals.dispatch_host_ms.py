"""Mean length of the program's `device.dispatch` spans in the window of the
ad-hoc cell: `stages.dispatch_host_ms`'s reader, as it is (that metric's list
of cells cannot take the cell). The host's side of one dispatch: the planes'
residency look-ups, the row mask, the binding of the execution's literal
values (`literals.bind_ms`, inside it) and the launch (`literals.launch_ms`).

Source: the program's spans (host clock). None where nothing dispatched.
"""

import twin

read = twin.reader_of("stages.dispatch_host_ms")
