"""Milliseconds per execution that belong to the physical operators
themselves (the self time of the `op.*` spans) in the ad-hoc cell:
`host.ops_ms`'s reader, as it is (that metric's list of cells cannot take the
cell). On the device tier: what the operator does around its device stage.

Source: the program's spans (host clock). None from a program without them.
"""

import twin

read = twin.reader_of("host.ops_ms")
