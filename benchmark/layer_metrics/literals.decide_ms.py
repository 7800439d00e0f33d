"""Milliseconds per execution in `placement.decide` in the ad-hoc cell:
`placement.decide_ms`'s reader, as it is (that metric's list of cells cannot
take the cell). The deciders price the stage the translation bound to the
plan node; one that walked the expressions again for a key would show here.

Source: the program's spans (host clock). None from a program without them.
"""

import twin

read = twin.reader_of("placement.decide_ms")
