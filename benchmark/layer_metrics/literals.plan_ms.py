"""Milliseconds per execution in `plan.optimize` and `plan.translate` in the
ad-hoc cell: `plan.plan_ms`'s reader, as it is (that metric's list of cells
cannot take the cell). The translation is where a query's expressions are
walked for their skeleton and literals and the compiled stage of the shape is
looked up (`ops/stage.bind_filter_agg_stage`): a dearer key shows here.

Source: the program's spans (host clock). None from a program without them.
"""

import twin

read = twin.reader_of("plan.plan_ms")
