"""Share of the HBM roofline that the parameterised scan programs reach:
`kernels.scan_hbm_share`'s reader, as it is, for the ad-hoc cell's templates
(that metric's list of cells cannot take the cell).

For every execution of a template that declares `scan_columns`: the least
time the chip could take to read those columns' resident planes once (their
bytes, from the device arrays' own `nbytes` through `benchmark/scanbytes.py`,
over the peak HBM bandwidth of `peaks.json`) over the seconds in which an
operation ran on the device inside that execution, from the trace. Summed
over the window's executions before dividing. A floor (validity planes are
left out), bound by memory bandwidth; it cannot pass 100%. None where no
operation ran on the device.
"""

import twin

read = twin.reader_of("kernels.scan_hbm_share")
