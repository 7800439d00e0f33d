"""Share of the HBM roofline that the star-join programs reach: `jointopn.join_hbm_share`'s reader, as it is, for the ad-hoc join cell (that metric's list of
cells cannot take the cell).

Source: as `jointopn.join_hbm_share`. None where that reader finds nothing to read.
"""

import twin

read = twin.reader_of("jointopn.join_hbm_share")
