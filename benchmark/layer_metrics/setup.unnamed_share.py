"""Share of `setup.query_s` that no cold site counted: 100 x (seconds inside
queries before the window, less uploads, dictionary encoding, content
hashing, residency builds, calibration and program building) / those
seconds. What is left is the warm-up executions' warm work and what a
`perf_opt` on set-up cannot yet be aimed at.

As it reads: the counters are self times on a thread, so they add up, but a
counter summed over pool threads can push the share below 0.

Source: `query_wall_us` and the counters of `setup_counters.NAMED`, each
total less the window's executions. None from a program without one of them,
or where no query ran before the window.
"""

import setup_counters as sc


def read(ctx):
    whole = sc.seconds_before_window(ctx, (sc.WALL,))
    named = sc.seconds_before_window(ctx, sc.NAMED)
    if not whole or named is None:
        return None
    return 100.0 * (whole - named) / whole
