"""Visibility programs the process has traced, set-up and window together.

A join's dimension filters take their literal values as arguments of one
jitted program a (chain, filter skeletons, dimension length), so the cell's 12
templates are three shapes: this reads 3 where a program that compiles a
value reads 12 or more.

Source: the program's `join_filter_program_traces` counter, bumped inside the
traced function, as the whole process has counted it (the programs are traced
in warm-up, before the harness reads counters around executions). None from a
program without the counter.
"""


def read(ctx):
    from daft_tpu.ops import counters

    return counters.snapshot().get("join_filter_program_traces")
