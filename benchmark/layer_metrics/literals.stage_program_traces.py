"""Stage programs the process has traced, set-up and window together.

The aggregate stages take a query's literal values as arguments, so a program
is traced for a query shape (and a bucket, and a mesh width), never for a
value: the ad-hoc cell's 24 templates are two shapes, and this reads 2 or so
where a program that compiles a value reads 24 or more.

Source: the program's `device_stage_program_traces` counter, bumped inside
the traced function of the stage programs, as the whole process has counted
it (the programs are traced in warm-up, before the harness reads counters
around executions). None from a program without the counter.
"""


def read(ctx):
    from daft_tpu.ops import counters

    return counters.snapshot().get("device_stage_program_traces")
