"""Adjacent-dimension gathers an execution made from the WHOLE of a pack
longer than a window: the fact is not ordered by that dimension's key, so no
batch-long window of the pack holds a dispatch's matched rows. q14 and q19
(`l_partkey` is uniform over `part`) read one a dispatch, 58 an execution at
SF10; q12 (`lineitem` follows `orders`) reads 0.

Source: the program's `join_unwindowed_gathers` counter, read around each
execution, over the window's executions. None from a program without the
counter or where no execution counted one.
"""


def read(ctx):
    runs = ctx["executions"]
    if not any("join_unwindowed_gathers" in e["counters"] for e in runs):
        return None
    return sum(e["counters"].get("join_unwindowed_gathers", 0) for e in runs) / len(runs)
