"""finish_wait_ms — compiled produce program (core/presto, core/execcache),
device wait, in ms per partition.

The program's ``presto.finish`` spans (a pool worker blocked until a
dispatched chunk is ready on the device) that start in the traced session,
summed over its partitions.  Moves samples_per_s: a worker that waits here
is not reading the next partition.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.finish",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
