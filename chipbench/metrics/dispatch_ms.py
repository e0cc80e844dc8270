"""dispatch_ms — compiled produce program (core/presto, core/execcache),
launch, in ms per partition.

The program's ``presto.dispatch`` spans (the call of the compiled produce
program on a chunk's placed pages; it returns before the device is done)
that start in the traced session, summed over its partitions.  Moves
samples_per_s where the host bounds the rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.dispatch",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
