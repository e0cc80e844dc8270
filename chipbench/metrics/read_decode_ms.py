"""read_decode_ms — storage read (data/storage), in ms per partition.

The program's ``presto.read.decode`` spans (``columnar.read_partition``: the
Python loop over the page table into encoded columns) that start in the
traced session, summed over its partitions.  Host clock, in the profiler's
trace; the third of the three parts of ``read_ms``, and the one that holds
the GIL.  Moves samples_per_s where the host bounds the rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.read.decode",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
