"""read_io_ms — storage read (data/storage), in ms per partition.

The program's ``presto.read.io`` spans (``columnar.read_partition``: open,
header and the read of the body) that start in the traced session, summed
over its partitions.  Host clock, in the profiler's trace; the first of the
three parts of ``read_ms``.  Moves samples_per_s where the host bounds the
rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.read.io",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
