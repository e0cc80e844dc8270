"""read_verify_ms — storage read (data/storage), in ms per partition.

The program's ``presto.read.verify`` spans (``columnar.read_partition``: the
sha256 of the body against its stored checksum) that start in the traced
session, summed over its partitions.  Host clock, in the profiler's trace;
the second of the three parts of ``read_ms``.  Moves samples_per_s where the
host bounds the rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.read.verify",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
