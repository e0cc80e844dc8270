"""build_ms — page build (core/preprocess), in ms per partition.

The program's ``presto.page_build`` spans (``pages_from_partition`` in
``PreStoEngine.stage_partition``) and ``presto.stack`` spans (``stack_pages``
of each chunk in the service) that start in the traced session, summed over
its partitions.  Unlike ``page_build_ms`` it counts the stack of a
megabatch.  Moves samples_per_s where the host bounds the rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.page_build", "presto.stack")


def read(ctx):
    return per_partition_ms(ctx, SPANS)
