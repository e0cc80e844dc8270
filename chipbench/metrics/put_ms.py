"""put_ms — host-to-device (core/presto._put_pages), in ms per partition.

The program's ``presto.put`` spans (``PreStoEngine._put_pages`` of each
dispatched chunk: ``jax.device_put`` where the backend donates, as a TPU
does) that start in the traced session, summed over its partitions.  Moves
samples_per_s where the host bounds the rate.
"""

from chipbench.spans import per_partition_ms

SPANS = ("presto.put",)


def read(ctx):
    return per_partition_ms(ctx, SPANS)
