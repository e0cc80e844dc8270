"""read_ms — storage read (data/storage), in ms per partition.

The mean duration of the store view's read spans (``PartitionedStore.read``:
file read, checksum and header decode of one stored partition) over the
traced session.  Host clock, in the profiler's trace.  Moves samples_per_s
where the host bounds the rate.
"""

from chipbench.tracing import READ_SPAN


def read(ctx):
    spans = ctx.trace.spans(READ_SPAN)
    if not spans:
        return None
    return sum(e - s for _, s, e in spans) / len(spans) / 1e6
