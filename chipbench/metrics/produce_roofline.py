"""produce_roofline — compiled produce program (core/presto), in %.

The least time the whole Transform of the traced session's partitions could
take on the chip, over the device time of its program executions ("XLA
Modules" of the trace).  The least time is a bandwidth bound: the encoded
page bytes the Transform must read plus the train-ready batch bytes it must
write, at the dataset's unpadded shapes, over the peak HBM bandwidth.  The
Transform's arithmetic (a log, a hash, a sorted search per value) is far
below the compute peak at these shapes, so the bytes bound applies.  Work is
counted from the dataset's shapes, never from the arrays the program passes,
so a change of layout is judged against the same work.
"""


def page_bytes(shape) -> int:
    """Encoded bytes in per partition: bytesplit dense words, bitpacked ids
    and lengths at unique-block geometry, the refs of a RecD partition, and
    the labels."""
    u = shape.unique_rows
    refs = 4 * shape.rows if shape.dup_factor > 1 else 0
    return (
        4 * shape.rows * shape.n_dense
        + u * shape.n_sparse * shape.max_sparse_len * shape.id_width // 8
        + u * shape.n_sparse * shape.len_width // 8
        + refs
        + 4 * shape.rows
    )


def batch_bytes(shape) -> int:
    """Train-ready bytes out per partition: dense f32, multi-hot ids at the
    staged length, lengths, one-hot generated ids, labels."""
    r = shape.rows
    return 4 * r * (
        shape.n_dense
        + shape.n_sparse * shape.max_sparse_len
        + shape.n_sparse
        + shape.n_generated
        + 1
    )


def read(ctx):
    t = ctx.trace.program_s()
    if t <= 0 or ctx.partitions <= 0:
        return None
    least = ctx.partitions * (page_bytes(ctx.shape) + batch_bytes(ctx.shape))
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / t
