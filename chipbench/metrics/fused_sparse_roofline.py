"""fused_sparse_roofline — Pallas kernels (kernels/fused), in %.

Least time of ``fused_sparse`` (bitpack decode, then SigridHash) over its
device time in the traced session.  Bandwidth bound: the bitpacked id words
in (``unique_rows x n_sparse x max_sparse_len x id_width`` bits) plus the
int32 ids out (``unique_rows x n_sparse x max_sparse_len x 4`` bytes), over
peak HBM bandwidth.  A RecD partition's sparse chain runs at unique rows.
The length words are decoded outside this kernel and are not counted.  Work
is counted from the dataset's shapes.
"""

KERNEL = "fused_sparse_pallas"


def least_bytes(shape) -> int:
    ids = shape.unique_rows * shape.n_sparse * shape.max_sparse_len
    return ids * shape.id_width // 8 + 4 * ids


def read(ctx):
    t = ctx.trace.kernel_s(KERNEL)
    if t <= 0 or ctx.partitions <= 0:
        return None
    least = ctx.partitions * least_bytes(ctx.shape)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / t
