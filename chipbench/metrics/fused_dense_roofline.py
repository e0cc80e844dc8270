"""fused_dense_roofline — Pallas kernels (kernels/fused), in %.

Least time of ``fused_dense`` (bytesplit decode, then Log) over its device
time in the traced session.  Bandwidth bound: the dense words in plus the
f32 values out, ``rows x n_dense x 4`` bytes each, over peak HBM bandwidth;
one log per value is far below the compute peak.  Work is counted from the
dataset's shapes.
"""

KERNEL = "fused_dense_pallas"


def read(ctx):
    t = ctx.trace.kernel_s(KERNEL)
    if t <= 0 or ctx.partitions <= 0:
        return None
    s = ctx.shape
    least = ctx.partitions * 2 * 4 * s.rows * s.n_dense
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / t
