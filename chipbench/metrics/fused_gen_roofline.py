"""fused_gen_roofline — Pallas kernels (kernels/fused), in %.

Least time of ``fused_gen`` (bytesplit decode, Bucketize, SigridHash) over
its device time in the traced session.  The least time is the larger of two
bounds:

* bytes: the generated features' source dense words in
  (``rows x n_generated x 4``), their boundaries in
  (``n_generated x m x 4``) and the int32 ids out (``rows x n_generated x
  4``), over peak HBM bandwidth;
* compares: a sorted search, ``rows x n_generated x ceil(log2(m + 1))``,
  over the bf16 peak (the only published compute peak of the chip).

At these shapes the bytes bound applies.  Work is counted from the
dataset's shapes.
"""

import math

KERNEL = "fused_gen_pallas"


def least_seconds(shape, peaks) -> float:
    g, r, m = shape.n_generated, shape.rows, shape.bucket_size
    nbytes = 4 * r * g + 4 * g * m + 4 * r * g
    compares = r * g * math.ceil(math.log2(m + 1))
    return max(nbytes / peaks["hbm_bytes_per_s"], compares / peaks["bf16_flops_per_s"])


def read(ctx):
    t = ctx.trace.kernel_s(KERNEL)
    if t <= 0 or ctx.partitions <= 0:
        return None
    return 100.0 * ctx.partitions * least_seconds(ctx.shape, ctx.peaks) / t
