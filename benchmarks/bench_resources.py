"""Table II analog — per-kernel on-chip (VMEM) footprint of the ISP units.

The paper reports FPGA LUT/BRAM/DSP utilization per unit; the TPU analog is
each Pallas kernel's VMEM working set (in+out blocks x2 for double
buffering) against the ~16 MiB/core budget, plus its arithmetic intensity.
"""

from __future__ import annotations

from benchmarks.common import emit

VMEM_BUDGET = 16 * 2**20  # bytes per TensorCore


def kernel_footprints() -> dict:
    from repro.kernels.bucketize import BOUNDARY_CHUNK
    from repro.kernels.decode import G_BLOCK
    from repro.kernels.lognorm import TILE_C, TILE_R
    from repro.kernels.sigridhash import VAL_TILE

    m = 4096  # RM5 bucket size
    w = 24  # RM id width
    return {
        # name: (in_bytes, out_bytes, scratch_bytes, flops_per_byte)
        "decode_bitpack": (G_BLOCK * w * 4, G_BLOCK * 32 * 4, 0, 2.0),
        "decode_bytesplit": (G_BLOCK * 4 * 4, G_BLOCK * 4 * 4, 0, 1.5),
        "bucketize": (G_BLOCK * 4 * 4 + m * 4, G_BLOCK * 4 * 4,
                      G_BLOCK * BOUNDARY_CHUNK, m / 8.0),
        "sigridhash": (VAL_TILE * 4 + 8, VAL_TILE * 4, 0, 12 / 8.0),
        "lognorm": (TILE_R * TILE_C * 4, TILE_R * TILE_C * 4, 0, 1 / 8.0),
        "fused_dense": (G_BLOCK * 4 * 4, G_BLOCK * 4 * 4, 0, 2.0),
        "fused_sparse": (G_BLOCK * w * 4 + 8, G_BLOCK * 32 * 4, 0, 3.5),
    }


def run() -> dict:
    results = {}
    total = 0
    for name, (i, o, s, ai) in kernel_footprints().items():
        working = 2 * (i + o) + s  # x2: grid pipelining double buffer
        frac = working / VMEM_BUDGET
        total += working
        emit(f"resources/{name}", 0.0,
             f"vmem_bytes={working} vmem_frac={frac:.4f} arith_intensity={ai:.2f}")
        results[name] = {"vmem": working, "frac": frac}
    emit("resources/all_units", 0.0,
         f"vmem_bytes={total} vmem_frac={total / VMEM_BUDGET:.4f} "
         f"(paper Table II: 54% LUT / 48% BRAM)")
    return results


if __name__ == "__main__":
    run()
