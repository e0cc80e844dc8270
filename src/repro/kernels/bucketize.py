"""Bucketize feature-generation kernel (Alg. 1) — Pallas TPU.

Paper's FPGA unit does a pipelined binary search per element.  The TPU-native
adaptation is a *vectorized compare-and-count*: for sorted boundaries b,
``digitize(a) = #{j : b[j] <= a}``, computed as a broadcast compare reduced
over boundary chunks.  Napkin math for why this beats binary search on TPU:

* binary search = log2(m) data-dependent gathers; VMEM gathers with vector
  indices are unsupported/slow on the VPU.
* compare-and-count = m compares/element on 8x128 lanes.  At ~7.7e12 vector
  ops/s/chip, a (512-value, m=4096) tile costs ~0.3 us and the kernel stays
  entirely compute-local: each HBM byte of feature data is read exactly once
  (Pallas grid pipelining double-buffers the next tile during compute — the
  paper's double-buffering, for free).

Inter-feature parallelism = grid dim 0 (one boundary set per feature).
Intra-feature parallelism = 8x128 vector lanes + grid dim 1 over row tiles,
laid out like the dense pages: (G, 4) value groups per block, so the fused
generation kernel (``fused.fused_gen_pallas``) shares ``count_le``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode import G_BLOCK

BOUNDARY_CHUNK = 512  # boundaries reduced per inner-loop iteration


def count_le(x: jax.Array, bounds_ref, m: int) -> jax.Array:
    """Bucket ids of a (G, C) f32 tile: ``#{j : b[j] <= x}`` against the m
    sorted boundaries in ``bounds_ref``'s (1, 1, m) block, int32 (G, C).

    One (G, 1) value column at a time broadcasts against a (1, chunk) row of
    boundaries, so no value vector is ever reshaped across the lane/sublane
    layout (Mosaic's layouts hold on the chip)."""
    nchunks = m // BOUNDARY_CHUNK
    rem = m - nchunks * BOUNDARY_CHUNK

    def count(col, b):
        return jnp.sum(col >= b, axis=1, keepdims=True, dtype=jnp.int32)

    counts = []
    for c in range(x.shape[1]):
        col = x[:, c : c + 1]  # (G, 1)

        def body(i, acc, col=col):
            b = bounds_ref[0, :, pl.ds(i * BOUNDARY_CHUNK, BOUNDARY_CHUNK)]
            return acc + count(col, b)

        acc = jnp.zeros(col.shape, jnp.int32)
        if nchunks:
            acc = jax.lax.fori_loop(0, nchunks, body, acc)
        if rem:
            acc = acc + count(col, bounds_ref[0, :, pl.ds(nchunks * BOUNDARY_CHUNK, rem)])
        counts.append(acc)
    return jnp.concatenate(counts, axis=1)


def _bucketize_kernel(vals_ref, bounds_ref, out_ref, *, m: int):
    out_ref[0] = count_le(vals_ref[0], bounds_ref, m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bucketize_pallas(
    values: jax.Array, boundaries: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """values (F, G, 4) f32 with G % G_BLOCK == 0 (the dense pages' group
    geometry); boundaries (F, 1, m) sorted f32 (pad with +inf to a lane
    multiple; the unit middle axis keeps the block's last two dims full).
    Returns (F, G, 4) int32 in [0, m]."""
    f, g, c = values.shape
    _, _, m = boundaries.shape
    assert g % G_BLOCK == 0, (g, G_BLOCK)
    return pl.pallas_call(
        functools.partial(_bucketize_kernel, m=m),
        out_shape=jax.ShapeDtypeStruct((f, g, c), jnp.int32),
        grid=(f, g // G_BLOCK),
        in_specs=[
            pl.BlockSpec((1, G_BLOCK, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, m), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G_BLOCK, c), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(values, boundaries)
