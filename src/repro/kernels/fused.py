"""Fused ISP pipelines — Pallas TPU.  The paper's accelerator in one pass.

PreSto's PE reads encoded bytes once from flash and emits train-ready values;
every intermediate stays on-chip.  The TPU analogue: one kernel that decodes
the columnar page AND applies the transform inside VMEM, so HBM traffic is
exactly (encoded bytes in) + (train-ready bytes out).  Pallas grid
pipelining overlaps the next tile's HBM fetch with the current tile's
compute — the paper's double buffering.

fused_dense : bytesplit words --decode--> f32 --Log--> normalized f32
fused_sparse: bitpacked ids   --decode--> i32 --SigridHash--> table indices
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bucketize import count_le
from repro.kernels.decode import G_BLOCK, _bitunpack_body, _bytesplit_body
from repro.kernels.lognorm import log1p_body
from repro.kernels.sigridhash import hash_body


def _fused_dense_kernel(p_ref, o_ref):
    o_ref[0] = log1p_body(_bytesplit_body(p_ref[0]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_dense_pallas(plane_words: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(F, G, 4) encoded words -> (F, G, 4) log-normalized f32."""
    f, g, four = plane_words.shape
    assert four == 4 and g % G_BLOCK == 0, plane_words.shape
    return pl.pallas_call(
        _fused_dense_kernel,
        out_shape=jax.ShapeDtypeStruct((f, g, 4), jnp.float32),
        grid=(f, g // G_BLOCK),
        in_specs=[pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(plane_words)


def _fused_sparse_kernel(p_ref, params_ref, o_ref, *, width: int):
    ids = _bitunpack_body(p_ref[0], width)  # (G, 32) uint32
    o_ref[0] = hash_body(ids, params_ref[0, 0, 0], params_ref[0, 0, 1])


def _fused_gen_kernel(p_ref, bounds_ref, params_ref, o_ref, *, m: int):
    """Feature GENERATION fully fused: bytesplit-decode -> Bucketize ->
    SigridHash, one HBM read of encoded words, one write of table ids.

    §Perf (preprocess cell): the unfused path writes/rereads the raw dense
    values and the bucket ids; fusing the whole generated-feature chain
    keeps both intermediates in VMEM (3 HBM round trips -> 1)."""
    buckets = count_le(_bytesplit_body(p_ref[0]), bounds_ref, m)  # (G, 4)
    o_ref[0] = hash_body(
        buckets.astype(jnp.uint32), params_ref[0, 0, 0], params_ref[0, 0, 1]
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gen_pallas(
    plane_words: jax.Array,  # (F, G, 4) encoded dense words (gen sources)
    boundaries: jax.Array,  # (F, 1, m) sorted bucket boundaries
    params: jax.Array,  # (F, 1, 2) uint32 [seed, max]
    *,
    interpret: bool = False,
) -> jax.Array:
    f, g, four = plane_words.shape
    _, _, m = boundaries.shape
    assert four == 4 and g % G_BLOCK == 0, plane_words.shape
    return pl.pallas_call(
        functools.partial(_fused_gen_kernel, m=m),
        out_shape=jax.ShapeDtypeStruct((f, g, 4), jnp.int32),
        grid=(f, g // G_BLOCK),
        in_specs=[
            pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, m), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(plane_words, boundaries, params)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def fused_sparse_pallas(
    packed: jax.Array, params: jax.Array, *, width: int, interpret: bool = False
) -> jax.Array:
    """packed (F, G, w) uint32, params (F, 1, 2) uint32 [seed, max] -> (F, G, 32) i32."""
    f, g, w = packed.shape
    assert w == width and g % G_BLOCK == 0, (packed.shape, width)
    return pl.pallas_call(
        functools.partial(_fused_sparse_kernel, width=width),
        out_shape=jax.ShapeDtypeStruct((f, g, 32), jnp.int32),
        grid=(f, g // G_BLOCK),
        in_specs=[
            pl.BlockSpec((1, G_BLOCK, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G_BLOCK, 32), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(packed, params)
