"""SigridHash feature-normalization kernel (Alg. 2) — Pallas TPU.

Seeded avalanche hash + range reduction, elementwise over sparse ids.  TPU
lanes are 32-bit so we use a murmur3-finalizer mix (see kernels/ref.py for
the contract note).  One HBM read + one HBM write per element; fully
VPU-bound.  Per-feature (seed, max_value) pairs ride in as a tiny (F, 1, 2)
param array (unit middle axis, like the values) — grid dim 0 is the feature
(inter-feature parallelism), the 8x128 lanes cover ids (intra-feature
parallelism).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VAL_TILE = 1024


def hash_body(v: jax.Array, seed: jax.Array, d: jax.Array) -> jax.Array:
    """murmur3-finalizer seeded hash + range reduce; all uint32 lane ops."""
    c1 = jnp.uint32(0xCC9E2D51)
    c2 = jnp.uint32(0x85EBCA6B)
    c3 = jnp.uint32(0xC2B2AE35)
    golden = jnp.uint32(0x9E3779B1)
    h = (v ^ (seed * golden)) * c1 + seed
    h = h ^ (h >> 16)
    h = h * c2
    h = h ^ (h >> 13)
    h = h * c3
    h = h ^ (h >> 16)
    return (h % d).astype(jnp.int32)


def _hash_kernel(vals_ref, params_ref, out_ref):
    v = vals_ref[0].astype(jnp.uint32)  # (1, VAL_TILE)
    out_ref[0] = hash_body(v, params_ref[0, 0, 0], params_ref[0, 0, 1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def sigridhash_pallas(
    values: jax.Array, params: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """values (F, 1, N) int32, params (F, 1, 2) uint32 [seed, max_value] ->
    (F, 1, N) i32 (unit middle axis: Mosaic-legal (1, n) blocks)."""
    f, _, n = values.shape
    assert n % VAL_TILE == 0, (n, VAL_TILE)
    return pl.pallas_call(
        _hash_kernel,
        out_shape=jax.ShapeDtypeStruct((f, 1, n), jnp.int32),
        grid=(f, n // VAL_TILE),
        in_specs=[
            pl.BlockSpec((1, 1, VAL_TILE), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, 2), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, VAL_TILE), lambda i, j: (i, 0, j)),
        interpret=interpret,
    )(values, params)
