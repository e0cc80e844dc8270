"""Public jit'd wrappers around the Pallas preprocessing kernels.

Handles padding to tile boundaries, dtype plumbing, and the interpret-mode
switch (Pallas TPU kernels execute in interpret mode on CPU hosts, which is
how the CPU test suite validates them; on a TPU the same calls compile to
Mosaic kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bucketize as _bk
from repro.kernels import decode as _dk
from repro.kernels import fused as _fk
from repro.kernels import lognorm as _lk
from repro.kernels import sigridhash as _sk


def _interpret(flag: bool | None) -> bool:
    """An explicit flag wins; else interpret exactly when JAX's backend is not
    a TPU.  Decided per call, never at import: importing this module must
    not pick a backend, and a caller that requires the chip checks for it
    before the first kernel call."""
    return jax.default_backend() != "tpu" if flag is None else flag


def _pad_axis(x: jax.Array, axis: int, multiple: int, value) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def _hash_params(seeds, max_values) -> jax.Array:
    """Per-feature (seed, max) pairs as the kernels' (F, 1, 2) uint32 array."""
    return jnp.stack(
        [jnp.asarray(seeds, jnp.uint32), jnp.asarray(max_values, jnp.uint32)], axis=1
    )[:, None, :]


def bucketize(values, boundaries, *, interpret: bool | None = None) -> jax.Array:
    """Feature generation (Alg. 1). values (F, R) f32, boundaries (F, m) sorted.

    Returns (F, R) int32 bucket ids in [0, m]."""
    interpret = _interpret(interpret)
    values = jnp.asarray(values, jnp.float32)
    boundaries = jnp.asarray(boundaries, jnp.float32)
    f = values.shape[0]
    v, r = _pad_axis(values, 1, 4 * _dk.G_BLOCK, 0.0)
    b, _ = _pad_axis(boundaries, 1, 128, jnp.inf)
    out = _bk.bucketize_pallas(v.reshape(f, -1, 4), b[:, None], interpret=interpret)
    return out.reshape(f, -1)[:, :r]


def sigridhash(values, seeds, max_values, *, interpret: bool | None = None) -> jax.Array:
    """Feature normalization (Alg. 2). values (F, N) i32 -> (F, N) i32 in [0, d)."""
    interpret = _interpret(interpret)
    values = jnp.asarray(values)
    if values.dtype != jnp.int32:
        values = values.astype(jnp.int32)
    v, n = _pad_axis(values, 1, _sk.VAL_TILE, 0)
    out = _sk.sigridhash_pallas(
        v[:, None], _hash_params(seeds, max_values), interpret=interpret
    )
    return out[:, 0, :n]


def lognorm(x, *, interpret: bool | None = None) -> jax.Array:
    """Dense normalization: log1p(max(x, 0)) elementwise, any shape."""
    interpret = _interpret(interpret)
    x = jnp.asarray(x, jnp.float32)
    shape = x.shape
    flat = x.reshape(-1)
    tile = _lk.TILE_R * _lk.TILE_C
    padded, n = _pad_axis(flat, 0, tile, 0.0)
    out = _lk.lognorm_pallas(
        padded.reshape(-1, _lk.TILE_C), interpret=interpret
    ).reshape(-1)
    return out[:n].reshape(shape)


def decode_bitpack(packed, *, width: int, interpret: bool | None = None) -> jax.Array:
    """Grouped bitpack decode: (F, G, w) words -> (F, G*32) int32 values."""
    interpret = _interpret(interpret)
    packed = jnp.asarray(packed).view(jnp.uint32) if isinstance(packed, np.ndarray) else jnp.asarray(packed)
    packed = packed.astype(jnp.uint32)
    f, g, w = packed.shape
    p, gorig = _pad_axis(packed, 1, _dk.G_BLOCK, 0)
    out = _dk.bitunpack_pallas(p, width=width, interpret=interpret)
    return out[:, :gorig].reshape(f, gorig * 32)


def decode_bytesplit(plane_words, *, interpret: bool | None = None) -> jax.Array:
    """Grouped byte-split decode: (F, G, 4) words -> (F, G*4) f32 values."""
    interpret = _interpret(interpret)
    w = jnp.asarray(plane_words).astype(jnp.uint32)
    f, g, _ = w.shape
    p, gorig = _pad_axis(w, 1, _dk.G_BLOCK, 0)
    out = _dk.bytesplit_pallas(p, interpret=interpret)
    return out[:, :gorig].reshape(f, gorig * 4)


def fused_dense(plane_words, *, interpret: bool | None = None) -> jax.Array:
    """ISP dense path: decode + Log in one kernel. (F,G,4) -> (F, G*4) f32."""
    interpret = _interpret(interpret)
    w = jnp.asarray(plane_words).astype(jnp.uint32)
    f, g, _ = w.shape
    p, gorig = _pad_axis(w, 1, _dk.G_BLOCK, 0)
    out = _fk.fused_dense_pallas(p, interpret=interpret)
    return out[:, :gorig].reshape(f, gorig * 4)


def fused_gen(
    plane_words, boundaries, seeds, max_values, *, interpret: bool | None = None
) -> jax.Array:
    """ISP generation path: decode + Bucketize + SigridHash in one kernel.

    plane_words (F, G, 4) encoded dense sources, boundaries (F, m) sorted ->
    (F, G*4) int32 table indices."""
    interpret = _interpret(interpret)
    w = jnp.asarray(plane_words).astype(jnp.uint32)
    f, g, _ = w.shape
    b = jnp.asarray(boundaries, jnp.float32)
    b, _ = _pad_axis(b, 1, 128, jnp.inf)
    pw, gorig = _pad_axis(w, 1, _dk.G_BLOCK, 0)
    out = _fk.fused_gen_pallas(
        pw, b[:, None], _hash_params(seeds, max_values), interpret=interpret
    )
    return out[:, :gorig].reshape(f, gorig * 4)


def fused_sparse(
    packed, seeds, max_values, *, width: int, interpret: bool | None = None
) -> jax.Array:
    """ISP sparse path: decode + SigridHash in one kernel.

    packed (F, G, w) uint32 -> (F, G*32) int32 indices in [0, d)."""
    interpret = _interpret(interpret)
    packed = jnp.asarray(packed).astype(jnp.uint32)
    f, g, w = packed.shape
    p, gorig = _pad_axis(packed, 1, _dk.G_BLOCK, 0)
    out = _fk.fused_sparse_pallas(
        p, _hash_params(seeds, max_values), width=width, interpret=interpret
    )
    return out[:, :gorig].reshape(f, gorig * 32)


# -- host-side layout helpers -------------------------------------------------


def regroup_bitpack(packed_flat: np.ndarray, n_values: int, width: int) -> np.ndarray:
    """Flat packed words (from data.encoding.bitpack) -> (G, w) grouped layout.

    Requires n_values % 32 == 0 (dataset partitions guarantee this)."""
    assert n_values % 32 == 0, n_values
    g = n_values // 32
    return np.ascontiguousarray(packed_flat[: g * width].reshape(g, width))


def regroup_bytesplit(plane_words_flat: np.ndarray, n_values: int) -> np.ndarray:
    """Flat plane words (from bytesplit_encode) -> (G, 4) grouped layout."""
    assert n_values % 4 == 0, n_values
    g = n_values // 4
    planes = plane_words_flat[: g * 4].reshape(4, g)
    return np.ascontiguousarray(planes.T)
