"""Columnar page decoders — Pallas TPU (the paper's hardwired Decode unit).

Gather-free decode via the *aligned-group layout*: 32 consecutive w-bit
values occupy exactly w uint32 words, so a (G, w) word tile decodes to a
(G, 32) value tile with only static slices/shifts — no data-dependent
addressing, which the TPU VPU cannot do efficiently.  The j-th value of
every group lives at the same static (word, bit) offset, so the kernel is an
unrolled 32-step shift/or pipeline over full vectors.

Same trick for BYTE_STREAM_SPLIT floats: each group of 4 values takes one
word from each of the 4 byte planes; reassembly is static byte shuffling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

G_BLOCK = 128  # groups per grid step


def _lane_iota(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.uint32, (1, n), 1)


def _bitunpack_body(p: jax.Array, width: int) -> jax.Array:
    """(G, w) uint32 words -> (G, 32) uint32 values; static shifts only.

    Value j of a group sits at bit ``j*w``: its low part in word
    ``(j*w) >> 5`` shifted right by ``(j*w) & 31``, and — when it straddles
    a word boundary — its high part in the next word, shifted left.  The
    body walks the w words once; each (G, 1) word column broadcasts across
    the 32 output lanes, and per-lane shift amounts and word selectors come
    from a lane iota.  No 1-D vector and no lane concatenation ever forms,
    so Mosaic's layouts hold on the chip."""
    w = width
    mask = jnp.uint32(0xFFFFFFFF) if w == 32 else jnp.uint32((1 << w) - 1)
    bit = _lane_iota(32) * jnp.uint32(w)
    lo_word, off = bit >> 5, bit & jnp.uint32(31)
    straddles = (off != 0) & (off + jnp.uint32(w) > 32)
    hi_shift = jnp.where(straddles, jnp.uint32(32) - off, jnp.uint32(0))
    out = jnp.zeros((p.shape[0], 32), jnp.uint32)
    for i in range(w):
        word = p[:, i : i + 1]  # (G, 1)
        out = out | jnp.where(lo_word == i, word >> off, jnp.uint32(0))
        if i:
            hi = straddles & (lo_word == i - 1)
            out = out | jnp.where(hi, word << hi_shift, jnp.uint32(0))
    return out & mask


def _bitunpack_kernel(p_ref, o_ref, *, width: int):
    o_ref[0] = _bitunpack_body(p_ref[0], width).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def bitunpack_pallas(
    packed: jax.Array, *, width: int, interpret: bool = False
) -> jax.Array:
    """packed (F, G, w) uint32, G % G_BLOCK == 0 -> (F, G, 32) int32."""
    f, g, w = packed.shape
    assert w == width and g % G_BLOCK == 0, (packed.shape, width)
    return pl.pallas_call(
        functools.partial(_bitunpack_kernel, width=width),
        out_shape=jax.ShapeDtypeStruct((f, g, 32), jnp.int32),
        grid=(f, g // G_BLOCK),
        in_specs=[pl.BlockSpec((1, G_BLOCK, w), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, G_BLOCK, 32), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(packed)


def _bytesplit_body(p: jax.Array) -> jax.Array:
    """(G, 4) plane words -> (G, 4) f32 values.

    Value j of group g takes byte j of plane word k as its byte k; each
    (G, 1) plane column broadcasts across the 4 output lanes with a per-lane
    shift, as in ``_bitunpack_body``."""
    shift = _lane_iota(4) * jnp.uint32(8)
    words = jnp.zeros(p.shape, jnp.uint32)
    for k in range(4):
        byte = (p[:, k : k + 1] >> shift) & jnp.uint32(0xFF)
        words = words | (byte << jnp.uint32(8 * k))
    return jax.lax.bitcast_convert_type(words, jnp.float32)


def _bytesplit_kernel(p_ref, o_ref):
    o_ref[0] = _bytesplit_body(p_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bytesplit_pallas(plane_words: jax.Array, *, interpret: bool = False) -> jax.Array:
    """plane_words (F, G, 4) uint32 -> (F, G, 4) f32."""
    f, g, four = plane_words.shape
    assert four == 4 and g % G_BLOCK == 0, plane_words.shape
    return pl.pallas_call(
        _bytesplit_kernel,
        out_shape=jax.ShapeDtypeStruct((f, g, 4), jnp.float32),
        grid=(f, g // G_BLOCK),
        in_specs=[pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, G_BLOCK, 4), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(plane_words)
