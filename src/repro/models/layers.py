"""Foundational neural layers: schema-driven params, norms, RoPE, attention.

Parameters are declared via ParamDef schemas — a single source of truth that
yields (a) initialized pytrees, (b) PartitionSpec pytrees for pjit, so init
and sharding can never drift apart.

Attention is blockwise with online softmax (an XLA-level flash attention):
memory stays O(q_block x kv_block) regardless of sequence length, which is
what makes prefill_32k and long_500k lowerable.  Patterns (causal, sliding
window, chunked) are expressed as per-block masks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingRules

# ---------------------------------------------------------------------------
# Param schemas


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple  # logical axis names (len == ndim)
    init: str = "normal"  # normal | zeros | ones
    scale: Optional[float] = None  # None -> 1/sqrt(fan_in)

    def fan_in(self) -> int:
        # second-minor dim: correct for (d_in, d_out), stacked (L, d_in,
        # d_out), and expert (E, d_in, d_out) layouts alike
        return self.shape[-2] if len(self.shape) > 1 else self.shape[-1]


Schema = Dict[str, Any]  # nested dict of ParamDef


def _path_seed(path: str) -> int:
    import zlib

    return zlib.crc32(path.encode())


def init_from_schema(rng: jax.Array, schema: Schema, dtype) -> Dict[str, Any]:
    def walk(node, path):
        if isinstance(node, ParamDef):
            key = jax.random.fold_in(rng, _path_seed(path))
            if node.init == "zeros":
                return jnp.zeros(node.shape, dtype)
            if node.init == "ones":
                return jnp.ones(node.shape, dtype)
            scale = node.scale if node.scale is not None else 1.0 / math.sqrt(
                max(node.fan_in(), 1)
            )
            return (jax.random.normal(key, node.shape, jnp.float32) * scale).astype(
                dtype
            )
        return {k: walk(v, f"{path}/{k}") for k, v in node.items()}

    return walk(schema, "")


def pspecs_from_schema(schema: Schema, rules: ShardingRules) -> Dict[str, Any]:
    def walk(node):
        if isinstance(node, ParamDef):
            return rules.pspec(*node.axes)
        return {k: walk(v) for k, v in node.items()}

    return walk(schema)


def shapes_from_schema(schema: Schema, dtype) -> Dict[str, Any]:
    def walk(node):
        if isinstance(node, ParamDef):
            return jax.ShapeDtypeStruct(node.shape, dtype)
        return {k: walk(v) for k, v in node.items()}

    return walk(schema)


def stack_schema(schema: Schema, n: int) -> Schema:
    """Prepend a scan ('layers') axis of length n to every leaf."""

    def walk(node):
        if isinstance(node, ParamDef):
            return ParamDef(
                (n,) + node.shape, ("layers",) + node.axes, node.init, node.scale
            )
        return {k: walk(v) for k, v in node.items()}

    return walk(schema)


def load_weight(p: jax.Array, rules: ShardingRules, *axes, dtype) -> jax.Array:
    """FSDP weight load: cast to the compute dtype and constrain WITHOUT the
    fsdp axis — an explicit bf16 all-gather of the weight shard.

    Without this, XLA's SPMD partitioner may instead reshard the
    ACTIVATIONS to contract against the fsdp-sharded weight: measured on
    glm4-9b train, that choice moves f32 activation tensors ~8x per layer
    per microbatch (345 GB/step/device of all-gather alone) versus ~46 GB
    for bf16 weight-gathering.  §Perf iteration 1."""
    return rules.constrain(p.astype(dtype), *axes)


# ---------------------------------------------------------------------------
# Norms


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, D), positions (..., S) -> rotated x (half-split RoPE)."""
    d = x.shape[-1]
    half = d // 2
    freq = (theta ** (-np.arange(0, half, dtype=np.float32) / half)).astype(np.float32)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (train/prefill)


def _pattern_mask(
    qpos: jax.Array, kpos: jax.Array, pattern: str, window: int, chunk: int, causal: bool
) -> jax.Array:
    """(Qb, KVb) bool mask from positions."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if pattern == "swa" and window > 0:
        m &= (qpos[:, None] - kpos[None, :]) < window
    if pattern == "chunked" and chunk > 0:
        m &= (qpos[:, None] // chunk) == (kpos[None, :] // chunk)
    return m


def blockwise_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, K, D)
    v: jax.Array,  # (B, Skv, K, D)
    *,
    pattern: str = "full",
    window: int = 0,
    chunk: int = 0,
    causal: bool = True,
    q_offset: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
    segment_ids_q: Optional[jax.Array] = None,
    segment_ids_kv: Optional[jax.Array] = None,
) -> jax.Array:
    """Online-softmax attention, O(q_block*kv_block) memory. GQA via groups."""
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    assert Sq % q_block == 0 and Skv % kv_block == 0, (Sq, q_block, Skv, kv_block)
    nq, nk = Sq // q_block, Skv // kv_block
    scale = 1.0 / math.sqrt(D)

    qr = q.reshape(B, nq, q_block, K, G, D).transpose(1, 0, 3, 4, 2, 5)
    # qr: (nq, B, K, G, Qb, D)
    kr = k.reshape(B, nk, kv_block, K, D).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(B, nk, kv_block, K, D).transpose(1, 0, 3, 2, 4)
    # kr/vr: (nk, B, K, KVb, D)
    segq = (
        segment_ids_q.reshape(B, nq, q_block).transpose(1, 0, 2)
        if segment_ids_q is not None
        else None
    )
    segk = (
        segment_ids_kv.reshape(B, nk, kv_block).transpose(1, 0, 2)
        if segment_ids_kv is not None
        else None
    )

    def q_step(_, qi):
        qb, iq, sq = qi
        qpos = q_offset + iq * q_block + jnp.arange(q_block)

        def kv_step(carry, kj):
            m_run, l_run, acc = carry
            kb, vb, jk, sk = kj
            kpos = jk * kv_block + jnp.arange(kv_block)
            logits = (
                jnp.einsum(
                    "bkgqd,bkcd->bkgqc", qb.astype(jnp.float32), kb.astype(jnp.float32)
                )
                * scale
            )  # (B,K,G,Qb,KVb)
            mask = _pattern_mask(qpos, kpos, pattern, window, chunk, causal)
            if sq is not None:
                mask = mask & (sq[:, None, None, :, None] == sk[:, None, None, None, :])
                logits = jnp.where(mask, logits, -1e30)
            else:
                logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m_run, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p, vb.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_block, D), jnp.float32)
        # single-block KV: no loop — avoids while-loop overhead AND nested
        # scans, which old XLA cannot partition in partial-manual regions
        if nk == 1:
            (m, l, acc), _ = kv_step(
                (m0, l0, a0),
                (kr[0], vr[0], jnp.int32(0), segk[0] if segk is not None else None),
            )
        elif segk is not None:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (kr, vr, jnp.arange(nk), segk)
            )
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, x: kv_step(c, (x[0], x[1], x[2], None)),
                (m0, l0, a0),
                (kr, vr, jnp.arange(nk)),
            )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    if nq == 1:
        _, out1 = q_step(None, (qr[0], jnp.int32(0), segq[0] if segq is not None else None))
        outs = out1[None]
    elif segq is not None:
        _, outs = jax.lax.scan(q_step, None, (qr, jnp.arange(nq), segq))
    else:
        _, outs = jax.lax.scan(
            lambda c, x: q_step(c, (x[0], x[1], None)), None, (qr, jnp.arange(nq))
        )
    # outs: (nq, B, K, G, Qb, D) -> (B, Sq, H, D)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, K * G, D)


# ---------------------------------------------------------------------------
# Decode attention (single new token against a KV cache)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,  # (B, S, K, D)
    cache_len: jax.Array,  # (B,) valid prefix length (new token included)
    *,
    pattern: str = "full",
    window: int = 0,
    chunk: int = 0,
) -> jax.Array:
    B, S, K, D = k_cache.shape
    H = q.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, K, G, D)
    logits = (
        jnp.einsum("bkgd,bskd->bkgs", qr.astype(jnp.float32), k_cache.astype(jnp.float32))
        * scale
    )
    kpos = jnp.arange(S)[None, :]  # (1, S)
    qpos = cache_len[:, None] - 1  # (B, 1) position of the new token
    m = kpos < cache_len[:, None]
    if pattern == "swa" and window > 0:
        m &= (qpos - kpos) < window
    if pattern == "chunked" and chunk > 0:
        m &= (qpos // chunk) == (kpos // chunk)
    logits = jnp.where(m[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def cp_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    mesh,
    axis: str = "data",
    pattern: str = "full",
    window: int = 0,
    chunk: int = 0,
):
    """Context-parallel decode: KV cache sharded over `axis` along seq.

    Flash-decoding combine: each shard computes a partial (max, denom,
    weighted sum) over its local KV slice; partials merge with a psum-style
    logsumexp.  Used for long_500k where batch=1 cannot shard."""
    B, S, K, D = k_cache.shape
    H = q.shape[2]
    G = H // K
    n_shards = mesh.shape[axis]
    scale = 1.0 / math.sqrt(D)

    def body(q, kc, vc, clen):
        shard = jax.lax.axis_index(axis)
        s_local = kc.shape[1]
        qr = q.reshape(B, K, G, D)
        logits = (
            jnp.einsum("bkgd,bskd->bkgs", qr.astype(jnp.float32), kc.astype(jnp.float32))
            * scale
        )
        kpos = shard * s_local + jnp.arange(s_local)[None, :]
        qpos = clen[:, None] - 1
        m = kpos < clen[:, None]
        if pattern == "swa" and window > 0:
            m &= (qpos - kpos) < window
        if pattern == "chunked" and chunk > 0:
            m &= (qpos // chunk) == (kpos // chunk)
        logits = jnp.where(m[:, None, None, :], logits, -1e30)
        m_loc = logits.max(axis=-1)  # (B,K,G)
        p = jnp.exp(logits - m_loc[..., None])
        l_loc = p.sum(axis=-1)
        acc = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
        # combine partials across shards
        m_glob = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, axis)
        acc_glob = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob[..., None], 1e-30)
        return out.reshape(B, 1, H, D).astype(q.dtype)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P()),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)


# ---------------------------------------------------------------------------
# MLPs


def mlp_schema(cfg, kind: str) -> Schema:
    d, f = cfg.d_model, cfg.d_ff
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((d, f), ("fsdp", "ff")),
            "w_up": ParamDef((d, f), ("fsdp", "ff")),
            "w_down": ParamDef((f, d), ("ff", "fsdp")),
        }
    return {
        "w_in": ParamDef((d, f), ("fsdp", "ff")),
        "w_out": ParamDef((f, d), ("ff", "fsdp")),
    }


def mlp_apply(params, x: jax.Array, kind: str, rules: ShardingRules) -> jax.Array:
    dt = x.dtype
    if kind in ("swiglu", "geglu"):
        w_gate = load_weight(params["w_gate"], rules, None, "ff", dtype=dt)
        w_up = load_weight(params["w_up"], rules, None, "ff", dtype=dt)
        w_down = load_weight(params["w_down"], rules, "ff", None, dtype=dt)
        g = x @ w_gate
        u = x @ w_up
        g = rules.constrain(g, "batch", "seq", "ff")
        act = jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = act * u
        out = h @ w_down
    else:
        w_in = load_weight(params["w_in"], rules, None, "ff", dtype=dt)
        w_out = load_weight(params["w_out"], rules, "ff", None, dtype=dt)
        h = jax.nn.gelu(x @ w_in, approximate=True)
        h = rules.constrain(h, "batch", "seq", "ff")
        out = h @ w_out
    return rules.constrain(out, "batch", "seq", "embed")
