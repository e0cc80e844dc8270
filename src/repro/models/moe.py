"""Mixture-of-Experts FFN with GShard-style grouped capacity routing.

Groups = batch rows (so the group axis carries the batch sharding and every
rank participates); within a group, tokens are routed in sequence blocks of
`MOE_BLOCK_SEQ` with per-block expert capacity C = tb*k/E*cf.  All routing
math (cumsum positions, one-hot dispatch) is group-local: no cross-shard
dependencies, so pjit partitions the whole layer cleanly:

    dispatch  (G, tb, E, C) x (G, tb, d)  -> (G, E, C, d)     [batch-sharded]
    experts   (G, E, C, d)  x (E, d, f)   -> (G, E, C, f)     [EP/TP-sharded]
    combine   (G, tb, E, C) x (G, E, C, d)-> (G, tb, d)

Dispatch/combine overhead = 2*tb*k*cf*d flops/token — ~1% of expert compute
at tb=512.  Capacity drops are per (group, block), standard GShard dropping;
decode blocks (tb=1) never drop.  The einsum formulation renders the
token<->expert movement as XLA collectives on the expert buffers;
EXPERIMENTS.md §Perf compares it against a shard_map all-to-all dispatch.

Baseline-vs-history note: the first implementation scanned over flattened
token blocks; with batch-sharded activations the scan axis absorbed the
sharding and XLA replicated ALL routing compute per device (20x flops).
Group-blocked routing is the fix — kept as the paper-faithful baseline.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.distributed.sharding import ShardingRules
from repro.models.layers import ParamDef, Schema, load_weight

# Tokens routed per scan step, per group.  4096 makes train (seq 4k after
# microbatching) and decode single-block — critical because every scan step
# re-all-gathers the FSDP-sharded expert weights; only prefill_32k pays the
# multi-block cost (8 blocks), which §Perf attacks separately.
MOE_BLOCK_SEQ = 4096


def moe_schema(cfg) -> Schema:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    # 'ff' resolves to None when 'experts' already claims the model axis
    # (llama4, jamba: EP).  When experts replicate (grok: 8 experts < 16-way
    # axis, per-arch override), 'ff' claims model and each expert is TP'd.
    return {
        "router": ParamDef((d, e), (None, None)),
        "w_gate": ParamDef((e, d, f), ("experts", "fsdp", "ff")),
        "w_up": ParamDef((e, d, f), ("experts", "fsdp", "ff")),
        "w_down": ParamDef((e, f, d), ("experts", "ff", "fsdp")),
    }


def _route_block(
    xb: jax.Array, router: jax.Array, k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xb (G, tb, d) -> (dispatch (G,tb,E,C), gates (G,tb,E), aux scalar)."""
    e = router.shape[1]
    logits = xb.astype(jnp.float32) @ router.astype(jnp.float32)  # (G,tb,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (G,tb,k)
    sel = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=2)  # (G,tb,E)
    gates = sel * probs
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # capacity position within (group, block) — cumsum over the token axis
    pos = jnp.cumsum(sel, axis=1) - sel
    keep = sel * (pos < capacity)
    dispatch = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = dispatch * keep[..., None]  # (G,tb,E,C)
    frac_tokens = sel.mean(axis=(0, 1))
    frac_probs = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) / max(k, 1)
    return dispatch, gates, aux


def _moe_apply_a2a(params, x: jax.Array, cfg, rules: ShardingRules,
                   tb: int, nb: int, capacity: int, axis: str = "data"):
    """EP-over-data via explicit all-to-alls (shard_map, manual over 'data').

    §Perf iteration L3: with experts sharded over `data`, auto-SPMD renders
    the batch->expert reshard as a FULL all-gather of the microbatch
    activations per MoE layer (measured 1.5 TB/step/device on llama4).  The
    textbook EP exchange moves only the dispatched expert buffers:
    per-device a2a payload = |xe_local| = E*C*d/nd, ~20x smaller.  Dense
    token compute + routing stay local; expert FFNs run on all-to-all'd
    buffers; a reverse a2a returns outputs.  'model'-axis TP inside each
    expert stays on auto (partial-manual shard_map)."""
    import jax.numpy as jnp  # local alias for clarity

    mesh = rules.mesh
    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    nd = mesh.shape[axis]
    e_local = e // nd
    dt = x.dtype
    from jax.sharding import PartitionSpec as P

    def body(xb, router, w_gate, w_up, w_down):
        bl = xb.shape[0]

        def block(aux, xt):  # xt (bl, tb, d) local tokens
            dispatch, gates, aux_b = _route_block(xt, router, k, capacity)
            disp = dispatch.astype(dt)
            xe = jnp.einsum("gtec,gtd->gecd", disp, xt)  # (bl, E, C, d)
            xe = xe.reshape(bl, nd, e_local, capacity, d)
            xe = jax.lax.all_to_all(xe, axis, 1, 0, tiled=True)
            xe = xe.reshape(bl * nd, e_local, capacity, d)  # all groups, local experts
            g = jnp.einsum("gecd,edf->gecf", xe, w_gate)
            u = jnp.einsum("gecd,edf->gecf", xe, w_up)
            h = jax.nn.silu(g) * u
            ye = jnp.einsum("gecf,efd->gecd", h, w_down)
            ye = jax.lax.all_to_all(
                ye.reshape(bl * nd, 1, e_local, capacity, d), axis, 0, 1,
                tiled=True,
            )  # (bl, nd, e_local, C, d)
            ye = ye.reshape(bl, e, capacity, d)
            out = jnp.einsum("gtec,gecd->gtd", disp * gates[..., None].astype(dt), ye)
            return aux + aux_b, out

        if nb == 1:
            aux, out = block(jnp.zeros((), jnp.float32), xb)
        else:
            xs = xb.reshape(bl, nb, tb, d).transpose(1, 0, 2, 3)
            aux, outs = jax.lax.scan(block, jnp.zeros((), jnp.float32), xs)
            out = outs.transpose(1, 0, 2, 3).reshape(bl, s, d)
            aux = aux / nb
        return out, jax.lax.pmean(aux, axis)

    w3 = P(axis, None, None)
    out, aux = shard_map(
        body,
        mesh=mesh,
        axis_names={axis},
        in_specs=(P(axis, None, None), P(), w3, w3, w3),
        out_specs=(P(axis, None, None), P()),
        check_vma=False,
    )(
        x,
        params["router"].astype(jnp.float32),
        params["w_gate"].astype(dt),
        params["w_up"].astype(dt),
        params["w_down"].astype(dt),
    )
    return rules.constrain(out, "batch", "seq", "embed"), aux


def moe_apply(
    params, x: jax.Array, cfg, rules: ShardingRules
) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux_loss)."""
    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    tb = min(MOE_BLOCK_SEQ, s)
    while s % tb:  # largest divisor of s not exceeding the target block
        tb -= 1
    nb = s // tb
    capacity = min(tb * k, max(int(tb * k / e * cfg.capacity_factor), 1))
    dt = x.dtype

    # EP placement: when 'experts' maps to a batch mesh axis (llama4: data),
    # the expert buffers reshard batch->expert (the all-to-all of EP) and the
    # expert weights never move.  Otherwise (EP over model, or replicated
    # experts) the buffers keep their batch sharding.
    exp_ax = rules.mapping.get("experts")
    batch_axes = rules.mapping.get("batch") or ()
    if not isinstance(batch_axes, tuple):
        batch_axes = (batch_axes,)
    ep_over_batch = isinstance(exp_ax, str) and exp_ax in batch_axes
    if (
        ep_over_batch
        and rules.mesh is not None
        and exp_ax in rules.mesh.axis_names
        and e % rules.mesh.shape[exp_ax] == 0
        and b % rules.mesh.shape[exp_ax] == 0
    ):
        return _moe_apply_a2a(params, x, cfg, rules, tb, nb, capacity, axis=exp_ax)
    lead = None if ep_over_batch else "batch"

    def block(aux, xb):  # xb (B, tb, d)
        dispatch, gates, aux_b = _route_block(xb, params["router"], k, capacity)
        disp = dispatch.astype(dt)
        xe = jnp.einsum("gtec,gtd->gecd", disp, xb)  # (B, E, C, d)
        xe = rules.constrain(xe, lead, "experts", None, None)
        w_gate = load_weight(params["w_gate"], rules, "experts", None, "ff", dtype=dt)
        w_up = load_weight(params["w_up"], rules, "experts", None, "ff", dtype=dt)
        w_down = load_weight(params["w_down"], rules, "experts", "ff", None, dtype=dt)
        g = jnp.einsum("gecd,edf->gecf", xe, w_gate)
        u = jnp.einsum("gecd,edf->gecf", xe, w_up)
        g = rules.constrain(g, lead, "experts", None, "ff")
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("gecf,efd->gecd", h, w_down)
        ye = rules.constrain(ye, lead, "experts", None, None)
        out_b = jnp.einsum(
            "gtec,gecd->gtd", disp * gates[..., None].astype(dt), ye
        )
        return aux + aux_b, out_b

    if nb == 1:
        aux, out = block(jnp.zeros((), jnp.float32), x[:, :s, :])
        out = out.reshape(b, s, d)
    else:
        xs = x.reshape(b, nb, tb, d).transpose(1, 0, 2, 3)  # (nb, B, tb, d)
        aux, outs = jax.lax.scan(block, jnp.zeros((), jnp.float32), xs)
        out = outs.transpose(1, 0, 2, 3).reshape(b, s, d)
        aux = aux / nb
    return rules.constrain(out, "batch", "seq", "embed"), aux
