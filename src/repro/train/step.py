"""Train/serve step factories: pjit programs with explicit state shardings.

make_train_step        — grads (+ optional microbatch accumulation, optional
                         cross-pod int8 compression) + optimizer update.
make_train_step_with_ingest — ONE jit program: encoded pages -> PreSto
                         preprocessing -> model -> grads -> update.  This is
                         the paper's Fig. 1 pipeline fused end-to-end; in
                         presto placement the Extract+Transform stages add
                         zero collectives to the step.
make_serve_step        — one-token decode against caches.

TrainState is a plain dict {params, opt, step[, err]} so checkpointing and
elastic re-sharding stay format-trivial.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingRules
from repro.train.compression import crosspod_compressed_mean, init_error_state
from repro.train.optimizer import Optimizer


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)


# ---------------------------------------------------------------------------
# Optimizer-state pspecs via shape matching against param pspecs


def opt_state_pspecs(optimizer: Optimizer, params_struct, param_pspecs):
    """Derive opt-state PartitionSpecs: a state leaf whose shape equals the
    param's shape inherits the param pspec; factored (row/col) leaves drop
    the corresponding axis; scalars replicate."""
    state_struct = jax.eval_shape(optimizer.init, params_struct)
    pflat = jax.tree_util.tree_flatten(params_struct)[0]
    specflat = jax.tree_util.tree_flatten(
        param_pspecs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    by_shape: Dict[tuple, P] = {}
    for p, s in zip(pflat, specflat):
        by_shape.setdefault(tuple(p.shape), s)

    def match(leaf):
        shape = tuple(leaf.shape)
        if shape == ():
            return P()
        if shape in by_shape:
            return by_shape[shape]
        # factored leaf: find param whose shape[:-1] or shape[:-2]+[-1] matches
        for pshape, spec in by_shape.items():
            axes = list(spec) + [None] * (len(pshape) - len(list(spec)))
            if shape == pshape[:-1]:
                return P(*axes[:-1])
            if shape == pshape[:-2] + pshape[-1:]:
                return P(*(axes[:-2] + axes[-1:]))
        return P()

    return jax.tree.map(match, state_struct)


def state_shardings(
    mesh, optimizer: Optimizer, params_struct, param_pspecs, *, with_err: bool = False
):
    opt_specs = opt_state_pspecs(optimizer, params_struct, param_pspecs)
    specs = {"params": param_pspecs, "opt": opt_specs, "step": P()}
    if with_err:
        specs["err"] = param_pspecs
    if mesh is None:
        return specs
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def init_state(
    rng, init_params_fn: Callable, optimizer: Optimizer, *, with_err: bool = False
):
    params = init_params_fn(rng)
    state = {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if with_err:
        state["err"] = init_error_state(params)
    return state


# ---------------------------------------------------------------------------
# Train step


def make_train_step(
    loss_fn: Callable,  # (params, batch) -> (loss, metrics)
    optimizer: Optimizer,
    *,
    microbatches: int = 1,
    donate: bool = True,
):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return loss, metrics, grads

    def accumulate(params, batch):
        if microbatches <= 1:
            return grads_of(params, batch)
        split = lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])
        mb = jax.tree.map(split, batch)

        def body(carry, b):
            acc, loss_sum = carry
            loss, metrics, grads = grads_of(params, b)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_sum + loss), metrics

        # accumulate in the param dtype: f32 models keep f32 accumulation;
        # bf16 giants (grok/llama4) save a full f32 param-sized buffer
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
        (acc, loss_sum), metrics = jax.lax.scan(body, (zeros, 0.0), mb)
        grads = jax.tree.map(lambda g: g / microbatches, acc)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum / microbatches, metrics, grads

    def train_step(state, batch):
        loss, metrics, grads = accumulate(state["params"], batch)
        updates, opt, om = optimizer.update(grads, state["opt"], state["params"])
        params = apply_updates(state["params"], updates)
        new_state = dict(state, params=params, opt=opt, step=state["step"] + 1)
        return new_state, {**metrics, **om}

    return train_step


def make_compressed_train_step(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh,
    batch_pspec_fn: Callable[[Any], Any],  # batch struct -> pspecs
):
    """Train step with int8 + error-feedback gradient compression on the
    cross-pod (DCN) hop.  shard_map manual over 'pod' only: each pod computes
    its local-batch gradients (auto-sharded over data/model inside), then
    pods exchange int8 gradients.

    NOTE: `loss_fn` runs inside the pod-manual region, so it must be built
    with ShardingRules that do NOT reference the 'pod' axis (e.g.
    `ShardingRules.make(mesh, overrides={"batch": ("data",)})`) — mixing the
    manual axis into an auto sharding constraint is rejected by JAX."""
    assert "pod" in mesh.axis_names

    def train_step(state, batch):
        batch_specs = batch_pspec_fn(batch)

        def pod_body(params, opt, step, err, batch_pod):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch_pod
            )
            grads, err = crosspod_compressed_mean(grads, err, "pod")
            updates, opt, om = optimizer.update(grads, opt, params)
            params = apply_updates(params, updates)
            return params, opt, step + 1, err, {**metrics, **om}

        # metric structure is loss_fn-dependent: discover it via eval_shape
        npods = mesh.shape["pod"]
        local_batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // npods,) + x.shape[1:], x.dtype
            ),
            batch,
        )
        params_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state["params"]
        )
        metrics_struct = jax.eval_shape(
            lambda p, b: loss_fn(p, b)[1], params_struct, local_batch
        )
        metric_specs = jax.tree.map(
            lambda _: P(), {**metrics_struct, "grad_norm": 0, "lr": 0}
        )
        replicated = jax.tree.map(lambda _: P(), state["params"])
        opt_rep = jax.tree.map(lambda _: P(), state["opt"])
        out = shard_map(
            pod_body,
            mesh=mesh,
            axis_names={"pod"},
            in_specs=(replicated, opt_rep, P(), replicated, batch_specs),
            out_specs=(replicated, opt_rep, P(), replicated, metric_specs),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], state["err"], batch)
        params, opt, step, err, metrics = out
        return dict(params=params, opt=opt, step=step, err=err), metrics

    return train_step


def make_train_step_with_ingest(
    engine,  # PreStoEngine
    model_loss_fn: Callable,  # (params, minibatch) -> (loss, metrics)
    optimizer: Optimizer,
):
    """Fused Extract→Transform→Load→train program (paper Fig. 1)."""

    def step(state, pages):
        minibatch = engine.preprocess_global(pages)
        (loss, metrics), grads = jax.value_and_grad(model_loss_fn, has_aux=True)(
            state["params"], minibatch
        )
        updates, opt, om = optimizer.update(grads, state["opt"], state["params"])
        params = apply_updates(state["params"], updates)
        return dict(state, params=params, opt=opt, step=state["step"] + 1), {
            **metrics,
            **om,
        }

    return step


# ---------------------------------------------------------------------------
# Serve step


def make_serve_step(decode_fn: Callable):
    """decode_fn(params, token, caches, cache_len) -> (logits, caches)."""

    def serve_step(params, token, caches, cache_len):
        logits, new_caches = decode_fn(params, token, caches, cache_len)
        next_token = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_token[:, None], logits, new_caches

    return serve_step
