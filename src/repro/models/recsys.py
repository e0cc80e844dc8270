"""DLRM-style RecSys model (the paper's training stage, Table I).

Embedding tables (row-sharded over `model`), bottom MLP over dense features,
pairwise-dot feature interaction (batched GEMM), top MLP -> CTR logit.
Consumes the train-ready mini-batch produced by `repro.core.preprocess`
(dense + multi-hot SigridHashed ids + generated one-hot ids + labels).

Row-sharded embedding lookup runs in shard_map: each `model` shard gathers
ids that fall in its row range, mean-pools locally, and a single psum
combines — the standard row-wise sharding used by TorchRec/RecNMP-class
systems (one (B, T, D) all-reduce per batch).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.data.synth import RMDataConfig
from repro.distributed.sharding import ShardingRules
from repro.models.layers import (
    ParamDef,
    Schema,
    init_from_schema,
    pspecs_from_schema,
)


@dataclasses.dataclass(frozen=True)
class RecSysConfig:
    name: str
    data: RMDataConfig
    emb_dim: int = 128
    bottom_mlp: tuple = (512, 256, 128)
    top_mlp: tuple = (1024, 1024, 512, 256, 1)
    dtype: str = "float32"
    param_dtype: str = "float32"

    @property
    def n_tables(self) -> int:
        return self.data.n_tables

    @property
    def family(self) -> str:
        return "recsys"


def model_schema(cfg: RecSysConfig) -> Schema:
    nd = cfg.data.n_dense
    rows = cfg.data.embedding_rows
    s: Schema = {
        "tables": ParamDef(
            (cfg.n_tables, rows, cfg.emb_dim), (None, "vocab", None), scale=0.01
        ),
    }
    dims = (nd,) + cfg.bottom_mlp
    s["bottom"] = {
        f"w{i}": ParamDef((dims[i], dims[i + 1]), ("fsdp", None))
        for i in range(len(dims) - 1)
    }
    s["bottom_b"] = {
        f"b{i}": ParamDef((dims[i + 1],), (None,), init="zeros")
        for i in range(len(dims) - 1)
    }
    n_int = cfg.n_tables + 1
    top_in = n_int * (n_int - 1) // 2 + cfg.bottom_mlp[-1]
    tdims = (top_in,) + cfg.top_mlp
    s["top"] = {
        f"w{i}": ParamDef((tdims[i], tdims[i + 1]), ("fsdp", None))
        for i in range(len(tdims) - 1)
    }
    s["top_b"] = {
        f"b{i}": ParamDef((tdims[i + 1],), (None,), init="zeros")
        for i in range(len(tdims) - 1)
    }
    return s


def init_params(rng, cfg: RecSysConfig):
    return init_from_schema(rng, model_schema(cfg), jnp.dtype(cfg.param_dtype))


def param_pspecs(cfg: RecSysConfig, rules: ShardingRules):
    return pspecs_from_schema(model_schema(cfg), rules)


# ---------------------------------------------------------------------------
# Row-sharded embedding bag


def _local_bag(tables, ids, mask):
    """tables (T, R_local, D); ids (B, T, L) LOCAL row ids (may be invalid);
    mask (B, T, L) validity. Returns sum-pooled (B, T, D) + counts (B, T)."""
    r_local = tables.shape[1]
    valid = mask & (ids >= 0) & (ids < r_local)
    safe = jnp.clip(ids, 0, r_local - 1)

    def per_table(tab, idx, val):
        e = tab[idx]  # (B, L, D)
        return (e * val[..., None].astype(e.dtype)).sum(axis=1), val.sum(axis=1)

    pooled, counts = jax.vmap(per_table, in_axes=(0, 1, 1), out_axes=(1, 1))(
        tables, safe, valid
    )
    return pooled, counts  # (B, T, D), (B, T)


def embedding_bag(
    params_tables: jax.Array,  # (T, R, D) possibly row-sharded over model
    multi_ids: jax.Array,  # (B, S_tables, L)
    lengths: jax.Array,  # (B, S_tables)
    one_ids: jax.Array,  # (B, G_tables)
    cfg: RecSysConfig,
    rules: ShardingRules,
) -> jax.Array:
    """Mean-pooled embeddings for all tables -> (B, T, D)."""
    s_t = cfg.data.n_sparse
    L = cfg.data.max_sparse_len
    mask = jnp.arange(L)[None, None, :] < lengths[..., None]
    mesh = rules.mesh

    def bag(tables, mids, msk, oids):
        if mesh is not None and "model" in mesh.axis_names:
            shard = jax.lax.axis_index("model")
            r_local = tables.shape[1]
            offset = shard * r_local
        else:
            offset = 0
        pooled_m, cnt_m = _local_bag(tables[:s_t], mids - offset, msk)
        pooled_o, cnt_o = _local_bag(
            tables[s_t:], (oids - offset)[..., None], jnp.ones_like(oids[..., None], bool)
        )
        pooled = jnp.concatenate([pooled_m, pooled_o], axis=1)
        cnt = jnp.concatenate([cnt_m, cnt_o], axis=1)
        if mesh is not None and "model" in mesh.axis_names:
            pooled = jax.lax.psum(pooled, "model")
            cnt = jax.lax.psum(cnt, "model")
        return pooled / jnp.maximum(cnt[..., None], 1.0).astype(pooled.dtype)

    if mesh is None:
        return bag(params_tables, multi_ids, mask, one_ids)
    batch_axes = rules.mapping.get("batch")
    return shard_map(
        bag,
        mesh=mesh,
        in_specs=(
            P(None, rules.mapping.get("vocab"), None),
            P(batch_axes, None, None),
            P(batch_axes, None, None),
            P(batch_axes, None),
        ),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )(params_tables, multi_ids, mask, one_ids)


def _mlp(ws, bs, x, n):
    for i in range(n):
        x = x @ ws[f"w{i}"] + bs[f"b{i}"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def forward(params, minibatch: Dict[str, jax.Array], cfg: RecSysConfig,
            rules: ShardingRules) -> jax.Array:
    """Mini-batch -> CTR logits (B,)."""
    dense = rules.constrain(minibatch["dense"], "batch", None)
    bot = _mlp(params["bottom"], params["bottom_b"], dense, len(cfg.bottom_mlp))
    emb = embedding_bag(
        params["tables"],
        minibatch["multi_hot_ids"],
        minibatch["lengths"],
        minibatch["one_hot_ids"],
        cfg,
        rules,
    )  # (B, T, D)
    z = jnp.concatenate([bot[:, None, :], emb], axis=1)  # (B, T+1, D)
    inter = jnp.einsum("bnd,bmd->bnm", z, z)  # batched GEMM interaction
    n_int = cfg.n_tables + 1
    iu = jnp.triu_indices(n_int, k=1)
    flat = inter[:, iu[0], iu[1]]  # (B, n_int*(n_int-1)/2)
    top_in = jnp.concatenate([bot, flat], axis=1)
    logit = _mlp(params["top"], params["top_b"], top_in, len(cfg.top_mlp))
    return logit[:, 0]


def loss_fn(params, minibatch, cfg: RecSysConfig, rules: ShardingRules
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits = forward(params, minibatch, cfg, rules)
    labels = minibatch["labels"]
    loss = jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
    acc = jnp.mean((logits > 0) == (labels > 0.5))
    return loss, {"loss": loss, "accuracy": acc}
