"""The ETL Transform: encoded pages -> train-ready mini-batch.

The Transform itself is declared once as an operator graph
(``repro.core.opgraph``) and *lowered* per placement; everything in this
module is a thin wrapper over that lowering:

* ``preprocess_pages(mode="fused")``   — all families on ISP: decode+transform
  fused per column family (one HBM read of encoded bytes, one write of
  tensors) — the PreSto path.
* ``preprocess_pages(mode="unfused")`` — all families on host: the
  Disagg/CPU-style multi-step path (decode, then each transform as its own
  pass), used for the per-stage latency breakdown (paper Fig. 5 / Fig. 12).
* ``preprocess_pages(mode="hybrid")``  — per-family placement chosen by the
  cost model (bytes-moved vs compute roofline, ``core.costmodel``); a dict
  ``{family: "isp"|"host"}`` is also accepted.

Everything here is jit-able and shard_map-able; shapes are static given a
``PartitionSchema`` + ``TransformSpec``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.opgraph import (
    PAGE_WORDS,
    LoweredPlan,
    PageLayout,
    build_transform_graph,
    cut_pages,
    dataset_layout,
    kernel_pages,
    lower,
    page_geometry,
    prepare_env,
    resolve_placements,
)
from repro.core.spec import TransformSpec
from repro.data.columnar import Partition

MiniBatch = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Host-side page staging: Partition (numpy, flat pages) -> staged layout


def partition_words(part: Partition, layout: PageLayout) -> Tuple[np.ndarray, bool]:
    """A partition's page words in its schema's page order, and whether they
    are the stored body it was read as (a view, no copy).

    Every ``jax.device_put`` array costs the host a fixed time however few
    its bytes, so a mesh-less engine stages a partition as this ONE u32
    array and the compiled program cuts the kernels' arrays out of it
    (``opgraph.kernel_pages``).  A file stores its pages in this order, so a
    partition read from one stages its body as is; a partition built in
    memory (a synthetic source, an inflated dedup partition) is
    concatenated in the same order.  A body laid out otherwise raises: it
    is never mis-cut.
    """
    if part.body is not None:
        if part.body_table != layout.table:
            raise ValueError(
                f"partition {part.partition_id}: its stored pages are not in "
                "the dataset schema's page order"
            )
        return part.body, True
    pages = [part.columns[c].pages[p] for c, p, _ in layout.table]
    if [a.size for a in pages] != [n for *_, n in layout.table]:
        raise ValueError(
            f"partition {part.partition_id}: its pages are not the sizes of "
            "the dataset schema's"
        )
    return np.concatenate(pages), False


def stage_pages(part: Partition, spec: TransformSpec) -> Tuple[Dict[str, np.ndarray], bool]:
    """One partition's staged pages, as a mesh-less engine puts them:
    ``{PAGE_WORDS: (words,) u32}``, and whether that is its stored body.

    The partition's schema must be the dataset's (``spec.cfg``) at its
    rows, the layout the compiled program cuts by; another raises.
    """
    layout = dataset_layout(spec.cfg, part.schema.rows)
    if part.schema != layout.schema:
        raise ValueError(
            f"partition {part.partition_id}: its schema is not the schema of "
            f"dataset {spec.cfg.name}"
        )
    words, stored = partition_words(part, layout)
    return {PAGE_WORDS: words}, stored


def pages_from_partition(part: Partition, spec: TransformSpec) -> Dict[str, np.ndarray]:
    """One partition's pages at the kernels' shapes, as meshed engines stage
    them: ``dense_words`` ``(n_dense, rows/4, 4)``, ``sparse_words``
    ``(n_sparse, u*L/32, w)``, ``length_words`` ``(n_sparse, u/32, lw)``
    u32, ``label_words`` ``(rows,)`` u32 (and ``sparse_refs`` where the
    partition dedups) — its page words cut as the compiled program cuts
    them (``opgraph.cut_pages``), by the partition's own schema.
    """
    layout = PageLayout.of(part.schema, spec.cfg)
    words, _ = partition_words(part, layout)
    return {k: np.ascontiguousarray(v) for k, v in cut_pages(words, layout).items()}


def stack_pages(pages_list) -> Dict[str, np.ndarray]:
    """Stack K partitions' staged pages into one leading-axis megabatch.

    Input: K dicts from ``stage_partition`` (equal shapes — megabatches
    require uniform partition geometry, which the partitioned stores
    guarantee).  Output: one dict whose every array gains a leading K axis,
    the input of ``PreStoEngine.preprocess_megabatch``.
    """
    pages_list = list(pages_list)
    if len(pages_list) == 1:
        return {k: v[None] for k, v in pages_list[0].items()}
    return {
        k: np.stack([p[k] for p in pages_list]) for k in pages_list[0]
    }


def flatten_megabatch(
    stacked: Dict[str, jax.Array], spec: TransformSpec
) -> Dict[str, jax.Array]:
    """Fold the leading megabatch axis into the row-group axis (traceable).

    Every page array, viewed at its kernel shape (``kernel_pages``), is
    grouped ``(features, row_groups, words)`` with the feature axis leading
    (labels are flat ``(rows,)``), and every operator in the standard
    Transform is row-local — so a K-partition megabatch is exactly a single
    partition with K x the rows.  ``(K, F, G, w)`` becomes ``(F, K*G, w)``
    (partition-major row order) and ``(K, R)`` becomes ``(K*R,)``; the
    resulting mini-batch splits back per partition along its leading row
    axis.
    """
    stacked = kernel_pages(stacked, spec)
    out: Dict[str, jax.Array] = {}
    for name, v in stacked.items():
        if name == "sparse_refs":
            # (K, rows) block refs -> (K*rows,) into the K*u flattened unique
            # blocks: partition k's blocks land at offset k*u after the
            # sparse/length pages fold their own row-group axes below.
            k, _ = v.shape
            u = stacked["length_words"].shape[2] * 32
            off = (jnp.arange(k, dtype=v.dtype) * u)[:, None]
            out[name] = (v + off).reshape(-1)
        elif v.ndim == 2:  # label_words: (K, rows) -> (K*rows,)
            out[name] = v.reshape(-1)
        else:  # (K, F, G, w) -> (F, K*G, w)
            k, f, g, w = v.shape
            out[name] = jnp.moveaxis(v, 0, 1).reshape(f, k * g, w)
    return out


def megabatch_pages_shape_dtypes(
    spec: TransformSpec, rows: int, k: int
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for a K-partition stacked megabatch."""
    return {
        name: jax.ShapeDtypeStruct((k, *s.shape), s.dtype)
        for name, s in pages_shape_dtypes(spec, rows).items()
    }


def pages_shape_dtypes(spec: TransformSpec, rows: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the staged page arrays (dry-run inputs),
    as mesh-less engines stage them (``stage_pages``)."""
    words = dataset_layout(spec.cfg, rows).words
    return {PAGE_WORDS: jax.ShapeDtypeStruct((words,), jnp.uint32)}


def kernel_pages_shape_dtypes(
    spec: TransformSpec, rows: int
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the page arrays at the kernels' shapes,
    as ``pages_from_partition`` builds them and meshed engines stage them.

    Sparse/length pages live at unique-block geometry when the dataset
    dedups (``cfg.dup_factor > 1``), matching ``pages_from_partition``.
    """
    cfg = spec.cfg
    d = getattr(cfg, "dup_factor", 1)
    u32 = jnp.uint32
    out = {
        name: jax.ShapeDtypeStruct(shape, u32)
        for name, shape in page_geometry(cfg, rows, rows // d).items()
    }
    out["label_words"] = jax.ShapeDtypeStruct((rows,), u32)
    if d > 1:
        out["sparse_refs"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# Transform entry points (all lowered from the operator graph)


def execute_plan(plan: LoweredPlan, pages: Dict[str, jax.Array]) -> MiniBatch:
    """Run a lowered plan over staged pages, dedup-aware (traceable).

    Classic pages run ``plan.execute`` untouched.  Dedup pages (carrying
    ``sparse_refs``) run the sparse/length stages at unique-block geometry —
    decode + SigridHash touch each shared block once — then gather-expand
    ``sparse_hashed``/``lengths_i32`` through the refs just before
    ``form_batch``.  Every sparse-chain operator is per-value row-local
    (``kernels.ROW_LOCAL_KINDS``), so transform-then-expand is bitwise
    identical to expand-then-transform: the undeduped result, for fused,
    unfused and hybrid lowerings alike.
    """
    env = prepare_env(pages, plan.spec)
    refs = env.pop("sparse_refs", None)
    if refs is None:
        return plan.execute_env(env)
    refs = jnp.asarray(refs)
    cfg = plan.spec.cfg
    for st in plan.stages:
        if st.name == "form_batch":
            sh = env["sparse_hashed"]  # (n_sparse, u*L) at unique geometry
            s, ul = sh.shape
            blocks = sh.reshape(s, ul // cfg.max_sparse_len, cfg.max_sparse_len)
            env["sparse_hashed"] = jnp.take(blocks, refs, axis=1).reshape(
                s, refs.shape[0] * cfg.max_sparse_len
            )
            env["lengths_i32"] = jnp.take(env["lengths_i32"], refs, axis=0)
        vals = st.fn(*(env[k] for k in st.inputs))
        env.update(zip(st.outputs, vals))
    return env["minibatch"]


def preprocess_pages(
    pages: Dict[str, jax.Array],
    spec: TransformSpec,
    *,
    mode="fused",
    interpret: bool | None = None,
) -> MiniBatch:
    """Full Transform for one partition shard. Returns the train-ready batch.

    Output:
      dense          (rows, n_dense) f32      — Log-normalized
      multi_hot_ids  (rows, n_sparse, L) i32  — SigridHashed raw sparse ids
      lengths        (rows, n_sparse) i32     — multi-hot lengths
      one_hot_ids    (rows, n_generated) i32  — Bucketize+SigridHash generated
      labels         (rows,) f32
    """
    placements = resolve_placements(mode, spec)
    plan = lower(build_transform_graph(spec), spec, placements, interpret=interpret)
    return execute_plan(plan, pages)


def minibatch_shape_dtypes(spec: TransformSpec, rows: int) -> MiniBatch:
    cfg = spec.cfg
    return {
        "dense": jax.ShapeDtypeStruct((rows, cfg.n_dense), jnp.float32),
        "multi_hot_ids": jax.ShapeDtypeStruct(
            (rows, cfg.n_sparse, cfg.max_sparse_len), jnp.int32
        ),
        "lengths": jax.ShapeDtypeStruct((rows, cfg.n_sparse), jnp.int32),
        "one_hot_ids": jax.ShapeDtypeStruct((rows, cfg.n_generated), jnp.int32),
        "labels": jax.ShapeDtypeStruct((rows,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Stage-split functions for the latency breakdown (Fig. 5 / Fig. 12)


def stage_functions(spec: TransformSpec, *, interpret: bool | None = None):
    """Individually jit-able callables per ETL stage, for stage timing.

    Thin adapter over the all-host lowering: every body is a lowered graph
    stage (no transform logic lives here), regrouped into the paper's
    stage names.
    """
    plan = lower(
        build_transform_graph(spec), spec, resolve_placements("unfused", spec),
        interpret=interpret,
    )
    fns = {st.name: st.fn for st in plan.stages}
    src = jnp.asarray(np.asarray(spec.generated_source, np.int32))

    def extract_decode(pages):
        dense_raw = fns["decode_dense"](pages["dense_words"])[0]
        sparse_raw = fns["decode_sparse"](pages["sparse_words"])[0]
        return dense_raw, sparse_raw

    def gen_bucketize(dense_raw):
        return fns["bucketize_gen"](jnp.take(dense_raw, src, axis=0))[0]

    def norm_sigridhash(sparse_raw, bucket_ids):
        return fns["hash_sparse"](sparse_raw)[0], fns["hash_gen"](bucket_ids)[0]

    def norm_log(dense_raw):
        return fns["lognorm_dense"](dense_raw)[0]

    def form_minibatch(pages, dense_norm, hashed, gen_hashed):
        lengths = fns["decode_lengths"](pages["length_words"])[0]
        labels = fns["decode_labels"](pages["label_words"])[0]
        return fns["form_batch"](dense_norm, hashed, lengths, labels, gen_hashed)[0]

    return {
        "extract_decode": jax.jit(extract_decode),
        "gen_bucketize": jax.jit(gen_bucketize),
        "norm_sigridhash": jax.jit(norm_sigridhash),
        "norm_log": jax.jit(norm_log),
        "form_minibatch": jax.jit(form_minibatch),
    }
