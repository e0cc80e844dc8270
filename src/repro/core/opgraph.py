"""Operator-graph IR for the ETL Transform, with placement-aware lowering.

The Transform (encoded pages -> train-ready mini-batch) is declared ONCE as a
graph of typed operators over *column families* — independent groups of
columns that flow through their own decode->transform chain:

    family    pages consumed     chain                              batch key
    dense     dense_words        Decode(bytesplit) -> LogNorm       dense
    sparse    sparse_words       Decode(bitpack)   -> SigridHash    multi_hot_ids
    gen       gen_words [1]      Decode -> Bucketize -> SigridHash  one_hot_ids
    lengths   length_words       Decode(lengths)                    lengths
    labels    label_words        Decode(labels)                     labels

    [1] gen_words = the sourced dense planes (``spec.generated_source``),
        bound by ``prepare_env`` so the family is independent of `dense`.

A *placement* assigns each family to ``"isp"`` (the in-storage unit) or
``"host"`` (a CPU-style preprocessing server).  ``lower`` turns graph +
placement into an ordered stage list:

* an ISP-placed chain whose kind tuple appears in the op->kernel registry
  (``repro.kernels.FUSED_KERNELS``) lowers to ONE fused Pallas kernel —
  one read of encoded bytes, one write of tensors (the PreSto pipeline);
* a host-placed chain lowers to one stage per operator (the Disagg-style
  multi-pass baseline, also what the per-stage latency breakdown times).

The lowered plan is what every public entry point executes:
``preprocess_pages(mode=...)``, ``stage_functions`` and ``PreStoEngine``
are thin wrappers that build/lower this graph.  ``PreStoEngine`` renders a
family's host placement as collective-permutes on the data axis for exactly
that family's pages and outputs — so a ``hybrid`` placement moves only the
bytes of the families it actually sends to hosts.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import time
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spec import TransformSpec
from repro.data.columnar import REFS_COLUMN, PartitionSchema
from repro.data.synth import partition_schema
from repro.kernels import FUSED_KERNELS
from repro.kernels import ops as K
from repro.kernels import ref as R

ISP = "isp"
HOST = "host"
FAMILIES = ("dense", "sparse", "gen", "lengths", "labels")

# column family -> page values consumed / mini-batch keys produced.  The
# PreStoEngine uses these to hop exactly one family's traffic when that
# family is host-placed.
FAMILY_PAGE_VALUES: Dict[str, Tuple[str, ...]] = {
    "dense": ("dense_words",),
    "sparse": ("sparse_words",),
    "gen": ("gen_words",),
    "lengths": ("length_words",),
    "labels": ("label_words",),
}
FAMILY_BATCH_KEYS: Dict[str, Tuple[str, ...]] = {
    "dense": ("dense",),
    "sparse": ("multi_hot_ids",),
    "gen": ("one_hot_ids",),
    "lengths": ("lengths",),
    "labels": ("labels",),
}


# ---------------------------------------------------------------------------
# Nodes


@dataclasses.dataclass(frozen=True)
class OpNode:
    """One typed operator: consumes named values, produces one named value."""

    name: str
    family: str
    inputs: Tuple[str, ...]
    output: str

    @property
    def kind(self) -> str:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Decode(OpNode):
    encoding: str = "bytesplit"  # bytesplit | bitpack | lengths | labels
    width: int = 0  # bits per value (bitpack / lengths)

    @property
    def kind(self) -> str:
        return f"decode.{self.encoding}"


@dataclasses.dataclass(frozen=True)
class Bucketize(OpNode):
    @property
    def kind(self) -> str:
        return "bucketize"


@dataclasses.dataclass(frozen=True)
class SigridHash(OpNode):
    table: str = "sparse"  # which (seeds, max) bank of the spec: sparse | gen

    @property
    def kind(self) -> str:
        return "sigridhash"


@dataclasses.dataclass(frozen=True)
class LogNorm(OpNode):
    @property
    def kind(self) -> str:
        return "lognorm"


@dataclasses.dataclass(frozen=True)
class FormBatch(OpNode):
    @property
    def kind(self) -> str:
        return "formbatch"


# ---------------------------------------------------------------------------
# Graph


@dataclasses.dataclass(frozen=True)
class OpGraph:
    """Nodes + the page values bound externally; edges are value names."""

    nodes: Tuple[OpNode, ...]
    page_inputs: Tuple[str, ...]

    def __post_init__(self):
        produced = set(self.page_inputs)
        for n in self.nodes:  # nodes must already be topo-ordered
            missing = [i for i in n.inputs if i not in produced]
            if missing:
                raise ValueError(f"node {n.name} consumes unknown values {missing}")
            if n.output in produced:
                raise ValueError(f"value {n.output} produced twice")
            produced.add(n.output)

    def node(self, name: str) -> OpNode:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def family_chain(self, family: str) -> Tuple[OpNode, ...]:
        """The family's operators, in dependency order (graph order)."""
        return tuple(n for n in self.nodes if n.family == family)

    @property
    def families(self) -> Tuple[str, ...]:
        seen: List[str] = []
        for n in self.nodes:
            if n.family not in seen and not isinstance(n, FormBatch):
                seen.append(n.family)
        return tuple(seen)


def build_transform_graph(spec: TransformSpec) -> OpGraph:
    """The standard RecSys ETL Transform (paper Fig. 1) as an OpGraph."""
    cfg = spec.cfg
    nodes = (
        Decode("decode_dense", "dense", ("dense_words",), "dense_raw",
               encoding="bytesplit"),
        LogNorm("lognorm_dense", "dense", ("dense_raw",), "dense_norm"),
        Decode("decode_sparse", "sparse", ("sparse_words",), "sparse_raw",
               encoding="bitpack", width=cfg.id_width),
        SigridHash("hash_sparse", "sparse", ("sparse_raw",), "sparse_hashed",
                   table="sparse"),
        Decode("decode_gen", "gen", ("gen_words",), "gen_raw",
               encoding="bytesplit"),
        Bucketize("bucketize_gen", "gen", ("gen_raw",), "bucket_ids"),
        SigridHash("hash_gen", "gen", ("bucket_ids",), "gen_hashed",
                   table="gen"),
        Decode("decode_lengths", "lengths", ("length_words",), "lengths_i32",
               encoding="lengths", width=cfg.len_width),
        Decode("decode_labels", "labels", ("label_words",), "labels_f32",
               encoding="labels"),
        FormBatch(
            "form_batch", "batch",
            ("dense_norm", "sparse_hashed", "lengths_i32", "labels_f32",
             "gen_hashed"),
            "minibatch",
        ),
    )
    return OpGraph(
        nodes=nodes,
        page_inputs=("dense_words", "sparse_words", "length_words",
                     "label_words", "gen_words"),
    )


def page_geometry(cfg, rows: int, unique_rows: int) -> Dict[str, Tuple[int, int, int]]:
    """The kernels' ``(features, row_groups, words)`` shape of each grouped
    page array of a partition of `rows` (`unique_rows` sparse blocks)."""
    return {
        "dense_words": (cfg.n_dense, rows // 4, 4),
        "sparse_words": (
            cfg.n_sparse, unique_rows * cfg.max_sparse_len // 32, cfg.id_width
        ),
        "length_words": (cfg.n_sparse, unique_rows // 32, cfg.len_width),
    }


# the staged page buffer of a mesh-less engine: one partition's page words
PAGE_WORDS = "page_words"


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """Where the kernels' page arrays lie in a partition's page words.

    A partition's pages lie back to back in its schema's page order
    (``PartitionSchema.page_table``), as a file stores its body: each dense
    column's four byte planes, each sparse column's lengths and values
    (every bitpacked page ends in one guard word), the labels, and a dedup
    partition's block refs.  Every offset follows from the schema, so one
    compiled program cuts every partition of a dataset (``cut_pages``).
    """

    schema: PartitionSchema
    table: tuple  # the schema's page table
    words: int  # every page's words
    geometry: Dict[str, Tuple[int, int, int]]  # page_geometry of the schema
    dense: int  # first word of the dense columns, `rows` words each
    sparse: int  # first word of the sparse columns, `sparse_stride` each
    sparse_stride: int
    lengths: int  # a sparse column's lengths page, from the column's start
    values: int  # its values page, likewise
    labels: int
    refs: int | None  # dedup partitions only

    @property
    def rows(self) -> int:
        return self.schema.rows

    @staticmethod
    def of(schema: PartitionSchema, cfg) -> "PageLayout":
        first: Dict[str, int] = {}  # column -> its first word
        width: Dict[str, int] = {}  # column -> its words
        at: Dict[Tuple[str, str], int] = {}  # page -> word from its column's first
        table = schema.page_table()
        off = 0
        for col, page, n in table:
            first.setdefault(col, off)
            at[col, page] = off - first[col]
            width[col] = width.get(col, 0) + n
            off += n

        def block(prefix: str, count: int) -> Tuple[int, int]:
            """First word and words per column of `count` columns laid
            back to back."""
            names = [f"{prefix}{i}" for i in range(count)]
            if not names:
                return 0, 0
            start, w = first[names[0]], width[names[0]]
            if any(first[n] != start + i * w or width[n] != w
                   for i, n in enumerate(names)):
                raise ValueError(f"{prefix}* columns are not laid back to back")
            return start, w

        dense, dense_w = block("d", cfg.n_dense)
        if cfg.n_dense and dense_w != schema.rows:
            raise ValueError(f"a dense page holds {dense_w} words, not {schema.rows}")
        sparse, stride = block("s", cfg.n_sparse)
        return PageLayout(
            schema=schema,
            table=table,
            words=off,
            geometry=page_geometry(cfg, schema.rows, schema.unique_rows),
            dense=dense,
            sparse=sparse,
            sparse_stride=stride,
            lengths=at.get(("s0", "lengths"), 0),
            values=at.get(("s0", "values"), 0),
            labels=first["label"],
            refs=first.get(REFS_COLUMN),
        )


@functools.lru_cache(maxsize=None)
def dataset_layout(cfg, rows: int) -> PageLayout:
    """The layout of every partition of `rows` rows of dataset `cfg`."""
    return PageLayout.of(partition_schema(cfg, rows), cfg)


@functools.lru_cache(maxsize=None)
def staged_layout(cfg, words: int) -> PageLayout:
    """The layout of a partition of dataset `cfg` staged as `words` words.

    The rows are the one number of the schema the dataset leaves open, and
    the words fix them: rows come in steps of 32 sparse blocks, and each
    step adds the same number of words.
    """
    step = 32 * cfg.dup_factor
    base = dataset_layout(cfg, step).words
    per_step = dataset_layout(cfg, 2 * step).words - base
    steps, extra = divmod(words - base, per_step)
    if steps < 0 or extra:
        raise ValueError(f"no partition of {cfg.name} has {words} page words")
    return dataset_layout(cfg, step * (steps + 1))


def cut_pages(words, layout: PageLayout) -> Dict[str, Any]:
    """The kernels' page arrays cut out of page words (traceable).

    `words` is ``(..., layout.words)`` u32, a leading megabatch axis kept.
    Dense data pages ``(F, 4, G)`` turn to ``(F, G, 4)``; each sparse
    column's lengths and values pages lose their guard word; the refs page
    is bitcast to int32.  A numpy array gives numpy views.
    """
    lead = tuple(words.shape[:-1])
    rows = layout.rows
    f, g, _ = layout.geometry["dense_words"]
    dense = words[..., layout.dense : layout.dense + f * rows]
    out = {"dense_words": dense.reshape(*lead, f, 4, g).swapaxes(-1, -2)}
    f = layout.geometry["sparse_words"][0]
    sparse = words[..., layout.sparse : layout.sparse + f * layout.sparse_stride]
    sparse = sparse.reshape(*lead, f, layout.sparse_stride)
    for name, at in (("sparse_words", layout.values), ("length_words", layout.lengths)):
        f, g, w = layout.geometry[name]
        out[name] = sparse[..., at : at + g * w].reshape(*lead, f, g, w)
    out["label_words"] = words[..., layout.labels : layout.labels + rows]
    if layout.refs is not None:
        refs = words[..., layout.refs : layout.refs + rows]
        out["sparse_refs"] = (
            refs.view(np.int32) if isinstance(refs, np.ndarray)
            else jax.lax.bitcast_convert_type(refs, jnp.int32)
        )
    return out


def kernel_pages(pages: Dict[str, jax.Array], spec: TransformSpec) -> Dict[str, jax.Array]:
    """View staged pages at the kernels' ``(F, G, w)`` shapes (traceable).

    The one place where staged pages enter a compiled body.  A mesh-less
    engine stages a partition's page words (``PAGE_WORDS``, ``(..., words)``
    u32, a leading megabatch axis kept), which ``cut_pages`` cuts by the
    dataset's layout.  Pages already at the kernels' shapes, as meshed
    engines stage them, pass through.
    """
    words = pages.get(PAGE_WORDS)
    if words is None:
        return dict(pages)
    return cut_pages(words, staged_layout(spec.cfg, words.shape[-1]))


def staged_rows(pages: Dict[str, jax.Array], spec: TransformSpec) -> int:
    """Rows of one partition of staged pages, in either form."""
    words = pages.get(PAGE_WORDS)
    if words is None:
        return int(pages["label_words"].shape[-1])
    return staged_layout(spec.cfg, words.shape[-1]).rows


def prepare_env(pages: Dict[str, jax.Array], spec: TransformSpec) -> Dict[str, Any]:
    """Bind graph page inputs from the staged page arrays.

    The page arrays enter at the kernels' shapes (``kernel_pages``).
    ``gen_words`` (the generated features' source planes) is a static gather
    of dense pages — computed here so the gen family never depends on the
    dense family's placement.
    """
    env = kernel_pages(pages, spec)
    src = jnp.asarray(np.asarray(spec.generated_source, np.int32))
    env["gen_words"] = jnp.take(env["dense_words"], src, axis=0)
    return env


# ---------------------------------------------------------------------------
# Placement resolution


def resolve_placements(mode, spec: TransformSpec, rows: int | None = None) -> Dict[str, str]:
    """mode -> {family: "isp"|"host"}.

    str modes: "fused"/"presto"/"isp" (all ISP), "unfused"/"disagg"/"host"
    (all host), or "hybrid" (per-family choice by the cost model).  A dict is
    taken verbatim (validated).
    """
    if isinstance(mode, dict):
        unknown = set(mode) - set(FAMILIES)
        if unknown:
            raise ValueError(f"unknown column families {sorted(unknown)}")
        bad = {f: p for f, p in mode.items() if p not in (ISP, HOST)}
        if bad:
            raise ValueError(f"placements must be 'isp' or 'host', got {bad}")
        out = {f: ISP for f in FAMILIES}
        out.update(mode)
        return out
    if mode in ("fused", "presto", ISP):
        return {f: ISP for f in FAMILIES}
    if mode in ("unfused", "disagg", HOST):
        return {f: HOST for f in FAMILIES}
    if mode == "hybrid":
        from repro.core.costmodel import choose_placement  # lazy: avoids cycle

        return choose_placement(spec, rows)
    raise ValueError(f"unknown mode/placement {mode!r}")


# ---------------------------------------------------------------------------
# Byte accounting (shared by the cost model and the collective tests)


def family_page_bytes(spec: TransformSpec, rows: int) -> Dict[str, int]:
    """Encoded bytes each family reads, per partition of `rows`.

    Dedup datasets (``cfg.dup_factor > 1``) store sparse/length pages at
    unique-block geometry, so those families read ``rows / dup_factor``
    rows' worth of encoded words (plus the 4-byte-per-sample refs page,
    charged to the sparse family that consumes it).  Dense/gen/labels stay
    per-sample.
    """
    cfg = spec.cfg
    d = max(int(getattr(cfg, "dup_factor", 1)), 1)
    words = {
        name: f * g * w
        for name, (f, g, w) in page_geometry(cfg, rows, rows // d).items()
    }
    return {
        "dense": words["dense_words"] * 4,  # bytesplit: 4 plane bytes / value
        "sparse": words["sparse_words"] * 4 + (rows * 4 if d > 1 else 0),
        "gen": cfg.n_generated * rows * 4,  # sourced dense planes
        "lengths": words["length_words"] * 4,
        "labels": rows * 4,
    }


def family_batch_bytes(spec: TransformSpec, rows: int) -> Dict[str, int]:
    """Train-ready tensor bytes each family writes, per partition of `rows`."""
    cfg = spec.cfg
    return {
        "dense": rows * cfg.n_dense * 4,
        "sparse": rows * cfg.n_sparse * cfg.max_sparse_len * 4,
        "gen": rows * cfg.n_generated * 4,
        "lengths": rows * cfg.n_sparse * 4,
        "labels": rows * 4,
    }


# ---------------------------------------------------------------------------
# Lowering


@dataclasses.dataclass
class Stage:
    """One executable unit of the lowered plan (a fused kernel or one op)."""

    name: str
    kind: str
    family: str
    placement: str  # "isp" | "host" | "local" (pure assembly)
    inputs: Tuple[str, ...]
    outputs: Tuple[str, ...]
    fn: Callable[..., tuple]
    node_names: Tuple[str, ...]


def _spec_digest(spec: TransformSpec) -> str:
    """Content digest of everything the Transform's output depends on."""
    h = hashlib.sha256()
    h.update(
        json.dumps(dataclasses.asdict(spec.cfg), sort_keys=True, default=str).encode()
    )
    h.update(json.dumps([int(i) for i in spec.generated_source]).encode())
    for arr in (
        spec.bucket_boundaries,
        spec.sparse_seeds,
        spec.sparse_max,
        spec.gen_seeds,
        spec.gen_max,
    ):
        a = np.ascontiguousarray(np.asarray(arr))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class LoweredPlan:
    spec: TransformSpec
    placements: Dict[str, str]
    stages: List[Stage]
    graph: OpGraph

    def structural_hash(self) -> str:
        """Stable content hash of the lowered graph (survives re-lowering).

        Covers the spec's transform parameters (boundaries, seeds, table
        sizes, geometry), the per-family placements, and the lowered stage
        structure (names, kinds, wiring) — but NOT the bound Python callables,
        so two independent lowerings of the same spec+placement hash alike.
        This is the ``lowered-opgraph hash`` component of a feature-cache key
        (``core.featcache.CacheKey``)."""
        h = hashlib.sha256()
        h.update(_spec_digest(self.spec).encode())
        h.update(json.dumps(sorted(self.placements.items())).encode())
        for st in self.stages:
            h.update(
                json.dumps(
                    [st.name, st.kind, st.family, st.placement,
                     list(st.inputs), list(st.outputs), list(st.node_names)]
                ).encode()
            )
        return h.hexdigest()[:16]

    def execute_env(self, env: Dict[str, Any]) -> Dict[str, jax.Array]:
        env = dict(env)
        for st in self.stages:
            vals = st.fn(*(env[k] for k in st.inputs))
            env.update(zip(st.outputs, vals))
        return env["minibatch"]

    def execute(self, pages: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return self.execute_env(prepare_env(pages, self.spec))

    def stage(self, name: str) -> Stage:
        for st in self.stages:
            if st.name == name:
                return st
        raise KeyError(name)

    def host_families(self) -> Tuple[str, ...]:
        return tuple(f for f in FAMILIES if self.placements.get(f) == HOST)

    def megabatch_safe(self) -> bool:
        """True iff every lowered stage is row-local (``kernels.
        ROW_LOCAL_KINDS``), i.e. stacking K partitions along the row axis
        and running one launch is bitwise identical to K solo launches.
        The megabatched produce path (``PreStoEngine.preprocess_megabatch``)
        refuses plans where this does not hold."""
        from repro.kernels import ROW_LOCAL_KINDS  # late: kernels import ops

        return all(st.kind in ROW_LOCAL_KINDS for st in self.stages)


def _op_fn(node: OpNode, spec: TransformSpec, interpret) -> Callable[..., tuple]:
    """Standalone pass for one operator (host lowering)."""
    if isinstance(node, Decode):
        if node.encoding == "bytesplit":
            return lambda w: (K.decode_bytesplit(w, interpret=interpret),)
        if node.encoding == "bitpack":
            width = node.width
            return lambda w: (K.decode_bitpack(w, width=width, interpret=interpret),)
        if node.encoding == "lengths":
            width = node.width

            def decode_lengths(w):
                lens = R.bitunpack_grouped(w, width)  # (S, G, 32)
                return (lens.reshape(lens.shape[0], -1).T.astype(jnp.int32),)

            return decode_lengths
        if node.encoding == "labels":
            return lambda w: (jax.lax.bitcast_convert_type(w, jnp.float32),)
        raise ValueError(f"unknown decode encoding {node.encoding}")
    if isinstance(node, Bucketize):
        return lambda v: (K.bucketize(v, spec.bucket_boundaries, interpret=interpret),)
    if isinstance(node, SigridHash):
        seeds, maxv = (
            (spec.sparse_seeds, spec.sparse_max)
            if node.table == "sparse"
            else (spec.gen_seeds, spec.gen_max)
        )
        return lambda v: (K.sigridhash(v, seeds, maxv, interpret=interpret),)
    if isinstance(node, LogNorm):
        return lambda v: (K.lognorm(v, interpret=interpret),)
    if isinstance(node, FormBatch):
        cfg = spec.cfg

        def form_batch(dense_norm, sparse_hashed, lengths_i32, labels_f32,
                       gen_hashed):
            rows = labels_f32.shape[0]
            return ({
                "dense": dense_norm.T,
                "multi_hot_ids": sparse_hashed.reshape(
                    cfg.n_sparse, rows, cfg.max_sparse_len
                ).transpose(1, 0, 2),
                "lengths": lengths_i32,
                "one_hot_ids": gen_hashed.T,
                "labels": labels_f32,
            },)

        return form_batch
    raise TypeError(f"unknown node type {type(node).__name__}")


def _fused_fn(kinds: Tuple[str, ...], family: str, spec: TransformSpec,
              interpret) -> Callable[..., tuple]:
    """Bind one fused Pallas kernel to the spec params its chain needs."""
    kernel = FUSED_KERNELS[kinds]
    cfg = spec.cfg
    if family == "dense":
        return lambda w: (kernel(w, interpret=interpret),)
    if family == "sparse":
        return lambda w: (
            kernel(w, spec.sparse_seeds, spec.sparse_max, width=cfg.id_width,
                   interpret=interpret),
        )
    if family == "gen":
        return lambda w: (
            kernel(w, spec.bucket_boundaries, spec.gen_seeds, spec.gen_max,
                   interpret=interpret),
        )
    raise ValueError(f"no fused binding for family {family}")


def lower(
    graph: OpGraph,
    spec: TransformSpec,
    placements: Dict[str, str],
    *,
    interpret: bool | None = None,
) -> LoweredPlan:
    """Graph + per-family placement -> ordered stage list.

    ISP-placed chains whose kind tuple is registered in FUSED_KERNELS become
    one fused-kernel stage; everything else lowers to one stage per op.
    """
    stages: List[Stage] = []
    for family in graph.families:
        chain = graph.family_chain(family)
        place = placements.get(family, ISP)
        kinds = tuple(n.kind for n in chain)
        if place == ISP and kinds in FUSED_KERNELS:
            stages.append(
                Stage(
                    name=f"fused_{family}",
                    kind="fused:" + "+".join(kinds),
                    family=family,
                    placement=ISP,
                    inputs=chain[0].inputs,
                    outputs=(chain[-1].output,),
                    fn=_fused_fn(kinds, family, spec, interpret),
                    node_names=tuple(n.name for n in chain),
                )
            )
        else:
            for n in chain:
                stages.append(
                    Stage(
                        name=n.name,
                        kind=n.kind,
                        family=family,
                        placement=place,
                        inputs=n.inputs,
                        outputs=(n.output,),
                        fn=_op_fn(n, spec, interpret),
                        node_names=(n.name,),
                    )
                )
    form = graph.node("form_batch")
    stages.append(
        Stage(
            name=form.name,
            kind=form.kind,
            family=form.family,
            placement="local",
            inputs=form.inputs,
            outputs=(form.output,),
            fn=_op_fn(form, spec, None),
            node_names=(form.name,),
        )
    )
    return LoweredPlan(spec=spec, placements=dict(placements), stages=stages,
                       graph=graph)


def lower_transform(
    spec: TransformSpec, mode="fused", *, interpret: bool | None = None
) -> LoweredPlan:
    """Convenience: build + lower the standard Transform in one call."""
    return lower(
        build_transform_graph(spec), spec, resolve_placements(mode, spec),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Stage timing (latency breakdown + per-placement-group provisioning)


def time_stages(
    plan: LoweredPlan,
    pages: Dict[str, jax.Array],
    *,
    iters: int = 3,
    warmup: int = 1,
) -> Dict[str, float]:
    """Best-of-`iters` wall time per lowered stage, threading real values."""
    env = prepare_env(pages, plan.spec)
    times: Dict[str, float] = {}
    for st in plan.stages:
        fn = jax.jit(st.fn)
        args = [env[k] for k in st.inputs]
        out = None
        for _ in range(max(warmup, 1)):
            out = fn(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        times[st.name] = best
        env.update(zip(st.outputs, out))
    return times


def group_times_by_placement(plan: LoweredPlan, times: Dict[str, float]) -> Dict[str, float]:
    """Aggregate per-stage seconds into placement groups (isp/host/local)."""
    groups: Dict[str, float] = {}
    for st in plan.stages:
        groups[st.placement] = groups.get(st.placement, 0.0) + times[st.name]
    return groups
