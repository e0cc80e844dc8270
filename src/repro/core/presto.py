"""PreStoEngine: storage-centric vs. disaggregated vs. hybrid placement.

The paper's two system design points, plus the per-family generalization,
rendered in SPMD:

* ``presto`` (Fig. 8)   — every mesh shard preprocesses the partition rows it
  already owns; output batch sharding == input page sharding, so the compiled
  program contains **zero collectives** between Extract and Load.

* ``disagg`` (Fig. 7b)  — preprocessing happens on a *different* shard than
  both the storage shard and the consuming trainer shard.  We render the two
  network hops of server disaggregation as explicit ``ppermute``s on the
  ``data`` axis: raw pages hop storage→preprocessor, train-ready tensors hop
  preprocessor→trainer.  Their operand bytes are exactly the paper's
  copy-in/copy-out traffic and are measurable in the compiled HLO
  (see benchmarks/bench_comm.py and EXPERIMENTS.md §Dry-run).

* ``hybrid``            — per-column-family placement chosen by the cost
  model (``core.costmodel.choose_placement``) or passed explicitly: ISP
  families run the fused kernels locally (zero collectives); host families
  run the multi-pass kernels behind the two disagg hops — but only THEIR
  pages and outputs ride the permutes, so the HLO's collective bytes are
  exactly the host-placed families' traffic.

All placements execute the same operator graph (``core.opgraph``) — the
engine only decides per-family lowering (fused vs multi-pass) and which
family's traffic hops.  Both compose with the training step into ONE jit
program (`repro.train.step.make_train_step_with_ingest`), the end-to-end
"online preprocessing feeds training" pipeline of Fig. 1.
"""

from __future__ import annotations

import hashlib
import json
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common import trace
from repro.core.execcache import EXECUTABLES, ExecKey, mesh_key
from repro.core.opgraph import (
    FAMILIES,
    FAMILY_BATCH_KEYS,
    FAMILY_PAGE_VALUES,
    HOST,
    ISP,
    LoweredPlan,
    build_transform_graph,
    kernel_pages,
    lower,
    prepare_env,
    resolve_placements,
    staged_rows,
)
from repro.core.preprocess import (
    MiniBatch,
    execute_plan,
    flatten_megabatch,
    kernel_pages_shape_dtypes,
    pages_from_partition,
    pages_shape_dtypes,
    stack_pages,
    stage_pages,
)
from repro.core.spec import TransformSpec
from repro.data.columnar import inflate_partition
from repro.data.storage import PartitionedStore

PLACEMENTS = ("presto", "disagg", "hybrid")


def pages_pspec() -> Dict[str, P]:
    """Row-group axis of every page array is sharded over the data axis."""
    return {
        "dense_words": P(None, "data", None),
        "sparse_words": P(None, "data", None),
        "length_words": P(None, "data", None),
        "label_words": P("data"),
    }


def minibatch_pspec() -> Dict[str, P]:
    return {
        "dense": P("data", None),
        "multi_hot_ids": P("data", None, None),
        "lengths": P("data", None),
        "one_hot_ids": P("data", None),
        "labels": P("data"),
    }


class PreStoEngine:
    """Owns a TransformSpec and compiles the sharded preprocessing program."""

    def __init__(
        self,
        spec: TransformSpec,
        mesh: Optional[Mesh] = None,
        *,
        placement="presto",
        kernel_mode: Optional[str] = None,
        family_placements: Optional[Dict[str, str]] = None,
        interpret: bool | None = None,
        use_exec_cache: bool = True,
    ):
        if isinstance(placement, dict):
            family_placements, placement = dict(placement), "hybrid"
        assert placement in PLACEMENTS, placement
        self.spec = spec
        self.mesh = mesh
        self.placement = placement
        if placement == "hybrid":
            self.family_placements = resolve_placements(
                family_placements if family_placements is not None else "hybrid",
                spec,
            )
        else:
            uniform = ISP if placement == "presto" else HOST
            self.family_placements = {f: uniform for f in FAMILIES}
        # kernel_mode: "fused"/"unfused" force the kernel lowering regardless
        # of comm placement (presto/disagg historically both defaulted to the
        # fused kernels); None follows the family placements.
        self.kernel_mode = kernel_mode
        self.interpret = interpret
        # use_exec_cache=False opts out of the process-wide executable
        # registry (core.execcache): this engine then compiles privately,
        # exactly the pre-registry behavior (bench baseline / isolation).
        self.use_exec_cache = use_exec_cache
        self._plan: Optional[LoweredPlan] = None
        self._jit_cached = None
        self._jit_mega = None
        self._jit_rest = None
        self._jit_lock = threading.Lock()
        # Donating the page buffers lets XLA reuse their memory for outputs.
        # Only meaningful where the runtime honors donation (not the CPU
        # backend, which warns and ignores) and only safe for the produce
        # paths, which stage FRESH pages per call and never reuse them.
        self._donate = jax.default_backend() in ("gpu", "tpu")

    @property
    def lowered_plan(self) -> LoweredPlan:
        """The shared opgraph lowering every execution path runs through."""
        if self._plan is None:
            if self.kernel_mode is not None:
                kernel_placements = resolve_placements(self.kernel_mode, self.spec)
            elif self.placement == "disagg":
                # seed-compatible default: disagg moves the batch but still
                # runs the fused kernels on the preprocessing shard
                kernel_placements = resolve_placements("fused", self.spec)
            else:
                kernel_placements = self.family_placements
            self._plan = lower(
                build_transform_graph(self.spec),
                self.spec,
                kernel_placements,
                interpret=self.interpret,
            )
        return self._plan

    def host_families(self) -> tuple[str, ...]:
        return tuple(f for f in FAMILIES if self.family_placements[f] == HOST)

    def cache_signature(self) -> str:
        """Stable identity of this engine's Transform for feature-cache keys.

        Combines the lowered plan's structural hash (spec parameters + kernel
        placements + stage wiring) with the per-family comm placement (which
        families' traffic hops), so two engines that produce bitwise-equal
        batches for equal inputs — even engines built independently from an
        equal spec — share cache entries, and any placement that changes
        batch routing keys separately.  The engine-level placement *mode*
        string is deliberately NOT hashed here: it rides as ``CacheKey``'s
        third component (``core.service.JobSpec.cache_key_fn``)."""
        h = hashlib.sha256()
        h.update(self.lowered_plan.structural_hash().encode())
        h.update(json.dumps(sorted(self.family_placements.items())).encode())
        return h.hexdigest()[:16]

    def route_costs(self, rows: Optional[int] = None, model=None):
        """Whole-partition cost summary for the device-aware claim router.

        One ``costmodel.PartitionCosts`` per (engine, rows): modeled seconds
        on an idle ISP unit vs the host path, plus the ops and link bytes the
        device/host ledgers charge per produce.  Routing consumes these — it
        never changes the produced bytes."""
        from repro.core.costmodel import (  # local: costmodel is downstream
            DEFAULT_PLACEMENT_MODEL,
            partition_costs,
        )

        return partition_costs(
            self.spec, rows, model if model is not None else DEFAULT_PLACEMENT_MODEL
        )

    # -- single-shard (local) path -------------------------------------------
    def preprocess_local(self, pages: Dict[str, jax.Array]) -> MiniBatch:
        # dedup-staged pages (carrying ``sparse_refs``) run the sparse chain
        # at unique-block geometry and gather-expand inside the program —
        # bitwise identical to classic pages (preprocess.execute_plan)
        return execute_plan(self.lowered_plan, pages)

    # -- sharded global path ---------------------------------------------------
    def preprocess_global(self, pages: Dict[str, jax.Array]) -> MiniBatch:
        """Preprocess a global batch of encoded pages on the mesh.

        ISP-placed families are pure local compute.  Host-placed families'
        pages hop +1 on the data axis before compute and their mini-batch
        keys hop -1 after, modeling the disaggregated pool's copy-in/copy-out
        (the hops are real collective-permutes in the HLO).  ``presto`` = no
        host families (zero collectives); ``disagg`` = all host families.
        """
        if self.mesh is None:
            return self.preprocess_local(pages)
        mesh = self.mesh
        data_axis = "data"
        n_data = mesh.shape[data_axis]
        host_fams = self.host_families()
        plan = self.lowered_plan

        def body(pages):
            env = prepare_env(pages, self.spec)
            if host_fams and n_data > 1:
                perm_in = [(i, (i + 1) % n_data) for i in range(n_data)]
                # when dense pages hop anyway, gen's source planes are
                # recomputed from them on the far side instead of hopped —
                # disagg then moves exactly the seed's four page arrays
                skip_gen = "gen" in host_fams and "dense" in host_fams
                for fam in host_fams:
                    if fam == "gen" and skip_gen:
                        continue
                    for k in FAMILY_PAGE_VALUES[fam]:
                        env[k] = jax.lax.ppermute(env[k], data_axis, perm_in)
                if skip_gen:
                    src = jnp.asarray(
                        np.asarray(self.spec.generated_source, np.int32)
                    )
                    env["gen_words"] = jnp.take(env["dense_words"], src, axis=0)
            mb = plan.execute_env(env)
            if host_fams and n_data > 1:
                perm_out = [(i, (i - 1) % n_data) for i in range(n_data)]
                for fam in host_fams:
                    for k in FAMILY_BATCH_KEYS[fam]:
                        mb[k] = jax.lax.ppermute(mb[k], data_axis, perm_out)
            return mb

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(pages_pspec(),),
            out_specs=minibatch_pspec(),
            check_vma=False,
        )(pages)

    def jit_preprocess(self):
        """Compiled global preprocessing step with explicit shardings."""
        if self.mesh is None:
            return jax.jit(self.preprocess_local)
        in_sh = {
            k: NamedSharding(self.mesh, v) for k, v in pages_pspec().items()
        }
        out_sh = {
            k: NamedSharding(self.mesh, v) for k, v in minibatch_pspec().items()
        }
        return jax.jit(
            self.preprocess_global, in_shardings=(in_sh,), out_shardings=out_sh
        )

    def _exec_key(self, mode: str) -> ExecKey:
        # interpret changes the compiled program (interpreted vs native
        # Pallas), not the batch bytes — it keys the executable, never the
        # feature cache
        return ExecKey(
            signature=self.cache_signature(),
            mode=mode,
            mesh=mesh_key(self.mesh),
            interpret=self.interpret,
        )

    def _build_executable(self, mode: str, key: ExecKey):
        """jit wrapper for one execution mode, with trace accounting.

        The traced body notes each (re)compile in the process-wide registry
        — jit re-enters Python only when tracing, so the note count IS the
        compile count the discipline tests pin.  Page buffers are donated on
        backends that honor donation (the produce paths stage fresh pages
        every call and never reuse them).
        """
        if mode == "mega":
            inner = self.preprocess_megabatch

            def body(stacked):
                k = next(iter(stacked.values())).shape[0]
                EXECUTABLES.note_trace(
                    key, k=int(k), rows=staged_rows(stacked, self.spec)
                )
                return inner(stacked)

            return jax.jit(body, donate_argnums=(0,) if self._donate else ())
        if self.mesh is None:

            def body(pages):
                EXECUTABLES.note_trace(key, k=1, rows=staged_rows(pages, self.spec))
                return self.preprocess_local(pages)

            return jax.jit(body, donate_argnums=(0,) if self._donate else ())
        in_sh = {k: NamedSharding(self.mesh, v) for k, v in pages_pspec().items()}
        out_sh = {
            k: NamedSharding(self.mesh, v) for k, v in minibatch_pspec().items()
        }

        def body(pages):
            EXECUTABLES.note_trace(key, k=1, rows=staged_rows(pages, self.spec))
            return self.preprocess_global(pages)

        return jax.jit(body, in_shardings=(in_sh,), out_shardings=out_sh)

    def jit_preprocess_cached(self):
        """The compiled preprocessing step, shared process-wide.

        Sessions, provisioning probes, and pool workers all reuse the same
        compiled program, so a job's service-fed batches are bitwise
        identical to its single-tenant batches.  The executable is resolved
        through ``core.execcache.EXECUTABLES``: independently built engines
        with equal cache signatures (the multi-tenant norm) share ONE
        compile instead of one per engine, and concurrent cold first calls
        collapse to a single trace.  Locked per engine: concurrent first use
        must not resolve two registry entries.

        On donating backends (gpu/tpu) the page argument is DONATED: do not
        reuse the arrays you pass in after the call — stage fresh pages per
        call (the produce paths do) or pass a private ``jax.device_put``
        copy.
        """
        with self._jit_lock:
            if self._jit_cached is None:
                key = self._exec_key("solo")
                if self.use_exec_cache:
                    self._jit_cached = EXECUTABLES.get_or_build(
                        key, lambda: self._build_executable("solo", key)
                    )
                else:
                    self._jit_cached = self._build_executable("solo", key)
        return self._jit_cached

    # -- megabatched execution --------------------------------------------------

    def preprocess_megabatch(self, stacked: Dict[str, jax.Array]):
        """Transform a leading-axis megabatch of K partitions in ONE launch.

        ``stacked`` is ``preprocess.stack_pages`` output: every page array
        with a leading K axis.  The leading axis folds into the row-group
        axis (every Transform operator is row-local — asserted against
        ``kernels.ROW_LOCAL_KINDS``), the whole plan executes once at K x
        rows, and the fused mini-batch ``jnp.split``s back into K
        per-partition mini-batches, bitwise identical to K solo runs.
        Traceable; mesh-less engines only (the pool-worker produce path).
        """
        assert self.mesh is None, "megabatching is a local (per-unit) launch"
        k = int(next(iter(stacked.values())).shape[0])
        assert k == 1 or self.lowered_plan.megabatch_safe(), (
            "lowered plan has a non-row-local stage; megabatch would not be "
            "bitwise identical to solo runs"
        )
        mb = self.preprocess_local(flatten_megabatch(stacked, self.spec))
        if k == 1:
            return (mb,)
        split = {key: jnp.split(v, k, axis=0) for key, v in mb.items()}
        return tuple({key: split[key][i] for key in mb} for i in range(k))

    def jit_preprocess_megabatch_cached(self):
        """Compiled megabatch launch, shared process-wide like the solo one.

        One registry entry per engine signature; megabatch width K and rows
        specialize inside it through jit's shape cache (static shapes — each
        (K, rows) compiles once per process, then every engine and worker
        reuses it).
        """
        with self._jit_lock:
            if self._jit_mega is None:
                key = self._exec_key("mega")
                if self.use_exec_cache:
                    self._jit_mega = EXECUTABLES.get_or_build(
                        key, lambda: self._build_executable("mega", key)
                    )
                else:
                    self._jit_mega = self._build_executable("mega", key)
        return self._jit_mega

    # -- staging ----------------------------------------------------------------
    def stage_partition(self, store: PartitionedStore, pid: int) -> Dict[str, np.ndarray]:
        """Extract(Read): fetch + lay out one partition's pages (host side).

        Mesh-less engines stage the partition's page words as ONE u32 array
        (``preprocess.stage_pages``): a partition read from a file stages
        the body it was read as, with no copy, so the host-to-device put is
        one plain copy; the compiled program cuts the kernels' shapes out.
        The page-build span says which (``stored=1``: the stored body;
        ``0``: concatenated).  Meshed engines shard pages along the
        row-group axis (``pages_pspec``), which must stay aligned with
        rows, so they keep the kernels' ``(F, G, w)`` shapes — and a dedup
        partition's unique-geometry pages would break that row alignment,
        so those inflate (``columnar.inflate_partition``, bitwise faithful)
        to the classic per-sample layout first.  The I/O ledger still
        charges only the UNIQUE bytes (``store.read`` streams the stored
        form; inflation is host-side decompression after the read).
        """
        part = store.read(pid)
        with TraceAnnotation(trace.PAGE_BUILD, pid=pid) as span:
            if self.mesh is not None:
                return pages_from_partition(inflate_partition(part), self.spec)
            pages, stored = stage_pages(part, self.spec)
            span.set_metadata(stored=int(stored))
            return pages

    def stage_megabatch(
        self, store: PartitionedStore, pids: Sequence[int]
    ) -> Dict[str, np.ndarray]:
        """Extract(Read) K partitions and stack their pages leading-axis.

        Reads go through ``store.read`` one partition at a time, so every
        partition's bytes are charged to its OWNING device's ledger — a
        megabatch never blurs per-device accounting.
        """
        return stack_pages(self.stage_partition(store, pid) for pid in pids)

    def _put_pages(self, pages):
        """Host pages -> device, donation-aware.

        On donating backends the pages are placed once and their buffers
        donated to the launch (no host round-trip copy survives the call);
        elsewhere the numpy arrays go straight into jit, which performs the
        single unavoidable host->device transfer itself — the old explicit
        ``tree.map(jnp.asarray, ...)`` pre-copy layer is gone.
        """
        return jax.device_put(pages) if self._donate else pages

    def produce_batch(self, store: PartitionedStore, pid: int) -> MiniBatch:
        """Extract + Transform one partition into a device-ready mini-batch.

        The unit of work one preprocessing worker performs (pool-shared or
        private); deterministic in (store, pid), which is what makes
        straggler re-issue and duplicate-drop safe.
        """
        pages = self._put_pages(self.stage_partition(store, pid))
        mb = self.jit_preprocess_cached()(pages)
        jax.block_until_ready(mb)
        return mb

    def produce_batches(
        self, store: PartitionedStore, pids: Sequence[int]
    ) -> List[MiniBatch]:
        """Extract + Transform K partitions with ONE megabatched launch.

        Returns the K mini-batches in `pids` order, bitwise identical to K
        ``produce_batch`` calls — the whole point is paying one kernel
        dispatch (and one compile, amortized process-wide) instead of K.
        Falls back to the solo path on meshed engines (megabatching is a
        per-unit local launch) and on plans with a non-row-local stage
        (where stacking rows would not be bitwise-safe).
        """
        pids = list(pids)
        if (
            len(pids) == 1
            or self.mesh is not None
            or not self.lowered_plan.megabatch_safe()
        ):
            return [self.produce_batch(store, pid) for pid in pids]
        stacked = self._put_pages(self.stage_megabatch(store, pids))
        batches = self.jit_preprocess_megabatch_cached()(stacked)
        jax.block_until_ready(batches)
        return list(batches)

    def produce_stream(
        self,
        store: PartitionedStore,
        pids: Iterable[int],
        *,
        megabatch: int = 1,
        overlap: bool = True,
        lookahead: int = 1,
    ) -> Iterator[Tuple[int, MiniBatch]]:
        """The zero-stall produce loop: megabatched launches, double-buffered.

        Yields ``(pid, mini-batch)`` in `pids` order.  Partitions are
        grouped into megabatches of up to ``megabatch`` and each group runs
        as one launch; with ``overlap`` the NEXT group's partition read and
        numpy page-build run on a staging thread while the current group's
        kernel executes (jax dispatch is async), and ``block_until_ready``
        happens only at delivery — per-partition cost tends to
        ``max(io, compute)`` instead of ``io + compute``.  Batches are
        bitwise identical to serial ``produce_batch`` calls either way —
        plans with a non-row-local stage degrade to K=1 (overlap only).

        ``lookahead`` is the staging window depth: how many chunks may be
        staged (read + page-built) ahead of the chunk whose kernel is in
        flight.  1 is the classic double buffer; deeper windows keep reads
        flowing while delivery (the consumer's side of ``yield``) stalls
        the dispatch loop, at the price of holding up to ``lookahead``
        chunks of pages in memory — the service path
        (``core.service.Session``) adds a byte budget on top
        (``JobSpec.stage_budget_bytes``); this raw loop does not.
        """
        pids = list(pids)
        k = max(1, int(megabatch))
        if k > 1 and not self.lowered_plan.megabatch_safe():
            k = 1
        chunks = [pids[i : i + k] for i in range(0, len(pids), k)]
        if not chunks:
            return
        assert self.mesh is None, "produce_stream is a per-unit local loop"
        lookahead = max(1, int(lookahead))

        def dispatch(stacked):
            """Launch one staged chunk without blocking on the result."""
            return self.jit_preprocess_megabatch_cached()(
                self._put_pages(stacked)
            )

        if not overlap:
            for chunk in chunks:
                batches = dispatch(self.stage_megabatch(store, chunk))
                jax.block_until_ready(batches)
                yield from zip(chunk, batches)
            return
        with ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="presto-stage"
        ) as stager:
            pending: List = []  # staged-chunk futures, window of `lookahead`
            nxt = 0

            def top_up() -> None:
                nonlocal nxt
                while len(pending) < lookahead and nxt < len(chunks):
                    pending.append(
                        stager.submit(self.stage_megabatch, store, chunks[nxt])
                    )
                    nxt += 1

            top_up()
            for chunk in chunks:
                batches = dispatch(pending.pop(0).result())
                top_up()  # refill behind the in-flight kernel
                for pid, mb in zip(chunk, batches):
                    jax.block_until_ready(mb)  # block only at delivery
                    yield pid, mb

    def pages_struct(self, rows: int) -> Dict[str, jax.ShapeDtypeStruct]:
        """Stand-ins for the pages ``stage_partition`` returns."""
        if self.mesh is None:
            return pages_shape_dtypes(self.spec, rows)
        return kernel_pages_shape_dtypes(self.spec, rows)

    # -- block-granularity cache hooks (dedup datasets) -------------------------
    #
    # A dedup partition's train-ready sparse content is fully determined by
    # its unique blocks: rows sharing a block have identical multi_hot_ids /
    # lengths slices.  ``extract_blocks`` pulls those per-block slices out of
    # a produced batch (publish side) and ``assemble_from_blocks`` rebuilds a
    # full batch from cached blocks plus the partial "rest" program over the
    # per-sample families (dense/gen/labels) — so overlapping tenants reuse
    # hashed sparse blocks across partitions and datasets
    # (``core.featcache.BlockKey``), bitwise identical to cold compute.

    def _preprocess_rest(self, pages: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Partial Transform: every family EXCEPT sparse/lengths (traceable)."""
        plan = self.lowered_plan
        env = prepare_env(pages, self.spec)
        for st in plan.stages:
            if st.family in ("sparse", "lengths") or st.name == "form_batch":
                continue
            vals = st.fn(*(env[k] for k in st.inputs))
            env.update(zip(st.outputs, vals))
        # exactly form_batch's assembly expressions for these keys
        return {
            "dense": env["dense_norm"].T,
            "one_hot_ids": env["gen_hashed"].T,
            "labels": env["labels_f32"],
        }

    def jit_preprocess_rest_cached(self):
        """Compiled rest-program (dense/gen/labels), shared process-wide."""
        with self._jit_lock:
            if self._jit_rest is None:
                key = self._exec_key("rest")
                build = lambda: jax.jit(self._preprocess_rest)
                if self.use_exec_cache:
                    self._jit_rest = EXECUTABLES.get_or_build(key, build)
                else:
                    self._jit_rest = build()
        return self._jit_rest

    def staged_refs(self, pages: Dict[str, np.ndarray]) -> Optional[np.ndarray]:
        """The ``(rows,)`` block refs of a dedup partition's staged pages
        (host side), else None."""
        return kernel_pages(pages, self.spec).get("sparse_refs")

    @staticmethod
    def extract_blocks(
        batch: MiniBatch, refs: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-unique-block hashed sparse content of a produced batch.

        Returns ``(ids (u, S, L) i32, lens (u, S) i32)`` — block b's slice is
        any row r with ``refs[r] == b`` (they are identical by construction;
        the first occurrence is taken).
        """
        refs = np.asarray(refs)
        _, first = np.unique(refs, return_index=True)
        ids = np.asarray(batch["multi_hot_ids"])[first]
        lens = np.asarray(batch["lengths"])[first]
        return ids, lens

    def assemble_from_blocks(
        self,
        pages: Dict[str, np.ndarray],
        block_ids: np.ndarray,
        block_lens: np.ndarray,
    ) -> MiniBatch:
        """Full batch from cached sparse blocks + the rest program.

        ``pages`` is dedup-staged (``stage_partition``) output; only its
        dense/label pages feed the compiled rest program's kernels — the
        sparse pages' decode+hash work is what the block cache saved.
        Bitwise identical to a cold produce of the same partition.
        """
        refs = np.asarray(self.staged_refs(pages), dtype=np.int64)
        batch = dict(self.jit_preprocess_rest_cached()(pages))
        batch["multi_hot_ids"] = jnp.asarray(np.asarray(block_ids)[refs])
        batch["lengths"] = jnp.asarray(np.asarray(block_lens)[refs])
        return batch
