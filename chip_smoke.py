#!/usr/bin/env python3
"""Chip smoke test: the PreSto produce path, end to end, on a TPU.

Drives the main path through the entry points a user calls —
``PartitionedStore`` -> ``PreprocessingService.submit(JobSpec)`` ->
``Session`` iteration -> ``TrainingPipeline.run_session`` — at the paper's
data widths (Table I), and checks every delivered mini-batch against an
independent numpy oracle computed from the raw features.

One chip (the default):
  a  fail unless JAX's first device is a TPU (no CPU fallback, ever);
  b  RM2: 8 partitions of 8,192 rows (504 dense, 42 sparse x 32 ids,
     21 generated, m=1024, id space 2^24) written to disk, then served by
     the default JobSpec (presto placement, pipelined pool);
  c  RM5: 4 partitions (42 generated, m=4096) served with megabatch=4;
  d  RM1: 5 DLRM train steps fed by a Session.  The 39 tables are cut from
     500,000 to 100,000 rows so params + AdamW state fit one 16 GB chip.

``--chips 4``: only the sharded program on a 4-chip ``data`` mesh at RM2
width, one partition per chip: presto and disagg placements, each shard
checked bitwise against the one-chip output of its partition; presto's HLO
must hold no collective-permute, disagg's must.

Every phase prints its compile seconds, peak device bytes, the
``tpu_custom_call`` count of its compiled produce program and the devices
holding its batches.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero and prints no such line.

    python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
STORE_ROOT = os.path.join(ROOT, ".smoke_store")  # removed on exit
ROWS = 8192  # rows per partition (Table I)
RM1_EMBEDDING_ROWS = 100_000  # per table; 500,000 would need ~24 GB
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compile seconds and count, as JAX reports them, per phase."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.count += 1

    def take(self) -> tuple[float, int]:
        with self._lock:
            out = (self.seconds, self.count)
            self.seconds, self.count = 0.0, 0
        return out


# -- independent numpy oracle --------------------------------------------------


def np_sigridhash(ids: np.ndarray, seeds: np.ndarray, maxes: np.ndarray) -> np.ndarray:
    """SigridHash (seeded murmur3 finalizer, then range reduction) in numpy
    uint32 arithmetic; seeds/maxes broadcast against ids."""
    with np.errstate(over="ignore"):
        v = ids.astype(np.uint32)
        s = np.asarray(seeds, np.uint32)
        h = (v ^ (s * np.uint32(0x9E3779B1))) * np.uint32(0xCC9E2D51) + s
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
        return (h % np.asarray(maxes, np.uint32)).astype(np.int32)


def oracle_batch(src, spec, pid: int) -> dict:
    """The train-ready batch of partition `pid`, from its raw features."""
    raw = src.raw(pid)
    sources = raw.dense[:, list(spec.generated_source)]
    buckets = np.stack(
        [np.digitize(sources[:, g], spec.bucket_boundaries[g])
         for g in range(sources.shape[1])],
        axis=1,
    )
    return {
        "dense": np.log1p(np.maximum(raw.dense, 0.0)),
        "multi_hot_ids": np_sigridhash(
            raw.sparse_values, spec.sparse_seeds[None, :, None],
            spec.sparse_max[None, :, None],
        ),
        "lengths": raw.sparse_lengths,
        "one_hot_ids": np_sigridhash(
            buckets, spec.gen_seeds[None, :], spec.gen_max[None, :]
        ),
        "labels": raw.labels,
    }


def check_batch(batch: dict, want: dict, tag: str) -> float:
    """Integer keys and labels bitwise, dense within 1e-6; returns the dense
    max abs error."""
    got = {k: np.asarray(v) for k, v in batch.items()}
    if set(got) != set(want):
        raise AssertionError(f"{tag}: batch keys {sorted(got)} != {sorted(want)}")
    for k in ("multi_hot_ids", "one_hot_ids", "lengths", "labels"):
        if got[k].shape != want[k].shape or not np.array_equal(got[k], want[k]):
            bad = np.count_nonzero(got[k] != want[k]) if got[k].shape == want[k].shape else "shape"
            raise AssertionError(f"{tag}: {k} differs from the oracle ({bad})")
    if got["dense"].shape != want["dense"].shape:
        raise AssertionError(f"{tag}: dense shape {got['dense'].shape}")
    err = float(np.max(np.abs(got["dense"] - want["dense"])))
    if not err <= 1e-6:
        raise AssertionError(f"{tag}: dense off the oracle by {err!r} > 1e-6")
    return err


# -- shared measurement helpers ------------------------------------------------


def memory_stats(device) -> dict:
    """The allocator's counters as the backend reports them (peaks
    cumulative over the process)."""
    return dict(device.memory_stats() or {})


def peak_bytes(device) -> int | None:
    return memory_stats(device).get("peak_bytes_in_use")


def produce_kernels(engine, rows: int, k: int) -> tuple[int, float]:
    """``tpu_custom_call`` count and compile seconds of the engine's K=k
    produce program, compiled ahead of time from its page shapes."""
    import jax

    from repro.core.preprocess import megabatch_pages_shape_dtypes, pages_shape_dtypes

    t0 = time.perf_counter()
    if k == 1:
        lowered = jax.jit(engine.preprocess_local).lower(
            pages_shape_dtypes(engine.spec, rows)
        )
    else:
        lowered = jax.jit(engine.preprocess_megabatch).lower(
            megabatch_pages_shape_dtypes(engine.spec, rows, k)
        )
    text = lowered.compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    if n <= 0:
        raise AssertionError("produce program holds no tpu_custom_call (Mosaic kernel)")
    return n, time.perf_counter() - t0


def batch_devices(batch: dict) -> list[str]:
    return sorted({str(d) for v in batch.values() for d in v.devices()})


def materialized_store(src, n_parts: int, name: str):
    """Write `n_parts` partitions to disk and return a store that can only
    read them back (no synthetic source: a read never generates data)."""
    from repro.data.storage import PartitionedStore

    root = os.path.join(STORE_ROOT, name)
    PartitionedStore(n_parts, num_devices=8, source=src, root=root).materialize(
        range(n_parts)
    )
    return PartitionedStore(n_parts, num_devices=8, root=root)


# -- phases ----------------------------------------------------------------------


def serve_phase(name: str, cfg, n_parts: int, megabatch: int, rows: int,
                seed: int, clock: CompileClock, device) -> dict:
    """Serve `n_parts` stored partitions through the default JobSpec and check
    every delivered batch against the oracle."""
    from repro.core.presto import PreStoEngine
    from repro.core.service import JobSpec, PreprocessingService
    from repro.core.spec import TransformSpec
    from repro.data.synth import SyntheticRecSysSource

    src = SyntheticRecSysSource(cfg, rows=rows, seed=seed)
    spec = TransformSpec.from_source(src)
    t0 = time.perf_counter()
    store = materialized_store(src, n_parts, name)
    write_s = time.perf_counter() - t0
    engine = PreStoEngine(spec)  # the default JobSpec's engine: presto
    n_kernels, aot_s = produce_kernels(engine, rows, megabatch)
    clock.take()
    errs, devices = {}, set()
    t0 = time.perf_counter()
    with PreprocessingService() as service:
        session = service.submit(JobSpec(
            name=name, engine=engine, store=store, partitions=range(n_parts),
            megabatch=megabatch,
        ))
        for pid, batch in session:
            errs[pid] = check_batch(batch, oracle_batch(src, spec, pid), f"{name} pid {pid}")
            devices.update(batch_devices(batch))
    serve_s = time.perf_counter() - t0
    compile_s, n_compiles = clock.take()
    if sorted(errs) != list(range(n_parts)):
        raise AssertionError(f"{name}: delivered {sorted(errs)}, want 0..{n_parts - 1}")
    return {
        "phase": name, "partitions": n_parts, "rows": rows, "megabatch": megabatch,
        "oracle": "agree", "dense_max_abs_err": max(errs.values()),
        "tpu_custom_call": n_kernels, "aot_compile_s": aot_s,
        "serve_compile_s": compile_s, "serve_compiles": n_compiles,
        "write_s": write_s, "serve_s": serve_s,
        "peak_bytes_in_use": peak_bytes(device), "batch_devices": sorted(devices),
        "memory_stats": memory_stats(device),
    }


def train_phase(rows: int, seed: int, clock: CompileClock, device, *,
                steps: int = 5, n_parts: int = 8,
                embedding_rows: int = RM1_EMBEDDING_ROWS, rm: str = "rm1") -> dict:
    """DLRM steps on RM1 fed by a Session, as ``repro.launch.train`` does."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_recsys
    from repro.core.pipeline import TrainingPipeline
    from repro.core.presto import PreStoEngine
    from repro.core.service import JobSpec, PreprocessingService
    from repro.core.spec import TransformSpec
    from repro.data.synth import SyntheticRecSysSource
    from repro.distributed.sharding import ShardingRules
    from repro.models import recsys as RS
    from repro.train import adamw, make_train_step, warmup_cosine

    full = get_recsys(rm)
    data = dataclasses.replace(full.data, embedding_rows=embedding_rows)
    rcfg = dataclasses.replace(full, data=data)
    log(f"# {rm}-train: embedding_rows cut {full.data.embedding_rows} -> "
        f"{embedding_rows} per table ({rcfg.n_tables} tables x {rcfg.emb_dim} f32)")
    src = SyntheticRecSysSource(data, rows=rows, seed=seed)
    spec = TransformSpec.from_source(src)
    store = materialized_store(src, n_parts, f"{rm}-train")
    engine = PreStoEngine(spec)
    n_kernels, aot_s = produce_kernels(engine, rows, 1)

    rules = ShardingRules.make(None)
    opt = adamw(warmup_cosine(1e-3, 20, 100))
    step = jax.jit(
        make_train_step(lambda p, b: RS.loss_fn(p, b, rcfg, rules), opt),
        donate_argnums=(0,),  # params + AdamW state updated in place
    )
    params = RS.init_params(jax.random.PRNGKey(seed), rcfg)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    fed = []

    def train_step(state, mb):
        fed.append(mb)
        return step(state, mb)

    clock.take()
    t0 = time.perf_counter()
    with PreprocessingService() as service:
        session = service.submit(JobSpec(
            name=f"{rm}-train", engine=engine, store=store, partitions=range(n_parts),
        ))
        state, stats, metrics = TrainingPipeline(train_step=train_step).run_session(
            state, session, max_steps=steps
        )
    train_s = time.perf_counter() - t0
    compile_s, n_compiles = clock.take()
    losses = [float(m["loss"]) for m in metrics]
    if len(losses) != steps or not all(np.isfinite(losses)):
        raise AssertionError(f"{rm}-train: losses {losses}")
    # each batch the trainer took is one stored partition's oracle batch
    wants = [oracle_batch(src, spec, pid) for pid in range(n_parts)]
    errs, devices = [], set()
    for i, mb in enumerate(fed):
        labels = np.asarray(mb["labels"])
        pids = [p for p, w in enumerate(wants) if np.array_equal(labels, w["labels"])]
        if len(pids) != 1:
            raise AssertionError(f"{rm}-train: step {i} batch matches partitions {pids}")
        errs.append(check_batch(mb, wants[pids[0]], f"{rm}-train step {i}"))
        devices.update(batch_devices(mb))
    return {
        "phase": f"{rm}-train", "steps": stats.steps, "rows": rows,
        "embedding_rows": embedding_rows, "losses": losses,
        "oracle": "agree", "dense_max_abs_err": max(errs),
        "tpu_custom_call": n_kernels, "aot_compile_s": aot_s,
        "train_compile_s": compile_s, "train_compiles": n_compiles,
        "train_s": train_s, "peak_bytes_in_use": peak_bytes(device),
        "batch_devices": sorted(devices), "memory_stats": memory_stats(device),
    }


def mesh_phase(cfg, rows: int, seed: int, clock: CompileClock, devices) -> dict:
    """The sharded preprocessing program on a `data` mesh, one partition per
    device: presto (no collectives) and disagg (collective-permutes), each
    shard checked bitwise against the one-chip program's output."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core.preprocess import pages_from_partition
    from repro.core.presto import PreStoEngine, pages_pspec
    from repro.core.spec import TransformSpec
    from repro.data.synth import SyntheticRecSysSource
    from repro.launch.hlo_cost import analyze
    from repro.launch.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh((n,), ("data",), devices=devices)
    src = SyntheticRecSysSource(cfg, rows=rows, seed=seed)
    spec = TransformSpec.from_source(src)
    parts = [pages_from_partition(src.partition(p), spec) for p in range(n)]
    # the global batch: partition p's rows are the p-th block of every
    # page array's row-group axis (labels: the p-th block of rows)
    pages = {
        k: np.concatenate([p[k] for p in parts], axis=0 if k == "label_words" else 1)
        for k in parts[0]
    }
    one_chip = jax.jit(PreStoEngine(spec).preprocess_local)
    refs = [jax.device_get(one_chip(p)) for p in parts]
    for p, ref in enumerate(refs):
        check_batch(ref, oracle_batch(src, spec, p), f"one-chip pid {p}")
    shardings = {k: NamedSharding(mesh, v) for k, v in pages_pspec().items()}
    out = {"phase": f"mesh-{n}", "partitions": n, "rows": rows, "oracle": "agree"}
    clock.take()
    for placement in ("presto", "disagg"):
        engine = PreStoEngine(spec, mesh, placement=placement)
        t0 = time.perf_counter()
        compiled = engine.jit_preprocess().lower(pages).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        coll = analyze(text)
        permute = coll.coll_breakdown.get("collective-permute", 0)
        if placement == "presto" and coll.coll_bytes != 0:
            raise AssertionError(f"presto program moves {coll.coll_bytes} collective bytes")
        if placement == "disagg" and not permute > 0:
            raise AssertionError("disagg program holds no collective-permute")
        mb = compiled(jax.device_put(pages, shardings))
        jax.block_until_ready(mb)
        holders = set()
        for key, arr in mb.items():
            for shard in arr.addressable_shards:
                start = shard.index[0].start or 0
                p = start // rows
                got = np.asarray(shard.data)
                if not np.array_equal(got, refs[p][key]):
                    raise AssertionError(f"{placement}: {key} shard {p} != one-chip output")
                holders.add(shard.device.id)
        if len(holders) != n:
            raise AssertionError(f"{placement}: output shards on devices {sorted(holders)}")
        out[placement] = {
            "compile_s": compile_s, "collective_bytes": coll.coll_bytes,
            "collective_permute_bytes": permute,
            "tpu_custom_call": text.count('custom_call_target="tpu_custom_call"'),
            "shard_devices": sorted(holders),
            "peak_bytes_in_use": [peak_bytes(d) for d in devices],
        }
    out["memory_stats"] = [memory_stats(d) for d in devices]
    return out


# -- main ----------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip mesh path and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0, help="data and weight seed")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":  # phase a: no accelerator, no result
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              "refusing to fall back", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"# device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.common.util import enable_compile_cache
    from repro.data.synth import RM_CONFIGS

    log(f"# compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    shutil.rmtree(STORE_ROOT, ignore_errors=True)
    failed = []
    try:
        if args.chips == 4:
            phases = [lambda: mesh_phase(RM_CONFIGS["rm2"], ROWS, args.seed, clock,
                                         devices[:4])]
        else:
            phases = [
                lambda: serve_phase("rm2", RM_CONFIGS["rm2"], 8, 1, ROWS, args.seed,
                                    clock, dev),
                lambda: serve_phase("rm5", RM_CONFIGS["rm5"], 4, 4, ROWS, args.seed,
                                    clock, dev),
                lambda: train_phase(ROWS, args.seed, clock, dev),
            ]
        for i, run in enumerate(phases):
            t0 = time.perf_counter()
            try:
                rec = run()
            except Exception:  # noqa: BLE001 — reported; later phases still run
                traceback.print_exc()
                failed.append(i)
                continue
            rec["phase_s"] = time.perf_counter() - t0
            log("# phase " + json.dumps(rec))
    finally:
        shutil.rmtree(STORE_ROOT, ignore_errors=True)
    if failed:
        print(f"chip_smoke: phase(s) {failed} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
