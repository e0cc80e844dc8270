"""Training driver: end-to-end RecSys (PreSto-fed) or LM training.

RecSys mode runs the paper's full Fig. 1 pipeline: the PartitionedStore
serves encoded columnar partitions, the PreStoEngine transforms them (fused
ISP kernels, presto or disagg placement), and the DLRM trains on the
resulting mini-batches — with checkpointing and elastic restart.

LM mode trains any --arch on synthetic token shards.

Examples (CPU-sized):
  PYTHONPATH=src python -m repro.launch.train --mode recsys --rm rm1 \
      --reduced --steps 50 --rows 512
  PYTHONPATH=src python -m repro.launch.train --mode lm \
      --arch mamba2-1.3b --reduced --steps 20 --batch 8 --seq 256
"""

from __future__ import annotations

import argparse
import time


def train_recsys(args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_recsys
    from repro.core.pipeline import TrainingPipeline
    from repro.core.presto import PreStoEngine
    from repro.core.service import JobSpec, PreprocessingService
    from repro.core.spec import TransformSpec
    from repro.data.storage import PartitionedStore
    from repro.data.synth import SyntheticRecSysSource
    from repro.distributed.sharding import ShardingRules
    from repro.models import recsys as RS
    from repro.train import CheckpointManager, adamw, make_train_step, warmup_cosine

    rcfg = get_recsys(args.rm, reduced=args.reduced)
    src = SyntheticRecSysSource(rcfg.data, rows=args.rows or None)
    spec = TransformSpec.from_source(src)
    store = PartitionedStore(args.partitions, num_devices=8, source=src,
                             root=args.store_root)
    rules = ShardingRules.make(None)
    engine = PreStoEngine(spec, mesh=None, placement=args.placement)

    opt = adamw(warmup_cosine(args.lr, 20, max(args.steps, 100)))
    loss_fn = lambda p, b: RS.loss_fn(p, b, rcfg, rules)
    step = jax.jit(make_train_step(loss_fn, opt))

    params = RS.init_params(jax.random.PRNGKey(args.seed), rcfg)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    pipeline = TrainingPipeline(train_step=step)
    t0 = time.time()
    with PreprocessingService(num_workers=args.workers) as service:
        session = service.submit(JobSpec(
            name=f"{rcfg.name}-{args.placement}", engine=engine, store=store,
            partitions=range(args.partitions), units=args.workers))
        state, stats, metrics = pipeline.run_session(
            state, session, max_steps=args.steps
        )
    wall = time.time() - t0
    if ckpt:
        ckpt.save(int(state["step"]), state)
        ckpt.wait()
    first, last = metrics[0]["loss"], metrics[-1]["loss"]
    print(f"recsys {rcfg.name} [{args.placement}]: {stats.steps} steps in "
          f"{wall:.1f}s, loss {first:.4f} -> {last:.4f}, "
          f"consumer-util {stats.utilization:.2f}, reissues {stats.reissues}")
    return {"first_loss": first, "last_loss": last, "steps": stats.steps}


def train_lm(args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_arch
    from repro.data.tokens import TokenSynthesizer
    from repro.distributed.sharding import ShardingRules
    from repro.launch.specs import make_optimizer_for, _model_module
    from repro.train import make_train_step

    entry = get_arch(args.arch)
    cfg = entry.reduced if args.reduced else entry.config
    mod = _model_module(cfg)
    rules = ShardingRules.make(None)
    opt = make_optimizer_for(cfg)
    loss_fn = lambda p, b: mod.loss_fn(p, b, cfg, rules)
    step = jax.jit(make_train_step(loss_fn, opt))

    params = mod.init_params(jax.random.PRNGKey(args.seed), cfg)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    synth = TokenSynthesizer(cfg.vocab_size, args.seq, seed=args.seed)
    losses = []
    t0 = time.time()
    for i in range(args.steps):
        raw = synth.shard_batch(0, i, args.batch)
        batch = {
            "tokens": jnp.asarray(raw["tokens"]),
            "labels": jnp.asarray(raw["labels"]),
            "mask": jnp.asarray(raw["mask"], jnp.float32),
        }
        if cfg.is_encdec:
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(i), (args.batch, args.seq, cfg.d_model)
            ).astype(cfg.dtype)
        if cfg.family == "vlm" and cfg.frontend_positions:
            batch["prefix_embeds"] = jax.random.normal(
                jax.random.PRNGKey(i),
                (args.batch, cfg.frontend_positions, cfg.d_model),
            ).astype(cfg.dtype)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    wall = time.time() - t0
    print(f"lm {cfg.name}: {args.steps} steps in {wall:.1f}s, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["recsys", "lm"], default="recsys")
    ap.add_argument("--rm", default="rm1")
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--placement", choices=["presto", "disagg", "hybrid"],
                    default="presto")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--partitions", type=int, default=64)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--store-root", default=None)
    args = ap.parse_args()
    from repro.common.util import enable_compile_cache

    enable_compile_cache()
    if args.mode == "recsys":
        train_recsys(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
