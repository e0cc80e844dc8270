"""End-to-end driver: train a ~100M-parameter DLRM for a few hundred steps
through the full PreSto pipeline (Fig. 1): Extract (columnar store) ->
Transform (fused ISP kernels, shared service pool) -> Load (session stream)
-> train (consumer), with T/P provisioning driving the job's QoS target,
checkpointing, and restart safety.

    PYTHONPATH=src python examples/train_recsys_e2e.py [--steps 200]
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    JobSpec,
    PreprocessingService,
    PreStoEngine,
    TrainingPipeline,
    TransformSpec,
)
from repro.data.storage import PartitionedStore
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.distributed.sharding import ShardingRules
from repro.models.recsys import RecSysConfig, init_params, loss_fn
from repro.train import CheckpointManager, adamw, make_train_step, warmup_cosine
from repro.common.util import param_count


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args()

    # ~100M params: RM1 feature geometry with 20k-row embedding tables
    # (39 tables x 20,000 x 128 = 99.8M) + MLPs.
    data = RMDataConfig("rm1-100m", 13, 26, 1, 1, 13, 1024, 1 << 20, 20_000,
                        rows_per_partition=args.rows)
    rcfg = RecSysConfig(name="rm1-100m", data=data)
    src = SyntheticRecSysSource(data, rows=args.rows)
    spec = TransformSpec.from_source(src)
    store = PartitionedStore(args.steps + 8, num_devices=8, source=src)
    engine = PreStoEngine(spec)
    rules = ShardingRules.make(None)

    params = init_params(jax.random.PRNGKey(0), rcfg)
    print(f"model: {param_count(params)/1e6:.1f}M parameters")
    opt = adamw(warmup_cosine(2e-3, 20, args.steps))
    step = jax.jit(make_train_step(lambda p, b: loss_fn(p, b, rcfg, rules), opt))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}

    pipe = TrainingPipeline(engine, store, step)
    plan = pipe.provision(state)
    print(f"provisioning: T={plan.train_throughput:.0f} rows/s, "
          f"P={plan.worker_throughput:.0f} rows/s/worker -> "
          f"{plan.workers_required} preprocessing workers (paper step 2: T/P)")

    # the provisioned pool, as a service; the job's QoS target is the
    # measured training throughput T, so demand converges to ceil(T/P)
    service = PreprocessingService(num_workers=args.workers)
    session = service.submit(JobSpec(
        name="rm1-100m", engine=engine, store=store,
        partitions=range(args.steps + 8),
        target_samples_per_s=plan.train_throughput))

    with tempfile.TemporaryDirectory() as ckdir:
        ckpt = CheckpointManager(ckdir, keep=2)
        t0 = time.time()
        state, stats, metrics = pipe.run_session(
            state, session, max_steps=args.steps
        )
        ckpt.save(int(state["step"]), state)
        ckpt.wait()
        wall = time.time() - t0
        losses = [m["loss"] for m in metrics]
        k = max(len(losses) // 10, 1)
        sess_stats = session.stats()
        print(f"trained {stats.steps} steps ({stats.steps*args.rows} samples) "
              f"in {wall:.0f}s; consumer-util {stats.utilization:.2f}; "
              f"straggler re-issues {stats.reissues}; "
              f"QoS demand {sess_stats.demand_units} unit(s)")
        print(f"loss: first10={np.mean(losses[:k]):.4f} "
              f"last10={np.mean(losses[-k:]):.4f} (should decrease)")
        print(f"checkpoint at step {ckpt.latest_step()} -> restart-safe")
        assert np.mean(losses[-k:]) < np.mean(losses[:k]), "loss did not improve"
    service.close()


if __name__ == "__main__":
    main()
