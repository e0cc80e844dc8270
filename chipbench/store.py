"""A cell's stored partitions: this benchmark's raw features, written to
disk by the program's own encoder and store.

Imports the program's data layer but never JAX, so the pool that writes the
files can run beside the process that holds the chip.
"""

from __future__ import annotations

import dataclasses
import json
import multiprocessing
import os
import shutil

from repro.data.storage import PartitionedStore
from repro.data.synth import RawBatch, RMDataConfig, SyntheticRecSysSource

from chipbench.datagen import Generator, Shape

STORE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".store")


def program_config(config: dict, traffic: dict) -> RMDataConfig:
    """The dataset as the program describes it."""
    shape = Shape.of(config, traffic)
    return RMDataConfig(
        name=config["name"],
        n_dense=shape.n_dense,
        n_sparse=shape.n_sparse,
        avg_sparse_len=shape.avg_sparse_len,
        max_sparse_len=shape.max_sparse_len,
        n_generated=shape.n_generated,
        bucket_size=shape.bucket_size,
        id_space=shape.id_space,
        embedding_rows=shape.embedding_rows,
        rows_per_partition=shape.rows,
        dense_encoding=config["dense_encoding"],
        sparse_encoding=config["sparse_encoding"],
        dup_factor=shape.dup_factor,
    )


class BenchSource(SyntheticRecSysSource):
    """The program's source with this benchmark's raw features and bucket
    boundaries in place of its own; ``partition`` encodes them with the
    program's schema and encoder."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        cfg = program_config(config, traffic)
        super().__init__(cfg, rows=cfg.rows_per_partition, seed=seed)
        self.gen = Generator(Shape.of(config, traffic), seed)
        self.bucket_boundaries = self.gen.bucket_boundaries
        self.generated_source = self.gen.generated_source

    def raw(self, partition_id: int) -> RawBatch:
        r = self.gen.raw(partition_id)
        return RawBatch(r.dense, r.sparse_values, r.sparse_lengths, r.labels,
                        r.sparse_refs)


def _write(job: tuple) -> None:
    root, config, traffic, seed, pid = job
    m = int(config["stored_partitions"])
    src = BenchSource(config, traffic, seed)
    PartitionedStore(m, num_devices=1, source=src, root=root).materialize([pid])


def materialize(cell: str, config: dict, traffic: dict, seed: int) -> str:
    """Write the cell's stored partitions for `seed` (reused when complete
    and of the same shape and encoding) and return their root.  One seed's
    files are kept per cell: another seed's are removed first, so the disk
    holds one store a cell."""
    cell_dir = os.path.join(STORE_DIR, cell)
    root = os.path.join(cell_dir, str(seed))
    done = os.path.join(root, "complete")
    stamp = json.dumps([dataclasses.asdict(program_config(config, traffic)),
                        int(config["stored_partitions"])])
    if os.path.exists(done):
        with open(done) as f:
            if f.read() == stamp:
                return root
    if os.path.isdir(cell_dir):
        for name in os.listdir(cell_dir):
            shutil.rmtree(os.path.join(cell_dir, name), ignore_errors=True)
    os.makedirs(root)
    m = int(config["stored_partitions"])
    jobs = [(root, config, traffic, seed, pid) for pid in range(m)]
    procs = max(1, min(m, len(os.sched_getaffinity(0)) - 2))
    pool = multiprocessing.get_context("spawn").Pool(procs)
    try:
        pool.map(_write, jobs)
        pool.close()
    finally:
        pool.terminate()
        pool.join()
    with open(done, "w") as f:
        f.write(stamp)
    return root
