"""Raw features of the paper's Table I datasets, drawn from a seed.

This is the yardstick's own copy of the generator in the program's
``repro/data/synth.py`` (numpy only, no import of the program): the same seed
gives bit-for-bit the same raw features, and a later change to the program's
generator cannot move what the benchmark stores or what its reference
computes.  ``tests/test_datagen.py`` checks that the two still agree.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one cell's dataset: a configuration file's numbers plus
    the sample sharing its traffic asks for."""

    n_dense: int
    n_sparse: int
    avg_sparse_len: int
    max_sparse_len: int
    n_generated: int
    bucket_size: int  # m, boundaries per generated feature
    id_space: int
    embedding_rows: int
    rows: int  # rows per partition
    dup_factor: int = 1  # RecD: rows of one session share a sparse block
    # The bucket boundaries are the job's Transform, fixed for a dataset
    # while its rows vary: drawn from this seed, never from the run's.  (The
    # produce program holds them as constants, so a run-seeded Transform
    # would recompile on every run.)
    boundary_seed: int = 0

    @classmethod
    def of(cls, config: dict, traffic: dict) -> "Shape":
        return cls(
            n_dense=int(config["n_dense"]),
            n_sparse=int(config["n_sparse"]),
            avg_sparse_len=int(config["avg_sparse_len"]),
            max_sparse_len=int(config["max_sparse_len"]),
            n_generated=int(config["n_generated"]),
            bucket_size=int(config["bucket_size"]),
            id_space=int(config["id_space"]),
            embedding_rows=int(config["embedding_rows"]),
            rows=int(config["rows_per_partition"]),
            dup_factor=int(traffic.get("dup_factor", 1)),
            boundary_seed=int(config["bucket_boundary_seed"]),
        )

    @property
    def unique_rows(self) -> int:
        return self.rows // self.dup_factor

    @property
    def id_width(self) -> int:
        return max(int(self.id_space - 1).bit_length(), 1)

    @property
    def len_width(self) -> int:
        return max(int(self.max_sparse_len).bit_length(), 1)


@dataclasses.dataclass
class Raw:
    """One partition's decoded raw features."""

    dense: np.ndarray  # (rows, n_dense) f32
    sparse_values: np.ndarray  # (rows, n_sparse, max_len) i32, logical view
    sparse_lengths: np.ndarray  # (rows, n_sparse) i32
    labels: np.ndarray  # (rows,) f32 in {0, 1}
    sparse_refs: np.ndarray | None = None  # (rows,) unique block per row


class Generator:
    """Rows deterministic in (shape, seed, partition id); bucket boundaries
    in (shape, shape.boundary_seed)."""

    def __init__(self, shape: Shape, seed: int):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        if shape.rows % shape.dup_factor or shape.unique_rows % 32:
            raise ValueError(
                f"rows={shape.rows} needs rows/dup_factor divisible by 32"
            )
        self.shape = shape
        self.seed = seed
        rng = np.random.default_rng(shape.boundary_seed ^ 0x5EED)
        self.bucket_boundaries = np.sort(
            rng.lognormal(
                mean=1.0, sigma=2.0, size=(shape.n_generated, shape.bucket_size)
            ).astype(np.float32),
            axis=-1,
        )
        # generated feature g bucketizes dense column g mod n_dense
        self.generated_source = (
            np.arange(shape.n_generated, dtype=np.int32) % max(shape.n_dense, 1)
        )

    def _sparse_blocks(self, rng, n: int):
        s = self.shape
        if s.max_sparse_len == 1:
            lengths = np.ones((n, s.n_sparse), dtype=np.int32)
        else:
            lengths = np.clip(
                rng.poisson(s.avg_sparse_len, size=(n, s.n_sparse)),
                1,
                s.max_sparse_len,
            ).astype(np.int32)
        # skewed toward small ids (a squared uniform), then scattered over
        # the id space by a multiplicative hash
        u = rng.random(size=(n, s.n_sparse, s.max_sparse_len))
        ids = (u * u * (s.id_space - 1)).astype(np.int64)
        ids = (ids * 2654435761) % s.id_space
        mask = np.arange(s.max_sparse_len)[None, None, :] < lengths[..., None]
        return np.where(mask, ids, 0).astype(np.int32), lengths

    def raw(self, pid: int) -> Raw:
        s = self.shape
        rng = np.random.default_rng((self.seed << 20) ^ pid)
        dense = rng.lognormal(mean=1.0, sigma=2.0, size=(s.rows, s.n_dense)).astype(
            np.float32
        )
        if s.dup_factor <= 1:
            ids, lengths = self._sparse_blocks(rng, s.rows)
            labels = (rng.random(size=(s.rows,)) < 0.25).astype(np.float32)
            return Raw(dense, ids, lengths, labels)
        uids, ulens = self._sparse_blocks(rng, s.unique_rows)
        labels = (rng.random(size=(s.rows,)) < 0.25).astype(np.float32)
        refs = np.arange(s.rows, dtype=np.int64) // s.dup_factor
        return Raw(dense, uids[refs], ulens[refs], labels, refs)
