"""The benchmark's copy of the raw-feature generator gives the program's
raw features (``repro.data.synth``) bit for bit."""

import dataclasses

import numpy as np
import pytest

from chipbench.datagen import Generator, Shape
from repro.data.synth import RM_CONFIGS, SyntheticRecSysSource

SEED = 2**31 + 17  # seeds past 32 signed bits must work


def _shape(cfg, rows, dup, boundary_seed):
    return Shape(cfg.n_dense, cfg.n_sparse, cfg.avg_sparse_len, cfg.max_sparse_len,
                 cfg.n_generated, cfg.bucket_size, cfg.id_space, cfg.embedding_rows,
                 rows, dup, boundary_seed)


@pytest.mark.parametrize("name,dup", [("rm1", 1), ("rm5", 1), ("rm5", 4)])
def test_generator_matches_program(name, dup):
    rows = 256
    cfg = dataclasses.replace(RM_CONFIGS[name], dup_factor=dup, rows_per_partition=rows)
    theirs = SyntheticRecSysSource(cfg, rows=rows, seed=SEED)
    ours = Generator(_shape(cfg, rows, dup, 7), SEED)
    # the boundaries are the program's at the boundary seed, the rows at the run's
    np.testing.assert_array_equal(
        ours.bucket_boundaries,
        SyntheticRecSysSource(cfg, rows=rows, seed=7).bucket_boundaries)
    np.testing.assert_array_equal(ours.generated_source, theirs.generated_source)
    for pid in (0, 5):
        a, b = ours.raw(pid), theirs.raw(pid)
        for key in ("dense", "sparse_values", "sparse_lengths", "labels", "sparse_refs"):
            x, y = getattr(a, key), getattr(b, key)
            if y is None:
                assert x is None
            else:
                assert x.dtype == y.dtype
                np.testing.assert_array_equal(x, y)


def test_shape_from_files_and_bad_seed():
    config = {"n_dense": 504, "n_sparse": 42, "avg_sparse_len": 20, "max_sparse_len": 32,
              "n_generated": 42, "bucket_size": 4096, "id_space": 1 << 24,
              "embedding_rows": 500000, "rows_per_partition": 8192,
              "bucket_boundary_seed": 0}
    s = Shape.of(config, {"dup_factor": 4})
    assert (s.unique_rows, s.id_width, s.len_width) == (2048, 24, 6)
    with pytest.raises(ValueError):
        Generator(s, -1)
