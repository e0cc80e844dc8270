"""The numpy reference agrees with the program's produce path (Pallas in
interpret mode on the CPU) at 256 rows, and its control, the same reference
in bfloat16, fails the limits."""

import jax
import numpy as np
import pytest

from chipbench import oracle
from chipbench.store import BenchSource
from repro.core.presto import PreStoEngine
from repro.core.spec import TransformSpec

SEED = 2**31 + 3
# RM5's and RM1's per-feature shapes at a few features, 256 rows
CONFIGS = {
    "rm5-like": {"name": "rm5", "n_dense": 6, "n_sparse": 3, "avg_sparse_len": 20,
                 "max_sparse_len": 32, "n_generated": 4, "bucket_size": 4096,
                 "id_space": 1 << 24, "embedding_rows": 500000,
                 "rows_per_partition": 256, "dense_encoding": "bytesplit",
                 "sparse_encoding": "bitpack", "stored_partitions": 2,
                 "bucket_boundary_seed": 0},
    "rm1-like": {"name": "rm1", "n_dense": 13, "n_sparse": 26, "avg_sparse_len": 1,
                 "max_sparse_len": 1, "n_generated": 13, "bucket_size": 1024,
                 "id_space": 1 << 24, "embedding_rows": 500000,
                 "rows_per_partition": 256, "dense_encoding": "bytesplit",
                 "sparse_encoding": "bitpack", "stored_partitions": 2,
                 "bucket_boundary_seed": 0},
}


def _within(reading):
    return all(reading[k] <= lim for k, lim in oracle.LIMITS.items())


@pytest.mark.parametrize("name,dup", [("rm5-like", 1), ("rm5-like", 4), ("rm1-like", 1)])
def test_reference_agrees_with_program(name, dup):
    src = BenchSource(CONFIGS[name], {"dup_factor": dup}, SEED)
    engine = PreStoEngine(TransformSpec.from_source(src))
    for pid in (0, 1):
        from repro.core.preprocess import pages_from_partition

        pages = pages_from_partition(src.partition(pid), engine.spec)
        got = jax.device_get(jax.jit(engine.preprocess_local)(pages))
        reading = oracle.compare(got, oracle.reference_batch(src.gen, pid))
        assert _within(reading), reading


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_control_fails(name):
    from chipbench.control import control_readings

    src = BenchSource(CONFIGS[name], {"dup_factor": 1}, SEED)
    reading = control_readings(src.gen.shape, SEED, range(2))
    assert reading["dense_max_abs_err"] > 10 * oracle.LIMITS["dense_max_abs_err"]
    assert reading["one_hot_ids_mismatch"] > 0
    assert not _within(reading)


def test_compare_counts_missing_and_misshapen_keys():
    src = BenchSource(CONFIGS["rm1-like"], {"dup_factor": 1}, SEED)
    want = oracle.reference_batch(src.gen, 1)
    half = {k: v[: v.shape[0] // 2] for k, v in want.items()}
    r = oracle.compare(half, want)
    assert r["dense_max_abs_err"] == oracle.DENSE_UNUSABLE
    assert r["labels_mismatch"] == want["labels"].size
    r = oracle.compare({}, want)
    assert r["multi_hot_ids_mismatch"] == want["multi_hot_ids"].size
    assert oracle.merge([oracle.compare(want, want)] * 2) == {
        "dense_max_abs_err": 0.0, "multi_hot_ids_mismatch": 0,
        "one_hot_ids_mismatch": 0, "lengths_mismatch": 0, "labels_mismatch": 0}
