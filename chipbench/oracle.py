"""The plain reference: train-ready batches from raw features, in numpy.

Imports nothing of the program.  The Transform is the paper's (Table I,
Alg. 1 and 2): dense features Log-normalized, sparse ids SigridHashed into
their tables, and generated features Bucketized against sorted boundaries
and then hashed.  The hash seeds and table sizes follow the formulas of the
program's ``TransformSpec`` at the commit that defined this benchmark.

``compare`` gives the numbers that decide ``correct``; ``reference_batch``
with ``dtype=bfloat16`` is the control (the same reference one precision
step below the float32 the configuration states).
"""

from __future__ import annotations

import numpy as np

from chipbench.datagen import Generator, Shape

INT_KEYS = ("multi_hot_ids", "one_hot_ids", "lengths", "labels")
BATCH_KEYS = ("dense",) + INT_KEYS
# what an unusable dense output (missing, or of another shape) reads as
DENSE_UNUSABLE = float(np.finfo(np.float32).max)
# Largest allowed reading of each number compared.  Integer outputs are
# exact.  The dense limit lies between the program's worst reading over
# sound runs and the control's least (PERF.md gives both).
LIMITS = {
    "dense_max_abs_err": 1e-3,
    "multi_hot_ids_mismatch": 0,
    "one_hot_ids_mismatch": 0,
    "lengths_mismatch": 0,
    "labels_mismatch": 0,
}


def hash_params(shape: Shape):
    """(sparse_seeds, sparse_max, gen_seeds, gen_max), uint32 each."""
    with np.errstate(over="ignore"):
        sparse_seeds = np.arange(shape.n_sparse, dtype=np.uint32) * np.uint32(
            2654435761
        ) + np.uint32(1)
        gen_seeds = np.arange(shape.n_generated, dtype=np.uint32) * np.uint32(
            40503
        ) + np.uint32(7)
    sparse_max = np.full(shape.n_sparse, shape.embedding_rows, np.uint32)
    gen_max = np.full(shape.n_generated, shape.embedding_rows, np.uint32)
    return sparse_seeds, sparse_max, gen_seeds, gen_max


def sigridhash(ids: np.ndarray, seeds: np.ndarray, maxes: np.ndarray) -> np.ndarray:
    """Seeded murmur3 finalizer, then range reduction, in uint32 arithmetic;
    seeds and maxes broadcast against ids."""
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


def reference_batch(gen: Generator, pid: int, dtype=np.float32) -> dict:
    """Partition `pid`'s train-ready batch, with the dense arithmetic (the
    Log and the Bucketize comparisons) carried out in `dtype`."""
    shape = gen.shape
    raw = gen.raw(pid)
    sparse_seeds, sparse_max, gen_seeds, gen_max = hash_params(shape)
    dense = raw.dense.astype(dtype)
    bounds = gen.bucket_boundaries.astype(dtype)
    sources = dense[:, gen.generated_source]
    buckets = np.stack(
        [
            np.digitize(sources[:, g].astype(np.float32), bounds[g].astype(np.float32))
            for g in range(shape.n_generated)
        ],
        axis=1,
    )
    norm = np.log1p(np.maximum(dense, dtype(0)))
    return {
        "dense": norm.astype(np.float32),
        "multi_hot_ids": sigridhash(
            raw.sparse_values, sparse_seeds[None, :, None], sparse_max[None, :, None]
        ),
        "lengths": raw.sparse_lengths,
        "one_hot_ids": sigridhash(buckets, gen_seeds[None, :], gen_max[None, :]),
        "labels": raw.labels,
    }


def compare(got: dict, want: dict) -> dict:
    """One delivered batch against its reference: the largest dense error,
    and for every integer key (labels are 0/1 floats) the number of values
    that differ.  A key that is missing or of another shape counts every
    reference value as differing."""
    out = {}
    g = got.get("dense")
    if g is None or np.shape(g) != want["dense"].shape:
        out["dense_max_abs_err"] = DENSE_UNUSABLE
    else:
        err = np.abs(np.asarray(g, np.float32) - want["dense"])
        out["dense_max_abs_err"] = (
            DENSE_UNUSABLE if not np.all(np.isfinite(err)) else float(err.max())
        )
    for k in INT_KEYS:
        g = got.get(k)
        w = want[k]
        if g is None or np.shape(g) != w.shape:
            out[f"{k}_mismatch"] = int(w.size)
        else:
            out[f"{k}_mismatch"] = int(np.count_nonzero(np.asarray(g) != w))
    return out


def merge(readings: list[dict]) -> dict:
    """Worst reading of each number over several compared batches."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v) if k == "dense_max_abs_err" else out.get(k, 0) + v
    return out
