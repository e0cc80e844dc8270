"""A whole run at a small size on the CPU (the harness's look for a chip is
skipped), sound and with the timed path broken underneath: `correct` holds
for the sound run and fails for each fault this kind of cell can have."""

import json
import os
import time

import jax
import pytest

from chipbench import harness
from repro.core.execcache import EXECUTABLES
from repro.core.presto import PreStoEngine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = {"name": "tiny", "n_dense": 4, "n_sparse": 2, "avg_sparse_len": 20,
          "max_sparse_len": 32, "n_generated": 2, "bucket_size": 128,
          "id_space": 1 << 24, "embedding_rows": 500000, "rows_per_partition": 256,
          "dense_encoding": "bytesplit", "sparse_encoding": "bitpack",
          "stored_partitions": 2, "bucket_boundary_seed": 0}
TRAFFIC = {"megabatch": 1, "dup_factor": 1, "workers": 2, "queue_depth": 4,
           "logical_partitions": 100000, "warm_deliveries": 1, "trace_partitions": 4,
           "check_sample": 4}


CACHE_FLAGS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs")


def _altered(batch):
    return dict(batch, one_hot_ids=batch["one_hot_ids"].at[0, 0].add(1))


def _half(batch):
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}


@pytest.fixture
def cell(monkeypatch, tmp_path):
    # run_cell turns the persistent compile cache on; keep this process's as it was
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(harness.store, "STORE_DIR", str(tmp_path / "store"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    EXECUTABLES.clear()
    yield {"name": "tiny-cell", "chips": 1, "config": CONFIG, "traffic": TRAFFIC,
           "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"]}
    EXECUTABLES.clear()
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(cell):
    dev = jax.devices()[0]
    return harness.run_cell(cell, 2**31 + 11, 0.5, False, dev, None, time.perf_counter())


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"samples_per_s", "batch_wait_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [_altered, _half], ids=["answer_altered", "half_batch"])
def test_fault_is_not_correct(cell, monkeypatch, fault):
    produce = PreStoEngine.preprocess_local
    monkeypatch.setattr(PreStoEngine, "preprocess_local",
                        lambda self, pages: fault(produce(self, pages)))
    r = _run(cell)
    assert not r["correct"]
    assert r["failed"] > 0
