"""Mesh-less engines stage a partition's grouped pages packed into one
lane-dense buffer (``preprocess.pack_pages``) and the compiled program cuts
the kernels' ``(F, G, w)`` arrays back out (``opgraph.kernel_pages``).

Each geometry is served through ``PreprocessingService`` and every batch is
compared with the numpy reference of its raw partition, and bitwise with the
program run on the kernel-shaped pages of ``pages_from_partition``, which are
checked against a per-column stack of the encoded pages.  The geometries
cover RM1 (Criteo) at K=1 and K=4, a multi-hot RM5-like shape, a dedup
partition with ``sparse_refs``, and a partition whose words per feature are
not whole 128-lane rows, so the zero tail is packed and dropped.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.preprocess import pages_from_partition
from repro.core.presto import PreStoEngine
from repro.core.service import JobSpec, PreprocessingService
from repro.core.spec import TransformSpec
from repro.data.storage import PartitionedStore
from repro.data.columnar import partition_refs
from repro.data.synth import RM_CONFIGS, RMDataConfig, SyntheticRecSysSource
from repro.kernels import ops

PARTITIONS = 4

RM1 = RM_CONFIGS["rm1"]  # 13 dense, 26 one-hot of 24-bit ids, 13 generated
RM5_LIKE = RMDataConfig("rm5-like", 8, 4, 20, 32, 4, 4096, 1 << 24, 3_000_000)
DEDUP = dataclasses.replace(RM_CONFIGS["rm2"], n_dense=8, n_sparse=4,
                            n_generated=4, dup_factor=4)

# name -> (data config, rows, megabatch K)
GEOMETRIES = {
    "rm1-k1": (RM1, 4096, 1),  # every family whole lane rows, as at 8192
    "rm1-k4": (RM1, 4096, 4),
    "rm5-like": (RM5_LIKE, 256, 1),  # 32 ids of 24 bits a row, 6-bit lengths
    "dedup": (DEDUP, 512, 2),  # 128 unique blocks, refs in the packed stage
    "partial-rows": (RM1, 96, 2),  # every family ends in a part lane row
}


def _sigridhash(ids, seeds, maxes):
    """SigridHash in numpy uint32 arithmetic (seeded murmur3 finalizer)."""
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


def _reference(src, spec, pid):
    """The train-ready batch of partition `pid`, from its raw features."""
    raw = src.raw(pid)
    sources = raw.dense[:, list(spec.generated_source)]
    buckets = np.stack(
        [np.digitize(sources[:, g], spec.bucket_boundaries[g])
         for g in range(sources.shape[1])], axis=1)
    return {
        "dense": np.log1p(np.maximum(raw.dense, 0.0)),
        "multi_hot_ids": _sigridhash(raw.sparse_values,
                                     spec.sparse_seeds[None, :, None],
                                     spec.sparse_max[None, :, None]),
        "lengths": raw.sparse_lengths,
        "one_hot_ids": _sigridhash(buckets, spec.gen_seeds[None, :],
                                   spec.gen_max[None, :]),
        "labels": raw.labels,
    }


@pytest.fixture(scope="module", params=sorted(GEOMETRIES))
def geometry(request):
    cfg, rows, k = GEOMETRIES[request.param]
    src = SyntheticRecSysSource(cfg, rows=rows, seed=5)
    spec = TransformSpec.from_source(src)
    store = PartitionedStore(PARTITIONS, num_devices=1, source=src)
    return request.param, src, spec, store, k


def test_served_packed_pages_match_reference(geometry):
    name, src, spec, store, k = geometry
    engine = PreStoEngine(spec)
    with PreprocessingService(1) as svc:
        sess = svc.submit(JobSpec(
            name=name, partitions=range(PARTITIONS), engine=engine,
            store=store, megabatch=k, queue_depth=PARTITIONS, use_cache=False,
        ))
        got = {pid: jax.device_get(batch) for pid, batch in sess}
        stats = sess.stats()
    assert sorted(got) == list(range(PARTITIONS))
    assert stats.launches == PARTITIONS // k  # K=k programs ran
    for pid, batch in got.items():
        want = _reference(src, spec, pid)
        assert set(batch) == set(want)
        for key in ("multi_hot_ids", "one_hot_ids", "lengths", "labels"):
            assert batch[key].dtype == want[key].dtype, key
            np.testing.assert_array_equal(batch[key], want[key], err_msg=key)
        np.testing.assert_allclose(batch["dense"], want["dense"], rtol=0, atol=1e-6)
        # bitwise the program on the kernels' own (F, G, w) pages
        kernel = engine.preprocess_local(
            pages_from_partition(store.read(pid), spec))
        for key, v in kernel.items():
            np.testing.assert_array_equal(batch[key], np.asarray(v), err_msg=key)


def test_pages_struct_matches_staged_pages(geometry):
    _name, src, spec, store, _k = geometry
    engine = PreStoEngine(spec)
    staged = engine.stage_partition(store, 0)
    structs = engine.pages_struct(src.rows)
    assert set(structs) == set(staged)
    for key, s in structs.items():
        assert tuple(s.shape) == staged[key].shape, key
        assert np.dtype(s.dtype) == staged[key].dtype, key
    assert "page_rows" in staged and not {"dense_words", "sparse_words"} & set(staged)


def _stacked_pages(part, spec):
    """The kernels' page arrays, one encoded column at a time."""
    cfg, rows, u = spec.cfg, part.schema.rows, part.schema.unique_rows
    col = part.columns
    pages = {
        "dense_words": np.stack([
            ops.regroup_bytesplit(col[f"d{i}"].pages["data"], rows)
            for i in range(cfg.n_dense)]),
        "sparse_words": np.stack([
            ops.regroup_bitpack(col[f"s{i}"].pages["values"],
                                u * cfg.max_sparse_len, cfg.id_width)
            for i in range(cfg.n_sparse)]),
        "length_words": np.stack([
            ops.regroup_bitpack(col[f"s{i}"].pages["lengths"], u, cfg.len_width)
            for i in range(cfg.n_sparse)]),
        "label_words": col["label"].pages["data"][:rows],
    }
    refs = partition_refs(part)
    if refs is not None:
        pages["sparse_refs"] = refs.astype(np.int32)
    return pages


def test_kernel_pages_unpack_the_staged_buffer(geometry):
    """``pages_from_partition`` cuts the kernels' arrays out of the packed
    stage: bit for bit, dtype and shape, the per-column stack."""
    _name, _src, spec, store, _k = geometry
    part = store.read(1)
    want = _stacked_pages(part, spec)
    got = pages_from_partition(part, spec)
    assert set(got) == set(want)
    for key in want:
        assert got[key].dtype == want[key].dtype, key
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
