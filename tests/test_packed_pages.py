"""Mesh-less engines stage a partition's page words as ONE u32 array
(``preprocess.stage_pages``) and the compiled program cuts the kernels'
``(F, G, w)`` arrays out of it (``opgraph.cut_pages``).

A partition read from a file stages the body it was read as, with no copy
(the page-build span says ``stored=1``); one built in memory, by the
synthetic source, is concatenated in the same page order (``stored=0``).
Each geometry is served from both routes through ``PreprocessingService``:
every batch is compared with the numpy reference of its raw partition,
bitwise with the program run on the kernel-shaped pages of
``pages_from_partition``, and bitwise across the two routes, whose staged
words are the same bytes.  The geometries cover RM1 (Criteo) at K=1 and
K=4, a multi-hot RM5-like shape, a dedup partition with block refs, and a
partition whose pages are not whole 128-lane rows.  A partition whose
schema or page sizes are not the dataset's is refused, never mis-cut.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.common import trace
from repro.core import presto
from repro.core.preprocess import pages_from_partition, stage_pages
from repro.core.presto import PreStoEngine
from repro.core.service import JobSpec, PreprocessingService
from repro.core.spec import TransformSpec
from repro.data.columnar import (
    EncodedColumn,
    Partition,
    partition_refs,
    read_partition,
    write_partition,
)
from repro.data.storage import PartitionedStore
from repro.data.synth import RM_CONFIGS, RMDataConfig, SyntheticRecSysSource
from repro.kernels import ops

PARTITIONS = 4

RM1 = RM_CONFIGS["rm1"]  # 13 dense, 26 one-hot of 24-bit ids, 13 generated
RM5_LIKE = RMDataConfig("rm5-like", 8, 4, 20, 32, 4, 4096, 1 << 24, 3_000_000)
DEDUP = dataclasses.replace(RM_CONFIGS["rm2"], n_dense=8, n_sparse=4,
                            n_generated=4, dup_factor=4)

# name -> (data config, rows, megabatch K)
GEOMETRIES = {
    "rm1-k1": (RM1, 4096, 1),
    "rm1-k4": (RM1, 4096, 4),
    "rm5-like": (RM5_LIKE, 256, 1),  # 32 ids of 24 bits a row, 6-bit lengths
    "dedup": (DEDUP, 512, 2),  # 128 unique blocks, refs in the staged words
    "partial-rows": (RM1, 96, 2),  # no page is whole 128-lane rows
}

# route -> the page-build span's ``stored``: a file's body, or concatenated
ROUTES = {"stored": 1, "source": 0}


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


def _stores(src, root):
    """route -> a store of PARTITIONS partitions of `src`: files on disk
    (``stored``) or the synthetic source itself (``source``)."""
    on_disk = PartitionedStore(PARTITIONS, num_devices=1, source=src, root=root)
    on_disk.materialize(range(PARTITIONS))
    return {
        "stored": on_disk,
        "source": PartitionedStore(PARTITIONS, num_devices=1, source=src),
    }


@pytest.fixture(scope="module", params=sorted(GEOMETRIES))
def geometry(request, tmp_path_factory):
    cfg, rows, k = GEOMETRIES[request.param]
    src = SyntheticRecSysSource(cfg, rows=rows, seed=5)
    spec = TransformSpec.from_source(src)
    stores = _stores(src, str(tmp_path_factory.mktemp(request.param)))
    return request.param, src, spec, stores, k


class _PageBuildSpans:
    """Stands in for ``TraceAnnotation`` in the engine: records the
    ``stored`` metadata of every page-build span."""

    def __init__(self):
        self.stored = []

    def __call__(self, name, **_kwargs):
        return _Span(self.stored if name == trace.PAGE_BUILD else None)


class _Span:
    def __init__(self, sink):
        self._sink = sink

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    def set_metadata(self, **meta):
        if self._sink is not None:
            self._sink.append(meta.get("stored"))


def test_served_packed_pages_match_reference(geometry, monkeypatch):
    name, src, spec, stores, k = geometry
    engine = PreStoEngine(spec)
    served = {}
    for route, stored in ROUTES.items():
        spans = _PageBuildSpans()
        monkeypatch.setattr(presto, "TraceAnnotation", spans)
        with PreprocessingService(1) as svc:
            sess = svc.submit(JobSpec(
                name=f"{name}-{route}", partitions=range(PARTITIONS),
                engine=engine, store=stores[route], megabatch=k,
                queue_depth=PARTITIONS, use_cache=False,
            ))
            served[route] = {pid: jax.device_get(batch) for pid, batch in sess}
            stats = sess.stats()
        assert sorted(served[route]) == list(range(PARTITIONS)), route
        assert stats.launches == PARTITIONS // k, route  # K=k programs ran
        assert spans.stored and set(spans.stored) == {stored}, route
    for pid in range(PARTITIONS):
        want = _reference(src, spec, pid)
        # bitwise the program on the kernels' own (F, G, w) pages
        kernel = engine.preprocess_local(
            pages_from_partition(stores["source"].read(pid), spec))
        for route, batches in served.items():
            batch = batches[pid]
            assert set(batch) == set(want), route
            for key in ("multi_hot_ids", "one_hot_ids", "lengths", "labels"):
                assert batch[key].dtype == want[key].dtype, (route, key)
                np.testing.assert_array_equal(batch[key], want[key],
                                              err_msg=f"{route} {key}")
            np.testing.assert_allclose(batch["dense"], want["dense"],
                                       rtol=0, atol=1e-6, err_msg=route)
            for key, v in kernel.items():
                np.testing.assert_array_equal(batch[key], np.asarray(v),
                                              err_msg=f"{route} {key}")
                np.testing.assert_array_equal(batch[key], served["source"][pid][key],
                                              err_msg=f"{route} {key}")


def test_pages_struct_matches_staged_pages(geometry):
    """``pages_struct`` describes the one staged array of either route, and
    the two routes stage the same bytes."""
    _name, src, spec, stores, _k = geometry
    engine = PreStoEngine(spec)
    structs = engine.pages_struct(src.rows)
    staged = {route: engine.stage_partition(s, 0) for route, s in stores.items()}
    for route, pages in staged.items():
        assert set(structs) == set(pages), route
        for key, s in structs.items():
            assert tuple(s.shape) == pages[key].shape, (route, key)
            assert np.dtype(s.dtype) == pages[key].dtype, (route, key)
    (key,) = structs
    assert staged["stored"][key].ndim == 1
    np.testing.assert_array_equal(staged["stored"][key], staged["source"][key])


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
    """``pages_from_partition`` cuts the kernels' arrays out of the staged
    words of either route: bit for bit, dtype and shape, the per-column
    stack; the stored route's words are a view of the body it read."""
    _name, _src, spec, stores, _k = geometry
    for route, store in stores.items():
        part = store.read(1)
        want = _stacked_pages(part, spec)
        got = pages_from_partition(part, spec)
        assert set(got) == set(want), route
        for key in want:
            assert got[key].dtype == want[key].dtype, (route, key)
            np.testing.assert_array_equal(got[key], want[key],
                                          err_msg=f"{route} {key}")
        pages, stored = stage_pages(part, spec)
        (words,) = pages.values()
        assert stored == ROUTES[route], route
        assert (words is part.body) == bool(stored), route


class _Held:
    """A store that holds one partition."""

    def __init__(self, part):
        self.part = part

    def read(self, _pid):
        return self.part


def _longer_page(part):
    """`part` with one word more on its first sparse column's values page."""
    cols = {n: EncodedColumn(c.schema, dict(c.pages)) for n, c in part.columns.items()}
    values = cols["s0"].pages["values"]
    cols["s0"].pages["values"] = np.append(values, np.uint32(0))
    return Partition(part.partition_id, part.schema, cols)


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("fault", ["schema", "page-sizes"])
def test_staging_refuses_a_foreign_partition(tmp_path, fault, route):
    """A partition whose schema is not the engine's dataset's, or whose
    pages are not the schema's sizes, raises at staging."""
    rows = 256
    engine = PreStoEngine(TransformSpec.from_source(SyntheticRecSysSource(RM1, rows=rows)))
    if fault == "schema":  # another dataset: 20-bit ids
        part = SyntheticRecSysSource(dataclasses.replace(RM1, id_space=1 << 20),
                                     rows=rows).partition(0)
    else:
        part = _longer_page(SyntheticRecSysSource(RM1, rows=rows).partition(0))
    if route == "stored":
        path = str(tmp_path / "p0")
        write_partition(path, part)
        part = read_partition(path, spans=False)
        assert part.body is not None
    with pytest.raises(ValueError, match="partition 0"):
        engine.stage_partition(_Held(part), 0)
