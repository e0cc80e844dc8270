"""Sample-level dedup (RecD): shared blocks, unique-bytes ledgers, bitwise.

The load-bearing invariant everywhere: a dedup-aware path (encode, solo
preprocess, megabatch, block-cache assembly, spill tier) is bitwise
identical to the undeduped path it replaces — dedup only changes which
bytes move, never which batch comes out.
"""

import dataclasses
import os
import tempfile

import numpy as np
import pytest

from repro.core.costmodel import (
    DEFAULT_PLACEMENT_MODEL,
    ContentionAwareCostModel,
    family_compute_ops,
    partition_costs,
)
from repro.core.featcache import BlockKey, FeatureCache
from repro.core.opgraph import family_page_bytes
from repro.core.preprocess import pages_from_partition, stack_pages, stage_pages
from repro.core.presto import PreStoEngine
from repro.core.service import JobSpec, PreprocessingService
from repro.core.simclock import synthetic_costs
from repro.core.spec import TransformSpec
from repro.data.columnar import (
    decode_partition_numpy,
    inflate_partition,
    partition_refs,
    read_partition,
    write_partition,
)
from repro.data.storage import CacheSpillStore, DeviceFleet, PartitionedStore
from repro.data.synth import RM_CONFIGS, SyntheticRecSysSource


def _dedup_cfg(dup_factor=4, dup_pool=0, rows=128, name="rm2"):
    return dataclasses.replace(
        RM_CONFIGS[name],
        rows_per_partition=rows,
        dup_factor=dup_factor,
        dup_pool=dup_pool,
    )


@pytest.fixture(scope="module")
def dedup4():
    cfg = _dedup_cfg(dup_factor=4)
    src = SyntheticRecSysSource(cfg, seed=3)
    return cfg, src, TransformSpec.from_source(src)


# -- columnar round-trip ------------------------------------------------------


def test_dedup_partition_roundtrip_bitwise(dedup4):
    cfg, src, _ = dedup4
    part = src.partition(5)
    raw = src.raw(5)
    assert part.schema.dup_factor == 4
    assert part.schema.unique_rows == cfg.rows_per_partition // 4
    # stored strictly less than logical: sparse pages shrink by ~dup factor
    assert part.nbytes() < part.logical_nbytes()
    dec = decode_partition_numpy(part)
    np.testing.assert_array_equal(dec["sparse_values"]["s0"], raw.sparse_values[:, 0])
    np.testing.assert_array_equal(dec["sparse_lengths"]["s0"], raw.sparse_lengths[:, 0])
    np.testing.assert_allclose(dec["dense"]["d0"], raw.dense[:, 0])
    np.testing.assert_allclose(dec["dense"]["label"], raw.labels)
    np.testing.assert_array_equal(dec["sparse_refs"], raw.sparse_refs)
    # disk round-trip preserves the dedup encoding AND the decode
    with tempfile.TemporaryDirectory() as root:
        path = os.path.join(root, "p5.col")
        write_partition(path, part)
        back = read_partition(path)
        assert back.schema.dup_factor == 4
        assert back.nbytes() == part.nbytes()
        dec2 = decode_partition_numpy(back)
        np.testing.assert_array_equal(
            dec2["sparse_values"]["s0"], dec["sparse_values"]["s0"]
        )
        np.testing.assert_array_equal(dec2["sparse_refs"], dec["sparse_refs"])


def test_dup_factor_one_degenerates_to_classic_layout():
    """dup_factor=1 must be byte-identical to the pre-dedup format."""
    cfg = _dedup_cfg(dup_factor=1)
    src = SyntheticRecSysSource(cfg, seed=3)
    part = src.partition(2)
    assert part.schema.dup_factor == 1
    assert part.schema.unique_rows == cfg.rows_per_partition
    # no refs column, no dup_factor key in the serialized header
    assert all(c.kind != "refs" for c in part.schema.columns)
    assert "dup_factor" not in part.schema.to_json()
    assert part.nbytes() == part.logical_nbytes()
    assert partition_refs(part) is None
    dec = decode_partition_numpy(part)
    assert "sparse_refs" not in dec


def test_inflate_partition_bitwise(dedup4):
    _, src, _ = dedup4
    part = src.partition(1)
    flat = inflate_partition(part)
    assert flat.schema.dup_factor == 1
    assert flat.nbytes() == part.logical_nbytes()
    a, b = decode_partition_numpy(part), decode_partition_numpy(flat)
    for name in a["sparse_values"]:
        np.testing.assert_array_equal(a["sparse_values"][name], b["sparse_values"][name])
        np.testing.assert_array_equal(
            a["sparse_lengths"][name], b["sparse_lengths"][name]
        )
    for name in a["dense"]:
        np.testing.assert_array_equal(a["dense"][name], b["dense"][name])


def test_dedup_blocks_repeat_within_session(dedup4):
    """The duplication model: refs tile each unique block dup_factor times."""
    _, src, _ = dedup4
    raw = src.raw(0)
    refs = raw.sparse_refs
    assert refs is not None and refs.shape == (src.rows,)
    np.testing.assert_array_equal(refs, np.arange(src.rows) // 4)
    # every sample in a block carries the same sparse features
    for b in range(src.rows // 4):
        rows = slice(4 * b, 4 * b + 4)
        np.testing.assert_array_equal(
            raw.sparse_values[rows], np.broadcast_to(
                raw.sparse_values[4 * b], raw.sparse_values[rows].shape
            )
        )


# -- engine bitwise across every lowering -------------------------------------


@pytest.mark.parametrize("kernel_mode", ["fused", "unfused", "hybrid"])
def test_execute_plan_bitwise_vs_inflated(dedup4, kernel_mode):
    _, src, spec = dedup4
    eng = PreStoEngine(spec, interpret=True, kernel_mode=kernel_mode)
    part = src.partition(0)
    got = eng.preprocess_local(pages_from_partition(part, spec))
    ref = eng.lowered_plan.execute(
        pages_from_partition(inflate_partition(part), spec)
    )
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]))


def test_megabatch_bitwise_vs_solo(dedup4):
    _, src, spec = dedup4
    eng = PreStoEngine(spec, interpret=True)
    pages = [pages_from_partition(src.partition(p), spec) for p in (0, 1, 2)]
    mega = eng.preprocess_megabatch(stack_pages(pages))
    assert len(mega) == 3
    for i, pg in enumerate(pages):
        solo = eng.preprocess_local(pg)
        for k in solo:
            np.testing.assert_array_equal(np.asarray(mega[i][k]), np.asarray(solo[k]))


def test_pages_struct_matches_dedup_pages(dedup4):
    cfg, src, spec = dedup4
    eng = PreStoEngine(spec, interpret=True)
    pages, _stored = stage_pages(src.partition(0), spec)
    structs = eng.pages_struct(cfg.rows_per_partition)
    assert set(structs) == set(pages)
    for k, s in structs.items():
        assert tuple(s.shape) == tuple(pages[k].shape), k
        assert np.dtype(s.dtype) == pages[k].dtype, k


# -- ledgers: unique bytes charged, logical reported --------------------------


def test_store_charges_unique_bytes_under_skewed_ownership(dedup4):
    _, src, _ = dedup4
    fleet = DeviceFleet(4)
    # skew: device 0 owns 6 of 8 partitions
    owner_map = [0, 0, 0, 0, 0, 0, 1, 2]
    store = PartitionedStore(
        8, num_devices=4, source=src, fleet=fleet, owner_map=owner_map
    )
    parts = [store.read(p) for p in range(8)]
    unique = sum(p.nbytes() for p in parts)
    logical = sum(p.logical_nbytes() for p in parts)
    assert store.bytes_read == unique
    assert store.logical_bytes_read == logical
    assert unique < logical
    # the owning devices streamed exactly the UNIQUE bytes, skew preserved
    per_dev = [0] * 4
    for pid, p in enumerate(parts):
        per_dev[owner_map[pid]] += p.nbytes()
    for d in range(4):
        assert fleet[d].bytes_streamed == per_dev[d]
    assert per_dev[3] == 0 and per_dev[0] > per_dev[1]


def test_spill_store_row_dedup_roundtrip_and_charging():
    store = CacheSpillStore(num_devices=2)
    rng = np.random.default_rng(0)
    uniq = rng.integers(0, 1 << 20, size=(8, 64), dtype=np.int64)
    ids = uniq[np.arange(64) % 8]  # heavy row duplication
    flo = rng.random((64, 4)).astype(np.float32)  # floats: never row-deduped
    block = {"multi_hot_ids": ids, "dense": flo}
    written = store.write("k0", block)
    raw = ids.nbytes + flo.nbytes
    assert written < raw  # stored deduped: unique rows + refs
    assert store.bytes_written == written
    back = store.read("k0")
    np.testing.assert_array_equal(back["multi_hot_ids"], ids)
    np.testing.assert_array_equal(back["dense"], flo)
    assert store.bytes_read == written  # reads charge stored bytes too


# -- cost model: unique bytes/ops priced --------------------------------------


def test_costmodel_prices_unique_bytes_and_ops(dedup4):
    cfg, _, spec = dedup4
    flat_spec = TransformSpec.from_source(
        SyntheticRecSysSource(dataclasses.replace(cfg, dup_factor=1), seed=3)
    )
    rows = cfg.rows_per_partition
    pb_d, pb_f = family_page_bytes(spec, rows), family_page_bytes(flat_spec, rows)
    assert pb_d["sparse"] < pb_f["sparse"]
    assert pb_d["lengths"] < pb_f["lengths"]
    assert pb_d["dense"] == pb_f["dense"]  # dense is per-sample, unchanged
    ops_d, ops_f = family_compute_ops(spec, rows), family_compute_ops(flat_spec, rows)
    assert ops_d["sparse"] < ops_f["sparse"]  # hash at unique rows + gather
    c_d = partition_costs(spec, rows)
    c_f = partition_costs(flat_spec, rows)
    assert c_d.page_bytes < c_f.page_bytes
    assert c_d.ops < c_f.ops
    assert c_d.isp_s < c_f.isp_s
    assert c_d.batch_bytes == c_f.batch_bytes  # output tensors are logical


def test_simclock_costs_calibrate_from_spec(dedup4):
    cfg, _, spec = dedup4
    model = ContentionAwareCostModel()
    got = synthetic_costs(model, spec=spec, rows=cfg.rows_per_partition)
    assert got == partition_costs(spec, cfg.rows_per_partition, model)
    # no spec: the round synthetic defaults, unchanged
    dflt = synthetic_costs(model)
    assert dflt.page_bytes == 48 << 20


# -- block fingerprints + block cache tier ------------------------------------


def test_store_block_fingerprints_source_and_file(dedup4):
    _, src, _ = dedup4
    store = PartitionedStore(4, num_devices=2, source=src)
    fps = store.block_fingerprints(0)
    refs = store.block_refs(0)
    assert fps is not None and len(fps) == src.rows // 4
    np.testing.assert_array_equal(refs, np.arange(src.rows) // 4)
    assert fps == store.block_fingerprints(0)  # cached, stable
    # classic data: no block identity
    flat = SyntheticRecSysSource(_dedup_cfg(dup_factor=1), seed=3)
    assert PartitionedStore(4, num_devices=2, source=flat).block_fingerprints(0) is None
    # file-backed: content-hashed fps, equal content => equal fp
    with tempfile.TemporaryDirectory() as root:
        dstore = PartitionedStore(4, num_devices=2, source=src, root=root)
        dstore.materialize(range(2))
        ffps = dstore.block_fingerprints(0)
        assert ffps is not None and len(ffps) == len(fps)
        assert len(set(ffps)) == len(ffps)  # no pool: all blocks distinct


def test_pool_blocks_overlap_across_partitions():
    cfg = _dedup_cfg(dup_factor=4, dup_pool=8)
    src = SyntheticRecSysSource(cfg, seed=3)
    store = PartitionedStore(4, num_devices=2, source=src)
    a = set(store.block_fingerprints(0))
    b = set(store.block_fingerprints(1))
    assert a and a <= set(store.block_fingerprints(0))
    assert a & b, "pooled datasets must share blocks across partitions"
    assert len(a | b) <= 8  # at most the pool size


def test_feature_cache_block_tier():
    cache = FeatureCache(capacity_bytes=1 << 20, block_capacity_bytes=1 << 16)
    rng = np.random.default_rng(0)
    keys = [BlockKey(f"fp{i}", "plan", "presto") for i in range(4)]
    blocks = [
        (
            rng.integers(0, 100, size=(2, 8), dtype=np.int32),
            rng.integers(0, 8, size=(2,), dtype=np.int32),
        )
        for _ in range(4)
    ]
    for k, (ids, lens) in zip(keys, blocks):
        cache.put_block(k, ids, lens)
    got = cache.get_block(keys[1])
    np.testing.assert_array_equal(got[0], blocks[1][0])
    # all-or-nothing gather: full coverage stacks in ref order
    stacked = cache.get_blocks([keys[0], keys[2], keys[0]])
    assert stacked is not None
    ids, lens = stacked
    assert ids.shape == (3, 2, 8) and lens.shape == (3, 2)
    np.testing.assert_array_equal(ids[0], blocks[0][0])
    np.testing.assert_array_equal(ids[1], blocks[2][0])
    np.testing.assert_array_equal(ids[2], blocks[0][0])
    assert cache.get_blocks([keys[0], BlockKey("nope", "plan", "presto")]) is None
    st = cache.stats()
    assert st.block_insertions >= 4 and st.block_hits >= 4 and st.block_misses >= 1
    # LRU bound: a tiny block budget evicts, never overflows
    tiny = FeatureCache(capacity_bytes=1 << 20, block_capacity_bytes=256)
    big_ids = np.zeros((2, 16), np.int32)
    big_lens = np.zeros((2,), np.int32)
    for i in range(8):
        tiny.put_block(BlockKey(f"b{i}", "p", "x"), big_ids, big_lens)
    ts = tiny.stats()
    assert ts.block_resident_bytes <= 256
    assert ts.block_entries < 8


def test_service_cross_tenant_block_assembly():
    """Tenant B's batches assemble from tenant A's published blocks — and
    stay bitwise identical to a cold single-tenant run."""
    cfg = _dedup_cfg(dup_factor=4, dup_pool=16)
    src = SyntheticRecSysSource(cfg, seed=3)
    spec = TransformSpec.from_source(src)
    store = PartitionedStore(16, num_devices=2, source=src)
    eng = PreStoEngine(spec, interpret=True)
    svc = PreprocessingService(num_workers=2, cache=FeatureCache(capacity_bytes=64 << 20))
    try:
        # tenant A runs the self-tuning megabatched worker path: dedup pages
        # must stay bitwise through coalesced launches and the tuner too
        sA = svc.submit(
            JobSpec(name="A", spec=spec, store=store, engine=eng,
                    partitions=range(8), megabatch=4, autotune=True)
        )
        outA = dict(iter(sA))
        sB = svc.submit(
            JobSpec(name="B", spec=spec, store=store, engine=eng, partitions=range(8, 16))
        )
        outB = dict(iter(sB))
        stA, stB = sA.stats(), sB.stats()
    finally:
        svc.close()
    assert stA.blocks_published > 0
    assert stB.block_hits > 0  # cross-tenant: B never produced cold
    assert stB.block_hits == stB.cache_hits  # block assemblies count as hits
    ref = PreStoEngine(spec, interpret=True, use_exec_cache=False)
    for pid in range(16):
        want = ref.lowered_plan.execute(
            pages_from_partition(inflate_partition(src.partition(pid)), spec)
        )
        got = outA[pid] if pid in outA else outB[pid]
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))
    # the store charged unique bytes throughout
    assert store.bytes_read < store.logical_bytes_read


class _OomAssembly(PreStoEngine):
    def assemble_from_blocks(self, *args, **kwargs):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")


class _UnreadableBlockMeta(PartitionedStore):
    def block_fingerprints(self, partition_id):
        raise OSError("block metadata unreadable")


@pytest.mark.parametrize("fault", ["device", "storage"])
def test_block_tier_faults(fault):
    """A storage fault in the block-tier probe is a miss (the claim produces
    cold, bitwise); a device error assembling from cached blocks is real and
    reaches the consumer instead of hiding behind a cold produce."""
    cfg = _dedup_cfg(dup_factor=4, dup_pool=16)
    src = SyntheticRecSysSource(cfg, seed=3)
    spec = TransformSpec.from_source(src)
    store = PartitionedStore(16, num_devices=2, source=src)
    eng = PreStoEngine(spec, interpret=True)
    if fault == "device":
        eng_b, store_b = _OomAssembly(spec, interpret=True), store
    else:
        eng_b, store_b = eng, _UnreadableBlockMeta(16, num_devices=2, source=src)
    with PreprocessingService(
        num_workers=2, cache=FeatureCache(capacity_bytes=64 << 20)
    ) as svc:
        sA = svc.submit(JobSpec(name="A", spec=spec, store=store, engine=eng,
                                partitions=range(8)))
        dict(iter(sA))
        assert sA.stats().blocks_published > 0
        sB = svc.submit(JobSpec(name="B", spec=spec, store=store_b, engine=eng_b,
                                partitions=range(8, 16)))
        if fault == "device":
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                dict(iter(sB))
            sB.cancel()
            return
        outB = dict(iter(sB))
        assert sB.stats().block_hits == 0
    ref = PreStoEngine(spec, interpret=True, use_exec_cache=False)
    for pid in range(8, 16):
        want = ref.lowered_plan.execute(
            pages_from_partition(inflate_partition(src.partition(pid)), spec)
        )
        for k in want:
            np.testing.assert_array_equal(np.asarray(outB[pid][k]), np.asarray(want[k]))
