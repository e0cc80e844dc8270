"""Transform graph: fused vs unfused equivalence, oracle agreement, stages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.preprocess import (
    pages_from_partition,
    pages_shape_dtypes,
    preprocess_pages,
    stage_functions,
    stage_pages,
)
from repro.core.spec import TransformSpec
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.kernels import ref


@pytest.fixture(scope="module")
def small_rm():
    cfg = RMDataConfig("t", 4, 3, 4, 8, 2, 32, 1 << 16, 1024, rows_per_partition=256)
    src = SyntheticRecSysSource(cfg, rows=256)
    return src, TransformSpec.from_source(src)


def _pages(src, spec, pid=0):
    return {k: jnp.asarray(v) for k, v in
            pages_from_partition(src.partition(pid), spec).items()}


def test_fused_equals_unfused(small_rm):
    src, spec = small_rm
    pages = _pages(src, spec)
    a = preprocess_pages(pages, spec, mode="fused")
    b = preprocess_pages(pages, spec, mode="unfused")
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)


def test_preprocess_matches_raw_oracle(small_rm):
    src, spec = small_rm
    raw = src.raw(1)
    mb = preprocess_pages(_pages(src, spec, 1), spec)
    np.testing.assert_allclose(
        np.asarray(mb["dense"]), np.log1p(np.maximum(raw.dense, 0)), atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(mb["lengths"]), raw.sparse_lengths)
    np.testing.assert_allclose(np.asarray(mb["labels"]), raw.labels)
    # multi-hot = sigridhash(raw ids); generated = sigridhash(digitize(dense))
    s0 = np.asarray(ref.sigridhash(jnp.asarray(raw.sparse_values[:, 0]),
                                   int(spec.sparse_seeds[0]), int(spec.sparse_max[0])))
    np.testing.assert_array_equal(np.asarray(mb["multi_hot_ids"][:, 0]), s0)
    b0 = np.digitize(raw.dense[:, spec.generated_source[0]], spec.bucket_boundaries[0])
    g0 = np.asarray(ref.sigridhash(jnp.asarray(b0.astype(np.int32)),
                                   int(spec.gen_seeds[0]), int(spec.gen_max[0])))
    np.testing.assert_array_equal(np.asarray(mb["one_hot_ids"][:, 0]), g0)


def test_stage_functions_compose(small_rm):
    src, spec = small_rm
    pages = _pages(src, spec)
    stages = stage_functions(spec)
    dense_raw, sparse_raw = stages["extract_decode"](pages)
    bucket_ids = stages["gen_bucketize"](dense_raw)
    hashed, gen_hashed = stages["norm_sigridhash"](sparse_raw, bucket_ids)
    dense_norm = stages["norm_log"](dense_raw)
    mb = stages["form_minibatch"](pages, dense_norm, hashed, gen_hashed)
    direct = preprocess_pages(pages, spec)
    for k in direct:
        np.testing.assert_array_equal(np.asarray(mb[k]), np.asarray(direct[k]), k)


def test_pages_shape_dtypes_match(small_rm):
    src, spec = small_rm
    pages, _stored = stage_pages(src.partition(0), spec)
    struct = pages_shape_dtypes(spec, 256)
    assert set(struct) == set(pages)
    for k in pages:
        assert tuple(struct[k].shape) == tuple(pages[k].shape), k
        assert struct[k].dtype == pages[k].dtype, k


def test_preprocess_jit_once(small_rm):
    """One compiled program serves every partition (static schema)."""
    src, spec = small_rm
    fn = jax.jit(lambda p: preprocess_pages(p, spec))
    fn(_pages(src, spec, 0))
    n0 = fn._cache_size()
    fn(_pages(src, spec, 1))
    assert fn._cache_size() == n0
