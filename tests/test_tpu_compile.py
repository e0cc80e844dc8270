"""Compile the main-path kernels for a described TPU v5e, without the chip.

Interpret-mode tests never hand a kernel to Mosaic, the TPU kernel compiler,
so they cannot catch a block shape the chip's tiling refuses or a reshape
its layout pass cannot lower.  These tests lower each Pallas kernel of the
produce path (and the whole RM2 presto program) at the paper's widths with
``interpret=False`` and compile it for one chip of a described ``v5e:2x2``
topology.  Nothing runs: a pass means the TPU compiler accepted the program.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker imports
every test file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.preprocess import kernel_pages_shape_dtypes, pages_shape_dtypes
from repro.core.presto import PreStoEngine
from repro.core.spec import TransformSpec
from repro.data.synth import RM_CONFIGS, SyntheticRecSysSource
from repro.kernels import ops

ROWS = 8192  # rows per partition (paper Table I geometry)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(rm: str) -> TransformSpec:
    return TransformSpec.from_source(SyntheticRecSysSource(RM_CONFIGS[rm], rows=ROWS))


def _pages(rm: str, sharding) -> dict:
    """Page arrays at the kernels' shapes."""
    return {
        k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
        for k, s in kernel_pages_shape_dtypes(_spec(rm), ROWS).items()
    }


def _staged(rm: str, sharding) -> dict:
    """Page arrays as a mesh-less engine stages and puts them."""
    return {
        k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
        for k, s in pages_shape_dtypes(_spec(rm), ROWS).items()
    }


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("rm", ["rm2"])
def test_fused_dense_compiles(one_chip, rm):
    pages = _pages(rm, one_chip)
    _compile(lambda w: ops.fused_dense(w, interpret=False), pages["dense_words"])


@pytest.mark.parametrize("rm", ["rm2", "rm5"])
def test_fused_sparse_compiles(one_chip, rm):
    spec = _spec(rm)
    pages = _pages(rm, one_chip)
    assert pages["sparse_words"].shape[-1] == 24
    _compile(
        lambda w: ops.fused_sparse(
            w, spec.sparse_seeds, spec.sparse_max, width=spec.cfg.id_width,
            interpret=False,
        ),
        pages["sparse_words"],
    )


@pytest.mark.parametrize("rm", ["rm2", "rm5"])  # m = 1024, m = 4096
def test_fused_gen_compiles(one_chip, rm):
    spec = _spec(rm)
    cfg = spec.cfg
    gen_words = _struct((cfg.n_generated, ROWS // 4, 4), jnp.uint32, one_chip)
    _compile(
        lambda w: ops.fused_gen(
            w, spec.bucket_boundaries, spec.gen_seeds, spec.gen_max,
            interpret=False,
        ),
        gen_words,
    )


@pytest.mark.parametrize("which,width", [("length_words", 6), ("sparse_words", 24)])
def test_decode_bitpack_compiles(one_chip, which, width):
    pages = _pages("rm2", one_chip)
    assert pages[which].shape[-1] == width
    _compile(lambda w: ops.decode_bitpack(w, width=width, interpret=False), pages[which])


@pytest.mark.parametrize("rm", ["rm2", "rm5"])
def test_bucketize_compiles(one_chip, rm):
    spec = _spec(rm)
    vals = _struct((spec.cfg.n_generated, ROWS), jnp.float32, one_chip)
    _compile(lambda v: ops.bucketize(v, spec.bucket_boundaries, interpret=False), vals)


def test_sigridhash_compiles(one_chip):
    spec = _spec("rm2")
    cfg = spec.cfg
    ids = _struct((cfg.n_sparse, ROWS * cfg.max_sparse_len), jnp.int32, one_chip)
    _compile(
        lambda v: ops.sigridhash(v, spec.sparse_seeds, spec.sparse_max, interpret=False),
        ids,
    )


# kernel instruction names the benchmark's rooflines find the kernels by
KERNEL_OPS = ("fused_dense_pallas", "fused_sparse_pallas", "fused_gen_pallas")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+?)(?:\.\d+)? = ", re.M)


def test_rm2_presto_program_compiles(one_chip):
    """The whole K=1 produce program of the default JobSpec (presto, fused
    kernels), as ``PreStoEngine.jit_preprocess_cached`` would compile it.
    Each fused kernel keeps its instruction name, which a device trace
    reports as the op's name."""
    engine = PreStoEngine(_spec("rm2"), placement="presto", interpret=False)
    compiled = _compile(engine.preprocess_local, _staged("rm2", one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    names = set(_INSTRUCTION.findall(compiled.as_text()))
    assert set(KERNEL_OPS) <= names, set(KERNEL_OPS) - names


@pytest.mark.parametrize("rm", ["rm1", "rm2"])
def test_staged_pages_enter_row_major(one_chip, rm):
    """The K=1 produce program takes exactly one page parameter, the
    partition's u32 page words, in a row-major (or 1-D) layout
    (``major_to_minor`` ascending), the layout of numpy's C order, so the
    host-to-device put copies the stored body without relayout; the kernels
    keep their instruction names."""
    engine = PreStoEngine(_spec(rm), placement="presto", interpret=False)
    pages = _staged(rm, one_chip)
    (name,) = pages
    assert pages[name].dtype == jnp.uint32
    compiled = _compile(engine.preprocess_local, pages)
    (formats,), _ = compiled.input_formats
    assert set(formats) == {name}
    order = tuple(formats[name].layout.major_to_minor)
    assert order == tuple(range(len(pages[name].shape))), order
    names = set(_INSTRUCTION.findall(compiled.as_text()))
    assert set(KERNEL_OPS) <= names, set(KERNEL_OPS) - names
