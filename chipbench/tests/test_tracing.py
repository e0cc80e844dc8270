"""The trace reduction on a hand-built trace whose answers are known, and on
a small trace captured on the chip."""

import os
import types

import pytest

from chipbench import tracing
from chipbench.tracing import PAGE_BUILD, READ_SPAN, STAGE_SPAN, WINDOW_SPAN, Trace

MS = 1_000_000  # ns


def _trace():
    # window 0..100 ms; two worker threads (lines 2, 3) and the consumer (1)
    host = [
        (1, WINDOW_SPAN, 0, 100 * MS),
        (2, STAGE_SPAN, 0, 30 * MS), (2, READ_SPAN, 0, 20 * MS),
        (3, STAGE_SPAN, 10 * MS, 50 * MS), (3, READ_SPAN, 10 * MS, 40 * MS),
        (2, "PjitFunction(body)", 30 * MS, 32 * MS),
        (2, STAGE_SPAN, 60 * MS, 90 * MS), (2, READ_SPAN, 60 * MS, 80 * MS),
    ]
    ops = [
        ("fused_dense_pallas.1", 32 * MS, 40 * MS),
        ("fused_gen_pallas.1", 40 * MS, 50 * MS),
        ("copy.13", 45 * MS, 52 * MS),  # overlaps: busy counts it once
        ("fused_dense_pallas.1", 95 * MS, 110 * MS),  # clipped at the window
    ]
    modules = [("jit_body(1)", 32 * MS, 52 * MS), ("jit_body(2)", 95 * MS, 110 * MS)]
    return Trace(ops=ops, modules=modules, host=host, n_devices=1)


def test_names():
    assert tracing.op_name("%fused_gen_pallas.1 = s32[13,2048,4] custom-call(...)") == \
        "fused_gen_pallas.1"
    assert tracing.base_name("fused_gen_pallas.1") == "fused_gen_pallas"
    assert tracing.base_name("copy") == "copy"


def test_device_time():
    t = _trace()
    assert t.window_s() == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.025)  # 32..52 and 95..100
    assert t.kernel_s("fused_dense_pallas") == pytest.approx(0.013)
    assert t.kernel_s("fused_gen_pallas") == pytest.approx(0.010)
    assert t.program_s() == pytest.approx(0.025)


def test_host_spans():
    t = _trace()
    assert [e - s for _, s, e in t.spans(READ_SPAN)] == [20 * MS, 30 * MS, 20 * MS]
    assert [b[3] for b in t.page_builds()] == [10 * MS, 10 * MS, 10 * MS]


def test_breakdown():
    b = _trace().breakdown()
    assert b["device_ops"][0] == ["fused_dense_pallas.1", pytest.approx(0.013)]
    gaps = b["idle_gaps"]
    # idle: 0..32 (reads), 52..95 (reads, then page build), none after 100
    assert [g[1] for g in gaps] == [pytest.approx(0.043), pytest.approx(0.032)]
    assert gaps[0][0] == READ_SPAN
    assert gaps[1][0] == READ_SPAN
    lone = Trace(ops=[], modules=[], host=[(1, WINDOW_SPAN, 0, MS)], n_devices=1)
    assert lone.breakdown()["idle_gaps"] == [["no_host_span", pytest.approx(0.001)]]


def test_page_build_labels_a_gap():
    host = [(1, WINDOW_SPAN, 0, 10 * MS), (2, STAGE_SPAN, 0, 10 * MS),
            (2, READ_SPAN, 0, 2 * MS)]
    t = Trace(ops=[], modules=[], host=host, n_devices=1)
    assert t.breakdown()["idle_gaps"][0][0] == PAGE_BUILD


def test_no_window_reads_nothing():
    t = Trace(ops=[("copy", 0, MS)], modules=[], host=[], n_devices=1)
    assert t.window() is None and t.busy_s() == 0 and t.window_s() == 0
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}


# A trace captured on one TPU v5e: a traced rm1-k1 session of 6 partitions.
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "rm1_k1_six_partitions.xplane.pb")


def test_chip_trace():
    t = tracing.load(FIXTURE)
    assert t.n_devices == 1
    assert t.window_s() == pytest.approx(0.042479461)
    assert len(t.spans(READ_SPAN)) == 6 and len(t.page_builds()) == 6
    assert len(t.modules) == 6  # one K=1 launch per partition
    assert t.program_s() == pytest.approx(0.005253656)
    assert t.busy_s() == pytest.approx(0.005251715)
    assert t.kernel_s("fused_gen_pallas") == pytest.approx(0.00377828)
    assert t.kernel_s("fused_sparse_pallas") == pytest.approx(0.000561251)
    assert t.kernel_s("fused_dense_pallas") == pytest.approx(0.000387062)
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fused_gen_pallas.1"
    assert b["idle_gaps"][0] == [READ_SPAN, pytest.approx(0.014005644)]


def test_chip_trace_metrics():
    from chipbench.datagen import Shape
    from chipbench.harness import load_reader

    ctx = types.SimpleNamespace(
        trace=tracing.load(FIXTURE),
        shape=Shape(13, 26, 1, 1, 13, 1024, 1 << 24, 500000, 8192),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        partitions=6, compiles=0)
    got = {name: load_reader(name)(ctx) for name in (
        "read_ms", "page_build_ms", "device_idle_share", "produce_roofline",
        "fused_dense_roofline", "fused_sparse_roofline", "fused_gen_roofline")}
    assert got["device_idle_share"] == pytest.approx(100 * (1 - 0.005251715 / 0.042479461))
    assert 6 < got["read_ms"] < 9 and 1 < got["page_build_ms"] < 4
    for name in ("produce_roofline", "fused_dense_roofline", "fused_sparse_roofline",
                 "fused_gen_roofline"):
        assert 0 < got[name] < 100, (name, got[name])
