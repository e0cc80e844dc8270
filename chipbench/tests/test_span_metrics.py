"""The readers of the program's own spans on a hand-built trace whose answers
are known: two pool-worker lines and a consumer line, spans that straddle
the window's edges, and idle spans."""

import os
import types

import pytest

from chipbench import tracing
from chipbench.harness import load_reader
from chipbench.tracing import READ_SPAN, WINDOW_SPAN, Trace

MS = 1_000_000  # ns

READERS = ("worker_busy_share", "read_io_ms", "read_verify_ms", "read_decode_ms",
           "build_ms", "put_ms", "dispatch_ms", "finish_wait_ms")


def _worker(line, t, phases):
    """Consecutive spans on `line` from `t` ms: (name, ms) pairs."""
    out = []
    for name, ms in phases:
        out.append((line, name, int(t * MS), int((t + ms) * MS)))
        t += ms
    return out


def _trace():
    # window 0..100 ms on the consumer's line 1; workers on lines 2 and 3
    host = [(1, WINDOW_SPAN, 0, 100 * MS), (1, "presto.consumer_wait", 0, 30 * MS)]
    host += [(2, "presto.claim", -5 * MS, -1 * MS),
             (2, "presto.idle", -10 * MS, 5 * MS),  # 5 ms inside
             (2, "presto.idle", 90 * MS, 110 * MS),  # 10 ms inside
             (2, READ_SPAN, 6 * MS, 13 * MS)]
    host += _worker(2, 5, [
        ("presto.claim", 1), ("presto.read.io", 4), ("presto.read.verify", 2),
        ("presto.read.decode", 1), ("presto.page_build", 3), ("presto.stack", 0.5),
        ("presto.put", 0.5), ("presto.dispatch", 1), ("presto.finish", 10),
        ("presto.deliver", 1)])
    host += _worker(3, 1, [
        ("presto.claim", 1), ("presto.read.io", 6), ("presto.read.verify", 1),
        ("presto.read.decode", 2), ("presto.page_build", 2), ("presto.stack", 0.5),
        ("presto.put", 1), ("presto.dispatch", 2), ("presto.finish", 3.5),
        ("presto.deliver", 0.5)])
    host += [(3, "presto.idle", 50 * MS, 70 * MS),
             (3, "presto.read.io", 95 * MS, 105 * MS),  # starts inside: all 10 ms
             (3, "presto.read.io", -20 * MS, -10 * MS)]  # starts before: left out
    return Trace(ops=[], modules=[], host=host, n_devices=1)


def _ctx(trace, partitions=2):
    return types.SimpleNamespace(trace=trace, shape=None, peaks=None,
                                 partitions=partitions, compiles=0)


def test_readers_on_known_trace():
    ctx = _ctx(_trace())
    got = {name: load_reader(name)(ctx) for name in READERS}
    assert got == {
        # line 2 idles 15 of 100 ms, line 3 20 of 100; the consumer is no worker
        "worker_busy_share": pytest.approx((85 + 80) / 2),
        "read_io_ms": pytest.approx((4 + 6 + 10) / 2),
        "read_verify_ms": pytest.approx((2 + 1) / 2),
        "read_decode_ms": pytest.approx((1 + 2) / 2),
        "build_ms": pytest.approx((3 + 0.5 + 2 + 0.5) / 2),
        "put_ms": pytest.approx((0.5 + 1) / 2),
        "dispatch_ms": pytest.approx((1 + 2) / 2),
        "finish_wait_ms": pytest.approx((10 + 3.5) / 2),
    }


def test_worker_never_idle_is_fully_busy():
    host = [(1, WINDOW_SPAN, 0, 10 * MS), (2, "presto.claim", 0, MS)]
    t = Trace(ops=[], modules=[], host=host, n_devices=1)
    assert load_reader("worker_busy_share")(_ctx(t)) == pytest.approx(100.0)


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "rm1_k1_six_partitions.xplane.pb")


@pytest.mark.parametrize("name", READERS)
def test_absent_spans_read_none(name):
    # a trace of a program without the spans (captured on the chip), one
    # with the spans but no window, and one with neither
    chip = tracing.load(FIXTURE)
    no_window = Trace(ops=[], modules=[],
                      host=[h for h in _trace().host if h[1] != WINDOW_SPAN],
                      n_devices=1)
    empty = Trace(ops=[], modules=[], host=[(1, WINDOW_SPAN, 0, MS)], n_devices=1)
    for t, n in ((chip, 6), (no_window, 2), (empty, 2)):
        assert load_reader(name)(_ctx(t, n)) is None
