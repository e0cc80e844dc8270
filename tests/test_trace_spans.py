"""The served produce path's host spans and pipeline counters.

A small engine-backed session is served by a pool of two workers under
``jax.profiler.trace`` and the captured ``.xplane.pb`` is read back: every
span of ``repro.common.trace`` appears, the storage-read spans number the
partitions, every page build stages the stored body it read (``stored=1``),
the dispatch spans number the session's ``launches``, the spans are leaves
(none overlaps another on its thread), and the idle and consumer-wait spans
sit on the threads they belong to.
"""

import collections
import glob
import time

import jax
import pytest

from repro.common import trace
from repro.configs.registry import get_recsys
from repro.core.costmodel import ContentionAwareCostModel
from repro.core.presto import PreStoEngine
from repro.core.service import JobSpec, PreprocessingService
from repro.core.spec import TransformSpec
from repro.data.storage import PartitionedStore
from repro.data.synth import SyntheticRecSysSource

PARTITIONS = 8
DEVICES = 2


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An engine and a store of PARTITIONS files on disk, owned round-robin
    by DEVICES devices, so that every read goes through ``read_partition``."""
    src = SyntheticRecSysSource(get_recsys("rm1", reduced=True).data, rows=256)
    store = PartitionedStore(
        PARTITIONS, num_devices=DEVICES, source=src,
        root=str(tmp_path_factory.mktemp("store")),
    )
    store.materialize(range(PARTITIONS))
    engine = PreStoEngine(TransformSpec.from_source(src))
    engine.produce_batch(store, 0)  # compile the K=1 program outside the traces
    return engine, store


def _host_spans(trace_dir) -> dict:
    """line -> [(name, start_ns, end_ns, stats)] of the ``presto.*`` spans."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    lines = collections.defaultdict(list)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("presto."):
                    lines[i].append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return lines


@pytest.mark.parametrize("k", [1, 2])
def test_served_spans(served, tmp_path, k):
    engine, store = served
    # each worker is bound to one device and never falls back to the other's
    # partitions, so every chunk coalesces exactly k of its own claims
    model = ContentionAwareCostModel(queue_threshold=10**9)
    with jax.profiler.trace(str(tmp_path)):
        with PreprocessingService(2, devices=DEVICES, cost_model=model) as svc:
            sess = svc.submit(JobSpec(
                name="traced", partitions=range(PARTITIONS), engine=engine,
                store=store, megabatch=k, queue_depth=PARTITIONS, use_cache=False,
            ))
            got = []
            for pid, batch in sess:
                jax.block_until_ready(batch)
                got.append(pid)
            stats = sess.stats()
            time.sleep(0.1)  # nothing left to claim: both workers idle
    assert sorted(got) == list(range(PARTITIONS))
    assert stats.launches == PARTITIONS // k and stats.produced == PARTITIONS

    lines = _host_spans(tmp_path)
    spans = [s for line in lines.values() for s in line]
    names = collections.Counter(n for n, *_ in spans)
    assert set(trace.SPANS) <= set(names), set(trace.SPANS) - set(names)
    for name in (trace.READ_IO, trace.READ_VERIFY, trace.READ_DECODE,
                 trace.PAGE_BUILD):
        pids = sorted(st["pid"] for n, _s, _e, st in spans if n == name)
        assert pids == list(range(PARTITIONS)), name
    # every partition is read from its file and staged as the body it was read as
    assert [st["stored"] for n, _s, _e, st in spans if n == trace.PAGE_BUILD] == [
        1] * PARTITIONS
    dispatches = [st for n, _s, _e, st in spans if n == trace.DISPATCH]
    assert len(dispatches) == stats.launches
    assert all(st["k"] == k for st in dispatches)
    assert len({st["pid"] for st in dispatches}) == stats.launches

    for line in lines.values():  # leaves: no two spans overlap on a thread
        line.sort(key=lambda s: s[1])
        for (n0, _s0, e0, _), (n1, s1, _e1, _) in zip(line, line[1:]):
            assert s1 >= e0, (n0, n1)
    workers = {i for i, line in lines.items() if any(n == trace.CLAIM for n, *_ in line)}
    idle = {i for i, line in lines.items() if any(n == trace.IDLE for n, *_ in line)}
    waits = {i for i, line in lines.items()
             if any(n == trace.CONSUMER_WAIT for n, *_ in line)}
    assert len(workers) == 2 and idle <= workers
    assert len(waits) == 1 and not waits & workers


def test_backpressure_counted(served):
    engine, store = served
    with PreprocessingService(2) as svc:
        sess = svc.submit(JobSpec(
            name="paced", partitions=range(4), engine=engine, store=store,
            queue_depth=1, use_cache=False,
        ))
        for _pid, batch in sess:
            jax.block_until_ready(batch)
            time.sleep(0.05)  # a slow trainer step
        stats = sess.stats()
    assert stats.delivered == 4 and stats.launches == 4
    assert stats.backpressured > 0
