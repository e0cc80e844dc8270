"""One run of one cell: set-up, the served window, the reference check.

Imported by ``run.py`` once it has found the chip; tests drive ``run_cell``
directly on the CPU at a small size.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import threading
import time
import types

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import oracle, store, tracing
from chipbench.datagen import Generator, Shape
from repro.core.presto import PreStoEngine
from repro.core.service import JobSpec, PreprocessingService
from repro.core.spec import TransformSpec
from repro.data.storage import PartitionedStore

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".traces")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, so
    that only a cell's first run in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Programs compiled or read from the persistent cache, as JAX reports
    them (its backend-compile event spans both)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.count += 1


class StoreView:
    """The program's store of M files seen as an endless dataset: logical
    partition p is stored file p mod M.  Its reads are the benchmark's
    storage-read spans."""

    source = None  # no generator behind it: a read never makes data

    def __init__(self, root: str, m: int):
        self._store = PartitionedStore(m, num_devices=1, root=root)
        self._m = m

    def read(self, pid: int):
        with TraceAnnotation(tracing.READ_SPAN):
            return self._store.read(pid % self._m)


class SpannedEngine(PreStoEngine):
    """The user's engine with a host span around its read plus page build,
    for the traced run only."""

    def stage_partition(self, store, pid):
        with TraceAnnotation(tracing.STAGE_SPAN):
            return super().stage_partition(store, pid)


class Reservoir:
    """A uniform sample of at most `size` deliveries, drawn from the seed."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list = []
        self._seen = 0
        self._rng = np.random.default_rng([seed, 0xC4EC])

    def offer(self, item) -> None:
        self._seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self._seen))
        if j < self.size:
            self.items[j] = item


def warm_up(engine: PreStoEngine, view: StoreView, kmax: int) -> None:
    """Compile and run once every produce program the window can launch: a
    worker coalesces 1 to `kmax` claims into one launch."""
    pages = [engine.stage_partition(view, p) for p in range(kmax)]
    for k in range(1, kmax + 1):
        if k == 1:
            out = engine.jit_preprocess_cached()(jax.device_put(pages[0]))
        else:
            stacked = {key: np.stack([p[key] for p in pages[:k]]) for key in pages[0]}
            out = engine.jit_preprocess_megabatch_cached()(jax.device_put(stacked))
        jax.block_until_ready(out)


def _next_ready(it):
    pid, batch = next(it)
    jax.block_until_ready(batch)
    return pid, batch


def serve_window(service, job, seconds: float, warm: int, sample: Reservoir,
                 setup_start: float) -> dict:
    """Closed-loop consumer over a time window.  The first `warm` deliveries
    are set-up; each later delivery is counted once it is ready on the
    device, with the time the consumer was blocked on it."""
    session = service.submit(job)
    it = iter(session)
    errors = 0
    for _ in range(warm):
        _next_ready(it)
    t_start = time.perf_counter()
    deadline = t_start + seconds
    waits, rows = [], 0
    try:
        while True:
            t0 = time.perf_counter()
            try:
                pid, batch = _next_ready(it)
            except StopIteration:
                raise RuntimeError(
                    "the session ran out of partitions inside the window; "
                    "raise the traffic's logical_partitions"
                ) from None
            t1 = time.perf_counter()
            if t1 > deadline:
                break
            waits.append(t1 - t0)
            rows += int(batch["labels"].shape[0])
            sample.offer((pid, batch))
    except Exception as exc:  # noqa: BLE001 — a delivery that never comes
        errors += 1
        print(f"chipbench: delivery failed: {exc!r}", flush=True)
    finally:
        session.cancel()
    return {
        "setup_s": t_start - setup_start,
        "deliveries": len(waits),
        "samples_per_s": rows / seconds,
        "batch_wait_p95_ms": float(np.percentile(np.asarray(waits) * 1e3, 95))
        if waits else None,
        "errors": errors,
    }


def serve_traced(service, job, sample: Reservoir, compiles: CompileCounter,
                 trace_dir: str) -> dict:
    """A finite session of the traffic's trace_partitions under the profiler:
    every launch in the trace is one of its partitions, so work counts are
    exact."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans only: tracing every call would skew them
    shutil.rmtree(trace_dir, ignore_errors=True)
    n, errors = 0, 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        c0 = compiles.count
        with TraceAnnotation(tracing.WINDOW_SPAN):
            session = service.submit(job)
            try:
                for pid, batch in session:
                    jax.block_until_ready(batch)
                    n += 1
                    sample.offer((pid, batch))
            except Exception as exc:  # noqa: BLE001 — a delivery that never comes
                errors += 1
                print(f"chipbench: delivery failed: {exc!r}", flush=True)
                session.cancel()
        compiled = compiles.count - c0
    finally:
        jax.profiler.stop_trace()
    return {"deliveries": n, "errors": errors, "compiles": compiled}


def load_reader(name: str):
    """The `read(ctx)` function of per-layer metric `name`."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check(samples: list, shape: Shape, seed: int, m: int, errors: int) -> tuple:
    """Compare each sampled delivery with the reference of its stored
    partition.  Returns (checks, failed batches)."""
    gen = Generator(shape, seed)
    wants: dict = {}
    readings = []
    failed = errors
    for pid, got in samples:
        f = pid % m
        if f not in wants:
            wants[f] = oracle.reference_batch(gen, f)
        r = oracle.compare(got, wants[f])
        readings.append(r)
        failed += not all(r[k] <= lim for k, lim in oracle.LIMITS.items())
    merged = oracle.merge(readings)
    checks = {
        "batches_compared": {"value": len(samples), "min": 1},
        "delivery_errors": {"value": errors, "max": 0},
    }
    for k, lim in oracle.LIMITS.items():
        checks[k] = {"value": merged.get(k, 0), "max": lim}
    return checks, failed


def passed(c: dict) -> bool:
    return c["value"] >= c["min"] if "min" in c else c["value"] <= c["max"]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, device,
             peaks: dict | None, setup_start: float) -> dict:
    """One run of `cell` (the entry of BENCHMARK.json with its configuration
    and traffic loaded); returns the result line as a dict."""
    enable_compile_cache()
    compiles = CompileCounter()
    name, config, traffic = cell["name"], cell["config"], cell["traffic"]
    shape = Shape.of(config, traffic)
    m = int(config["stored_partitions"])
    t0 = time.perf_counter()
    root = store.materialize(name, config, traffic, seed)
    t1 = time.perf_counter()
    spec = TransformSpec.from_source(store.BenchSource(config, traffic, seed))
    engine = (SpannedEngine if trace else PreStoEngine)(spec)
    view = StoreView(root, m)
    k = int(traffic["megabatch"])
    warm_up(engine, view, k)
    print(f"chipbench: set-up: start {t0 - setup_start:.3f} s, store {t1 - t0:.3f} s, "
          f"warm-up of K=1..{k} {time.perf_counter() - t1:.3f} s "
          f"({compiles.count} programs compiled or loaded)", flush=True)
    sample = Reservoir(int(traffic["check_sample"]), seed)
    job = dict(name=name, engine=engine, store=view, megabatch=k, use_cache=False,
               queue_depth=int(traffic["queue_depth"]))
    trace_dir = os.path.join(TRACE_DIR, name, str(seed))
    with PreprocessingService(num_workers=int(traffic["workers"])) as service:
        if trace:
            n = int(traffic["trace_partitions"])
            served = serve_traced(service, JobSpec(partitions=range(n), **job),
                                  sample, compiles, trace_dir)
        else:
            served = serve_window(
                service,
                JobSpec(partitions=range(int(traffic["logical_partitions"])), **job),
                seconds, int(traffic["warm_deliveries"]), sample, setup_start,
            )
    mem = device.memory_stats() or {}
    samples = [(pid, jax.device_get(b)) for pid, b in sample.items]
    del sample, engine, view
    checks, failed = check(samples, shape, seed, m, served["errors"])
    device_out = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": mem.get("peak_bytes_in_use"),
    }
    result = {"correct": all(passed(c) for c in checks.values()),
              "attempted": served["deliveries"], "failed": failed}
    if trace:
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        tr = tracing.load(paths[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(trace=tr, shape=shape, peaks=peaks,
                                    partitions=served["deliveries"],
                                    compiles=served["compiles"])
        metrics = {}
        for metric in cell["per_layer"]:
            value = load_reader(metric["name"])(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        device_out.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        result.update(metrics=metrics, device=device_out, breakdown=tr.breakdown())
    else:
        metrics = {}
        for metric in cell["end_to_end"]:
            metrics[metric["name"]] = {"value": served[metric["name"]],
                                       "unit": metric["unit"]}
        result.update(metrics=metrics, device=device_out)
    result["checks"] = checks
    return result


def load_cell(name: str) -> dict:
    """Workload `name` of BENCHMARK.json with its configuration, traffic and
    the metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"name": name, "chips": int(w["chips"]), "config": config,
            "traffic": traffic, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}
