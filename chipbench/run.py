#!/usr/bin/env python3
"""On-chip benchmark of the PreSto produce path.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in BENCHMARK.json: a dataset
configuration (``configs/<config>.json``) served under a traffic mix
(``traffic/<traffic>.json``).  One process drives one chip through the entry
point a user calls, ``PreprocessingService.submit(JobSpec(...))``, and a
closed-loop consumer iterates the ``Session``, blocking until each delivered
batch is ready on the device.

Set-up (``setup_s``: from the start of this script to the first timed
request) enables the compile cache, writes the cell's stored partitions for
this seed (``store.py``), builds the engine and service as a user does,
compiles and runs once every produce program the window can launch, and
takes the session's first deliveries.

``--trace 0`` serves for ``--seconds`` and prints the end-to-end metrics.
``--trace 1`` serves a finite session of the traffic's ``trace_partitions``
under the profiler and prints the per-layer metrics (each read by its own
file, ``metrics/<name>.py``) and the trace's ``breakdown``.  Either way a
seeded sample of the delivered batches is compared, after the window, with
the numpy reference (``oracle.py``) of the stored partition it came from;
the numbers compared, each beside its limit, are the last lines of stderr
and the ``checks`` key of the result, the last line of stdout.

Without a TPU, with fewer chips than the cell asks for, or on a chip the
peak table (``peaks.json``) does not list, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="On-chip benchmark of the PreSto produce path")
    ap.add_argument("--workload", required=True, help="a workload of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="data seed (>= 0)")
    ap.add_argument("--seconds", type=float, required=True, help="measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chipbench: JAX found no TPU (first device: {dev.platform}); "
              "no result", file=sys.stderr)
        return 2
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(dev.device_kind)
    if peaks is None:
        print(f"chipbench: no peaks for device kind {dev.device_kind!r} in "
              "peaks.json; no result", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), dev,
                              peaks, T_START)
    for name, c in result["checks"].items():
        bound = f">= {c['min']}" if "min" in c else f"<= {c['max']}"
        print(f"check {name} {c['value']!r} {bound} "
              f"{'ok' if harness.passed(c) else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
