"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Run:
    PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


BENCHES = [
    ("latency_breakdown", "Fig 5/12: per-stage ETL latency, Disagg vs PreSto"),
    ("throughput", "Fig 11: preprocessing throughput PreSto vs Disagg(N)"),
    ("scaling", "Fig 3: throughput + consumer utilization vs #workers"),
    ("provisioning", "Fig 4/14: workers to saturate an 8-GPU node (T/P)"),
    ("comm", "Fig 13: collective bytes, presto=0 vs disagg"),
    ("tco", "Fig 15: cost- and energy-efficiency"),
    ("alt", "Fig 16: A100/U280/SmartSSD/v5e alternatives"),
    ("sensitivity", "Fig 17: latency vs #features"),
    ("resources", "Table II: per-kernel VMEM footprint"),
    ("roofline", "SRoofline: dry-run roofline table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.common.util import enable_compile_cache

    enable_compile_cache()
    failures = []
    for name, desc in BENCHES:
        if args.only and name != args.only:
            continue
        print(f"# --- {name}: {desc}", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            mod.run()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
