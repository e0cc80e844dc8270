#!/usr/bin/env python3
"""The control of the check that decides ``correct``.

The reference (``oracle.py``) computed one precision step below the float32
that the configurations state, in bfloat16, and put in the program's place:
compared with the float32 reference over every stored partition of a cell,
for each seed given, it must fail the limits.  Its smallest readings are the
upper ends from which ``oracle.LIMITS`` was set (PERF.md).

    python chipbench/control.py --workload rm5-k4 --seeds 11,12,13

Prints one JSON line per seed with the numbers compared.  The benchmark's
own runs never run it.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_readings(shape, seed: int, pids) -> dict:
    """The bfloat16 reference against the float32 one, worst over `pids`."""
    import ml_dtypes

    from chipbench import oracle
    from chipbench.datagen import Generator

    gen = Generator(shape, seed)
    return oracle.merge([
        oracle.compare(oracle.reference_batch(gen, p, dtype=ml_dtypes.bfloat16),
                       oracle.reference_batch(gen, p))
        for p in pids
    ])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import oracle
    from chipbench.datagen import Shape

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    w = {c["name"]: c for c in bench["workloads"]}[args.workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    shape = Shape.of(config, traffic)
    pids = range(int(config["stored_partitions"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        r = control_readings(shape, seed, pids)
        fails = [k for k, lim in oracle.LIMITS.items() if r[k] > lim]
        print(json.dumps({"workload": args.workload, "seed": seed, "control": r,
                          "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
