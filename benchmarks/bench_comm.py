"""Fig. 13 — inter-node data movement: PreSto eliminates preprocessing
collectives.

Compiles the sharded preprocessing program in all three placements on a
16-device mesh (subprocess) and reports HLO collective bytes: presto must be
ZERO, disagg pays raw-pages-in + train-tensors-out collective-permutes for
every column family, and the cost-model hybrid pays them only for its
host-placed families.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_SCRIPT = """
import json, jax, jax.numpy as jnp
from repro.core.spec import TransformSpec
from repro.core.presto import PreStoEngine
from repro.core.preprocess import pages_from_partition
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_mesh
cfg = RMDataConfig("b", 16, 8, 4, 8, 4, 64, 1 << 20, 100000, rows_per_partition=2048)
src = SyntheticRecSysSource(cfg, rows=2048)
spec = TransformSpec.from_source(src)
mesh = make_mesh((8, 2), ("data", "model"))
pages = {k: jnp.asarray(v) for k, v in pages_from_partition(src.partition(0), spec).items()}
out = {}
for placement in ("presto", "hybrid", "disagg"):
    eng = PreStoEngine(spec, mesh, placement=placement)
    txt = jax.jit(eng.preprocess_global).lower(pages).compile().as_text()
    c = analyze(txt)
    out[placement] = {"coll_bytes": c.coll_bytes, "breakdown": c.coll_breakdown,
                      "host_families": list(eng.host_families())}
print("RESULT" + json.dumps(out))
"""


def run() -> dict:
    env = dict(os.environ)
    # a CPU mesh by design; on a TPU host the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    presto = out["presto"]["coll_bytes"]
    disagg = out["disagg"]["coll_bytes"]
    hybrid = out["hybrid"]["coll_bytes"]
    emit("comm/presto_coll_bytes", 0.0, f"bytes={presto:.0f}")
    emit("comm/disagg_coll_bytes", 0.0,
         f"bytes={disagg:.0f} eliminated_by_presto=100%"
         if presto == 0 else f"bytes={disagg:.0f}")
    host_fams = ",".join(out["hybrid"]["host_families"]) or "-"
    emit("comm/hybrid_coll_bytes", 0.0,
         f"bytes={hybrid:.0f} host_families={host_fams}")
    return out


if __name__ == "__main__":
    run()
