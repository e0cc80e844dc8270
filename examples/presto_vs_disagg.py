"""PreSto vs Disagg vs Hybrid, side by side — the paper's core comparison
plus the per-family placement the operator-graph IR unlocks.

1. Kernel level (this host): fused ISP path vs multi-pass CPU-style path.
2. System level (16 simulated devices): the compiled collective footprint —
   storage-centric placement moves ZERO bytes between Extract and Load;
   disaggregated placement pays raw-pages-in + tensors-out permutes for
   every column family; hybrid pays them only for the families the cost
   model sends to hosts.

    PYTHONPATH=src python examples/presto_vs_disagg.py
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.core import TransformSpec, pages_from_partition, preprocess_pages
from repro.data.synth import RM_CONFIGS, SyntheticRecSysSource


def kernel_level() -> None:
    import time
    print("=== kernel level (RM5 geometry, 1024 rows) ===")
    src = SyntheticRecSysSource(RM_CONFIGS["rm5"], rows=1024)
    spec = TransformSpec.from_source(src)
    pages = {k: jnp.asarray(v)
             for k, v in pages_from_partition(src.partition(0), spec).items()}
    fused = jax.jit(lambda p: preprocess_pages(p, spec, mode="fused"))
    unfused = jax.jit(lambda p: preprocess_pages(p, spec, mode="unfused"))
    for fn in (fused, unfused):
        jax.block_until_ready(fn(pages))
    def t(fn):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(pages))
            best = min(best, time.perf_counter() - t0)
        return best
    tf, tu = t(fused), t(unfused)
    print(f"unfused (Disagg-style multi-pass): {tu*1e3:.1f} ms/partition")
    print(f"fused   (PreSto ISP pipeline):     {tf*1e3:.1f} ms/partition "
          f"-> {tu/tf:.2f}x")


_SH = """
import jax, jax.numpy as jnp
from repro.core import TransformSpec, PreStoEngine, pages_from_partition
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_mesh
cfg = RMDataConfig("x", 16, 8, 4, 8, 4, 64, 1 << 20, 100000, rows_per_partition=2048)
src = SyntheticRecSysSource(cfg, rows=2048)
spec = TransformSpec.from_source(src)
mesh = make_mesh((8, 2), ("data", "model"))
pages = {k: jnp.asarray(v) for k, v in pages_from_partition(src.partition(0), spec).items()}
for placement in ("presto", "hybrid", "disagg"):
    eng = PreStoEngine(spec, mesh, placement=placement)
    c = analyze(jax.jit(eng.preprocess_global).lower(pages).compile().as_text())
    host = ",".join(eng.host_families()) or "-"
    print(f"{placement:7s}: collective bytes = {c.coll_bytes/1e3:.1f} KB "
          f"(permute={c.coll_breakdown['collective-permute']/1e3:.1f} KB, "
          f"host families: {host})")
"""


def system_level() -> None:
    print("=== system level (16-device mesh, compiled HLO) ===")
    env = dict(os.environ)
    # a CPU mesh by design; on a TPU host this process may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SH], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    print(out.stdout.strip())
    print("(presto=0: preprocessing collocated with the consuming shard — "
          "the paper's in-storage placement, Fig. 8; hybrid moves only its "
          "host-placed families' bytes)")


if __name__ == "__main__":
    kernel_level()
    system_level()
