"""End-to-end test of the dry-run driver itself: lowers + compiles a
REDUCED config on the real production meshes (256/512 fake devices) via the
CLI, and checks the emitted record has memory + roofline terms."""

import json
import os
import subprocess
import sys
import tempfile

import pytest


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_cli_reduced(mesh):
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "dryrun.jsonl")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices; never the chip
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "h2o-danube-1.8b", "--shape", "train_4k",
             "--mesh", mesh, "--reduced", "--out", out],
            capture_output=True, text=True, env=env, timeout=420,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        rec = json.loads(open(out).read().splitlines()[0])
        assert rec["status"] == "ok"
        assert rec["chips"] == (512 if mesh == "multi" else 256)
        assert rec["memory"]["temp_bytes"] >= 0
        t = rec["roofline"]
        assert t["flops_per_dev"] > 0 and t["dominant"] in (
            "compute", "memory", "collective")
