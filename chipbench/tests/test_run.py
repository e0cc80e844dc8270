"""The command refuses to run without a TPU, and BENCHMARK.json's cells,
configurations, traffic mixes and per-layer metrics all resolve to files."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", "rm1-k1",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_resolves():
    from chipbench import harness

    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
        cell = harness.load_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"]
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        for key in c["reduced"]:
            assert key in config


def test_every_metric_has_a_reader():
    from chipbench import harness

    for m in _bench()["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_unknown_workload():
    from chipbench import harness

    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
