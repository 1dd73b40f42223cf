"""Operator micro-benchmark harness smoke (reference: benchmark/opperf)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_opperf_eager_and_graph(tmp_path):
    out = tmp_path / "opperf.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "opperf.py"),
         "--ops", "relu,dot,sample_normal", "--chain", "3",
         "--json", str(out)],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    recs = json.loads(out.read_text())
    assert {r["op"] for r in recs} == {"relu", "dot", "sample_normal"}
    assert all(r["avg_time_ms"] >= 0 for r in recs)
    # JAX_PLATFORMS must be honored
    assert all(r["backend"] == "cpu" for r in recs), recs

    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "opperf.py"),
         "--ops", "relu,sample_normal", "--mode", "graph", "--json",
         str(out)],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    recs = json.loads(out.read_text())
    # random ops are eager-only in graph mode
    assert {r["op"] for r in recs} == {"relu"}
    assert "random ops are eager-only" in res.stdout
