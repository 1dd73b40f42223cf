"""``BENCHMARK.json`` against the files it names, and the command's
refusal to run on anything but a TPU."""

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file_and_every_cell_its_metrics():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for c in b["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            BENCH_DIR, "references", cfg["family"] + ".py"))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        with open(os.path.join(BENCH_DIR, "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "loops", cell["kind"] + ".py"))
        mine = [m for m in b["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["per_layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= cells
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "readers", spec["reader"] + ".py"))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    layers = {m["layer"] for m in b["per_layer"]}
    assert all(len(name) <= 200 and "\n" not in name for name in layers)


def test_the_runner_names_no_cell_configuration_or_metric():
    b = _bench()
    with open(os.path.join(BENCH_DIR, "run.py")) as f:
        code = f.read().split('"""', 2)[2]  # past the module's docstring
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]
                if m["name"] != "setup_s"])  # the harness measures set-up
    assert not [n for n in names if n in code]


def test_the_command_refuses_anything_but_a_tpu():
    b = _bench()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable] + b["command"][1:] + [
            "--workload", b["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
