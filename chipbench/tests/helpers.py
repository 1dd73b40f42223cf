"""Drives ``run.drive`` on the CPU at a tiny size, past the command's
look for a chip."""

import argparse
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny(name):
    with open(os.path.join(HERE, "tiny", name + ".json")) as f:
        return json.load(f)


def drive_tiny(cfg_name, cell_name, *, seed=3, seconds=0.5, trace=0,
               chips=1, workload_edit=None):
    """Runs one tiny cell; returns the result object."""
    import jax

    from chipbench import run as R

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = tiny(cell_name)
    if workload_edit:
        workload_edit(workload)
    entry = {"name": "tiny.cell", "chips": chips}
    # the tiny cell reports whatever metric has no list of cells, plus
    # those of the real cell of its kind
    like = {"spmd_step": "bert_base.spmd_seq128",
            "generation_server": "gpt2_small.serve_chat"}.get(workload["kind"])
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + ["tiny.cell"]
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return R.drive(args, entry, bench, workload, tiny(cfg_name),
                   jax.devices()[:chips], PEAKS, time.perf_counter())
