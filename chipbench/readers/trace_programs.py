"""Programs launched on the first chip in the traced window for each run
of the program that takes most of the device's time there (a training
step's one big program): ``{"reader": "trace_programs"}``; with
``"per_match"``, for each run of the programs that expression finds."""

import re


def read(spec, run):
    t = run.trace
    if t is None or not t.devices:
        return None
    pattern = re.compile(spec["per_match"]) if spec.get("per_match") else None
    runs = t.module_runs(pattern)
    if not runs:
        return None
    return t.module_launches() / runs
