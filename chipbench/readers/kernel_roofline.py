"""A kernel's share of its roofline: the least time the chip could take
for the calls' operations and bytes over the kernel's device time in
the trace, in per cent.

``match`` finds the kernel's operations in the trace. ``calls`` names a
function of the family's glue that gives ``[(kind, flops, bytes)]`` for
the calls of one unit of work. The units done in the traced window are
the runs of the program that took most device time there (or of those
``per_match`` finds), times the counter ``times`` where one run makes
several units (a decode chunk's steps). The least time is taken call by
call, since each call has its own bound.
"""

import re

from ..harness import flops as F


def read(spec, run):
    t = run.trace
    fn = getattr(run.model, spec["calls"], None)
    if t is None or not t.devices or fn is None:
        return None
    spent = sum(t.op_seconds(re.compile(spec["match"])).values())
    per = re.compile(spec["per_match"]) if spec.get("per_match") else None
    units = t.module_runs(per) * run.counters.get(spec.get("times"), 1)
    if spent <= 0 or not units:
        return None
    itemsize = 2 if run.workload.get("dtype", "bfloat16") in (
        "bfloat16", "float16") else 4
    calls = fn(run.cfg, run.workload.get("shapes") or {}, itemsize,
               run.counters)
    if not calls:
        return None
    least = sum(F.roofline_seconds(fl, by, run.peaks)[0]
                for _, fl, by in calls) * units
    # op_seconds is a chip's average; each chip does its share of a unit
    return 100.0 * least / run.chips / spent
