"""The whole step's share of the chips' peak, in per cent: operations a
second that the work needs (from shapes, by the family's glue), over
chips times the peak.

``{"reader": "mfu", "flops": "train_flops_per_sample", "per_run":
"batch"}``: operations a unit, times the units one run of the program
makes (a counter), times the runs a second of the program that took
most device time in the traced window. ``{"reader": "mfu",
"flops_rate": "model_flops_per_s"}`` takes a counter that the loop
summed request by request over its window.
"""


def read(spec, run):
    if "flops_rate" in spec:
        rate = run.counters.get(spec["flops_rate"])
        if not rate:
            return None
    else:
        t = run.trace
        fn = getattr(run.model, spec["flops"], None)
        per_run = run.counters.get(spec["per_run"])
        if t is None or not t.devices or t.window_s <= 0 or fn is None \
                or not per_run:
            return None
        runs = t.module_runs()
        if not runs:
            return None
        rate = (fn(run.cfg, run.workload.get("shapes") or {}) * per_run
                * runs / t.window_s)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
