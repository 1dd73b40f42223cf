"""Scripts the builder of a benchmark PR runs by hand on the chip: the
readings the limits are set from, a look into a trace, the recorded
trace the tests read. The benchmark's command runs none of them."""


import json


def apply_sets(workload, items):
    """``["a.b=<json>", ...]`` onto a cell's parameters, in place."""
    for item in items:
        key, value = item.split("=", 1)
        at = workload
        *path, last = key.split(".")
        for k in path:
            at = at.setdefault(k, {})
        at[last] = json.loads(value)
    return workload
