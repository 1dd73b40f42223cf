"""Device time inside the programs whose names match ``match`` (a regular
expression) over the device's busy time, in per cent."""

import re


def read(spec, run):
    t = run.trace
    if t is None or not t.devices:
        return None
    inside = sum(t.module_seconds(re.compile(spec["match"])).values())
    busy = t.busy_s()
    if busy <= 0 or inside <= 0:
        return None
    return 100.0 * inside / busy
