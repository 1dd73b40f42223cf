"""``peak_bytes_in_use`` over ``bytes_limit`` of the fullest chip, in per
cent."""


def read(spec, run):
    m = run.memory or {}
    if not m.get("peak_bytes_in_use") or not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
