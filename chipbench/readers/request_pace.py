"""Where a served request's pace went, from the ``req.decode`` events
the engine leaves in its ring (``mxnet_tpu.serving.generation``): one a
request, its first token to its last, with ``tokens`` after the first,
``device_us`` (the chunks it was live in, staging to fetch) and
``stall_us`` (the other requests' prefills in between); the rest of its
duration is the scheduler's host turn.

``part`` picks ``stall`` or ``host``. The number is 100 x sum_i(part_i /
n_i) / sum_i(dur_i / n_i) over the traced window's requests
(``program_spans.window_events``): the share that part makes of their
mean pace, the mean that ``tpot_ms_mean`` takes over the stamps these
durations are made of.

A ring without such events (a program that does not split the pace, an
untraced run, a training cell) reads nothing.
"""

from .program_spans import window_events


def read(spec, run):
    part = spec["part"]
    num = den = 0.0
    for e in window_events(run, "request"):
        args = e.get("args") or {}
        n = args.get("tokens")
        if e.get("name") != "req.decode" or not n or "stall_us" not in args:
            continue
        stall = args["stall_us"]
        num += (stall if part == "stall"
                else e["dur"] - args["device_us"] - stall) / n
        den += e["dur"] / n
    return 100.0 * num / den if den > 0 else None
