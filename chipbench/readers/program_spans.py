"""The program's own spans (``mxnet_tpu.observability.span``), read from
its ring after the traced window. A span is live only while a profiler
session is open, and ``run.py`` opens one for exactly the traced part of
the window; the ring of a process that traced before (the tests) holds
older spans too, so the events are clipped by their ``ts`` to the traced
part: the trace's window length back from the ring's newest end.

``cat`` picks the spans' category; then one of

- ``self_share``: ``[names]``. 100 x the summed self time of the spans so
  named (a span's duration less what its child spans cover) over the
  time from the first start to the last end of the category's spans on
  the threads that ran them.
- ``mean_self_ms``: a name. The mean self time of the spans so named,
  in ms.
- ``max_arg_share``: ``{"name": .., "arg": .., "base": <counter>}``. 100 x
  the largest value the spans so named carry under ``arg`` over a
  counter of the run.

A program without such spans (or without the ring) reads nothing.
"""


def window_events(run, cat=None):
    """The ring's events that began in the traced part of ``run``'s
    window, oldest first; with ``cat``, that category's only."""
    try:
        from mxnet_tpu import observability as obs

        events = obs.tracer().events()
    except Exception:  # the program has no ring: nothing to read
        return []
    trace = getattr(run, "trace", None)
    if events and trace is not None and trace.window_s > 0:
        # the ring's clock is the trace's up to a constant the trace
        # does not hold; the traced part ends with the ring's newest span
        since = max(e["ts"] + e["dur"] for e in events) - trace.window_s * 1e6
        events = [e for e in events if e["ts"] >= since]
    return [e for e in events if cat is None or e.get("cat") == cat]


def covered(intervals, lo, hi):
    """Length of ``[lo, hi]`` that ``[(start, end)]`` cover."""
    total, at = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total


def self_times(events):
    """{id: the event's duration less what its children cover}, in the
    events' own unit (microseconds)."""
    children = {}
    for e in events:
        parent = (e.get("args") or {}).get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(
                (e["ts"], e["ts"] + e["dur"]))
    return {e["id"]: e["dur"] - covered(children.get(e["id"], ()),
                                        e["ts"], e["ts"] + e["dur"])
            for e in events}


def read(spec, run):
    events = [e for e in window_events(run, spec["cat"])
              if e.get("ph") == "X"]
    if not events:
        return None
    if "max_arg_share" in spec:
        m = spec["max_arg_share"]
        base = run.counters.get(m["base"])
        vals = [e["args"][m["arg"]] for e in events
                if e["name"] == m["name"] and m["arg"] in (e.get("args") or {})]
        if not vals or not base:
            return None
        return 100.0 * max(vals) / base
    own = self_times(events)
    if "mean_self_ms" in spec:
        mine = [own[e["id"]] for e in events
                if e["name"] == spec["mean_self_ms"]]
        return sum(mine) / len(mine) / 1e3 if mine else None
    names = set(spec["self_share"])
    threads = {e["tid"] for e in events if e["name"] in names}
    spent = sum(own[e["id"]] for e in events if e["name"] in names)
    extent = 0.0
    for tid in threads:
        mine = [e for e in events if e["tid"] == tid]
        extent += (max(e["ts"] + e["dur"] for e in mine)
                   - min(e["ts"] for e in mine))
    if extent <= 0:
        return None
    return 100.0 * spent / extent
