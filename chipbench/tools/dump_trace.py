#!/usr/bin/env python3
"""A look into a profiler trace: planes, lines, how many events each
holds and the names that take most time. ``python
chipbench/tools/dump_trace.py <trace dir or .xplane.pb> [top]``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv):
    from jax.profiler import ProfileData

    from chipbench.harness import trace as TR

    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 25
    if os.path.isdir(path):
        path = TR.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            spent, calls = {}, {}
            for e in events:
                b = TR.base_name(e.name)
                spent[b] = spent.get(b, 0) + e.duration_ns
                calls[b] = calls.get(b, 0) + 1
            for b in sorted(spent, key=spent.get, reverse=True)[:top]:
                print(f"    {spent[b] / 1e6:12.3f} ms {calls[b]:8d} x {b}")
            for e in events[:2]:
                stats = [(k, str(v)[:80]) for k, v in e.stats]
                print(f"    e.g. {e.name!r} {stats[:12]}")
    t = TR.Trace(path)
    print("window_s", t.window_s, "busy_s", t.busy_s(), "devices",
          [d.index for d in t.devices])
    print("top_ops", t.top_ops(10))
    print("idle_gaps", t.idle_gaps(10))
    print("module_runs", t.module_runs(), "launches", t.module_launches())


if __name__ == "__main__":
    main(sys.argv)
