#!/usr/bin/env python3
"""One traced run of a cell, then the program's own spans laid against
the device: run by hand on the chip, like ``try_cell.py``.

    python chipbench/tools/host_gaps.py --workload <cell> --seed 5 \
        [--seconds 40] [--out chiprun_out/host_gaps.<cell>.json]

Drives the cell with ``--trace 1`` and keeps the profiler's files (the
ring lives in this process, so the run has to be made here), prints the
result line of ``run.py``, and then:

(a) the parity of the program's spans between the ``.xplane.pb``
    (``mx:<name>`` host events) and the ring: counts by name, and the
    spread of (trace start - ring start) in microseconds, which is one
    constant a session if both are on one clock;
(b) the first chip's idle time by the innermost ``mx:`` span open on any
    host thread (a gap is split over the spans it lasts through), longest
    first, beside the ``cb:`` attribution of the result line; and what
    the call-and-wait spans hold before the device's first operation and
    after its last;
(c) ``req.queue``, ``req.prefill`` and ``req.decode`` as mean and p95
    over the requests that finished in the traced window, and the
    scheduler thread's time by span (self time).

Needs a TPU and a program that has the spans; without them the tables
are empty.
"""

import argparse
import bisect
import json
import os
import statistics
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PREFIX = "mx:"
NO_SPAN = "no mx: span open"


def host_spans(path):
    """{thread line: [(name, start_ns, end_ns)]} of the ``mx:`` host
    events, names without the prefix."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [(e.name[len(PREFIX):], e.start_ns,
                      e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith(PREFIX)]
            if spans:
                out[f"{plane.name}/{line.name}#{i}"] = spans
    return out


def innermost_segments(spans):
    """A thread's nested ``[(name, start, end)]`` flattened to
    ``[(start, end, name)]``: at each moment the innermost open span."""
    out, stack, cursor = [], [], 0

    def close():
        nonlocal cursor
        name, s, e = stack.pop()
        if e > max(cursor, s):
            out.append((max(cursor, s), e, name))
        cursor = max(cursor, e)

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack and s > max(cursor, stack[-1][1]):
            out.append((max(cursor, stack[-1][1]), s, stack[-1][0]))
        stack.append((name, s, e))
        cursor = s
    while stack:
        close()
    return out


def device_gaps(trace):
    """The first chip's idle intervals inside the traced window."""
    from chipbench.harness import trace as TR

    lo, hi = trace.window
    _, merged = TR.union_length([(s, e) for _, s, e in trace.devices[0].ops])
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def idle_by_span(gaps, by_thread):
    """[(span, seconds)] of the gaps, each split over the innermost
    ``mx:`` spans open while it lasted; where two threads have one open
    the latest to start wins."""
    threads = []
    for spans in by_thread.values():
        segs = innermost_segments(spans)
        threads.append((segs, [s for s, _, _ in segs]))
    spent = {}
    for lo, hi in gaps:
        # the segments that touch the gap, and the points they cut it at
        touching = []
        for segs, starts in threads:
            i = max(0, bisect.bisect_right(starts, lo) - 1)
            while i < len(segs) and segs[i][0] < hi:
                if segs[i][1] > lo:
                    touching.append(segs[i])
                i += 1
        cuts = sorted({lo, hi} | {t for s, e, _ in touching
                                  for t in (s, e) if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            open_now = [sg for sg in touching if sg[0] <= a and sg[1] >= b]
            name = max(open_now)[2] if open_now else NO_SPAN
            spent[name] = spent.get(name, 0.0) + (b - a) / 1e9
    return sorted(spent.items(), key=lambda kv: -kv[1])


def device_span_latency(trace, by_thread, suffixes=(".device", ".dispatch")):
    """{span: (n, mean ms from the span's start to the first device
    operation that starts inside it, mean ms from the end of the last
    operation that ends inside it to the span's end)}: what a
    call-and-wait span holds besides the device's own work."""
    ops = sorted((s, e) for _, s, e in trace.devices[0].ops)
    starts = [s for s, _ in ops]
    ends = sorted(e for _, e in ops)
    out = {}
    for spans in by_thread.values():
        for name, s, e in spans:
            if not name.endswith(suffixes):
                continue
            i = bisect.bisect_left(starts, s)
            k = bisect.bisect_right(ends, e) - 1
            if i >= len(starts) or starts[i] >= e or k < 0 or ends[k] <= s:
                continue  # no operation inside: a span outside the window
            n, lead, tail = out.get(name, (0, 0.0, 0.0))
            out[name] = (n + 1, lead + (starts[i] - s) / 1e6,
                         tail + (e - ends[k]) / 1e6)
    return {name: (n, lead / n, tail / n)
            for name, (n, lead, tail) in out.items()}


def parity(ring, by_thread):
    """{name: [ring count, trace count]} and the offsets (trace start -
    ring start, in us) of the spans paired in order of their starts."""
    in_trace = {}
    for spans in by_thread.values():
        for name, s, _ in spans:
            in_trace.setdefault(name, []).append(s / 1e3)
    in_ring = {}
    for e in ring:
        in_ring.setdefault(e["name"], []).append(e["ts"])
    counts, offsets = {}, []
    for name in sorted(set(in_ring) | set(in_trace)):
        r, t = sorted(in_ring.get(name, [])), sorted(in_trace.get(name, []))
        counts[name] = [len(r), len(t)]
        offsets += [b - a for a, b in zip(r, t)]
    return counts, offsets


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.trace, args.keep_trace = 1, True

    from chipbench import run as R
    from chipbench.harness import trace as TR

    bench, entry, workload, cfg = R.find_cell(args.workload)
    devices, peaks = R._devices(int(entry["chips"]))
    from mxnet_tpu import runtime

    runtime.setup_compile_cache(R.CACHE_DIR)
    result = R.drive(args, entry, bench, workload, cfg, devices, peaks,
                     T_START)
    R.report(result)
    report = tables(result, TR.find_xplane(R.TRACE_DIR))
    report.update(workload=args.workload, seed=args.seed)
    out = args.out or os.path.join(ROOT, "chiprun_out",
                                   f"host_gaps.{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print("\nwritten to", out)
    return 0


def tables(result, path):
    """Prints (a), (b) and (c) for the run that gave ``result`` and left
    the trace at ``path`` and its spans in this process's ring; returns
    them as one object."""
    from chipbench.harness import trace as TR
    from chipbench.harness import traffic as T
    from chipbench.readers import program_spans as PS

    trace = TR.Trace(path)
    by_thread = host_spans(path)
    events = PS.window_events(None)  # one traced run a process: all of it
    ring = [e for e in events if e.get("cat") != "request"
            and e.get("ph") == "X"]
    report = {"window_s": trace.window_s, "busy_s": trace.busy_s()}

    print("\n(a) the program's spans that ended inside the traced window: "
          "ring against trace")
    _, offsets = parity(ring, by_thread)
    # a span still open when the session is stopped reaches the ring
    # alone, so the counts are of those that ended with the window
    end_ns = trace.window[1]
    shift = statistics.median(offsets) if offsets else 0.0
    counts, offsets = parity(
        [e for e in ring if e["ts"] + e["dur"] + shift <= end_ns / 1e3],
        {line: [sp for sp in spans if sp[2] <= end_ns]
         for line, spans in by_thread.items()})
    for name, (r, t) in counts.items():
        print(f"  {name:24s} ring {r:6d}  trace {t:6d}"
              + ("" if r == t else "   <-- differ"))
    if offsets:
        q = statistics.quantiles(offsets, n=4) if len(offsets) > 1 \
            else [offsets[0]] * 3
        report["offset_us"] = {
            "n": len(offsets), "median": statistics.median(offsets),
            "min": min(offsets), "max": max(offsets),
            "spread": max(offsets) - min(offsets), "iqr": q[2] - q[0]}
        print("  trace start - ring start over {n} spans: median {median:.1f}"
              " us, min {min:.1f}, max {max:.1f}, spread {spread:.1f},"
              " interquartile {iqr:.1f}".format(**report["offset_us"]))
    report["counts"] = counts

    print("\n(b) the first chip's idle time by the innermost mx: span "
          "(any host thread)")
    gaps = device_gaps(trace) if trace.devices else []
    idle = idle_by_span(gaps, by_thread)
    total = sum(s for _, s in idle)
    for name, s in idle[:12]:
        print(f"  {name:24s} {s:9.4f} s  {100 * s / max(total, 1e-12):5.1f} %")
    named = sum(s for n, s in idle if n != NO_SPAN)
    print(f"  idle {total:.4f} s of a {trace.window_s:.3f}-s window; "
          f"{100 * named / max(total, 1e-12):.1f} % of it under an mx: span")
    print("  the result line's attribution (cb: spans):",
          result.get("breakdown", {}).get("idle_gaps"))
    report["device_span_latency_ms"] = device_span_latency(
        trace, by_thread) if trace.devices else {}
    for name, (n, lead, tail) in report["device_span_latency_ms"].items():
        print(f"  {name:24s} {n:5d} x  first operation {lead:7.3f} ms after "
              f"its start, last one ends {tail:7.3f} ms before its end")
    report["idle_by_mx_span"] = idle
    report["idle_by_cb_span"] = result.get("breakdown", {}).get("idle_gaps")
    report["idle_under_mx_share"] = 100 * named / max(total, 1e-12)

    print("\n(c) the requests' phases and the threads' time by span")
    report["requests_ms"] = {}
    for name in ("req", "req.queue", "req.prefill", "req.decode"):
        durs = [e["dur"] / 1e3 for e in events if e["name"] == name]
        if durs:
            report["requests_ms"][name] = {
                "n": len(durs), "mean": statistics.fmean(durs),
                "p95": T.percentile(durs, 95)}
            print("  {0:12s} n {n:5d}  mean {mean:9.2f} ms  p95 {p95:9.2f} ms"
                  .format(name, **report["requests_ms"][name]))
    own = PS.self_times(ring)
    report["self_time_s"] = {}
    for cat in sorted({e["cat"] for e in ring}):
        mine = [e for e in ring if e["cat"] == cat]
        extent = (max(e["ts"] + e["dur"] for e in mine)
                  - min(e["ts"] for e in mine)) / 1e6
        by_name = {}
        for e in mine:
            n, s = by_name.get(e["name"], (0, 0.0))
            by_name[e["name"]] = (n + 1, s + own[e["id"]] / 1e6)
        print(f"  cat {cat!r}: {extent:.3f} s from the first start to the "
              "last end; self time by span:")
        for name, (n, s) in sorted(by_name.items(), key=lambda kv: -kv[1][1]):
            print(f"    {name:22s} {n:6d} x  {s:9.4f} s  "
                  f"{100 * s / max(extent, 1e-12):5.1f} %  "
                  f"mean {1e3 * s / n:8.3f} ms")
        report["self_time_s"][cat] = {"extent": extent, "by_name": by_name}

    kernels = {}
    if trace.devices:
        for name, _, _ in trace.devices[0].ops:
            if "custom_call_target" in name:
                kernels.setdefault(TR.base_name(name), name[:400])
    print("\nthe kernels' operation lines in the trace:")
    for base, line in kernels.items():
        print(f"  {base}: {line}")
    report["kernel_lines"] = kernels
    report["result"] = result
    return report


if __name__ == "__main__":
    sys.exit(main())
