"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers ask for: busy and idle time, time by operation and by program,
programs launched, the longest idle gaps by
what the benchmark's host thread was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event an executed HLO operation and whose line ``XLA Modules`` holds one
event an executed program; the host is the plane ``/host:CPU`` with one
line a thread, where ``jax.profiler.TraceAnnotation`` spans appear under
their own names. The benchmark's spans start with ``cb:``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

SPAN_PREFIX = "cb:"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_length(intervals):
    """Total length covered by ``[(start, end)]``, and the merged list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def base_name(name):
    """A program's or an operation's own name without its number:
    ``jit_step(98765)`` -> ``jit_step``; ``%fusion.123 = ...`` ->
    ``fusion``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


_OPCODE = re.compile(r"[ )]([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name):
    """A short label for an operation whose trace name is its whole HLO
    line: the opcode (with a custom call's target) and the first array
    it writes, e.g. ``fusion bf16[64,128,3072]``. Operations that do the
    same work in different layers share a label."""
    if " = " not in name:
        return base_name(name)
    own, rest = name.split(" = ", 1)
    op = _OPCODE.search(" " + rest)
    shape = _SHAPE.search(rest)
    target = _TARGET.search(rest)
    label = op.group(1) if op else base_name(own)
    if target:
        label += f"({target.group(1)})"
    return label + (" " + shape.group(0) if shape else "")


class DeviceTrace:
    def __init__(self, index):
        self.index = index
        self.ops = []      # (name, start_ns, end_ns)
        self.modules = []  # (name, start_ns, end_ns)


class Trace:
    """A parsed trace, clipped to the ``cb:window`` span where there is
    one (else to the span of the device events)."""

    def __init__(self, path):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        names = {}  # one string a distinct name: they are whole HLO lines
        self.devices = []
        self.host_spans = []  # (name, start_ns, end_ns) of cb: spans
        window = None
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = DeviceTrace(int(m.group(1)))
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        dev.ops = [(names.setdefault(e.name, e.name),
                                    e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
                    elif line.name == MODULES_LINE:
                        dev.modules = [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
                self.devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            span = (e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                            if e.name == WINDOW_SPAN:
                                window = span[1:]
                            else:
                                self.host_spans.append(span)
        self.devices.sort(key=lambda d: d.index)
        self.devices = [d for d in self.devices if d.ops]
        if window is None and self.devices:
            window = (min(d.ops[0][1] for d in self.devices),
                      max(max(o[2] for o in d.ops) for d in self.devices))
        self.window = window or (0, 0)
        lo, hi = self.window
        for d in self.devices:
            d.ops = [(n, max(s, lo), min(e, hi)) for n, s, e in d.ops
                     if e > lo and s < hi]
            d.modules = [(n, max(s, lo), min(e, hi)) for n, s, e in d.modules
                         if e > lo and s < hi]

    # -- times ----------------------------------------------------------------
    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_length([(s, e) for _, s, e in d.ops])[0]
                   for d in self.devices) / len(self.devices) / 1e9

    def op_seconds(self, pattern=None):
        """{operation name: seconds}, averaged over the chips; with a
        compiled ``pattern``, only the names it finds."""
        out = {}
        for d in self.devices:
            for n, s, e in d.ops:
                if pattern is None or pattern.search(n):
                    out[n] = out.get(n, 0.0) + (e - s)
        k = max(1, len(self.devices)) * 1e9
        return {n: v / k for n, v in out.items()}

    def op_calls(self, pattern):
        """Executions of the matching operations on the first chip."""
        if not self.devices:
            return 0
        return sum(1 for n, _, _ in self.devices[0].ops if pattern.search(n))

    def module_seconds(self, pattern=None):
        out = {}
        for d in self.devices:
            for n, s, e in d.modules:
                if pattern is None or pattern.search(n):
                    out[n] = out.get(n, 0.0) + (e - s)
        k = max(1, len(self.devices)) * 1e9
        return {n: v / k for n, v in out.items()}

    def module_runs(self, pattern=None):
        """Executions on the first chip of the programs ``pattern``
        finds; without one, of the program that took most device time
        (a training step's one big program, a server's decode chunk)."""
        if not self.devices or not self.devices[0].modules:
            return 0
        mods = self.devices[0].modules
        if pattern is None:
            spent = {}
            for n, s, e in mods:
                b = base_name(n)
                spent[b] = spent.get(b, 0) + (e - s)
            top = max(spent, key=spent.get)
            return sum(1 for n, _, _ in mods if base_name(n) == top)
        return sum(1 for n, _, _ in mods if pattern.search(n))

    def module_launches(self):
        """Programs launched on the first chip inside the window."""
        return len(self.devices[0].modules) if self.devices else 0

    # -- the breakdown ----------------------------------------------------------
    def top_ops(self, n=10):
        by_label = {}
        for name, sec in self.op_seconds().items():
            b = op_label(name)
            by_label[b] = by_label.get(b, 0.0) + sec
        return sorted(by_label.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n=10):
        """The idle time of the first chip by the benchmark's span that
        was open on the host in the middle of each gap: [(span, seconds)],
        summed by span, longest first."""
        if not self.devices:
            return []
        lo, hi = self.window
        _, merged = union_length([(s, e) for _, s, e in self.devices[0].ops])
        gaps, at = [], lo
        for s, e in merged:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        spans = sorted(self.host_spans, key=lambda sp: sp[1])
        starts = [sp[1] for sp in spans]
        by_span = {}
        for s, e in gaps:
            mid = (s + e) / 2
            # the innermost open span: the latest start that covers mid
            # (the benchmark's spans nest a few deep at most)
            name = "outside the benchmark's spans"
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(-1, i - 8), -1):
                if spans[j][2] >= mid:
                    name = spans[j][0][len(SPAN_PREFIX):]
                    break
            by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
        return sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
