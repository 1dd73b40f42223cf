"""Low-overhead event tracer: step-scoped spans in a ring buffer.

Reference analog: MXNet's engine-integrated profiler dumping
chrome://tracing JSON (``src/profiler/profiler.cc::DumpProfile``). Here
events are plain dicts appended to a bounded ``deque`` (capacity
``MXTPU_TRACE_BUFFER``, default 65536 — old events fall off rather than
grow memory on long runs) and export two ways:

- ``dump_chrome_trace()`` — the ``{"traceEvents": [...]}`` JSON that
  chrome://tracing / Perfetto load directly,
- ``dump_jsonl()`` — one event object per line, the format
  ``tools/telemetry_report.py`` aggregates.

Timestamps are epoch microseconds (``time.time_ns()``): the clock the
``jax.profiler`` stamps its host events with, up to one constant per
profiler session, so a ring event can be laid beside a device operation
of the same session's ``.xplane.pb``. Callers that hand ``record()`` a
``perf_counter`` time are mapped through a ``(perf_counter, time_ns)``
pair read at that call, so a stepped wall clock moves them with the
:class:`Span` events beside them. ``tid`` is the thread's native id
(``threading.get_native_id()``), unique among the process's live
threads.

A :class:`Span` is the program's one span: on entry it opens a
``jax.profiler.TraceAnnotation("mx:" + name)`` (a no-op costing half a
microsecond outside a profiler session) and on exit appends one ring
event carrying its ``id`` and, in ``args["parent"]``, the id of the
innermost span open on the same thread. ``observability.span()`` hands
one out only while someone is looking (``ENABLED`` or a profiler
session, whoever opened it) and the shared :data:`NO_SPAN` otherwise.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from ..base import getenv

#: prefix of the program's spans in a profiler trace (the benchmark's
#: own are ``cb:``)
PROFILER_PREFIX = "mx:"

#: True inside a ``jax.profiler`` session, whoever opened it (~40 ns)
profiler_active = _Annotation.is_enabled


def _default_capacity() -> int:
    return getenv("MXTPU_TRACE_BUFFER", 65536, dtype=int)


class _NoSpan:
    """What ``observability.span()`` returns while nobody is looking."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


NO_SPAN = _NoSpan()


class Span:
    """Context manager recording one complete ("X") event on exit, and
    the same interval as ``mx:<name>`` in a profiler trace."""

    __slots__ = ("_tracer", "name", "cat", "args", "id", "_t0", "_ann",
                 "_stack")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **args):
        """Args known only once the span is under way, or just after
        it (the ring's event holds this dict); they reach the ring, not
        the profiler, which takes its args at entry."""
        self.args.update(args)

    def __enter__(self):
        tr = self._tracer
        stack = self._stack = tr._open_spans()
        if stack:
            self.args["parent"] = stack[-1].id
        self.id = tr.new_span_id()
        self._ann = _Annotation(PROFILER_PREFIX + self.name, **self.args)
        self._ann.__enter__()
        stack.append(self)
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        self._tracer._append(self.name, self.cat, "X", self.id,
                             self._t0 / 1e3, (t1 - self._t0) / 1e3,
                             self.args)
        return False


class Tracer:
    """Ring buffer of trace events."""

    def __init__(self, capacity=None):
        self._events = collections.deque(
            maxlen=capacity or _default_capacity())
        self.step = 0  # advanced by Trainer.step via mark_step()
        self._local = threading.local()
        # span ids: process-unique, monotonic, survive clear() — parent
        # links recorded before a clear must not collide after it
        self._span_ids = itertools.count(1)

    # -- recording -------------------------------------------------------
    def mark_step(self) -> int:
        """Advance the step counter; spans recorded afterwards carry the
        new step id in their args."""
        self.step += 1
        return self.step

    def new_span_id(self) -> int:
        """A process-unique span id (itertools.count — GIL-atomic).
        Correlated child events reference it via ``args["parent"]``
        (a :class:`Span` fills that in from its thread's open spans)."""
        return next(self._span_ids)

    def _open_spans(self) -> list:
        """This thread's stack of open spans."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _append(self, name, cat, ph, span_id, ts_us, dur_us, args):
        args["step"] = self.step
        ev = {
            "name": name,
            "cat": cat,
            "ph": ph,
            "id": span_id,
            "ts": ts_us,
            "dur": dur_us,
            "pid": os.getpid(),
            "tid": threading.get_native_id(),
            "args": args,
        }
        self._events.append(ev)
        return ev

    def record(self, name, cat="default", ts=None, dur=0.0, args=None,
               ph="X", span_id=None):
        """Append one event. ``ts``/``dur`` are perf_counter seconds
        (``ts=None`` means now). Every event carries a unique ``id``
        (pass ``span_id`` to stamp one minted earlier, e.g. before
        handing it to children as their parent)."""
        ts_us = time.time_ns() / 1e3
        if ts is not None:
            ts_us += (ts - time.perf_counter()) * 1e6
        return self._append(
            name, cat, ph,
            int(span_id) if span_id is not None else self.new_span_id(),
            ts_us, dur * 1e6, dict(args or ()))

    def instant(self, name, cat="default", **args):
        return self.record(name, cat=cat, dur=0.0, args=args, ph="i")

    def span(self, name, cat="default", **args) -> Span:
        return Span(self, name, cat, args)

    # -- read side -------------------------------------------------------
    def events(self) -> list:
        return list(self._events)

    def __len__(self):
        return len(self._events)

    def clear(self):
        self._events.clear()
        self.step = 0

    # -- exporters -------------------------------------------------------
    def dump_chrome_trace(self, path=None) -> str:
        """chrome://tracing JSON; written to ``path`` when given."""
        # default=float: event args may hold asynchronous device scalars
        # (the fused step's lazy grad norm) — sync them at dump time only
        body = json.dumps({"traceEvents": self.events(),
                           "displayTimeUnit": "ms"}, default=float)
        if path:
            with open(path, "w") as f:
                f.write(body)
        return body

    def dump_jsonl(self, path=None) -> str:
        """One JSON event per line; written to ``path`` when given."""
        body = "\n".join(json.dumps(ev, default=float)
                         for ev in self._events)
        if body:
            body += "\n"
        if path:
            with open(path, "w") as f:
                f.write(body)
        return body


def load_jsonl(source) -> list:
    """Parse a JSONL trace from a path or a string body."""
    if "\n" not in source and os.path.exists(source):
        with open(source) as f:
            text = f.read()
    else:
        text = source
    return [json.loads(line) for line in text.splitlines() if line.strip()]
