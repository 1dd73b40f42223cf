#!/usr/bin/env python
"""Unified multi-track chrome://tracing timeline from a trace dump.

The raw ring dump (``BENCH_telemetry.jsonl``, ``MXTPU_TRACE_JSONL``, or
a flight bundle's ``trace_events``) stamps every event with the REAL
``pid``/``tid`` — loading it in a viewer piles trainer spans, prefetcher
staging, collectives, checkpoint commits and serving batches onto
whatever threads happened to record them. This tool reconstructs the
timeline the way an operator reads it:

- one named TRACK per subsystem (train loop / attribution / prefetcher /
  collectives / checkpoint writer / serving batcher / generation
  scheduler / requests / compile / watchdog),
  mapped from each event's category and stably ordered;
- ``step.phases`` attribution spans EXPANDED into stacked per-phase
  child slices (input_wait -> h2d -> ckpt_overhead -> comm_exposed ->
  compute -> host_gap), so one glance shows where a step's period went;
- span-id correlation (PR-15 ``args.parent`` links, e.g. a serving
  request's phase spans under their batch) rendered as chrome flow
  arrows (``ph: s/f``) between parent and child tracks.

Usage:
    python tools/timeline.py TRACE.jsonl [-o timeline.json]
    python tools/timeline.py flight_1234.json -o timeline.json

The output is plain ``{"traceEvents": [...]}`` JSON — load it in
chrome://tracing or https://ui.perfetto.dev. Import-safe as a module
(the attribution tests call ``build_timeline``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

#: (track title, predicate over event) — first match wins; order is the
#: top-to-bottom track order in the viewer
TRACKS = (
    ("train loop", lambda ev: ev.get("cat") in ("trainer", "train")),
    ("attribution", lambda ev: ev.get("cat") == "attribution"),
    ("prefetcher", lambda ev: ev.get("cat") == "io"),
    ("collectives", lambda ev: ev.get("cat") == "comms"),
    ("checkpoint writer", lambda ev: ev.get("cat") == "resilience"),
    ("serving batcher", lambda ev: ev.get("cat") == "serving"),
    ("generation", lambda ev: ev.get("cat") == "generation"),
    ("request", lambda ev: ev.get("cat") == "request"),
    ("compile", lambda ev: ev.get("cat") == "compile"),
    ("watchdog", lambda ev: ev.get("cat") == "watchdog"),
)
MISC_TRACK = "host (other)"

#: the attribution phase stacking order (matches the budget order the
#: plane decomposes in — see mxnet_tpu/observability/attribution.py)
PHASES = ("input_wait", "h2d", "ckpt_overhead", "comm_exposed",
          "compute", "host_gap")

_PID = 1  # everything lands in one synthetic "mxnet_tpu" process


def load_events(source) -> list:
    """Trace events from a path or string: JSONL ring dumps, chrome
    ``{"traceEvents": [...]}`` exports, and flight bundles
    (``{"trace_events": [...]}``) all load."""
    if isinstance(source, str) and "\n" not in source \
            and os.path.exists(source):
        with open(source) as f:
            text = f.read()
    else:
        text = source
    try:  # one whole-text JSON document (chrome export / flight bundle)
        doc = json.loads(text)
    except ValueError:  # JSONL ring dump: one event object per line
        return [json.loads(line) for line in text.splitlines()
                if line.strip()]
    if isinstance(doc, dict):
        return list(doc.get("traceEvents") or doc.get("trace_events") or [])
    return list(doc)


def _track_of(ev) -> str:
    for title, pred in TRACKS:
        try:
            if pred(ev):
                return title
        except Exception:
            pass
    return MISC_TRACK


def _phase_slices(ev, tid) -> list:
    """Expand one ``step.phases`` span into stacked child slices laid
    end-to-end across the span (phase args are per-step amortized; the
    span covers the whole k-step period, so each slice is phase * k)."""
    args = ev.get("args") or {}
    k = max(int(args.get("k") or 1), 1)
    out = []
    cursor = float(ev.get("ts") or 0.0)
    for ph in PHASES:
        ms = args.get(f"{ph}_ms")
        if ms is None:
            continue
        dur_us = float(ms) * 1e3 * k
        if dur_us <= 0.0:
            continue
        out.append({"name": ph, "cat": "attribution.phase", "ph": "X",
                    "ts": cursor, "dur": dur_us, "pid": _PID, "tid": tid,
                    "args": {"step": args.get("step"), "site":
                             args.get("site"), "per_step_ms": float(ms)}})
        cursor += dur_us
    return out


def build_timeline(events) -> dict:
    """The multi-track chrome://tracing document (a plain dict)."""
    tracks = {}  # title -> tid

    def tid_of(title):
        if title not in tracks:
            tracks[title] = len(tracks)
        return tracks[title]

    for title, _ in TRACKS:  # stable top-to-bottom order even if empty
        tid_of(title)

    out = []
    by_id = {}  # event id -> (ts, tid) for flow correlation
    for ev in sorted(events, key=lambda e: float(e.get("ts") or 0.0)):
        tid = tid_of(_track_of(ev))
        ne = {"name": ev.get("name", "?"), "cat": ev.get("cat", "default"),
              "ph": ev.get("ph", "X"), "ts": float(ev.get("ts") or 0.0),
              "dur": float(ev.get("dur") or 0.0), "pid": _PID, "tid": tid,
              "args": dict(ev.get("args") or {})}
        if ev.get("id") is not None:
            ne["args"]["span_id"] = ev["id"]
            by_id[ev["id"]] = (ne["ts"], tid)
        if ne["ph"] == "i":
            ne["s"] = "t"  # instant scope: thread
            ne.pop("dur", None)
        out.append(ne)
        if ev.get("name") == "step.phases":
            out.extend(_phase_slices(ev, tid))
        parent = (ev.get("args") or {}).get("parent")
        if parent is not None and parent in by_id:
            # flow arrow: parent span -> this event (chrome needs the
            # start stamped at the parent's coordinates)
            pts, ptid = by_id[parent]
            flow = {"cat": "correlation", "name": "span",
                    "id": int(parent), "pid": _PID}
            out.append(dict(flow, ph="s", ts=pts, tid=ptid))
            out.append(dict(flow, ph="f", bp="e", ts=ne["ts"], tid=tid))

    meta = [{"ph": "M", "name": "process_name", "pid": _PID, "tid": 0,
             "args": {"name": "mxnet_tpu"}}]
    for title, tid in tracks.items():
        meta.append({"ph": "M", "name": "thread_name", "pid": _PID,
                     "tid": tid, "args": {"name": title}})
        meta.append({"ph": "M", "name": "thread_sort_index", "pid": _PID,
                     "tid": tid, "args": {"sort_index": tid}})
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-track chrome://tracing export from a "
                    "mxnet_tpu trace dump (JSONL ring / flight bundle)")
    ap.add_argument("trace", help="trace file: JSONL dump, chrome "
                                  "traceEvents JSON, or flight bundle")
    ap.add_argument("-o", "--out", default=None,
                    help="output path (default: <trace>.timeline.json)")
    args = ap.parse_args(argv)
    events = load_events(args.trace)
    doc = build_timeline(events)
    out = args.out or (os.path.splitext(args.trace)[0] + ".timeline.json")
    with open(out, "w") as f:
        json.dump(doc, f, default=float)
    n_tracks = sum(1 for e in doc["traceEvents"]
                   if e.get("ph") == "M" and e.get("name") == "thread_name")
    print(f"timeline: {len(events)} events -> {out} "
          f"({n_tracks} tracks; load in chrome://tracing or perfetto)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
