#!/usr/bin/env python3
"""The benchmark's command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration and the metrics by name in
``BENCHMARK.json`` and in the files under ``chipbench/``; builds the
cell's loop; warms it up (set-up); measures a window of ``--seconds``;
frees the program; compares what the timed path produced with the plain
reference; prints one JSON object as the last line of standard output.
Needs a TPU whose ``device_kind`` has a row in ``harness/peaks.json``
and as many chips as the cell asks for; otherwise it exits non-zero
before anything runs and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(Exception):
    """The run cannot start: no result is printed."""


def _load(path):
    with open(path) as f:
        return json.load(f)


def find_cell(name):
    """(benchmark, its entry for the cell, the cell's file, the
    configuration's file)."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    workload = _load(os.path.join(HERE, "workloads", name + ".json"))
    cfg = _load(os.path.join(ROOT, conf["file"]))
    return bench, entry, workload, cfg


def metrics_for(bench, cell, kind):
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) that
    this cell reports, each with its file under ``metrics/``."""
    reports = {m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if cell not in m.get("workloads", [cell]):
            continue
        if kind == "per_layer" and "workloads" not in m \
                and m["moves"] not in reports:
            continue
        spec = _load(os.path.join(HERE, "metrics", m["name"] + ".json"))
        out.append((m, spec))
    return out


class Run:
    """What a loop and the readers see of one run."""

    def __init__(self, args, entry, workload, cfg, devices, peaks):
        self.cell = entry["name"]
        self.chips = int(entry["chips"])
        self.workload, self.cfg = workload, cfg
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.tracing = bool(int(args.trace))
        self.devices, self.peaks = devices, peaks
        self.trace = None
        self.memory = None
        self.counters = {}
        self._snapshot = None
        self._window_span = None
        self._trace_until = None
        self.traced = {}

    # -- spans and the traced part of the window ----------------------------
    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("cb:" + name)

    def open_window(self, snapshot=None):
        self._snapshot = snapshot
        if not self.tracing:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        jax.profiler.start_trace(TRACE_DIR)
        self._window_span = jax.profiler.TraceAnnotation("cb:window")
        self._window_span.__enter__()
        # a cell may trace only the first part of its window (a training
        # step is a thousand operations); else the trace ends with it
        cap = self.workload.get("trace_seconds")
        self._trace_until = (time.perf_counter() + float(cap)
                             if cap and float(cap) < self.seconds else None)

    def tick(self):
        if self._window_span is not None and self._trace_until \
                and time.perf_counter() >= self._trace_until:
            self._stop_trace()

    def close_window(self):
        if self._window_span is not None:
            self._stop_trace()

    def _stop_trace(self):
        import jax

        self._window_span.__exit__(None, None, None)
        self._window_span = None
        if self._snapshot is not None:
            self.traced = dict(self._snapshot())
        jax.profiler.stop_trace()


def _devices(chips):
    import jax

    from chipbench.harness import peaks as P

    devices = jax.devices()  # one attempt; a backend error ends the run
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {dev.platform!r} "
                      f"({dev.device_kind})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX found "
                      f"{len(devices)}")
    return devices[:chips], P.peaks_for(dev.device_kind)


def _memory(devices):
    """``memory_stats()`` of the fullest chip."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))


def make_loop(args, entry, workload, cfg, devices, peaks):
    """(the run, the cell's loop over it), nothing set up yet."""
    run = Run(args, entry, workload, cfg, devices, peaks)
    run.model = importlib.import_module(
        f"chipbench.models.{cfg['family']}")
    loop = importlib.import_module(
        f"chipbench.loops.{workload['kind']}").Loop(run)
    return run, loop


def drive(args, entry, bench, workload, cfg, devices, peaks, t_start):
    """A whole run on ``devices``; returns the result object. The
    command reaches it only on a TPU; the rehearsals under ``tests/``
    call it on the CPU at a tiny size."""
    from chipbench.harness.compiles import CompileCounter

    compiles = CompileCounter()
    run, loop = make_loop(args, entry, workload, cfg, devices, peaks)
    loop.setup()
    c0 = compiles.count
    t_open, t_close = loop.window(run.seconds)
    in_window = compiles.count - c0
    attempted, failed = loop.outcome()
    run.memory = dict(_memory(devices))
    run.counters = dict(loop.counters)
    run.counters.update(setup_s=t_open - t_start,
                        compiles_in_window=in_window,
                        **{k + "_traced": v for k, v in run.traced.items()})
    loop.release()
    if run.tracing:
        from chipbench.harness import trace as TR

        run.trace = TR.Trace(TR.find_xplane(TRACE_DIR))
        if not getattr(args, "keep_trace", False):
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_check = time.perf_counter()
    compared = loop.check()
    t_done = time.perf_counter()
    correct = failed == 0 and in_window == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim, _ in compared)
    kind = "per_layer" if run.tracing else "end_to_end"
    metrics = {}
    for m, spec in metrics_for(bench, run.cell, kind):
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        value = reader.read(spec, run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory.get("peak_bytes_in_use")}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps(10)]}
    # not the contract's: where a run's own time went
    result["seconds"] = {"setup": t_open - t_start, "window": t_close - t_open,
                         "reference": t_done - t_check,
                         "whole": t_done - t_start}
    result["counters"] = {k: v for k, v in run.counters.items()
                          if isinstance(v, (int, float))}
    result["compared"] = {
        name: {"value": v, "limit": lim, "at": str(where)}
        for name, v, lim, where in compared}
    result["compared"]["compiles_in_window"] = {"value": in_window,
                                                "limit": 0, "at": "window"}
    result["compared"]["failed"] = {"value": failed, "limit": 0,
                                    "at": f"{attempted} attempted"}
    return result


def report(result):
    """Each number compared beside its limit as the last lines of
    standard error; the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"chipbench: {name} = {c['value']:.6g} (limit {c['limit']:g}, "
              f"at {c['at']})", file=sys.stderr)
    print(f"chipbench: correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under "
                         ".chipbench_trace/ (for tools/dump_trace.py)")
    args = ap.parse_args(argv)
    try:
        bench, entry, workload, cfg = find_cell(args.workload)
        devices, peaks = _devices(int(entry["chips"]))
    except Exception as err:  # nothing ran: no result line
        print(f"chipbench: refused: {err}", file=sys.stderr)
        return 1
    from mxnet_tpu import runtime

    # a cache placed from outside wins (setup_compile_cache then sets no
    # directory); else a fixed path inside the checkout
    runtime.setup_compile_cache(CACHE_DIR)
    report(drive(args, entry, bench, workload, cfg, devices, peaks, _T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
