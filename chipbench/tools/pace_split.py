#!/usr/bin/env python3
"""Where a serve cell's pace goes, window after window on one engine:
run by hand on the chip, like ``try_cell.py``.

    python chipbench/tools/pace_split.py --workload <cell> \
        --seeds 5,6 [--untraced 5] [--seconds 40] \
        [--out chiprun_out/pace_split.<cell>.json]

Sets the cell's engine up once (the weights of the first seed), then
for each seed of ``--seeds`` opens a window of the seed's traffic: first
untraced where ``--untraced`` lists the seed, then traced. Each window
prints one JSON line:

- ``tpot_ms_mean`` from the loop's stamps, as the result line has it;
- for a traced window, every per-layer metric of the cell as ``run.py``
  reads it (the pace's two shares among them where the program splits
  the pace);
- ``parity``: over the ``req.decode`` events of the traced window, the
  mean of ``dur / tokens after the first`` beside the mean pace of the
  same requests (matched by ``rid``) from the stamps on their futures,
  and the largest gap of one request;
- ``pace``: where the program keeps ``stats()["pace"]``, its growth over
  the window as shares of the decode time (weighted by tokens, not by
  requests).

No reference is run, so a window says nothing of ``correct``. Nothing is
compiled after set-up. Needs a TPU.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def parity(run, futures):
    """The window's ``req.decode`` events against the stamps of the
    futures that made them."""
    from chipbench.readers import program_spans as PS

    reqs = {f._req.rid: f._req for f in futures
            if f.done() and f._req.error is None}
    ring, stamps, worst = [], [], 0.0
    for e in PS.window_events(run, "request"):
        req = reqs.get((e.get("args") or {}).get("rid"))
        if e["name"] != "req.decode" or req is None or len(req.tokens) < 2:
            continue
        n = len(req.tokens) - 1
        mine = e["dur"] / n
        theirs = (req.t_last - req.t_first) * 1e6 / n
        ring.append(mine)
        stamps.append(theirs)
        worst = max(worst, abs(mine - theirs) / theirs)
    if not ring:
        return None
    a, b = statistics.fmean(ring), statistics.fmean(stamps)
    return {"requests": len(ring), "ring_mean_pace_us": a,
            "stamps_mean_pace_us": b, "rel_gap": abs(a - b) / b,
            "worst_request_rel_gap": worst}


def pace_delta(before, after):
    if before is None or after is None:
        return None
    d = {k: after[k] - before[k] for k in after}
    if d["decode_s"] <= 0:
        return None
    host = d["decode_s"] - d["device_s"] - d["stall_s"]
    return {"intervals": d["intervals"], "decode_s": d["decode_s"],
            "device_share": 100 * d["device_s"] / d["decode_s"],
            "stall_share": 100 * d["stall_s"] / d["decode_s"],
            "host_share": 100 * host / d["decode_s"]}


def window(run, loop, bench, seed, traced, seconds):
    """One window of ``seed``'s traffic on the loop's engine."""
    import importlib

    from chipbench import run as R
    from chipbench.harness import trace as TR

    run.tracing, run.seed, loop.seed = traced, seed, seed
    run.trace, run.traced = None, {}
    eng = loop.engine
    futures, submit = [], eng.submit

    def spy(*args, **kwargs):
        fut = submit(*args, **kwargs)
        futures.append(fut)
        return fut

    eng.submit = spy
    pace0 = eng.stats().get("pace")
    try:
        t_open, t_close = loop.window(seconds)
    finally:
        eng.submit = submit
    out = {"seed": seed, "traced": traced,
           "tpot_ms_mean": loop.counters.get("tpot_ms_mean"),
           "requests": len(loop.results),
           "failed": loop.outcome()[1],
           "pace": pace_delta(pace0, eng.stats().get("pace"))}
    if traced:
        run.memory = dict(R._memory(run.devices))
        run.counters = dict(loop.counters)
        run.counters.update(compiles_in_window=0, **{
            k + "_traced": v for k, v in run.traced.items()})
        run.trace = TR.Trace(TR.find_xplane(R.TRACE_DIR))
        shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
        out["window_s"] = run.trace.window_s
        out["metrics"] = {}
        for m, spec in R.metrics_for(bench, run.cell, "per_layer"):
            reader = importlib.import_module(
                f"chipbench.readers.{spec['reader']}")
            try:
                value = reader.read(spec, run)
            except Exception as err:  # a tool: say so and go on
                value = f"raised {err!r}"
            if value is not None:
                out["metrics"][m["name"]] = value
        out["parity"] = parity(run, futures)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; a traced window each")
    ap.add_argument("--untraced", default="",
                    help="comma-separated seeds that get an untraced "
                         "window first")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    untraced = {int(s) for s in args.untraced.split(",") if s}

    from chipbench import run as R

    bench, entry, workload, cfg = R.find_cell(args.workload)
    devices, peaks = R._devices(int(entry["chips"]))
    from mxnet_tpu import runtime

    runtime.setup_compile_cache(R.CACHE_DIR)
    ns = argparse.Namespace(seed=seeds[0], seconds=args.seconds, trace=1)
    run, loop = R.make_loop(ns, entry, workload, cfg, devices, peaks)
    loop.setup()
    report = {"workload": args.workload, "setup_s":
              time.perf_counter() - T_START, "windows": []}
    print(json.dumps({"setup_s": report["setup_s"]}), flush=True)
    try:
        for seed in seeds:
            for traced in ([False] if seed in untraced else []) + [True]:
                w = window(run, loop, bench, seed, traced, args.seconds)
                report["windows"].append(w)
                print(json.dumps(w), flush=True)
    finally:
        loop.release()
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"pace_split.{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
