#!/usr/bin/env python3
"""The one sweep that finds the highest rate a serving cell sustains:
the cell's own engine and traffic at each of ``--rates``, one engine for
all of them, a line a rate. A rate is sustained while the queue at the
window's close stays short and the first-token tail does not grow with
the window. Needs a TPU.

    python chipbench/tools/sweep.py --workload <cell> --rates 4,8,12 \
        --seconds 20 [--out chiprun_out/sweep.jsonl]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=77)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cache-blocks", type=int, default=None,
                    help="try another pool size than the cell's")
    ap.add_argument("--slots", type=int, default=None,
                    help="try another decode batch than the cell's")
    ap.add_argument("--ramp", type=float, default=None)
    args = ap.parse_args(argv)

    from chipbench import run as R

    bench, entry, workload, cfg = R.find_cell(args.workload)
    devices, peaks = R._devices(int(entry["chips"]))
    if args.cache_blocks:
        workload["engine"]["cache_blocks"] = args.cache_blocks
    if args.slots:
        workload["engine"]["slots"] = args.slots
    if args.ramp is not None:
        workload["traffic"]["ramp_s"] = args.ramp
    from mxnet_tpu import runtime

    runtime.setup_compile_cache(R.CACHE_DIR)
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0)
    run, loop = R.make_loop(ns, entry, workload, cfg, devices, peaks)
    loop.setup()
    print(json.dumps({"engine": workload["engine"],
                      "memory_stats": devices[0].memory_stats()}),
          flush=True)
    out = open(args.out, "a") if args.out else None
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        workload["traffic"]["arrivals"]["rate_per_s"] = rate
        loop.seed = args.seed + k
        loop.window(args.seconds)
        attempted, failed = loop.outcome()
        c = loop.counters
        rec = {"rate_per_s": rate, "attempted": attempted, "failed": failed,
               "queue_at_end": loop.engine.queue_depth(),
               **{key: c.get(key) for key in (
                   "tokens_per_s", "ttft_ms_p50", "ttft_ms_p95",
                   "tpot_ms_p50", "tpot_ms_p95", "generator_late_ms_p95",
                   "tokens_generated", "prefills", "decode_chunks",
                   "mean_live_context_tokens", "window_s")}}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    loop.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
