#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, at the cell's
own size, many seeds in one process.

    python chipbench/tools/readings.py --workload <cell> --seeds 11,12,13 \
        [--control fp8] [--faults half_batch,state_unchanged] \
        [--reference-only] [--drive-control] \
        [--seconds 8] [--out chiprun_out/x.jsonl]

For every seed: the program's numbers against the plain reference (the
lower reading is the largest of them over a dozen seeds); with
``--control`` the same numbers for the reference computed at that lower
precision and put in the program's place (the upper reading is the
smallest of them); with ``--faults`` the numbers for the planted faults
(a training cell: half of the batch left out; a state left unchanged
reads 1 by construction). ``--reference-only`` (a training cell) skips
the program: the control and the faults are the reference against
itself and need none. ``--drive-control`` then makes one whole run
(``run.drive``, on the seed after the last) with the control put in the
program's place and prints its result line: ``correct`` has to read
false there, and the tool exits with 1 if it reads true. One JSON line
a seed. Needs a TPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", default="",
                    help="a training cell: half_batch, state_unchanged")
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--drive-control", action="store_true")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--leaves", default=None,
                    help="a training cell: write every leaf's norms here")
    ap.add_argument("--set", action="append", default=[],
                    help="a dotted key of the cell's file = a JSON value, "
                         "e.g. dtype='\"float32\"' for a witness run")
    args = ap.parse_args(argv)

    from chipbench import run as R
    from chipbench.harness import train_check
    from chipbench.tools import apply_sets

    bench, entry, workload, cfg = R.find_cell(args.workload)
    devices, peaks = R._devices(int(entry["chips"]))
    apply_sets(workload, args.set)
    from mxnet_tpu import runtime

    runtime.setup_compile_cache(R.CACHE_DIR)
    out = open(args.out, "a") if args.out else None
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        t0 = time.perf_counter()
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        run, loop = R.make_loop(ns, entry, workload, cfg, devices, peaks)
        rec = {"workload": args.workload, "seed": seed}
        if hasattr(loop, "reference_reading"):  # a training cell
            leaves = {}
            if args.reference_only:
                loop.draw_inputs()
                ref = loop.reference_reading()
            else:
                loop.setup()
                loop.window_losses = []
                loop.release()
                ref = loop.reference_reading()
                rec["program"] = _numbers(train_check.compare(
                    loop.program_reading, ref))
                rec["losses"] = {"program": loop.program_reading["losses"],
                                 "reference": ref["losses"]}
                leaves["program"] = loop.program_reading
            leaves["reference"] = ref
            if args.control:
                ctl = loop.reference_reading(quant=args.control)
                leaves["control"] = ctl
                rec["control_" + args.control] = _numbers(
                    train_check.compare(ctl, ref))
            if args.leaves:
                with open(args.leaves, "a") as f:
                    f.write(json.dumps({"seed": seed, **leaves}) + "\n")
            for fault in filter(None, args.faults.split(",")):
                planted = {"half_batch": {"drop_half": True},
                           "state_unchanged": {"freeze": True}}[fault]
                rec["fault_" + fault] = _numbers(train_check.compare(
                    loop.reference_reading(**planted), ref))
        else:  # a served model: a short window at the cell's own load
            loop.setup()
            loop.window(args.seconds)
            attempted, failed = loop.outcome()
            loop.release()
            worst, tokens = loop.gaps()
            rec.update(attempted=attempted, failed=failed,
                       program={"logit_gap": worst}, checked_tokens=tokens,
                       counters={k: v for k, v in loop.counters.items()
                                 if isinstance(v, (int, float))})
            if args.control:
                rec["control_" + args.control] = {
                    "logit_gap": loop.gaps(quant=args.control)[0]}
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del loop, run
    if args.drive_control:
        ns = argparse.Namespace(seed=seeds[-1] + 1, seconds=args.seconds,
                                trace=0)
        put_control_in_place(workload["kind"], args.control)
        result = R.drive(ns, entry, bench, workload, cfg, devices, peaks,
                         time.perf_counter())
        R.report(result)
        if out:
            out.write(json.dumps({"drive_control": args.control,
                                  **result}) + "\n")
        return 1 if result["correct"] else 0
    return 0


def put_control_in_place(kind, control):
    """What ``run.drive`` compares is from here on the reference at the
    precision ``control``, not what the program of loop ``kind`` made."""
    import importlib

    cls = importlib.import_module(f"chipbench.loops.{kind}").Loop
    if hasattr(cls, "reference_reading"):
        release = cls.release

        def release_then_swap(self):
            release(self)
            self.program_reading = self.reference_reading(quant=control)

        cls.release = release_then_swap
    else:
        gaps = cls.gaps
        cls.gaps = lambda self, quant=None: gaps(self, quant=control)


def _numbers(compared):
    return {k: {"value": v, "at": str(at)} for k, (v, at) in compared.items()}


if __name__ == "__main__":
    sys.exit(main())
