#!/usr/bin/env python3
"""A whole run of a cell with some of its parameters changed, for sizing
a cell on the chip before its file is written:

    python chipbench/tools/try_cell.py --workload <cell> --seed 5 \
        --seconds 5 [--trace 1] --set max_ahead=1 --set engine.slots=64

``--set`` takes a dotted key of the cell's file and a JSON value. Prints
the result line of ``run.py``. Needs a TPU.
"""

import argparse
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)

    from chipbench import run as R
    from chipbench.tools import apply_sets

    bench, entry, workload, cfg = R.find_cell(args.workload)
    apply_sets(workload, args.set)
    devices, peaks = R._devices(int(entry["chips"]))
    from mxnet_tpu import runtime

    runtime.setup_compile_cache(R.CACHE_DIR)
    R.report(R.drive(args, entry, bench, workload, cfg, devices, peaks,
                     T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
