#!/usr/bin/env python3
"""The witness for ``assumed.state`` of a ``retention_lm`` configuration:
one run of a cell, as ``run.py`` makes it, with the retention prefill's
products in float32 at ``highest`` precision.

    python chipbench/tools/wide_state_read.py --workload <cell> --seed 11 \
        --seconds 40

Under bfloat16 weights the program's chunked form reads the float32 state
and ``phi(q)`` rounded to bfloat16 where they meet between chunks
(``mxnet_tpu/ops/retention.py``). Here queries, keys and values enter the
chunked form as float32, which makes every product of it float32 at
``highest``, as under a float32 net; the weights, the decode kernel and
everything else are the cell's. ``logit_gap`` of this run beside the
program's on the same seed says what the bfloat16 read costs against the
reference; its pace is not the cell's (prefill is slower). Needs a TPU
at the cell's size.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    import jax.numpy as jnp

    from chipbench import run as R
    from mxnet_tpu.ops import retention

    narrow = retention.power_retention_chunked

    def wide(q, k, v, log_g, state, length, chunk=256):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        return narrow(q, k, v, log_g, state, length, chunk)

    # the decoder takes the function from its module as it builds prefill
    retention.power_retention_chunked = wide
    return R.main(argv)


if __name__ == "__main__":
    sys.exit(main())
