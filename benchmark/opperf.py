#!/usr/bin/env python
"""Operator micro-benchmark harness (reference: ``benchmark/opperf/`` —
``opperf.py`` + per-category ``nd_operations/``).

Times registered ops at benchmark-scale shapes on the CURRENT backend
(whatever JAX picks; ``JAX_PLATFORMS=cpu`` pins the CPU).
Chained-dependent iterations amortize the dispatch+sync cost
(``mxnet_tpu.test_utils.chain_time_per_iter``).

Usage:
  python benchmark/opperf.py                       # default op set
  python benchmark/opperf.py --ops dot,Convolution --backward
  python benchmark/opperf.py --category nn --json out.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _specs():
    """op name -> (category, input factory, attrs). Shapes follow the
    reference harness's defaults (1024-square elemwise, conv at
    ResNet-stage shapes, fc at transformer shapes)."""
    R = np.random.RandomState(0)

    def f32(*shape):
        return R.rand(*shape).astype(np.float32)

    big = (1024, 1024)
    return {
        # elemwise / tensor
        "broadcast_add": ("tensor", lambda: [f32(*big), f32(*big)], {}),
        "broadcast_mul": ("tensor", lambda: [f32(*big), f32(*big)], {}),
        "broadcast_div": ("tensor", lambda: [f32(*big), f32(*big) + 1], {}),
        "exp": ("tensor", lambda: [f32(*big)], {}),
        "log": ("tensor", lambda: [f32(*big) + 1], {}),
        "sqrt": ("tensor", lambda: [f32(*big)], {}),
        "tanh": ("tensor", lambda: [f32(*big)], {}),
        "sigmoid": ("tensor", lambda: [f32(*big)], {}),
        "relu": ("tensor", lambda: [f32(*big)], {}),
        "sum": ("tensor", lambda: [f32(*big)], {}),
        "max": ("tensor", lambda: [f32(*big)], {}),
        "argmax": ("tensor", lambda: [f32(*big)], {"axis": 1}),
        "transpose": ("tensor", lambda: [f32(*big)], {}),
        "dot": ("tensor", lambda: [f32(*big), f32(*big)], {}),
        "batch_dot": ("tensor",
                      lambda: [f32(32, 256, 256), f32(32, 256, 256)], {}),
        "topk": ("tensor", lambda: [f32(*big)],
                 {"k": 10, "ret_typ": "value"}),
        "sort": ("tensor", lambda: [f32(4, 65536)], {}),
        "take": ("tensor",
                 lambda: [f32(65536, 64),
                          R.randint(0, 65536, (8192,)).astype(np.int32)], {}),
        "concat": ("tensor", lambda: [f32(*big), f32(*big)], {"dim": 1}),
        "where": ("tensor",
                  lambda: [(R.rand(*big) > 0.5).astype(np.float32),
                           f32(*big), f32(*big)], {}),
        # nn
        "FullyConnected": ("nn", lambda: [f32(128, 1024), f32(4096, 1024),
                                          f32(4096)], {"num_hidden": 4096}),
        "Convolution": ("nn",
                        lambda: [f32(32, 64, 56, 56), f32(64, 64, 3, 3),
                                 f32(64)],
                        {"kernel": (3, 3), "num_filter": 64,
                         "pad": (1, 1)}),
        "Pooling": ("nn", lambda: [f32(32, 64, 56, 56)],
                    {"kernel": (2, 2), "stride": (2, 2),
                     "pool_type": "max"}),
        "BatchNorm": ("nn",
                      lambda: [f32(32, 64, 56, 56), f32(64), f32(64),
                               np.zeros(64, np.float32),
                               np.ones(64, np.float32)],
                      {"training": True, "fix_gamma": False}),
        "LayerNorm": ("nn", lambda: [f32(128, 1024), f32(1024), f32(1024)],
                      {}),
        "softmax": ("nn", lambda: [f32(128, 32768)], {}),
        "log_softmax": ("nn", lambda: [f32(128, 32768)], {}),
        "Embedding": ("nn",
                      lambda: [R.randint(0, 30000, (128, 128))
                               .astype(np.int32), f32(30000, 768)],
                      {"input_dim": 30000, "output_dim": 768}),
        "flash_attention": ("nn",
                            lambda: [f32(1, 8, 1024, 64), f32(1, 8, 1024, 64),
                                     f32(1, 8, 1024, 64)], {"causal": True}),
        # random
        "sample_normal": ("random",
                          lambda: [np.zeros(big, np.float32),
                                   np.ones(big, np.float32)], {}),
        "sample_uniform": ("random",
                           lambda: [np.zeros(big, np.float32),
                                    np.ones(big, np.float32)], {}),
        # optimizer
        "sgd_mom_update": ("optimizer",
                           lambda: [f32(*big), f32(*big), f32(*big)],
                           {"lr": 0.1, "momentum": 0.9}),
        "adam_update": ("optimizer",
                        lambda: [f32(*big), f32(*big), f32(*big), f32(*big)],
                        {"lr": 1e-3}),
    }


def _time_op_graph(name, arrays, attrs, chain=50):
    """Kernel-time measurement: the op chained inside jitted fori_loops
    with a two-point slope (test_utils.chain_time_per_iter), so per-call
    dispatch AND the closing sync drop out — the analog of the
    reference harness's warmed-up native timing. Chains are long
    (2*chain / 42*chain iterations; 100/2100 at the default --chain 50)
    because sub-50us kernels need hundreds of ms of spread to rise above
    host-clock jitter."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.registry import get
    from mxnet_tpu.test_utils import chain_time_per_iter

    fn = get(name).fn
    raws = [jnp.asarray(a) for a in arrays]
    fi = next(i for i, r in enumerate(raws)
              if jnp.issubdtype(r.dtype, jnp.floating))

    def step(c):
        ins = list(raws)
        ins[fi] = ins[fi] + c  # carry -> input dependency
        out = fn(*ins, **attrs)
        if isinstance(out, (list, tuple)):
            out = out[0]
        # consume the WHOLE output NON-LINEARLY: a single-element carry
        # lets XLA dead-code-eliminate all but that element, and a plain
        # sum(A@B) gets algebraically rewritten to a dot of row/column
        # sums (measured 0.0 ms). sum(|out|) cannot be factored. Note:
        # elementwise ops still fuse with this consuming reduce — graph
        # mode reports the FUSED cost, which is the cost XLA programs
        # actually pay.
        return jnp.sum(jnp.abs(out)).astype(jnp.float32) * 1e-30

    return chain_time_per_iter(step, jnp.float32(0), 2 * chain, 42 * chain)


def _time_op(name, arrays, attrs, backward, warmup=3, chain=50):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, engine
    from mxnet_tpu.ops.dispatch import invoke

    nd_in = [mx.nd.array(a) for a in arrays]

    def run_fwd():
        r = invoke(name, *nd_in, **attrs)
        return r[0] if isinstance(r, (list, tuple)) else r

    if backward:
        float_in = [a for a in nd_in
                    if np.issubdtype(np.dtype(str(a.dtype)), np.floating)]
        for a in float_in:
            a.attach_grad()

        def once():
            with autograd.record():
                out = run_fwd()
            out.backward()
            return out
    else:
        once = run_fwd

    def sync(last_out):
        engine.wait(last_out.data)
        if backward:
            # the forward output can be ready before the grad kernels
            # run (engine.wait blocks only on the waited array)
            for a in float_in:
                if a.grad is not None:
                    engine.wait(a.grad.data)

    for _ in range(warmup):
        out = once()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(chain):
        out = once()
    sync(out)
    return (time.perf_counter() - t0) / chain


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=str, default="",
                    help="comma-separated op names (default: all specs)")
    ap.add_argument("--category", type=str, default="",
                    help="limit to a category: tensor/nn/random/optimizer")
    ap.add_argument("--backward", action="store_true",
                    help="time forward+backward through the tape")
    ap.add_argument("--mode", choices=("eager", "graph"), default="eager",
                    help="eager: imperative dispatch latency; graph: "
                         "pure kernel time via a jitted dependent chain")
    ap.add_argument("--chain", type=int, default=50)
    ap.add_argument("--json", type=str, default="",
                    help="also write results to this JSON file")
    args = ap.parse_args()
    if args.mode == "graph" and args.backward:
        ap.error("graph mode times forward kernels; use --mode eager "
                 "for tape backward")

    import jax

    specs = _specs()
    names = [n.strip() for n in args.ops.split(",") if n.strip()] or \
        sorted(specs)
    results = []
    backend = jax.default_backend()
    print(f"# opperf backend={backend} backward={args.backward}")
    for name in names:
        if name not in specs:
            print(f"# skip {name}: no spec")
            continue
        cat, factory, attrs = specs[name]
        if args.category and cat != args.category:
            if args.ops:
                print(f"# skip {name}: category {cat} != {args.category}")
            continue
        try:
            if args.mode == "graph" and cat == "random":
                # samplers draw keys from the host-side stream (the
                # mx.random.seed contract) — eager-only by design
                print(f"# skip {name}: random ops are eager-only in "
                      "graph mode")
                continue
            if args.mode == "graph":
                per = _time_op_graph(name, factory(), attrs,
                                     chain=args.chain)
            else:
                per = _time_op(name, factory(), attrs, args.backward,
                               chain=args.chain)
            rec = {"op": name, "category": cat, "avg_time_ms":
                   round(max(per, 0.0) * 1e3, 4),
                   "backward": args.backward,
                   "mode": args.mode, "backend": backend}
            if args.mode == "graph" and per < 5e-6:
                rec["below_resolution"] = True  # < timing noise floor
            results.append(rec)
            print(json.dumps(rec), flush=True)
        except Exception as e:  # keep sweeping past unsupported combos
            print(f"# {name} FAILED: {type(e).__name__}: {e}"[:200],
                  flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
