"""Autoregressive generation on the decode fast path (CPU-runnable).

Part 1 drives a :class:`~mxnet_tpu.serving.GenerationEngine` directly:
a paged KV cache, per-prompt-bucket sealed prefill executables, and a
single-dispatch chunk-of-T decode loop with on-device sampling. It
prints per-token latency, where that time went (``stats()["pace"]``:
decode chunks, other prompts' prefills, the scheduler's own turn) and
the engine's SLO counters — note
``tokens/dispatch`` (several tokens ride each XLA dispatch) and
``recompiles_after_warmup == 0`` under ragged traffic.

Part 2 serves the SAME decoder through the PR-17 serving fleet: the
plain-dict ``{"decoder": ...}`` spec crosses the replica boundary, the
repository picks the generation engine automatically, and routing /
health / brownout policies apply unchanged.

Run:  python examples/generate.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from mxnet_tpu.serving import (
    GenerationEngine,
    ServingFleet,
    TransformerDecoderLM,
)

PROMPTS = [
    ("greedy ", [11, 4, 27, 3], dict(greedy=True)),
    ("top-k  ", [8, 30, 2], dict(greedy=False, temperature=0.8,
                                 top_k=12, seed=7)),
    ("nucleus", [5, 5, 19, 40, 22, 1], dict(greedy=False, temperature=1.1,
                                            top_p=0.9, seed=13)),
]


def main():
    net = TransformerDecoderLM(vocab_size=96, num_layers=2, d_model=64,
                               num_heads=4, kv_heads=2, max_seq=128,
                               seed=0)

    # -- part 1: the engine, directly --------------------------------------
    print("== GenerationEngine (paged KV cache, chunked decode) ==")
    eng = GenerationEngine(net, shapes=[8, 16], slots=4, chunk=8,
                           name="lm-demo")
    try:
        t0 = time.perf_counter()
        futs = [(tag, eng.submit(np.array(p, np.int32),
                                 max_new_tokens=24, **kw))
                for tag, p, kw in PROMPTS]
        for tag, fut in futs:
            toks = fut.result(timeout=120.0)
            t_first, t_last = fut.token_times()
            itl_ms = (t_last - t_first) / max(1, len(toks) - 1) * 1e3
            print(f"  {tag} ttft {1e3 * (t_first - t0):7.1f} ms   "
                  f"itl {itl_ms:5.2f} ms/tok   "
                  f"tokens {[int(t) for t in toks[:8]]}"
                  f"{'...' if len(toks) > 8 else ''}")
        st = eng.stats()
        print(f"  SLO: {st['tokens_generated']} tokens in "
              f"{st['dispatches']} dispatches "
              f"({st['tokens_per_dispatch']:.1f} tok/dispatch), "
              f"recompiles_after_warmup={st['recompiles_after_warmup']}")
        # where the time between a request's tokens went, per token
        pace = st["pace"]
        ms = 1e3 / max(1, pace["intervals"])
        host = pace["decode_s"] - pace["device_s"] - pace["stall_s"]
        print(f"  pace {ms * pace['decode_s']:.2f} ms/tok = chunks "
              f"{ms * pace['device_s']:.2f} + other prompts' prefills "
              f"{ms * pace['stall_s']:.2f} + scheduler's turn "
              f"{ms * host:.2f}")
        print(f"  cache: {st['cache']['blocks_used']} blocks still held "
              f"(freed on retirement), {st['cache']['forks']} forks")
    finally:
        eng.close()

    # -- part 2: the same decoder behind the serving fleet -----------------
    print("== ServingFleet (decoder spec, PR-17 stack unchanged) ==")
    spec = {"net": net.spec(), "shapes": [8, 16],
            "engine": {"slots": 4, "chunk": 8}}
    fleet = ServingFleet(spec, name="lm-fleet", replicas=2)
    try:
        toks = fleet.predict(np.array([11, 4, 27, 3], np.int32),
                             max_new_tokens=12, greedy=True, timeout=120.0)
        print(f"  fleet generated {len(toks)} tokens: "
              f"{[int(t) for t in toks]}")
        st = fleet.stats()
        live = st["replicas"].get("live", 0)
        print(f"  fleet SLO: {live} live replicas, "
              f"brownout level {st['brownout']}, "
              f"queue fraction {st['queue_fraction']:.2f}, "
              f"p99 {st['p99_ms'] if st['p99_ms'] is None else round(st['p99_ms'], 2)} ms")
    finally:
        fleet.close()
    print("done.")


if __name__ == "__main__":
    main()
