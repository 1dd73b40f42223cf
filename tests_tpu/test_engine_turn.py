"""The scheduler's turn on the chip, less the chunk's device time: what
the host costs a decode turn of ``gpt2_small.serve_chat``'s engine.

The cell's engine (128 slots, chunk 8, its own pool) with 3 and with 32
requests held live, its scheduler driven from the test's thread the way
``GenerationEngine._loop`` drives it (``_admit`` then ``_step_chunk``),
so that nothing else competes for the interpreter: the wall time of a
turn, and beside it the device's own time for that chunk (the same
dispatch again, back to back with its operands resident and nothing
read between two of them). Their difference is everything the host adds
to a turn: growing the sequences, the one packed upload, the call, the
one fetch, handing the tokens out.

    chiprun -- python -m pytest tests_tpu/test_engine_turn.py -q -s -p no:xdist

``-s`` shows the JSON line each case prints (what PERF.md quotes).

Before PR 33 the scheduler staged ten arrays a chunk and read six back
one by one. The parent's figures, by ``perf_counter`` around the same
calls with its scheduler on its own thread and the chunk's device time
by the same replay (my chip runs, PR 33, call 3): under the cell's own
traffic (2.4 slots live) a turn of 14.90 ms over a chunk of 7.19 ms,
7.7 ms of host time, of which 2.61 ms staging and 2.73 ms reading back;
with 3 requests held live 6.07 ms of host time (turn 13.96, chunk 7.89)
and with 32 held live 5.09 ms (turn 20.06, chunk 14.97). The change's,
the same way: 1.53 ms (turn 9.64, chunk 8.11) and 1.25 ms (turn 16.31,
chunk 15.06) (PERF.md, Findings, PR 33). This test holds the host's
part of a turn under half the parent's.
"""

import json
import time

import numpy as np
import pytest

TURNS, REPLAYS = 30, 20
PARENT_HOST_MS = {3: 6.07, 32: 5.09}


@pytest.mark.parametrize("held", [3, 32])
def test_the_host_adds_little_to_a_turn(held):
    import jax

    from test_pool_in_place import cell_engine

    eng = cell_engine()  # autostart=False: the test is the scheduler
    try:
        rng = np.random.RandomState(held)
        for _ in range(held):
            eng.submit(rng.randint(0, eng.vocab_size, 96).astype(np.int32),
                       max_new_tokens=480, greedy=True)
        eng._admit()  # every prefill
        assert eng.active_slots() == held
        for _ in range(3):
            eng._step_chunk()
        before = eng.stats()
        t0 = time.perf_counter()
        for _ in range(TURNS):
            eng._admit()
            eng._step_chunk()
        turn_ms = 1e3 * (time.perf_counter() - t0) / TURNS
        after = eng.stats()
        assert eng.active_slots() == held
        assert after["decode_chunks"] - before["decode_chunks"] == TURNS
        crossings = {k: after["host_transfers"][k]
                     - before["host_transfers"][k]
                     for k in ("uploads", "fetches")}
        assert crossings == {"uploads": TURNS, "fetches": TURNS}

        arrays, rows, key = eng._chunk_operands()
        out = eng._chunk_exe(eng._params, arrays, rows, key)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(REPLAYS):
            out = eng._chunk_exe(eng._params, out[0], rows, key)
        jax.block_until_ready(out)
        device_ms = 1e3 * (time.perf_counter() - t0) / REPLAYS
        eng.cache.adopt(out[0])
    finally:
        eng.close()
    got = {"held": held, "turn_ms": turn_ms, "device_chunk_ms": device_ms,
           "host_ms": turn_ms - device_ms,
           "parent_host_ms": PARENT_HOST_MS[held]}
    print("\n" + json.dumps({"engine_turn": got}))
    assert 0 < got["host_ms"] < 0.5 * PARENT_HOST_MS[held], got
