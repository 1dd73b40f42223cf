"""The Mamba decode kernel on the chip, alone, at the stores of
``jamba2_3b.serve_reasoning``: 26 layers, 256 slots and the null slot,
``d_inner`` 5,120, ``d_state`` 16, 4 taps. Both of its calls against
their ``jax.numpy`` forms with 1, 108 and 242 live rows (the cell's
mean at its rate, and at twice it), the live rows scattered among the
256 and each row's state slot another than its row; every slot that
is not a live row's (the null slot too), and every other layer, left
bit for bit. Then each call timed alone at each load: ms a call on the
host's clock (the call's few XLA operations around the kernel
included), the kernel's own device time from a profiler trace, and the
bytes the live rows need (what ``mamba_decode_roofline`` counts) over
that time, as GB/s and as a share of the chip's 819 GB/s.

On a machine with a TPU, in one process (the chip belongs to one):

    python -m pytest tests_tpu/test_mamba_decode.py -q -s -p no:xdist

``-s`` shows the JSON lines (what PERF.md quotes).
"""

import json
import time

import numpy as np
import pytest

L, B, DI, N, K = 26, 256, 5120, 16, 4
LAYER = 17
LIVE = (1, 108, 242)
# bytes a live row needs (``chipbench/models/jamba_lm.py``): the window
# or the state read and written, the float32 rows in and out
BYTES = {"conv": (2 * (K - 1) * DI + 2 * DI) * 4,
         "scan": (2 * N * DI + 3 * DI + 2 * N) * 4}


def _rows(n_live, seed):
    """``slots`` (a row's state slot, a permutation of the 256) and
    ``active`` with ``n_live`` rows picked at random."""
    rng = np.random.RandomState(seed)
    active = np.zeros(B, bool)
    active[rng.choice(B, n_live, replace=False)] = True
    return rng.permutation(B).astype(np.int32), active


def _inputs(seed):
    """A step's rows and a layer's parameters in Mamba's ranges, and the
    two whole stores, drawn on the device."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import mamba as M

    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    s_shape, w_shape = M.state_shapes(L, B, DI, N, K)
    return dict(
        x=jax.random.normal(k[0], (B, DI), jnp.bfloat16),
        w=(jax.random.normal(k[1], (K, DI)) * 0.5).astype(jnp.bfloat16),
        b=(jax.random.normal(k[2], (DI,)) * 0.1).astype(jnp.bfloat16),
        dt=jnp.exp(jax.random.uniform(k[3], (B, DI), jnp.float32,
                                      np.log(1e-3), np.log(0.3))),
        A=-jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                            (N, DI)),
        bc=jax.random.normal(k[4], (2, B, N), jnp.float32),
        D=jnp.ones((DI,), jnp.bfloat16),
        state=jax.random.normal(k[5], s_shape, jnp.float32),
        window=jax.random.normal(k[6], w_shape, jnp.float32))


def _calls():
    """``{name: (kernel, oracle, store key)}``, each ``f(inp, store,
    slots, active) -> (rows out, store)``."""
    from mxnet_tpu.ops import mamba as M

    def conv(step):
        return lambda inp, store, slots, active: step(
            inp["x"], store, inp["w"], inp["b"], slots, active, LAYER)

    def scan(step):
        return lambda inp, store, slots, active: step(
            inp["x"].astype("float32"), inp["dt"], inp["A"], inp["bc"][0],
            inp["bc"][1], inp["D"], store, slots, active, LAYER)

    return {"conv": (conv(M._pallas_conv), conv(M._jnp_conv), "window"),
            "scan": (scan(M._pallas_scan), scan(M._jnp_scan), "state")}


@pytest.mark.parametrize("n_live", LIVE)
@pytest.mark.parametrize("call", ["conv", "scan"])
def test_kernel_agrees_with_the_jnp_step_and_leaves_dead_slots_alone(
        call, n_live):
    import jax
    import jax.numpy as jnp

    kernel, oracle, key = _calls()[call]
    inp = _inputs(n_live)
    slots, active = _rows(n_live, n_live)
    store = inp.pop(key)
    y, new = jax.jit(lambda s, i, *r: kernel(i, s, *r))(store, inp, slots,
                                                          active)
    want_y, want = jax.jit(lambda s, i, *r: oracle(i, s, *r))(store, inp,
                                                              slots, active)
    at = slots[active]
    np.testing.assert_allclose(np.asarray(y)[active],
                               np.asarray(want_y)[active], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(y)[~active].any()
    np.testing.assert_allclose(np.asarray(new[LAYER, at]),
                               np.asarray(want[LAYER, at]), rtol=1e-5,
                               atol=1e-5)

    @jax.jit
    def others_kept(new, old):
        """Every slot but the live rows' (of the layer), bit for bit."""
        touched = jnp.zeros(old.shape[:2], bool).at[LAYER, at].set(True)
        same = (jax.lax.bitcast_convert_type(new, jnp.int32)
                == jax.lax.bitcast_convert_type(old, jnp.int32))
        same = same.reshape(old.shape[:2] + (-1,)).all(-1)
        return jnp.logical_or(same, touched).all()

    assert bool(others_kept(new, store))


def _device_seconds(trace_dir):
    """Device seconds of the operations named ``mxtpu_mamba_decode``."""
    from chipbench.harness.trace import Trace, find_xplane

    trace = Trace(find_xplane(trace_dir))
    return sum(e - s for d in trace.devices for n, s, e in d.ops
               if "mxtpu_mamba_decode" in n.split(" = ", 1)[0]) / 1e9


def test_kernel_time_against_the_memory_bound(tmp_path):
    import jax

    n = 50
    inp = _inputs(0)
    stores = {key: inp.pop(key) for key in ("state", "window")}
    for call, (kernel, _, key) in _calls().items():
        store = stores.pop(key)
        for n_live in LIVE:
            slots, active = _rows(n_live, n_live)
            step = jax.jit(lambda s, i, *r: kernel(i, s, *r)[::-1],
                           donate_argnums=0)
            store, y = step(store, inp, slots, active)
            jax.block_until_ready(store)
            t0 = time.perf_counter()
            for _ in range(n):
                store, y = step(store, inp, slots, active)
            jax.block_until_ready(store)
            wall = (time.perf_counter() - t0) / n
            trace_dir = str(tmp_path / f"{call}{n_live}")
            jax.profiler.start_trace(trace_dir)
            for _ in range(n):
                store, y = step(store, inp, slots, active)
            jax.block_until_ready(store)
            jax.profiler.stop_trace()
            dev = _device_seconds(trace_dir) / n
            moved = n_live * BYTES[call]
            print(json.dumps({
                "mamba_decode_call": call, "live": n_live,
                "call_ms": wall * 1e3, "kernel_ms": dev * 1e3,
                "live_bytes": moved, "GB_per_s": moved / dev / 1e9,
                "share_of_819_GB_per_s": moved / dev / 819e9}))
            if n_live >= 108:
                assert moved / dev > 0.3 * 819e9
        del store
