"""Mamba-1's selective state space (``ops/mamba.py``), the
config-driven decoder's Mamba layers beside an attention layer, and the
cache manager holding a paged pool and a state store at once, on the
CPU at a tiny size: the prefill scan and convolution against the
recurrence a position at a time, the decode kernel in the interpreter
against its jnp form, and prefill-then-decode through
``GenerationEngine`` against the benchmark's plain reference
(``chipbench/references/jamba_lm.py``, which imports nothing of the
program)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import jamba_lm as glue
from chipbench.references import jamba_lm as ref
from mxnet_tpu.ops import mamba as M
from mxnet_tpu.ops import retention as R
from mxnet_tpu.serving import (
    GenerationEngine,
    KVCacheOOM,
    SequenceCache,
    StateStore,
    TransformerDecoderLM,
)
from mxnet_tpu.serving import decoder, kvcache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DI, N, K, T = 128, 16, 4, 37


def _tiny_cfg():
    with open(os.path.join(_ROOT, "chipbench", "tests", "tiny",
                           "jamba_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def chunks_of_four(monkeypatch):
    """Prefill's chunk is the program's constant, 16; at 4 a tiny
    prompt crosses several."""
    monkeypatch.setattr(decoder, "MAMBA_CHUNK", 4)


@pytest.fixture(scope="module")
def seq():
    """One sequence's scan inputs: steps in Mamba's range, A as Mamba
    draws it."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, DI), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (T, DI))), jnp.float32)
    A = -jnp.asarray(np.arange(1, N + 1)[:, None] * np.ones(DI), jnp.float32)
    B = jnp.asarray(rng.randn(T, N), jnp.float32)
    C = jnp.asarray(rng.randn(T, N), jnp.float32)
    D = jnp.asarray(rng.randn(DI), jnp.float32)
    return x, dt, A, B, C, D


def _sequential(x, dt, A, B, C, D, s):
    """The recurrence a position at a time, in numpy float64."""
    x, dt, A, B, C, D, s = (np.asarray(a, np.float64)
                            for a in (x, dt, A, B, C, D, s))
    ys = []
    for t in range(x.shape[0]):
        s = np.exp(dt[t] * A) * s + (dt[t] * x[t])[None] * B[t][:, None]
        ys.append(C[t] @ s + D * x[t])
    return np.stack(ys), s


def _zeros():
    return jnp.zeros((N, DI), jnp.float32)


@pytest.mark.parametrize("chunk,length", [(1, T), (4, T), (8, 30),
                                          (16, 37), (5, 23), (64, 17)])
def test_the_chunked_scan_is_the_sequential_scan(seq, chunk, length):
    y, s = M.selective_scan(*seq, _zeros(), length, chunk)
    want_y, want_s = _sequential(*(a[:length] if a.ndim and
                                   a.shape[0] == T else a for a in seq),
                                 np.zeros((N, DI)))
    np.testing.assert_allclose(np.asarray(y)[:length], want_y, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 16])
def test_a_padded_bucket_leaves_state_and_window_at_the_real_length(
        seq, chunk):
    """What lies past ``length`` neither moves the state nor enters the
    window."""
    x, dt, A, B, C, D = seq
    length = 21
    rng = np.random.RandomState(3)
    w = jnp.asarray(rng.randn(K, DI), jnp.float32)
    b = jnp.asarray(rng.randn(DI), jnp.float32)
    window0 = jnp.asarray(rng.randn(K - 1, DI // 128, 128), jnp.float32)
    junk = x.at[length:].set(99.0)
    _, win = M.causal_conv(junk, window0, w, b, length)
    np.testing.assert_array_equal(
        np.asarray(win).reshape(K - 1, DI), np.asarray(x[length - K + 1:length]))
    _, s = M.selective_scan(x, dt.at[length:].set(5.0), A, B, C, D, _zeros(),
                            length, chunk)
    _, s_short = M.selective_scan(x[:length], dt[:length], A, B[:length],
                                  C[:length], D, _zeros(), length, chunk)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_short),
                               rtol=1e-6, atol=1e-7)
    # a prompt shorter than the window keeps the window's last entries
    _, win2 = M.causal_conv(junk[:2], window0, w, b, 2)
    np.testing.assert_array_equal(
        np.asarray(win2).reshape(K - 1, DI)[0],
        np.asarray(window0).reshape(K - 1, DI)[2])


def test_a_state_carried_between_two_calls_is_one_call(seq):
    x, dt, A, B, C, D = seq
    rng = np.random.RandomState(4)
    w = jnp.asarray(rng.randn(K, DI), jnp.float32)
    b = jnp.asarray(rng.randn(DI), jnp.float32)
    win0 = jnp.zeros((K - 1, DI // 128, 128), jnp.float32)
    whole_pre, whole_win = M.causal_conv(x, win0, w, b, T)
    whole_y, whole_s = M.selective_scan(x, dt, A, B, C, D, _zeros(), T, 8)
    cut = 13
    pre1, win1 = M.causal_conv(x[:cut], win0, w, b, cut)
    pre2, win2 = M.causal_conv(x[cut:], win1, w, b, T - cut)
    y1, s1 = M.selective_scan(x[:cut], dt[:cut], A, B[:cut], C[:cut], D,
                              _zeros(), cut, 8)
    y2, s2 = M.selective_scan(x[cut:], dt[cut:], A, B[cut:], C[cut:], D, s1,
                              T - cut, 8)
    np.testing.assert_allclose(np.concatenate([pre1, pre2]), whole_pre,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(win2), np.asarray(whole_win))
    np.testing.assert_allclose(np.concatenate([y1, y2]), whole_y, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s2, whole_s, rtol=1e-5, atol=1e-6)


# the decode kernel's walks over a store of six slots: its DMA group
# held to two slots (at these widths every row would fit one group), and
# the rows' live set and state slots at the grouping's edges
ROWS, GROUP = 6, 2
WALKS = {  # name: (the live rows, each row's state slot)
    "no_row_live": ([], [2, 0, 1, 5, 3, 4]),
    "every_row_live": ([0, 1, 2, 3, 4, 5], [2, 0, 1, 5, 3, 4]),
    "one_whole_group": ([1, 4], [2, 0, 1, 5, 3, 4]),
    "a_group_and_a_half": ([0, 2, 3], [2, 0, 1, 5, 3, 4]),
    "rows_against_slot_order": ([0, 1, 3, 5], [5, 4, 3, 2, 1, 0]),
}


def _walk(name):
    """``(active, slots, the live rows' slots, the other slots of a
    layer with the null slot)``."""
    live, at = WALKS[name]
    active = np.isin(np.arange(ROWS), live)
    at = np.asarray(at, np.int32)
    touched = at[active]
    return active, jnp.asarray(at), touched, [
        s for s in range(ROWS + 1) if s not in touched]


def _steps(step, monkeypatch):
    """The two calls, the kernel's in the interpreter and compiled once."""
    if step == "jnp":
        return M._jnp_conv, M._jnp_scan
    monkeypatch.setattr(M, "_group_slots", lambda *_: GROUP)
    return (jax.jit(lambda *a: M._pallas_conv(*a, interpret=True)),
            jax.jit(lambda *a: M._pallas_scan(*a, interpret=True)))


@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("step", ["jnp", "pallas_interpret"])
def test_the_decode_step_is_the_prefill_form(seq, step, walk, monkeypatch):
    """Token by token through stores of six slots, every row given the
    same token: the live rows reproduce the prefill form and leave its
    window and state in their slots; the dead rows' outputs are zero,
    and their slots and the other layer are never touched, nor, by the
    kernel, the null slot."""
    x, dt, A, B, C, D = seq
    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(K, DI) * 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(DI) * 0.1, jnp.float32)
    conv, scan = _steps(step, monkeypatch)
    active, slots, touched, kept = _walk(walk)
    if step == "jnp":  # its dead rows read and write the null slot
        kept = kept[:-1]
    s_shape, w_shape = M.state_shapes(2, ROWS, DI, N, K)
    state, window = jnp.zeros(s_shape), jnp.zeros(w_shape)
    pre, want_win = M.causal_conv(x, window[1, 0], w, b, T)
    xc = jax.nn.silu(pre)
    want_y, want_s = M.selective_scan(xc, dt, A, B, C, D, _zeros(), T, 4)

    def rows(a):
        return jnp.stack([a] * ROWS)

    ys = []
    for t in range(T):
        xct, window = conv(rows(x[t]), window, w, b, slots, active, 1)
        np.testing.assert_allclose(
            np.asarray(xct)[active],
            np.broadcast_to(np.asarray(xc[t]), (active.sum(), DI)),
            rtol=1e-5, atol=1e-6)
        assert not np.asarray(xct)[~active].any()
        y, state = scan(xct, rows(dt[t]), A, rows(B[t]), rows(C[t]), D,
                        state, slots, active, 1)
        ys.append(np.asarray(y))
    ys = np.stack(ys)
    np.testing.assert_allclose(
        ys[:, active],
        np.broadcast_to(np.asarray(want_y)[:, None], (T, active.sum(), DI)),
        rtol=1e-4, atol=1e-5)
    assert not ys[:, ~active].any()
    for slot in touched:
        np.testing.assert_allclose(state[1, slot], want_s, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(window[1, slot]),
                                      np.asarray(want_win))
    assert not np.asarray(state[0]).any() and not np.asarray(window[0]).any()
    assert not np.asarray(state[1, kept]).any()
    assert not np.asarray(window[1, kept]).any()


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_dead_slots_are_left_bit_for_bit(seq, walk, monkeypatch):
    """The kernel's two calls once over random stores, each row on its
    own input: the live rows' outputs, windows and states are the jnp
    form's; every other slot of every layer, the null slot too, is
    what it was, bit for bit, and the dead rows' outputs are zero."""
    _, dt, A, _, _, D = seq
    rng = np.random.RandomState(6)
    s_shape, w_shape = M.state_shapes(2, ROWS, DI, N, K)
    state = jnp.asarray(rng.randn(*s_shape), jnp.float32)
    window = jnp.asarray(rng.randn(*w_shape), jnp.float32)
    w = jnp.asarray(rng.randn(K, DI), jnp.float32)
    b = jnp.asarray(rng.randn(DI), jnp.float32)
    x = jnp.asarray(rng.randn(ROWS, DI), jnp.float32)
    B, C = (jnp.asarray(rng.randn(ROWS, N), jnp.float32) for _ in "BC")
    dts = jnp.asarray(dt[:ROWS])
    conv, scan = _steps("pallas_interpret", monkeypatch)
    active, slots, touched, kept = _walk(walk)
    xc, win1 = conv(x, window, w, b, slots, active, 0)
    y, s1 = scan(xc, dts, A, B, C, D, state, slots, active, 0)
    want_xc, want_win = M._jnp_conv(x, window, w, b, slots, active, 0)
    want_y, want_s = M._jnp_scan(want_xc, dts, A, B, C, D, state, slots,
                                 active, 0)
    for got, want in ((xc, want_xc), (y, want_y)):
        np.testing.assert_allclose(np.asarray(got)[active],
                                   np.asarray(want)[active], rtol=1e-5,
                                   atol=1e-5)
        assert not np.asarray(got)[~active].any()
    for new, old, want in ((win1, window, want_win), (s1, state, want_s)):
        new, old, want = map(np.asarray, (new, old, want))
        np.testing.assert_array_equal(new[0, kept], old[0, kept])
        np.testing.assert_array_equal(new[1], old[1])
        np.testing.assert_allclose(new[0, touched], want[0, touched],
                                   rtol=1e-5, atol=1e-5)


def test_the_stores_have_their_layout():
    """``d_inner`` minor in both: the state's rows are ``d_state``, a
    window's inputs ``(d_inner / 128, 128)`` tiles; at the published
    widths both minor pairs are whole ``(8, 128)`` tiles, so nothing is
    padded."""
    state, window = M.state_shapes(26, 256, 5120, 16, 4)
    assert (state, window) == ((26, 257, 16, 5120), (26, 257, 3, 40, 128))
    for shape in (state, window):
        assert shape[-2] % 8 == 0 and shape[-1] % 128 == 0


def test_retention_keeps_its_state_shapes():
    """Retention's store, byte for byte, now that the store takes the
    shapes a kind declares."""
    assert kvcache.STATE_SHAPES["retention"](
        {"layers": 8, "kv_heads": 8, "head_dim": 128}, 16) == (
        (8, 17, 8, 128, 8320), (8, 17, 8, 65, 128))
    store = StateStore(R.state_shapes(8, 16, 8, 128))
    assert [a.shape for a in store.arrays()] == [(8, 17, 8, 128, 8320),
                                                 (8, 17, 8, 65, 128)]
    assert store.bytes_per_state == 8 * 8 * (128 * 8320 + 65 * 128) * 4


# ---------------------------------------------------------------------------
# the decoder and the engine, against the reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """(configuration, the benchmark's seeded weights, the served net
    holding them)."""
    cfg = _tiny_cfg()
    params = ref.init_params(cfg, 7, "float32")
    return cfg, params, glue.build_net(cfg, params, "float32")


def test_the_tiny_preset_is_the_tiny_configuration(tiny):
    cfg, _, net = tiny
    want = {**decoder.PRESETS["jamba_tiny"], "seed": 0, "dtype": "float32"}
    got = net.spec()["decoder"]
    assert {k: got[k] for k in want} == want
    assert net.cache_spec() == {
        "attention": {"layers": 1, "kv_heads": 1, "head_dim": 16},
        "mamba": {"layers": 3, "d_inner": 128, "d_state": 16, "d_conv": 4}}
    # a run of two Mamba layers stacked, the attention layer, a run of one
    layers = net.params()["layers"]
    assert [lyr["ln1_g"].shape for lyr in layers] == [(2, 64), (64,),
                                                      (1, 64)]
    assert "head" not in net.params()


def test_the_dense_oracle_is_the_reference(tiny):
    cfg, params, net = tiny
    tokens = np.random.RandomState(2).randint(0, 128, (2, 29))
    got = np.asarray(net.forward_fn()(net.params(), jnp.asarray(tokens)))
    want = np.asarray(ref.logits(params, tokens, cfg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# one prompt through two buckets: padding must reach neither the state,
# nor the window, nor the pool
@pytest.mark.parametrize("bucket", [16, 32])
def test_prefill_then_decode_logits_are_the_reference(tiny, bucket):
    """The net's faces by hand against a cache of both parts: the
    prompt's last logits from prefill, then every decode step's,
    elementwise against the reference's full forward pass."""
    cfg, params, net = tiny
    rng = np.random.RandomState(bucket)
    plen, n_out = 13, 9
    prompt = rng.randint(0, 128, plen)
    cache = SequenceCache(net.cache_spec(), slots=2, max_seq=64,
                          num_blocks=40, block_size=4, name="m")
    seq = cache.allocate(plen + n_out)
    other = cache.allocate(5 + n_out)  # a second live row, on another prompt
    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.decode_step_fn())
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = prompt
    padded[0, plen:] = 99  # what lies in the padding must not matter
    tables, states = cache.rows([seq])
    logits, *arrays = prefill(net.params(), padded, *cache.arrays(), tables,
                              states, np.array([plen], np.int32))
    pad2 = np.zeros((1, bucket), np.int32)
    pad2[0, :5] = rng.randint(0, 128, 5)
    _, *arrays = prefill(net.params(), pad2, *arrays,
                         *cache.rows([other]), np.array([5], np.int32))
    got = [np.asarray(logits[0])]
    toks = list(prompt)
    for i in range(n_out):
        toks.append(int(got[-1].argmax()))
        logits, *arrays = step(
            net.params(), np.array([toks[-1], 3], np.int32),
            np.array([len(toks) - 1, 5 + i], np.int32), *arrays,
            *cache.rows([seq, other]), np.array([True, True]))
        got.append(np.asarray(logits[0]))
    want = np.asarray(ref.logits(params, np.asarray(toks)[None], cfg))[0]
    np.testing.assert_allclose(np.stack(got), want[plen - 1:], rtol=5e-4,
                               atol=5e-5)


def _gap(params, cfg, prompt, toks):
    """The widest gap by which a served token's logit lies under the
    reference's best, in units of the position's largest |logit| (the
    benchmark's ``logit_gap``)."""
    seq = np.concatenate([prompt, toks])[None]
    rows = np.asarray(ref.logits(params, seq, cfg))[0][
        len(prompt) - 1:len(prompt) - 1 + len(toks)]
    chosen = rows[np.arange(len(toks)), toks]
    return float(((rows.max(-1) - chosen) / np.abs(rows).max(-1)).max())


def _serve(tiny, name):
    """Nine requests through two slots, every slot's state released and
    re-used several times, prompts in both buckets: the largest gap of
    any served token, and the engine's stats."""
    cfg, params, net = tiny
    eng = GenerationEngine(net, [16, 32], slots=2, chunk=4, cache_blocks=64,
                           cache_block_size=4, name=name)
    try:
        assert eng.cache.pool is not None and eng.cache.states is not None
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 128, n).astype(np.int32)
                   for n in (5, 16, 17, 30, 9, 32, 12, 25, 3)]
        futs = [eng.submit(p, max_new_tokens=11, greedy=True)
                for p in prompts]
        worst = 0.0
        for p, f in zip(prompts, futs):
            toks = f.result(timeout=120.0)
            assert len(toks) == 11
            worst = max(worst, _gap(params, cfg, p, toks))
        return worst, eng.stats()
    finally:
        eng.close()


def test_the_engine_serves_the_reference_through_reused_slots(tiny):
    """Every served token is the reference's first (its gap under the
    reference's best logit is rounding), the pool and the states are
    given back, and the state bytes are the store's."""
    worst, s = _serve(tiny, "jamba-test")
    assert worst < 1e-4
    assert s["prefills"] == 9 and s["failed"] == 0
    c = s["cache"]
    assert c["blocks_used"] == 0 and c["states"]["blocks_used"] == 0
    assert c["states"]["state_bytes_in_use"] == 0
    per = 3 * (16 * 128 + 3 * 128) * 4  # layers x (state + window)
    assert c["states"]["state_bytes_reserved"] == 3 * per


def test_a_slot_not_zeroed_fails_the_serving_check(tiny, monkeypatch):
    """The same check, with the store's zeroing of a slot it hands out
    taken away: a re-used slot starts from its last request's window and
    state, and the served tokens leave the reference."""
    real = StateStore._clear
    built = []

    def once(self, slot):  # the constructor's own call, then never
        if self not in built:
            built.append(self)
            real(self, slot)

    monkeypatch.setattr(StateStore, "_clear", once)
    worst, _ = _serve(tiny, "jamba-fault")
    assert worst > 1e-2


def test_the_manager_admits_on_what_the_pool_and_the_states_can_hold():
    spec = {"attention": {"layers": 1, "kv_heads": 1, "head_dim": 16},
            "mamba": {"layers": 2, "d_inner": 128, "d_state": 16,
                      "d_conv": 4}}
    cache = SequenceCache(spec, slots=1, max_seq=32, num_blocks=9,
                          block_size=4, name="m")
    assert [a.shape for a in cache.arrays()] == [
        (1, 9, 4, 16), (1, 9, 4, 16), (2, 2, 16, 128), (2, 2, 3, 1, 128)]
    with pytest.raises(KVCacheOOM):
        cache.allocate(64)              # the pool has 8 usable blocks
    assert cache.states.blocks_used() == 0  # and its state went back
    seq = cache.allocate(8)
    assert seq.table.blocks and seq.state == 0
    with pytest.raises(KVCacheOOM):
        cache.allocate(4)               # the one state is held
    assert cache.pool.blocks_used() == 2   # the refused one holds nothing
    tables, states = cache.rows([seq, None])
    assert tables.shape == (2, 8) and list(states) == [0, 1]
    # allocate zeroes the slot in both arrays
    cache.adopt([a + 1.0 for a in cache.arrays()])
    cache.release(seq)
    again = cache.allocate(4)
    assert not any(np.asarray(a[:, again.state]).any()
                   for a in cache.arrays()[2:])
    cache.release(again)
    assert cache.blocks_used() == 0 and cache.states.blocks_used() == 0
    with pytest.raises(ValueError, match="cache spec"):
        SequenceCache({**spec, "retention": {
            "layers": 1, "kv_heads": 1, "head_dim": 16}}, slots=1)


def test_the_spec_rebuilds_the_same_net():
    net = TransformerDecoderLM.from_preset("jamba_tiny", seed=11)
    again = TransformerDecoderLM(**net.spec()["decoder"])
    a, b = jax.tree_util.tree_leaves(net.params()), \
        jax.tree_util.tree_leaves(again.params())
    assert len(a) == len(b) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))
    # Mamba's own initialisation of its memory
    run = net.params()["layers"][0]
    np.testing.assert_allclose(np.exp(np.asarray(run["a_log"][0, :, 0])),
                               np.arange(1, 17), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(run["b_dt"], np.float64)))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert (np.asarray(run["d_skip"]) == 1).all()
