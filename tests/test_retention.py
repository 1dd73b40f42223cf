"""Power retention (``ops/retention.py``), the config-driven decoder's
retention layers and the per-slot state store, on the CPU at a tiny
size: the feature map, the three forms of the mathematics against each
other and against the benchmark's plain reference (its attention form,
``chipbench/references/retention_lm.py``, which imports nothing of the
program), and prefill-then-decode through ``GenerationEngine`` and the
state store against that reference's full forward pass."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import retention_lm as glue
from chipbench.references import retention_lm as ref
from mxnet_tpu.ops import retention as R
from mxnet_tpu.serving import (
    GenerationEngine,
    KVCacheOOM,
    SequenceCache,
    StateStore,
    TransformerDecoderLM,
)
from mxnet_tpu.serving import decoder

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, KVH, D, T = 4, 2, 16, 37


def _tiny_cfg():
    with open(os.path.join(_ROOT, "chipbench", "tests", "tiny",
                           "brumby_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    """Prefill's chunk is the program's constant, 256; at 8 a tiny
    prompt still crosses chunks."""
    monkeypatch.setattr(decoder, "RETENTION_CHUNK", 8)


@pytest.fixture(scope="module")
def seq():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(T, KVH, D), jnp.float32)
    v = jnp.asarray(rng.randn(T, KVH, D), jnp.float32)
    log_g = jnp.asarray(-np.abs(rng.randn(T, KVH)) * 0.05, jnp.float32)
    return q, k, v, log_g


@pytest.fixture(scope="module")
def attention_form(seq):
    """The reference's attention form of the layer's mixer."""
    q, k, v, log_g = seq
    o = ref._retain(q.reshape(T, KVH, H // KVH, D), k, v, log_g,
                    lambda a, amax=None: a)
    return np.asarray(o.reshape(T, H, D))


def _zero_state():
    return (jnp.zeros((KVH, D, R.feature_dim(D))),
            jnp.zeros((KVH, R.feature_rows(D), D)))


@pytest.mark.parametrize("d", [2, 4, 16, 128])
def test_phi_is_the_symmetric_second_power(d):
    rng = np.random.RandomState(d)
    a, b = (jnp.asarray(rng.randn(3, d), jnp.float32) for _ in range(2))
    got = np.asarray(jnp.sum(R.phi(a) * R.phi(b), axis=(-2, -1)))
    want = np.asarray(jnp.sum(a * b, -1)) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert R.phi(a).shape == (3, d // 2 + 1, d)
    assert R.feature_dim(128) == 8320  # the triangle has 8,256


# chunks that do and do not divide the length, one chunk, a chunk
# longer than the sequence
@pytest.mark.parametrize("chunk", [1, 5, 8, 16, 37, 64])
def test_chunked_form_is_the_attention_form(seq, attention_form, chunk):
    o, _ = R.power_retention_chunked(*seq, _zero_state(), T, chunk)
    np.testing.assert_allclose(np.asarray(o), attention_form, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 48])
def test_a_padded_bucket_gives_the_state_at_the_real_length(seq, chunk):
    """Positions at or beyond ``length`` neither decay the state nor add
    to it, whatever they hold."""
    o, state = R.power_retention_chunked(*seq, _zero_state(), T, 8)

    def pad(a, value):
        return jnp.pad(a, ((0, 48 - T),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=value)

    q, k, v, log_g = seq
    o2, state2 = R.power_retention_chunked(
        pad(q, 1.0), pad(k, 2.0), pad(v, 3.0), pad(log_g, -0.3),
        _zero_state(), T, chunk)
    np.testing.assert_allclose(np.asarray(o2[:T]), np.asarray(o), rtol=1e-4,
                               atol=1e-5)
    assert not np.asarray(o2[T:]).any()
    for a, b in zip(state, state2):  # another chunking: another rounding
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def test_a_state_carried_between_two_calls_is_one_call(seq):
    """Chunked prefill in two pieces (the entering state is the first
    piece's leaving state) is the whole prompt at once."""
    q, k, v, log_g = seq
    whole, state = R.power_retention_chunked(*seq, _zero_state(), T, 8)
    a, mid = R.power_retention_chunked(q[:20], k[:20], v[:20], log_g[:20],
                                       _zero_state(), 20, 8)
    b, end = R.power_retention_chunked(q[20:], k[20:], v[20:], log_g[20:],
                                       mid, T - 20, 8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b])),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)
    for x, y in zip(state, end):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("step", ["jnp", "pallas_interpret"])
def test_the_recurrence_is_the_attention_form(seq, attention_form, step):
    """Token by token through a store of three slots: two rows live on
    states 2 and 1, one row not live. The live rows reproduce the
    attention form and leave the chunked form's state; the dead row, the
    other layer and the unused slot are never touched."""
    q, k, v, log_g = seq
    fn = R._jnp_step if step == "jnp" else (
        lambda *a: R._pallas_step(*a, interpret=True))
    s_shape, z_shape = R.state_shapes(2, 3, KVH, D)
    S, z = jnp.zeros(s_shape), jnp.zeros(z_shape)
    slots = jnp.asarray([2, 0, 1], jnp.int32)
    active = jnp.asarray([True, False, True])
    outs = []
    for t in range(T):
        rows = [jnp.stack([a[t]] * 3) for a in (q, k, v, log_g)]
        o, S, z = fn(*rows, S, z, slots, active, 1)
        outs.append(np.asarray(o))
    outs = np.stack(outs)
    np.testing.assert_allclose(outs[:, 0], attention_form, rtol=1e-4,
                               atol=2e-5)
    np.testing.assert_allclose(outs[:, 2], attention_form, rtol=1e-4,
                               atol=2e-5)
    assert not outs[:, 1].any()
    _, (s_want, z_want) = R.power_retention_chunked(*seq, _zero_state(), T, 8)
    for slot in (2, 1):
        np.testing.assert_allclose(np.asarray(S[1, slot]),
                                   np.asarray(s_want), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(z[1, slot]),
                                   np.asarray(z_want), rtol=1e-4, atol=1e-4)
    assert not np.asarray(S[0]).any() and not np.asarray(S[1, 0]).any()
    if step != "jnp":  # the kernel never visits the null slot either
        assert not np.asarray(S[1, 3]).any()


def test_a_step_with_no_live_slot_changes_no_state(seq):
    q, k, v, log_g = seq
    s_shape, z_shape = R.state_shapes(1, 2, KVH, D)
    rng = np.random.RandomState(1)
    S = jnp.asarray(rng.randn(*s_shape), jnp.float32)
    z = jnp.asarray(rng.randn(*z_shape), jnp.float32)
    rows = [jnp.stack([a[0]] * 2) for a in (q, k, v, log_g)]
    o, S1, z1 = R._pallas_step(*rows, S, z, jnp.asarray([0, 1], jnp.int32),
                               jnp.zeros(2, bool), 0, interpret=True)
    assert not np.asarray(o).any()
    # only the null slot may have been written
    assert (np.asarray(S1)[:, :2] == np.asarray(S)[:, :2]).all()
    assert (np.asarray(z1)[:, :2] == np.asarray(z)[:, :2]).all()


# the prefill kernel in the interpreter, at a whole 128-lane head, small
# chunks and two KV heads of two queries each: (bucket, length, chunk,
# entering state)
_PREFILL = {
    "whole_chunks": (64, 64, 16, False),
    "partial_last_chunk": (64, 50, 16, False),
    "shorter_than_a_chunk": (64, 10, 16, False),
    "wholly_padded_chunks": (96, 30, 16, False),
    "entering_state": (64, 57, 32, True),
}


def _prefill_operands(T, entering, seed):
    rng = np.random.RandomState(seed)
    d = 128
    q = jnp.asarray(rng.randn(T, H, d) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(T, KVH, d) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(T, KVH, d), jnp.bfloat16)
    log_g = jnp.asarray(-np.abs(rng.randn(T, KVH)) * 0.05, jnp.float32)
    s_shape, z_shape = (KVH, d, R.feature_dim(d)), (KVH, R.feature_rows(d), d)
    if entering:
        state = (jnp.asarray(rng.randn(*s_shape) * 0.1, jnp.float32),
                 jnp.asarray(np.abs(rng.randn(*z_shape)) + 1.0, jnp.float32))
    else:
        state = (jnp.zeros(s_shape), jnp.zeros(z_shape))
    return q, k, v, log_g, state


@pytest.mark.parametrize("case", sorted(_PREFILL))
def test_the_prefill_kernel_is_the_jnp_form(case):
    """``mxtpu_retention_prefill`` in the Pallas interpreter against the
    jnp form on the same bfloat16 operands: outputs (zero at and past
    ``length``) and the leaving state. The kernel rounds the update's
    operands and the chunk's attention weights to bfloat16 on their way
    into the matrix unit, where the jnp form on the CPU keeps them in
    float32 (on the chip both round: ``tests_tpu``), so ``o`` and ``S``
    agree to a hundredth of their scale (measured: 1.2e-3..4.2e-3); the
    normaliser's update is float32 in both."""
    T, length, chunk, entering = _PREFILL[case]
    args = _prefill_operands(T, entering, T + length)
    o, (S, z) = R._pallas_chunked(*args, length, chunk, interpret=True)
    want_o, (want_S, want_z) = R._jnp_chunked(*args, length, chunk)
    assert o.shape == want_o.shape and S.dtype == z.dtype == jnp.float32
    assert not np.asarray(o)[length:].any()
    for got, want, tol in ((o, want_o, 1e-2), (S, want_S, 1e-2),
                           (z, want_z, 1e-5)):
        got, want = np.asarray(got), np.asarray(want)
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


@pytest.mark.parametrize("dtype,path", [("float32", "jnp"),
                                        ("bfloat16", "kernel")])
def test_the_chunked_form_takes_its_path_from_the_operands(
        monkeypatch, dtype, path):
    """Where the kernel could run (a backend that is not the CPU, heads
    of 128 lanes, chunks of them), bfloat16 operands take it and float32
    operands keep the jnp form, in float32 at ``highest``."""
    monkeypatch.setattr(R, "_use_pallas", lambda d: True)
    taken = []

    def kernel(*args):
        taken.append(args[0].dtype)
        return R._jnp_chunked(*args)

    monkeypatch.setattr(R, "_pallas_chunked", kernel)
    q, k, v, log_g, state = _prefill_operands(40, False, 3)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    o, _ = R.power_retention_chunked(q, k, v, log_g, state, 33, 128)
    assert taken == ([jnp.dtype(dtype)] if path == "kernel" else [])
    want, _ = R._jnp_chunked(q, k, v, log_g, state, 33, 128)
    assert (np.asarray(o) == np.asarray(want)).all()


# ---------------------------------------------------------------------------
# the decoder's retention layers against the plain reference
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
    want = {**decoder.PRESETS["brumby_tiny"], "seed": 0, "dtype": "float32"}
    got = net.spec()["decoder"]
    assert {k: got[k] for k in want if k != "layer_kinds"} == \
        {k: v for k, v in want.items() if k != "layer_kinds"}
    assert got["layer_kinds"] == ["retention"] * 2
    assert net.cache_spec() == {"retention": {
        "layers": 2, "kv_heads": 2, "head_dim": 16}}


def test_the_dense_oracle_is_the_reference(tiny):
    cfg, params, net = tiny
    tokens = np.random.RandomState(2).randint(0, 128, (2, 29))
    got = np.asarray(net.forward_fn()(net.params(), jnp.asarray(tokens)))
    want = np.asarray(ref.logits(params, tokens, cfg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# one prompt through two buckets: padding must not reach the state
@pytest.mark.parametrize("bucket", [16, 32])
def test_prefill_then_decode_logits_are_the_reference(tiny, bucket):
    """The net's faces by hand against a state store: the prompt's last
    logits from prefill, then every decode step's, elementwise against
    the reference's full forward pass over prompt and answer."""
    cfg, params, net = tiny
    rng = np.random.RandomState(bucket)
    plen, n_out = 13, 9
    prompt = rng.randint(0, 128, plen)
    store = StateStore(R.state_shapes(2, 2, 2, 16))
    slot = store.allocate()
    other = store.allocate()  # a second live row, on another prompt
    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.decode_step_fn())
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = prompt
    padded[0, plen:] = 99  # what lies in the padding must not matter
    logits, *arrays = prefill(net.params(), padded, *store.arrays(),
                              np.array([slot], np.int32),
                              np.array([plen], np.int32))
    pad2 = np.zeros((1, bucket), np.int32)
    pad2[0, :5] = rng.randint(0, 128, 5)
    _, *arrays = prefill(net.params(), pad2, *arrays,
                         np.array([other], np.int32), np.array([5], np.int32))
    got = [np.asarray(logits[0])]
    seq = list(prompt)
    for i in range(n_out):
        seq.append(int(got[-1].argmax()))
        logits, *arrays = step(
            net.params(), np.array([seq[-1], 3], np.int32),
            np.array([len(seq) - 1, 5 + i], np.int32), *arrays,
            np.array([slot, other], np.int32), np.array([True, True]))
        got.append(np.asarray(logits[0]))
    want = np.asarray(ref.logits(params, np.asarray(seq)[None], cfg))[0]
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


def test_the_engine_serves_the_reference_through_reused_slots(tiny):
    """Two slots, nine requests in flight at once: every slot's state is
    released and re-used several times, prompts land in both buckets,
    and every served token is the reference's first (its gap under the
    reference's best logit is rounding)."""
    cfg, params, net = tiny
    eng = GenerationEngine(net, [16, 32], slots=2, chunk=4, name="ret-test")
    try:
        assert eng.cache.pool is None and eng.cache.num_blocks == 3
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 128, n).astype(np.int32)
                   for n in (5, 16, 17, 30, 9, 32, 12, 25, 3)]
        futs = [eng.submit(p, max_new_tokens=11, greedy=True)
                for p in prompts]
        for p, f in zip(prompts, futs):
            toks = f.result(timeout=120.0)
            assert len(toks) == 11
            assert _gap(params, cfg, p, toks) < 1e-4
        s = eng.stats()
        assert s["prefills"] == 9 and s["failed"] == 0
        assert s["cache"]["blocks_used"] == 0
        assert s["cache"]["state_bytes_in_use"] == 0
        per = 2 * 2 * (16 * 144 + 9 * 16) * 4  # layers x heads x (S + z)
        assert s["cache"]["state_bytes_reserved"] == 3 * per
    finally:
        eng.close()


def test_a_net_of_both_layer_kinds_serves_its_own_oracle():
    """An attention layer over the paged pool and a retention layer over
    the state store in one net, one engine, one cache manager."""
    net = TransformerDecoderLM.from_preset(
        "brumby_tiny", seed=3, layer_kinds=["attention", "retention"])
    assert set(net.cache_spec()) == {"attention", "retention"}
    fwd = jax.jit(net.forward_fn())
    eng = GenerationEngine(net, [16, 32], slots=2, chunk=4, cache_blocks=64,
                           cache_block_size=4, name="mixed-test")
    try:
        assert eng.cache.pool is not None and eng.cache.states is not None
        rng = np.random.RandomState(1)
        for plen in (5, 16, 27, 9, 30):
            p = rng.randint(0, 128, plen).astype(np.int32)
            toks = eng.predict(p, max_new_tokens=10, greedy=True,
                               timeout=120.0)
            logits = np.asarray(fwd(net.params(),
                                    np.concatenate([p, toks])[None]))[0]
            want = logits[plen - 1:plen - 1 + len(toks)].argmax(-1)
            assert list(toks) == list(want)
        s = eng.stats()["cache"]
        assert s["blocks_used"] == 0 and s["states"]["blocks_used"] == 0
    finally:
        eng.close()


def test_the_spec_rebuilds_the_same_net():
    net = TransformerDecoderLM.from_preset("brumby_tiny", seed=11)
    again = TransformerDecoderLM(**net.spec()["decoder"])
    a, b = jax.tree_util.tree_leaves(net.params()), \
        jax.tree_util.tree_leaves(again.params())
    assert len(a) == len(b) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))
    # its own gates remember too
    forget = 1 / (1 + np.exp(np.asarray(net.params()["layers"]["bg"],
                                        np.float64)))
    assert 1e-4 * 0.99 <= forget.min() and forget.max() <= 1e-2 * 1.01


def test_layers_are_stacked_where_all_are_retention_layers():
    stacked = TransformerDecoderLM.from_preset("brumby_tiny")
    assert stacked.scan_layers
    assert stacked.params()["layers"]["wq"].shape[0] == 2
    for kinds in ("attention", ["retention", "attention"]):
        net = TransformerDecoderLM(num_layers=2, layer_kinds=kinds)
        assert not net.scan_layers and len(net.params()["layers"]) == 2
    with pytest.raises(ValueError, match="layer_kinds"):
        TransformerDecoderLM(num_layers=2, layer_kinds=["attention"])


# ---------------------------------------------------------------------------
# the state store and the manager
# ---------------------------------------------------------------------------

def test_the_state_store_zeroes_a_slot_it_hands_out():
    store = StateStore(R.state_shapes(2, 2, 2, 16), name="s")
    assert store.num_blocks == 3 and store.blocks_used() == 0
    a = store.allocate()
    store.adopt(*(x.at[:, a].set(7.0) for x in store.arrays()))
    store.release(a)
    b, c = store.allocate(), store.allocate()
    assert a in (b, c)
    assert not any(np.asarray(x[:, a]).any() for x in store.arrays())
    with pytest.raises(KVCacheOOM):
        store.allocate()
    st = store.stats()
    assert st["blocks_used"] == 2 and st["occupancy"] == 1.0
    assert st["state_bytes_in_use"] == 2 * store.bytes_per_state
    assert st["state_bytes_reserved"] == 3 * store.bytes_per_state


def test_the_manager_admits_on_what_every_part_can_hold():
    spec = {"attention": {"layers": 1, "kv_heads": 2, "head_dim": 16},
            "retention": {"layers": 1, "kv_heads": 2, "head_dim": 16}}
    cache = SequenceCache(spec, slots=1, max_seq=32, num_blocks=9,
                          block_size=4, name="m")
    assert len(cache.arrays()) == 4
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
    cache.written(seq, 8)
    cache.ensure(seq, 20)
    assert cache.pool.blocks_used() == 5 and seq.table.length == 8
    cache.release(seq)
    cache.release(seq)                  # idempotent
    assert cache.blocks_used() == 0 and cache.states.blocks_used() == 0
    with pytest.raises(ValueError, match="cache spec"):
        SequenceCache({"conv": {}}, slots=1)


def test_under_a_bfloat16_net_the_chunked_form_keeps_bfloat16_operands(seq):
    """The products take the activations' width: bfloat16 queries meet
    a state rounded to bfloat16 (accumulated in float32). Against the
    float32 attention form of the same rounded inputs the outputs lie
    within bfloat16's rounding of the values' scale, not beyond it."""
    q, k, v, log_g = seq
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    o, state = R.power_retention_chunked(qb, kb, vb, log_g, _zero_state(),
                                         T, 8)
    assert o.dtype == jnp.float32 and state[0].dtype == jnp.float32
    want = ref._retain(
        qb.astype(jnp.float32).reshape(T, KVH, H // KVH, D),
        kb.astype(jnp.float32), vb.astype(jnp.float32), log_g,
        lambda a, amax=None: a).reshape(T, H, D)
    err = np.abs(np.asarray(o) - np.asarray(want)).max()
    assert 1e-5 < err < 0.05 * float(np.abs(np.asarray(want)).max())
