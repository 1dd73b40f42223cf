"""Autoregressive decode fast path (``mxnet_tpu.serving.generation``):
the paged-cache decode must match a dense full-context recompute
token-for-token, spend at most ~1 dispatch per chunk of tokens, never
retrace after warmup, and keep the continuous-batching contracts
(late join without drain, typed refusals, lifecycle).

One module-scoped engine carries most tests — the sealed executables
compile once; every test asserts on stat DELTAS so ordering never
matters."""

import time

import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.serving import (
    EngineClosed,
    GenerationEngine,
    LocalReplica,
    ModelRepository,
    ReplicaDead,
    RequestCancelled,
    RequestTimeout,
    RetraceForbidden,
    ServingError,
    TransformerDecoderLM,
    sample_tokens,
)

VOCAB, MAX_SEQ, BUCKETS, SLOTS, CHUNK = 48, 64, [4, 8, 16], 4, 4


@pytest.fixture(autouse=True)
def _telemetry_state():
    obs.set_enabled(False)
    obs.reset()
    yield
    obs.set_enabled(False)
    obs.reset()


@pytest.fixture(scope="module")
def net():
    return TransformerDecoderLM(vocab_size=VOCAB, num_layers=2,
                                d_model=32, num_heads=4, kv_heads=2,
                                max_seq=MAX_SEQ, seed=0)


@pytest.fixture(scope="module")
def eng(net):
    e = GenerationEngine(net, BUCKETS, slots=SLOTS, chunk=CHUNK,
                         queue_cap=64, cache_blocks=96,
                         cache_block_size=4, name="gen-test")
    yield e
    e.close()


def _assert_matches_dense(net, prompt, toks, fwd=None):
    """Dense full-context recompute check: ONE causal forward over
    prompt+generated must greedy-predict every generated token from its
    own prefix (equivalent to re-running the dense net per step — the
    first mismatch fails exactly where a stepwise oracle would).
    ``fwd`` is the net's forward, jitted, where a caller keeps one."""
    fwd, params = fwd or net.forward_fn(), net.params()
    seq = np.array([int(t) for t in prompt] + [int(t) for t in toks],
                   np.int32)
    logits = np.asarray(fwd(params, seq[None]))
    want = logits[0, len(prompt) - 1:len(seq) - 1].argmax(-1)
    assert [int(t) for t in toks] == [int(t) for t in want]


def _drain(eng, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while (eng.active_slots() or eng.queue_depth()) \
            and time.perf_counter() < deadline:
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# correctness vs dense recompute
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prompt,n", [
    ([3, 1, 4], 10),            # bucket 4, crosses 2 chunk boundaries
    ([7, 2, 9, 11, 5, 40], 9),  # bucket 8, partial final chunk
    (list(range(2, 15)), 17),   # bucket 16, multi-block prompt
])
def test_greedy_decode_matches_dense_recompute(eng, net, prompt, n):
    toks = eng.predict(np.array(prompt, np.int32),
                       max_new_tokens=n, greedy=True, timeout=60.0)
    assert toks.dtype == np.int32
    assert len(toks) == n
    _assert_matches_dense(net, prompt, toks)


@pytest.mark.parametrize("cache_blocks", [40, 513])
def test_greedy_tokens_do_not_depend_on_the_pool_size(net, cache_blocks):
    """The served tokens are the dense recompute's under a small pool
    and under one thirteen times larger: the pool is addressed in
    place, through the tables, whatever its size."""
    e = GenerationEngine(net, BUCKETS, slots=SLOTS, chunk=CHUNK,
                         cache_blocks=cache_blocks, cache_block_size=4,
                         name=f"gen-pool{cache_blocks}")
    try:
        for prompt, n in [([3, 1, 4], 10), (list(range(2, 15)), 17)]:
            toks = e.predict(np.array(prompt, np.int32), max_new_tokens=n,
                             greedy=True, timeout=60.0)
            assert len(toks) == n
            _assert_matches_dense(net, prompt, toks)
    finally:
        e.close()


def test_pool_temp_share_is_reported_after_deploy(eng, caplog):
    """Deploy measures every executable's temporaries against one
    pool's bytes: ``stats()`` holds the largest, each compile record
    its own, and a deploy over 0.25 warns once, naming the executable.
    (A toy pool is smaller than the logits, so the toy engines warn;
    at a deployment's pool the share is a few per cent.)"""
    import logging

    share = eng.stats()["pool_temp_share"]
    assert isinstance(share, float) and np.isfinite(share) and share > 0
    obs.set_enabled(True)
    net2 = TransformerDecoderLM(vocab_size=VOCAB, num_layers=1,
                                d_model=32, num_heads=4, max_seq=MAX_SEQ)
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.serving.generation"):
        e = GenerationEngine(net2, [4, 8], slots=2, chunk=2,
                             cache_blocks=8, cache_block_size=4,
                             name="gen-temp", autostart=False)
    try:
        recs = [ev["args"] for ev in obs.tracer().events()
                if ev["name"] == "serving.compile"
                and ev["args"]["model"] == "gen-temp"]
        assert [r["bucket"] for r in recs] == [
            "decode_chunk", "decode_prefill[4]", "decode_prefill[8]"]
        assert max(r["pool_temp_share"] for r in recs) \
            == e.stats()["pool_temp_share"] > 0.25
        warned = [r.getMessage() for r in caplog.records
                  if "gen-temp" in r.getMessage()]
        assert len(warned) == 1
        worst = max(recs, key=lambda r: r["pool_temp_share"])["bucket"]
        assert f"executable {worst} " in warned[0]
    finally:
        e.close()


def test_batch_axis_squeeze_and_validation(eng):
    a = eng.predict(np.array([[5, 6, 7]], np.int32),
                    max_new_tokens=3, timeout=60.0)
    b = eng.predict(np.array([5, 6, 7], np.int32),
                    max_new_tokens=3, timeout=60.0)
    assert list(a) == list(b)
    with pytest.raises(ServingError):
        eng.submit(np.zeros((2, 3), np.int32))  # real batches: one each
    with pytest.raises(ServingError):
        eng.submit(np.array([], np.int32))


def test_eos_stops_early_and_is_included(eng, net):
    prompt = [3, 1, 4]
    ref = eng.predict(np.array(prompt, np.int32), max_new_tokens=12,
                      greedy=True, timeout=60.0)
    _assert_matches_dense(net, prompt, ref)  # trusted greedy reference
    eos = int(ref[5])
    want = [int(t) for t in ref[:list(ref).index(eos) + 1]]
    toks = eng.predict(np.array(prompt, np.int32), max_new_tokens=12,
                       eos=eos, timeout=60.0)
    assert [int(t) for t in toks] == want
    assert toks[-1] == eos


def test_max_new_clipped_to_max_seq(eng):
    prompt = np.arange(1, 14, dtype=np.int32)  # plen 13, bucket 16
    toks = eng.predict(prompt, max_new_tokens=10_000, timeout=120.0)
    assert len(toks) == MAX_SEQ - 13


def test_sampling_first_token_is_seed_deterministic(eng):
    """The documented reproducibility contract: the prefill token is
    drawn from the request's own seed (bit-stable run to run); later
    tokens ride the engine-level per-chunk key stream."""
    kw = dict(max_new_tokens=8, greedy=False, temperature=0.8,
              top_k=12, seed=7, timeout=60.0)
    a = eng.predict(np.array([9, 8, 7], np.int32), **kw)
    b = eng.predict(np.array([9, 8, 7], np.int32), **kw)
    assert a[0] == b[0]
    c = eng.predict(np.array([9, 8, 7], np.int32),
                    **{**kw, "seed": 1234})
    for toks in (a, b, c):
        assert np.all(toks >= 0) and np.all(toks < VOCAB)


# ---------------------------------------------------------------------------
# on-device sampler unit tests
# ---------------------------------------------------------------------------

def test_sample_tokens_policies():
    import jax

    rs = np.random.RandomState(0)
    logits = np.asarray(rs.randn(3, 16), np.float32)
    key = jax.random.PRNGKey(0)
    ones = np.ones(3, np.float32)
    zeros_i = np.zeros(3, np.int32)
    amax = logits.argmax(-1)

    def draw(temperature=ones, top_k=zeros_i, top_p=ones,
             greedy=np.zeros(3, bool), k=key):
        return np.asarray(sample_tokens(
            np.asarray(logits), k, np.asarray(temperature),
            np.asarray(top_k), np.asarray(top_p), np.asarray(greedy)))

    assert np.array_equal(draw(greedy=np.ones(3, bool)), amax)
    # top_k=1 collapses to argmax no matter the temperature
    assert np.array_equal(
        draw(temperature=ones * 5.0, top_k=np.ones(3, np.int32)), amax)
    # a tiny nucleus keeps only the argmax (it always survives)
    assert np.array_equal(draw(top_p=ones * 1e-6), amax)
    # per-row policies compose inside ONE call
    mixed = draw(greedy=np.array([True, False, False]),
                 top_k=np.array([0, 1, 0], np.int32))
    assert mixed[0] == amax[0] and mixed[1] == amax[1]
    # seeded: same key -> same draw; keys differ -> free to differ
    t = ones * 3.0
    assert np.array_equal(draw(temperature=t), draw(temperature=t))
    assert np.all(draw() >= 0) and np.all(draw() < 16)


def _sample_tokens_unconditional(logits, key, temperature, top_k, top_p,
                                 greedy, live=None):
    """The sampler as it was before it branched: every row filtered and
    drawn, the greedy rows' draws then thrown away. The plain reference
    for what a row gets (``live`` is taken, so that it can stand in for
    the module's, and ignored)."""
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kk = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    kth = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
    limited = jnp.where(scaled < kth, -jnp.inf, scaled)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = mass_before < top_p[:, None]
    thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    limited = jnp.where(scaled < thresh, -jnp.inf, limited)
    drawn = jax.random.categorical(key, limited, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1),
                     drawn).astype(jnp.int32)


def _sampler_operands(greedy):
    """Six rows over a vocabulary of 40, a policy of its own each."""
    import jax.numpy as jnp

    rs = np.random.RandomState(7)
    return (jnp.asarray(rs.randn(6, 40), jnp.float32),
            jnp.asarray(rs.uniform(0.5, 3.0, 6), jnp.float32),
            jnp.asarray(rs.choice([0, 3, 12], 6), jnp.int32),
            jnp.asarray(rs.choice([1.0, 0.9, 0.5], 6), jnp.float32),
            jnp.asarray(greedy, bool))


@pytest.mark.parametrize("live", [None, [True, True, False, True, True,
                                         False]],
                         ids=["every_row_live", "two_rows_dead"])
@pytest.mark.parametrize("greedy", [[True] * 6,
                                    [True, False, True, True, False, True]],
                         ids=["all_greedy", "mixed"])
def test_sample_tokens_returns_what_the_unconditional_sampler_did(
        greedy, live):
    """All greedy (the argmax branch) or mixed with a live sampled row
    (the filter's branch): every row gets the id the unconditional
    sampler gave it under the same key, the dead rows too."""
    import jax

    logits, temp, top_k, top_p, greedy = _sampler_operands(greedy)
    live = None if live is None else np.asarray(live)
    for seed in range(4):
        key = jax.random.PRNGKey(seed)
        got = sample_tokens(logits, key, temp, top_k, top_p, greedy, live)
        want = _sample_tokens_unconditional(logits, key, temp, top_k,
                                            top_p, greedy)
        assert got.dtype == want.dtype == np.int32
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_dead_sampled_row_does_not_engage_the_filter():
    """The only rows that ask for a draw are dead (a slot keeps its last
    request's policy): the live rows get the argmax, as from the
    unconditional sampler, and so do the dead ones, because the filter
    never ran. In the compiled sampler the vocabulary's sort lies in a
    conditional's branch and nowhere else."""
    import jax
    from conftest import sorts_by_conditional

    logits, temp, top_k, top_p, greedy = _sampler_operands(
        [True, True, False, True, True, False])
    live = np.asarray(greedy)  # exactly the rows that draw are dead
    key = jax.random.PRNGKey(11)
    got = np.asarray(sample_tokens(logits, key, temp, top_k, top_p, greedy,
                                   live))
    want = np.asarray(_sample_tokens_unconditional(logits, key, temp, top_k,
                                                   top_p, greedy))
    assert np.array_equal(got[live], want[live])
    assert np.array_equal(got, np.asarray(logits).argmax(-1))
    assert not np.array_equal(got, want)  # the dead rows' draws are gone
    hlo = jax.jit(sample_tokens).lower(
        logits, key, temp, top_k, top_p, greedy, live).compile().as_text()
    outside, inside = sorts_by_conditional(hlo)
    assert outside == [] and len(inside) >= 1
    hlo = jax.jit(_sample_tokens_unconditional).lower(
        logits, key, temp, top_k, top_p, greedy).compile().as_text()
    assert len(sorts_by_conditional(hlo)[0]) >= 1  # the check can fail


_SAMPLED = dict(max_new_tokens=11, greedy=False, temperature=1.3, top_k=9,
                top_p=0.9, seed=77)


@pytest.mark.parametrize("company", ["alone", "among_greedy"])
def test_sampled_request_gets_the_unconditional_samplers_tokens(
        net, monkeypatch, company):
    """A greedy burst first (chunks of the argmax branch), then a
    sampled request, alone or sharing its chunks with greedy ones: token
    for token what an engine built on the unconditional sampler serves,
    because the key is split once a step whichever branch runs. The
    counter reads the chunks that held a live sampled slot."""
    from mxnet_tpu.serving import generation

    def run():
        e = GenerationEngine(net, BUCKETS, slots=SLOTS, chunk=CHUNK,
                             cache_blocks=96, cache_block_size=4, seed=5,
                             name=f"gen-{company}")
        try:
            burst = [e.predict(np.array([3, 1, 4], np.int32),
                               max_new_tokens=9, greedy=True, timeout=60.0)]
            before = e.stats()
            assert before["filtered_chunks"] == 0
            futs = [e.submit(np.array([7, 2, 9, 11], np.int32), **_SAMPLED)]
            if company == "among_greedy":
                # admitted behind it, in the same turn of the scheduler or
                # the next: they never run a chunk before it does
                futs += [e.submit(np.array([5, 3, i], np.int32),
                                  max_new_tokens=14, greedy=True)
                         for i in range(2)]
            outs = [f.result(120.0) for f in futs]
            after = e.stats()
            return burst + outs, before, after
        finally:
            e.close()

    got, before, after = run()
    chunks = after["decode_chunks"] - before["decode_chunks"]
    filtered = after["filtered_chunks"] - before["filtered_chunks"]
    # 10 tokens after the prefill's: ceil(10 / 4) chunks hold the slot
    assert filtered == -(-(_SAMPLED["max_new_tokens"] - 1) // CHUNK)
    assert filtered == chunks if company == "alone" else filtered < chunks
    monkeypatch.setattr(generation, "sample_tokens",
                        _sample_tokens_unconditional)
    want, _, _ = run()
    for g, w in zip(got, want):
        assert list(g) == list(w)
    assert len(got[1]) == _SAMPLED["max_new_tokens"]


def test_a_finished_sampled_request_leaves_no_filter_behind(eng, net):
    """Nothing resets a slot's policy when its request leaves: after a
    sampled request has run in a slot, an all-greedy burst over every
    slot counts no filtered chunk, and its tokens are the dense
    recompute's."""
    st0 = eng.stats()
    toks = eng.predict(np.array([9, 8, 7], np.int32), timeout=60.0,
                       **_SAMPLED)
    assert len(toks) == _SAMPLED["max_new_tokens"]
    _drain(eng)
    st1 = eng.stats()
    assert st1["filtered_chunks"] - st0["filtered_chunks"] \
        == st1["decode_chunks"] - st0["decode_chunks"] > 0
    assert not eng._greedy.all()  # the stale policy is still there
    prompts = [[3, 1, 4], [2, 7], [1, 8, 2, 8], [6, 6, 6], [4, 4], [9, 1]]
    futs = [eng.submit(np.array(p, np.int32), max_new_tokens=10,
                       greedy=True) for p in prompts]
    outs = [f.result(120.0) for f in futs]
    st2 = eng.stats()
    assert st2["decode_chunks"] > st1["decode_chunks"]
    assert st2["filtered_chunks"] == st1["filtered_chunks"]
    for p, o in zip(prompts, outs):
        _assert_matches_dense(net, p, o)


# ---------------------------------------------------------------------------
# sealed-engine + dispatch-budget contracts
# ---------------------------------------------------------------------------

def test_over_bucket_prompt_is_typed_refusal_not_retrace(eng):
    st0 = eng.stats()
    with pytest.raises(RetraceForbidden, match="no prefill bucket"):
        eng.submit(np.arange(17, dtype=np.int32))  # > max bucket 16
    with pytest.raises(RetraceForbidden):
        eng.submit(np.zeros(MAX_SEQ, np.int32))    # prompt fills max_seq
    st1 = eng.stats()
    assert st1["refused"] - st0["refused"] == 2
    assert st1["compiles"] == st0["compiles"]


def test_single_dispatch_chunk_budget(eng):
    """One request of N tokens costs 1 prefill + ~ceil((N-1)/chunk)
    chunk dispatches — the whole point of the fast path. Checked on the
    engine's own counters AND the XLA dispatch telemetry."""
    obs.set_enabled(True)
    d0c = obs.XLA_DISPATCH_TOTAL.value(site="decode_chunk")
    d0p = obs.XLA_DISPATCH_TOTAL.value(site="decode_prefill")
    st0 = eng.stats()
    n = 9  # prefill token + 8 more = 2 full chunks of 4
    toks = eng.predict(np.array([2, 4, 6], np.int32),
                       max_new_tokens=n, greedy=True, timeout=60.0)
    assert len(toks) == n
    st1 = eng.stats()
    assert st1["prefills"] - st0["prefills"] == 1
    chunks = st1["decode_chunks"] - st0["decode_chunks"]
    assert chunks == -(-(n - 1) // CHUNK)  # exactly ceil, no slack
    assert obs.XLA_DISPATCH_TOTAL.value(site="decode_chunk") - d0c \
        == chunks
    assert obs.XLA_DISPATCH_TOTAL.value(site="decode_prefill") - d0p == 1
    assert st1["compiles"] == st0["compiles"]


def test_ragged_traffic_never_retraces_and_frees_cache(eng, net):
    """A burst of mixed prompt lengths / budgets / sampling policies:
    zero compiles after warmup, zero retraces, amortized dispatch cost
    under 1/chunk + scheduling slack, and the cache drains to empty."""
    st0 = eng.stats()
    rs = np.random.RandomState(3)
    futs, oracle_checks = [], []
    for i in range(14):
        plen = int(rs.choice([3, 4, 6, 8, 11, 16]))
        prompt = rs.randint(0, VOCAB, plen).astype(np.int32)
        n = int(rs.choice([2, 5, 8, 13]))
        if i % 3 == 0:
            futs.append(eng.submit(prompt, max_new_tokens=n, greedy=True))
            oracle_checks.append((len(futs) - 1, list(prompt), n))
        else:
            futs.append(eng.submit(prompt, max_new_tokens=n, greedy=False,
                                   temperature=0.9, top_k=10,
                                   top_p=0.95, seed=i))
    outs = [f.result(120.0) for f in futs]
    st1 = eng.stats()
    assert st1["requests_ok"] - st0["requests_ok"] == 14
    assert st1["compiles"] == st0["compiles"]  # warm: nothing compiled
    assert st1["recompiles_after_warmup"] == 0
    assert st1["retraces_after_warmup"] == 0
    for idx, prompt, n in oracle_checks:  # greedy ones stay exact
        assert len(outs[idx]) == n
        _assert_matches_dense(net, prompt, outs[idx])
    tokens = st1["tokens_generated"] - st0["tokens_generated"]
    disp = st1["dispatches"] - st0["dispatches"]
    prefills = st1["prefills"] - st0["prefills"]
    assert (disp - prefills) <= ((tokens - prefills) / CHUNK) * 1.5 + 3
    _drain(eng)
    assert eng.stats()["cache"]["blocks_used"] == 0


def test_late_join_rides_next_chunk_without_drain(eng):
    long_f = eng.submit(np.array([1, 2, 3], np.int32),
                        max_new_tokens=40, greedy=True)
    deadline = time.perf_counter() + 10.0
    while eng.active_slots() == 0 and time.perf_counter() < deadline:
        time.sleep(0.001)
    assert eng.active_slots() > 0
    short_f = eng.submit(np.array([9, 9], np.int32),
                         max_new_tokens=3, greedy=True)
    assert len(short_f.result(60.0)) == 3
    assert len(long_f.result(60.0)) == 40
    # the short request joined mid-flight and retired first — token-
    # level batching, not request-level (no drain between admissions)
    assert short_f.token_times()[1] < long_f.token_times()[1]


def test_deadline_expires_in_queue(eng):
    longs = [eng.submit(np.array([5, 3], np.int32), max_new_tokens=30,
                        greedy=True) for _ in range(SLOTS + 1)]
    f = eng.submit(np.array([1, 1], np.int32), max_new_tokens=30,
                   deadline_ms=0.1)
    with pytest.raises(RequestTimeout):
        f.result(60.0)
    for lf in longs:
        assert len(lf.result(120.0)) == 30  # bystanders unharmed


def test_cancel_only_before_admission(eng):
    longs = [eng.submit(np.array([5, 3], np.int32), max_new_tokens=25,
                        greedy=True) for _ in range(SLOTS + 2)]
    victim = eng.submit(np.array([2, 2], np.int32), max_new_tokens=4)
    assert victim.cancel() is True
    assert victim.cancelled()
    with pytest.raises(RequestCancelled):
        victim.result(10.0)
    done = longs[0]
    done.result(120.0)
    assert done.cancel() is False  # too late: already ran
    for lf in longs[1:]:
        lf.result(120.0)


# ---------------------------------------------------------------------------
# lifecycle + integration (dedicated engines: these ones die)
# ---------------------------------------------------------------------------

def _tiny_net(**kw):
    kw.setdefault("vocab_size", 32)
    kw.setdefault("num_layers", 1)
    kw.setdefault("d_model", 16)
    kw.setdefault("num_heads", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("seed", 0)
    return TransformerDecoderLM(**kw)


_TINY_ENG = dict(slots=2, chunk=2, cache_blocks=24, cache_block_size=4)


def test_pause_resume_kill_lifecycle():
    e = GenerationEngine(_tiny_net(), [4], name="gen-life", **_TINY_ENG)
    try:
        assert len(e.predict([1, 2], max_new_tokens=2, timeout=60.0)) == 2
        e.pause()
        with pytest.raises(EngineClosed):
            e.submit(np.array([1, 2], np.int32))
        e.resume()
        assert len(e.predict([1, 2], max_new_tokens=2, timeout=60.0)) == 2
        f = e.submit(np.array([3, 1], np.int32), max_new_tokens=20)
        e.kill()  # host death: in-flight fails typed, nothing hangs
        with pytest.raises(ReplicaDead):
            f.result(30.0)
        with pytest.raises(EngineClosed):
            e.resume()
    finally:
        e.close()  # idempotent after kill


def test_close_drains_inflight():
    e = GenerationEngine(_tiny_net(), [4], name="gen-drain", **_TINY_ENG)
    f = e.submit(np.array([1, 2, 3], np.int32), max_new_tokens=10)
    e.close()
    assert len(f.result(1.0)) == 10  # drained, not aborted
    with pytest.raises(EngineClosed):
        e.submit(np.array([1, 2], np.int32))


def test_repository_dispatches_decode_capable_nets():
    """``repo.load`` sees ``decode_step_fn`` and serves the net with a
    GenerationEngine behind the same repository surface — the fleet
    stack from PR 17 needs zero changes."""
    repo = ModelRepository()
    try:
        engine = repo.load("lm", _tiny_net(), [4], version="v1",
                           **_TINY_ENG)
        assert isinstance(engine, GenerationEngine)
        st = repo.stats("lm")
        assert st["engine"] == "generation"
        toks = repo.predict("lm", np.array([1, 2, 3], np.int32),
                            max_new_tokens=4, timeout=60.0)
        assert len(toks) == 4
        assert repo.stats("lm")["requests_ok"] >= 1
    finally:
        repo.close()


def test_local_replica_serves_decoder_spec():
    """The plain-dict ``{"decoder": ...}`` spec crosses the replica
    boundary: same seed -> identical weights -> greedy output matches a
    directly-built engine."""
    net = _tiny_net()
    spec = {"net": net.spec(), "shapes": [4], "version": "v1",
            "engine": dict(_TINY_ENG)}
    rep = LocalReplica(0, spec, name="lm")
    try:
        assert rep.state == "live"
        got = rep.submit(np.array([4, 2, 1], np.int32),
                         max_new_tokens=5, greedy=True).result(60.0)
        direct = GenerationEngine(net, [4], name="lm-ref", **_TINY_ENG)
        try:
            want = direct.predict(np.array([4, 2, 1], np.int32),
                                  max_new_tokens=5, greedy=True,
                                  timeout=60.0)
        finally:
            direct.close()
        assert list(got) == list(want)
    finally:
        rep.close()


# ---------------------------------------------------------------------------
# the scheduler's and the requests' spans under a profiler session
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(eng, tmp_path_factory):
    """One profiler session over the module's engine serving a dozen
    requests with telemetry off: the ring's events of that session, the
    requests' answers and the cache's counters. The ring names a thread
    by its native id, so another engine idling on a thread of its own
    puts none of its ``gen.admit`` and ``gen.idle`` among these."""
    import jax

    obs.set_enabled(False)
    obs.reset()
    cap = {}
    try:
        eng.predict(np.array([1, 2, 3], np.int32), max_new_tokens=4,
                    timeout=60.0)  # spans outside a session: none recorded
        cap["ring_before"] = len(obs.tracer())
        try:
            jax.profiler.start_trace(str(tmp_path_factory.mktemp("prof")))
        except Exception as err:  # pragma: no cover - env-specific plugin
            pytest.skip(f"jax profiler unavailable here: {err}")
        try:
            rng = np.random.RandomState(0)
            futs = []
            for i in range(12):
                prompt = rng.randint(1, VOCAB, size=rng.randint(2, 15))
                futs.append((prompt, 6 + 3 * (i % 5), eng.submit(
                    prompt.astype(np.int32), max_new_tokens=6 + 3 * (i % 5))))
                time.sleep(0.002)
            cap["answers"] = [(p, n, f.result(120.0)) for p, n, f in futs]
            time.sleep(0.05)  # a few idle turns
        finally:
            jax.profiler.stop_trace()
        cap["stats"] = eng.cache.stats()
        # what another thread of the process recorded is not this engine's
        tid = eng._thread.native_id
        cap["ring"] = [ev for ev in obs.tracer().events()
                       if ev["cat"] != "generation" or ev["tid"] == tid]
    finally:
        obs.reset()
    return cap


def _cat(cap, cat):
    return [ev for ev in cap["ring"] if ev["cat"] == cat]


def test_scheduler_spans_tile_its_thread(traced):
    assert traced["ring_before"] == 0  # off and unwatched: nothing
    gen = sorted(_cat(traced, "generation"), key=lambda ev: ev["ts"])
    ids = {ev["id"] for ev in gen}
    top = [ev for ev in gen if ev["args"].get("parent") not in ids]
    assert {ev["name"] for ev in top} <= {
        "gen.admit", "gen.chunk", "gen.idle",
        # a span open when the session began is not recorded, and its
        # children stand as top-level spans
        "gen.prefill", "gen.prefill.device", "gen.chunk.prep",
        "gen.chunk.device", "gen.chunk.fetch", "gen.chunk.deliver"}
    assert {"gen.admit", "gen.chunk", "gen.idle"} \
        <= {ev["name"] for ev in top}
    for a, b in zip(top, top[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a, b)  # no overlap
    # device waits + host self time + idle are the thread's whole time
    children = {}
    for ev in gen:
        children.setdefault(ev["args"].get("parent"), []).append(ev)
    own = {ev["id"]: ev["dur"] - sum(c["dur"]
                                     for c in children.get(ev["id"], ()))
           for ev in gen}
    assert all(v >= -1.0 for v in own.values())
    thread = max(ev["ts"] + ev["dur"] for ev in gen) - gen[0]["ts"]
    assert sum(own.values()) <= thread + 1.0
    # the top-level spans and the gaps between them are the thread's
    # time, whatever the host schedules: no span starts before the
    # first top-level one or ends after the last (a stamp is epoch
    # microseconds in a float64, a quarter-microsecond grid, so the sum
    # is held to half a microsecond a span). How the time divides is
    # engine_host_work_share.serve's to read, on the chip
    gaps = [b["ts"] - (a["ts"] + a["dur"]) for a, b in zip(top, top[1:])]
    assert sum(own.values()) + sum(gaps) == pytest.approx(
        thread, abs=1.0 + 0.5 * len(top))
    # every prefill sits in an admit, every phase of a chunk in a chunk,
    # the one fetch at the end of the chunk's wait for the device
    by_id = {ev["id"]: ev for ev in gen}
    for ev in gen:
        parent = by_id.get(ev["args"].get("parent"))
        if parent is not None:
            assert parent["name"] == {
                "gen.prefill": "gen.admit",
                "gen.prefill.device": "gen.prefill",
                "gen.chunk.fetch": "gen.chunk.device"}.get(
                    ev["name"], "gen.chunk")
    fetched = [ev["args"]["parent"] for ev in gen
               if ev["name"] == "gen.chunk.fetch"]
    assert sorted(fetched) == sorted(
        ev["id"] for ev in gen if ev["name"] == "gen.chunk.device")


def test_request_phases_share_a_rid_and_sum_to_the_request(traced):
    reqs = _cat(traced, "request")
    whole = [ev for ev in reqs if ev["name"] == "req"]
    assert len(whole) == len(traced["answers"]) == 12
    prefills = {ev["args"]["rid"]: ev for ev in _cat(traced, "generation")
                if ev["name"] == "gen.prefill"}
    asked = sorted((len(p), n) for p, n, toks in traced["answers"])
    assert sorted((ev["args"]["prompt_len"], ev["args"]["tokens"])
                  for ev in whole) == asked
    for ev in whole:
        rid = ev["args"]["rid"]
        assert ev["id"] == rid and ev["args"]["outcome"] == "ok"
        parts = {p["name"]: p for p in reqs
                 if p["args"]["rid"] == rid and p is not ev}
        assert set(parts) == {"req.queue", "req.prefill", "req.decode"}
        assert all(p["args"]["parent"] == rid for p in parts.values())
        assert sum(p["dur"] for p in parts.values()) \
            == pytest.approx(ev["dur"], abs=1000.0)  # 1 ms, in us
        assert parts["req.queue"]["ts"] == pytest.approx(ev["ts"], abs=1.0)
        # the request's own prefill ran inside its req.prefill phase
        pf = prefills[rid]
        assert pf["args"]["prompt_len"] == ev["args"]["prompt_len"]
        assert parts["req.prefill"]["ts"] <= pf["ts"] + 1000.0


def test_a_requests_decode_carries_the_split_of_its_pace(traced):
    """``req.decode`` carries its tokens after the first, the wall of
    the chunks it was live in and that of the other requests' prefills
    between its first and its last token: no less than the scheduler's
    spans of those chunks' device waits and of those prefills inside
    its interval, and together no more than the interval."""
    reqs = _cat(traced, "request")
    tokens = {ev["args"]["rid"]: ev["args"]["tokens"] for ev in reqs
              if ev["name"] == "req"}
    gen = _cat(traced, "generation")
    decodes = [ev for ev in reqs if ev["name"] == "req.decode"]
    assert len(decodes) == 12
    stalled = 0
    for ev in decodes:
        a, rid = ev["args"], ev["args"]["rid"]
        lo, hi = ev["ts"], ev["ts"] + ev["dur"]

        def inside(name):
            return [g["dur"] for g in gen if g["name"] == name
                    and g["args"].get("rid") != rid
                    and lo <= g["ts"] and g["ts"] + g["dur"] <= hi]

        assert a["tokens"] == tokens[rid] - 1 >= 1
        assert 0 < a["device_us"] and 0 <= a["stall_us"]
        assert a["device_us"] + a["stall_us"] <= ev["dur"] + 1.0
        waits, prefills = inside("gen.chunk.device"), inside("gen.prefill")
        assert waits and sum(waits) <= a["device_us"] + len(waits)
        assert sum(prefills) <= a["stall_us"] + len(prefills)
        stalled += bool(prefills)
    assert stalled  # a dozen requests through four slots: some waited


def test_every_chunk_carries_the_pools_blocks_in_use(traced):
    chunks = [ev for ev in _cat(traced, "generation")
              if ev["name"] == "gen.chunk"]
    seen = [ev["args"]["blocks_used"] for ev in chunks]
    st = traced["stats"]
    assert seen and all(1 <= n < st["num_blocks"] for n in seen)
    # read after the chunk's growth: the longest answer's blocks (of 4
    # tokens) were all in use at its last chunk
    longest = max(len(p) + n for p, n, toks in traced["answers"])
    assert max(seen) >= (longest - 1) // st["block_size"]
    assert st["blocks_used"] == 0  # all retired


# ---------------------------------------------------------------------------
# latent layers over the latent pool, expert layers and their counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def joyai():
    """``joyai_tiny`` (a dense latent layer, then an expert layer)
    behind an engine of two slots."""
    import jax

    net = TransformerDecoderLM.from_preset("joyai_tiny", seed=7)
    eng = GenerationEngine(net, [16, 32], slots=2, chunk=4, cache_blocks=24,
                           cache_block_size=4, name="joyai-test")
    yield net, eng, jax.jit(net.forward_fn())
    eng.close()


def test_latent_prefill_then_decode_gives_the_forward_logits(joyai):
    """Prefill writes the latent rows through the block table, decode
    attends to them absorbed: every greedy token is the oracle's."""
    net, eng, fwd = joyai
    assert eng.cache.pool is not None and eng.cache.states is None
    assert len(eng.cache.arrays()) == 1  # one pool, not K and V
    rng = np.random.RandomState(3)
    for plen in (5, 16, 27):
        p = rng.randint(0, 128, plen).astype(np.int32)
        toks = eng.predict(p, max_new_tokens=9, greedy=True, timeout=120.0)
        logits = np.asarray(fwd(net.params(),
                                np.concatenate([p, toks])[None]))[0]
        assert list(toks) == list(
            logits[plen - 1:plen - 1 + len(toks)].argmax(-1))


def test_latent_slots_and_blocks_are_reused_after_release(joyai):
    """Seven requests at once through two slots: blocks are freed and
    taken again (a stale row in a re-used block would show), and the
    pool drains."""
    net, eng, fwd = joyai
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 128, n).astype(np.int32)
               for n in (9, 30, 17, 5, 32, 12, 25)]
    futs = [eng.submit(p, max_new_tokens=11, greedy=True) for p in prompts]
    for p, f in zip(prompts, futs):
        toks = f.result(timeout=120.0)
        logits = np.asarray(fwd(net.params(),
                                np.concatenate([p, toks])[None]))[0]
        assert list(toks) == list(
            logits[len(p) - 1:len(p) - 1 + len(toks)].argmax(-1))
    assert eng.stats()["cache"]["blocks_used"] == 0


def test_the_expert_counters_add_up(joyai):
    """``stats()["experts"]``: every decoded token of the one expert
    layer routes two pairs; a step hits between one expert and as many
    as it has pairs; the fullest expert holds at least the mean."""
    net, eng, _ = joyai
    before = dict(eng.stats()["experts"])
    s0 = eng.stats()
    rng = np.random.RandomState(6)
    futs = [eng.submit(rng.randint(0, 128, n).astype(np.int32),
                       max_new_tokens=m, greedy=True)
            for n, m in ((7, 6), (20, 9), (11, 1))]
    for f in futs:
        f.result(timeout=120.0)
    s1 = eng.stats()
    got = {k: s1["experts"][k] - before[k] for k in before}
    decoded = (s1["tokens_generated"] - s0["tokens_generated"]) \
        - (s1["prefills"] - s0["prefills"])
    assert decoded == 5 + 8 + 0
    assert got["routed_pairs"] == 2 * decoded
    steps = (s1["decode_chunks"] - s0["decode_chunks"]) * 4
    assert decoded / 2 <= got["experts_hit"] <= got["routed_pairs"]
    assert got["routed_pairs"] / 8 <= got["load_max"] <= got["routed_pairs"]
    assert got["load_max"] <= 2 * steps  # two live tokens a step at most


def test_a_net_without_experts_reports_no_expert_counters(eng):
    assert "experts" not in eng.stats()


# ---------------------------------------------------------------------------
# a dispatch crosses to the device once each way: one packed upload a
# chunk, one fetch; the packed rows are the scheduler's own mirrors
# ---------------------------------------------------------------------------

_FAMILIES = {
    # an attention net (K and V pools, a block table a slot), a retention
    # net (a state a slot, no table) and an expert net over the latent
    # pool (a table, and the expert counters in the chunk's result)
    "attention": lambda: TransformerDecoderLM(
        vocab_size=VOCAB, num_layers=2, d_model=32, num_heads=4,
        kv_heads=2, max_seq=MAX_SEQ, seed=0),
    "retention": lambda: TransformerDecoderLM.from_preset("brumby_tiny",
                                                          seed=3),
    "experts": lambda: TransformerDecoderLM.from_preset("joyai_tiny",
                                                        seed=7),
}


@pytest.fixture(scope="module", params=sorted(_FAMILIES))
def family(request):
    import jax

    net = _FAMILIES[request.param]()
    return request.param, net, jax.jit(net.forward_fn())


def _family_engine(net, name, **kw):
    kw = {"cache_blocks": 40, **kw}
    return GenerationEngine(net, [8, 16], slots=2, chunk=4,
                            cache_block_size=4, seed=11, name=name, **kw)


def _assert_mirrors(eng):
    """The packed rows say what the scheduler holds: a live slot's
    index row is its sequence's, an empty slot's the null block's and
    the null state's, with no length, token or budget left in it."""
    from mxnet_tpu.serving import generation as G

    for s, (req, seq) in enumerate(zip(eng._slot_req, eng._slot_seqs)):
        index = eng._rows[s, G._SLOT_COLS:]
        want = np.empty_like(index)
        eng.cache.write_row(want, seq)  # no sequence: the null row
        assert index.tolist() == want.tolist()
        if req is None:
            assert seq is None
            assert not eng._rows[s, :G._CARRY_COLS].any()
        else:
            assert eng._active[s] == 1
            assert eng._lens[s] == len(req.prompt) + len(req.tokens) - 1
            assert eng._token[s] == req.tokens[-1]
            assert eng._remaining[s] == req.max_new - len(req.tokens)


def test_a_dispatch_crosses_to_the_device_once_each_way(family, monkeypatch):
    """Five greedy requests and a sampled one through two slots: every
    chunk is one upload and one fetch, every prefill two uploads (the
    padded prompt and the request's packed row) and its one deliberate
    fetch; the greedy tokens are the dense recompute's and the sampled
    ones what an engine built on the unconditional sampler serves."""
    from mxnet_tpu.serving import generation

    kind, net, fwd = family
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 40, n).astype(np.int32)
               for n in (3, 9, 14, 6, 11)]
    sampled = rng.randint(1, 40, 7).astype(np.int32)

    def run(name):
        e = _family_engine(net, name)
        try:
            futs = [e.submit(p, max_new_tokens=5 + 3 * i, greedy=True)
                    for i, p in enumerate(prompts)]
            outs = [f.result(120.0) for f in futs]
            drawn = e.predict(sampled, timeout=120.0, **_SAMPLED)
            return outs, drawn, e.stats()
        finally:
            e.close()

    outs, drawn, st = run(f"cross-{kind}")
    chunks, prefills = st["decode_chunks"], st["prefills"]
    assert prefills == 6 and chunks > 0
    assert st["dispatches"] == chunks + prefills
    assert st["host_transfers"] == {"uploads": chunks + 2 * prefills,
                                    "fetches": chunks + prefills}
    assert st["host_transfers"]["uploads"] <= 2 * st["dispatches"]
    assert st["host_transfers"]["fetches"] <= st["dispatches"]
    for i, (p, toks) in enumerate(zip(prompts, outs)):
        assert len(toks) == 5 + 3 * i
        _assert_matches_dense(net, p, toks, fwd)
    assert len(drawn) == _SAMPLED["max_new_tokens"]
    monkeypatch.setattr(generation, "sample_tokens",
                        _sample_tokens_unconditional)
    want_outs, want_drawn, _ = run(f"cross-ref-{kind}")
    assert list(drawn) == list(want_drawn)
    for got, want in zip(outs, want_outs):
        assert list(got) == list(want)


def test_a_slot_that_changes_hands_takes_the_newcomers_row(family):
    """The scheduler driven by hand, turn by turn: a request seated in
    a slot that another just left gets its own index row there, the
    other slot's row is the null block's again, and between any two
    turns the packed rows agree with what the slots hold."""
    kind, net, fwd = family
    eng = _family_engine(net, f"hands-{kind}", autostart=False)
    try:
        _assert_mirrors(eng)  # deployed: every row a null row
        rng = np.random.RandomState(8)
        first = [rng.randint(1, 40, n).astype(np.int32) for n in (5, 13)]
        futs = [eng.submit(first[0], max_new_tokens=4, greedy=True),
                eng.submit(first[1], max_new_tokens=14, greedy=True)]
        eng._admit()
        assert [r is not None for r in eng._slot_req] == [True, True]
        _assert_mirrors(eng)
        eng._step_chunk()  # the short one leaves slot 0
        assert futs[0].done() and eng._slot_req[0] is None
        _assert_mirrors(eng)
        late = rng.randint(1, 40, 10).astype(np.int32)
        futs.append(eng.submit(late, max_new_tokens=9, greedy=True))
        eng._admit()       # and the newcomer takes it
        assert eng._slot_req[0] is not None
        _assert_mirrors(eng)
        for _ in range(8):
            eng._admit()
            if not eng._active.any():
                break
            eng._step_chunk()
            _assert_mirrors(eng)
        assert all(f.done() for f in futs)
        for p, f in zip(first + [late], futs):
            _assert_matches_dense(net, p, f.result(0), fwd)
        _assert_mirrors(eng)  # every slot empty: null rows
        assert eng.stats()["cache"]["blocks_used"] == 0
        st = eng.stats()
        assert st["host_transfers"]["fetches"] == st["dispatches"]
    finally:
        eng.close()


@pytest.mark.parametrize("kind", ["attention", "experts"])
def test_a_request_shed_for_want_of_blocks_leaves_a_null_row(kind):
    """A pool of three usable blocks under two sequences of one block
    each: the chunk's growth backs the first and sheds the second
    (typed OOM); its slot's row is the null block's again, its block is
    free, and the first request runs on to the dense recompute's
    tokens, taking that block."""
    from mxnet_tpu.serving import KVCacheOOM

    net = _FAMILIES[kind]()
    eng = _family_engine(net, f"shed-{kind}", autostart=False,
                         cache_blocks=4)
    try:
        a, b = (np.array(p, np.int32) for p in ([3, 1, 4, 1], [2, 7, 1, 8]))
        fa = eng.submit(a, max_new_tokens=8, greedy=True)
        fb = eng.submit(b, max_new_tokens=8, greedy=True)
        eng._admit()
        assert eng.cache.blocks_used() == 2
        eng._step_chunk()
        with pytest.raises(KVCacheOOM):
            fb.result(0)
        assert eng._slot_req[1] is None
        _assert_mirrors(eng)
        assert eng.cache.blocks_used() == 2  # a's two; b's came back
        while eng._active.any():
            eng._step_chunk()
            _assert_mirrors(eng)
        _assert_matches_dense(net, a, fa.result(0))
        assert eng.cache.blocks_used() == 0
        assert eng.stats()["failed"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# where a request's pace goes: the chunks it was live in, the other
# requests' prefills between its tokens, and the scheduler's host turn
# ---------------------------------------------------------------------------

def _host_s(req):
    """What ``_account`` leaves to the scheduler's turn."""
    return (req.t_last - req.t_first) - req.device_s - req.stall_s


def _burst(eng, seed):
    """A dozen ragged requests, one of a single token, through the
    module's four slots; their requests once all have finished."""
    rng = np.random.RandomState(seed)
    futs = [eng.submit(rng.randint(1, VOCAB, rng.randint(2, 15))
                       .astype(np.int32),
                       max_new_tokens=1 if i == 5 else 3 + 4 * (i % 4))
            for i in range(12)]
    for f in futs:
        f.result(120.0)
    return [f._req for f in futs]


def test_a_requests_pace_is_its_chunks_its_stalls_and_the_host(eng):
    for req in _burst(eng, 21):
        n = len(req.tokens) - 1
        if n == 0:  # one token, no pace to split
            assert req.device_s == req.stall_s == 0.0
            continue
        decode_s = req.t_last - req.t_first
        assert 0 < req.device_s < decode_s
        assert 0 <= req.stall_s < decode_s
        # the parts never overlap: the host's turn is what is left
        assert _host_s(req) >= -1e-6
        assert req.device_s + req.stall_s + _host_s(req) == pytest.approx(
            decode_s, abs=1e-6)


def test_stats_pace_sums_the_finished_requests(eng):
    before = dict(eng.stats()["pace"])
    reqs = _burst(eng, 22)
    after = eng.stats()["pace"]
    assert set(after) == {"decode_s", "device_s", "stall_s", "intervals"}
    assert after["intervals"] - before["intervals"] \
        == sum(len(r.tokens) - 1 for r in reqs)
    for key, part in (("decode_s", lambda r: r.t_last - r.t_first),
                      ("device_s", lambda r: r.device_s),
                      ("stall_s", lambda r: r.stall_s)):
        assert after[key] - before[key] == pytest.approx(
            sum(part(r) for r in reqs), abs=1e-9)
    assert after["stall_s"] > before["stall_s"]  # some waited on prefills
    # a chunk's wall over its tokens is no request's pace: not reported
    assert not [k for k in eng.stats()
                if k == "tokens_per_s" or k.startswith("itl_")]


def test_a_request_decoding_alone_never_stalls(net):
    eng = _family_engine(net, "pace-alone", autostart=False)
    try:
        fut = eng.submit(np.array([3, 1, 4, 1, 5], np.int32),
                         max_new_tokens=13, greedy=True)
        eng._admit()
        while eng._active.any():
            eng._step_chunk()
            eng._admit()
        req = fut._req
        assert len(fut.result(0)) == 13
        assert req.stall_s == 0.0
        assert 0 < req.device_s <= req.t_last - req.t_first
        assert eng.stats()["pace"]["intervals"] == 12
    finally:
        eng.close()


def test_a_prefill_between_its_tokens_is_a_requests_stall(net):
    """Driven turn by turn with telemetry on: the second request's
    prefill runs while the first is decoding, and the first's stall is
    at least that prefill's ``gen.prefill`` span; the second, which
    decoded beside no newcomer, never stalled."""
    obs.set_enabled(True)
    eng = _family_engine(net, "pace-stall", autostart=False)
    try:
        first = eng.submit(np.array([2, 7, 1, 8], np.int32),
                           max_new_tokens=14, greedy=True)
        eng._admit()
        eng._step_chunk()
        second = eng.submit(np.array([9, 9, 3], np.int32),
                            max_new_tokens=6, greedy=True)
        eng._admit()
        while eng._active.any():
            eng._step_chunk()
        a, b = first._req, second._req
        assert first.done() and second.done()
        (span,) = [ev for ev in obs.tracer().events()
                   if ev["name"] == "gen.prefill"
                   and ev["args"]["rid"] == b.rid]
        assert a.stall_s * 1e6 >= span["dur"] > 0
        assert b.stall_s == 0.0
        assert _host_s(a) >= -1e-6 and _host_s(b) >= -1e-6
    finally:
        eng.close()


def test_a_request_shed_mid_decode_takes_no_later_prefill(net):
    """Three usable blocks: the first request decodes to five tokens,
    a second's prefill takes the last free block, and the first's next
    growth is shed (typed OOM). Its pace ends at its last token, before
    that prefill: it stalled on nothing and its parts still add up."""
    from mxnet_tpu.serving import KVCacheOOM

    eng = _family_engine(net, "pace-shed", autostart=False, cache_blocks=4)
    try:
        first = eng.submit(np.array([3, 1, 4, 1], np.int32),
                           max_new_tokens=8, greedy=True)
        eng._admit()
        eng._step_chunk()
        second = eng.submit(np.array([2, 7], np.int32), max_new_tokens=3,
                            greedy=True)
        eng._admit()
        assert eng.cache.blocks_used() == 3
        eng._step_chunk()
        with pytest.raises(KVCacheOOM):
            first.result(0)
        a = first._req
        assert len(a.tokens) == 5
        assert a.stall_s == 0.0 and a.device_s > 0
        assert _host_s(a) >= -1e-6
        while eng._active.any():
            eng._step_chunk()
        assert len(second.result(0)) == 3
        st = eng.stats()["pace"]
        assert st["intervals"] == 4 + 2  # the shed request's count too
    finally:
        eng.close()
