"""Autoregressive decode fast path: single-dispatch chunked decode,
on-device sampling, and token-level continuous batching.

The one-shot :class:`~.engine.InferenceEngine` answers a request with
one dispatch; a generative request is HUNDREDS of sequential steps, so
the host round trip per token — not the math — dominates. This engine
removes it at three levels:

- **one executable for the whole decode batch**: a ``lax.scan`` over
  ``MXTPU_DECODE_CHUNK`` steps (model step + sampling + EOS/budget
  bookkeeping all in-graph) is AOT-compiled ONCE at deploy for a fixed
  slot count, so the host touches the loop once per chunk —
  amortized XLA dispatches per generated token are ``<= 1/chunk``
  (tests/test_generation.py::test_single_dispatch_chunk_budget counts
  them), and a dispatch crosses to the device once each way: the
  slots' state goes up as one packed array and the chunk's results
  come back in one fetch (``stats()["host_transfers"]``);
- **on-device sampling** (:func:`sample_tokens`): greedy / temperature
  / top-k / top-p per SLOT (every request carries its own knobs as
  operands, so mixed sampling policies share one executable), PRNG
  keys folded and threaded device-side — no sync to pick a token; the
  filter's sort of the vocabulary runs only in a step where some live
  slot draws (``stats()["filtered_chunks"]``);
- **token-level continuous batching** (Orca-style iteration-level
  scheduling): the decode batch is ``MXTPU_DECODE_SLOTS`` slots;
  requests JOIN an idle slot between chunks (prefill is its own
  per-prompt-bucket executable) and LEAVE the moment EOS or their
  token budget retires them — a late submit never waits for the
  running batch to drain, and a finished sequence never pads it.

What a live sequence keeps lives in the :class:`~.kvcache.SequenceCache`
that the net's ``cache_spec()`` configures: K/V rows in the
:class:`~.kvcache.PagedKVCache` block pool for attention layers, a
fixed state a slot in the :class:`~.kvcache.StateStore` for retention
layers. Its arrays are DONATED as one pytree through every
prefill/decode dispatch, so cache memory is constant and aliased in
place. Slot liveness is an operand
(never a shape): ragged traffic — joins, retirements, wildly different
lengths — reuses the same sealed executables with ZERO retraces after
warmup (``RetraceForbidden`` otherwise, the PR-13 contract).

Sampling reproducibility: a request's first token is drawn from its
own ``seed`` (folded in-graph), so prefill is per-request
deterministic; subsequent tokens draw from the engine's device-side
key stream, which advances per CHUNK — deterministic for a fixed
admission order. ``greedy=True`` (the default) is always bit-stable.

Served through :class:`~.repository.ModelRepository` and the PR-17
fleet unchanged: ``submit()/predict()/stats()/queue_depth()`` plus the
pause/resume/kill/close lifecycle mirror ``InferenceEngine``, and
``repo.load`` picks this engine automatically for nets exposing
``decode_step_fn`` (e.g. :class:`~.decoder.TransformerDecoderLM`).

Knobs: ``MXTPU_DECODE_SLOTS`` / ``MXTPU_DECODE_CHUNK`` /
``MXTPU_DECODE_MAX_NEW`` (docs/env_vars.md); metrics:
``mxtpu_decode_*`` + ``mxtpu_kvcache_*`` (docs/observability.md);
recipe: docs/serving.md "Generation".
"""

from __future__ import annotations

import collections
import logging
import threading
import time

import numpy as _np

from .. import base
from .. import observability as _obs
from ..base import MXNetError
from .engine import serve_queue_cap
from .errors import (
    EngineClosed,
    KVCacheOOM,
    ReplicaDead,
    RequestCancelled,
    RequestTimeout,
    RetraceForbidden,
    ServerOverloaded,
    ServingError,
)
from .kvcache import SequenceCache, split_index

_SLOTS_DEFAULT = 8
_CHUNK_DEFAULT = 8
_MAX_NEW_DEFAULT = 32


def decode_slots() -> int:
    """Decode-batch width in slots (``MXTPU_DECODE_SLOTS``, default 8).
    ONE decode executable is compiled for exactly this many slots;
    requests join/leave between chunks. More slots = more concurrent
    sequences per dispatch (throughput) at more pool pressure."""
    return max(1, base.getenv("MXTPU_DECODE_SLOTS", _SLOTS_DEFAULT,
                              dtype=int))


def decode_chunk() -> int:
    """Decode steps fused per dispatch (``MXTPU_DECODE_CHUNK``, default
    8) — the ``lax.scan`` length. Raising it amortizes the host round
    trip over more tokens (dispatches/token = 1/chunk) but delays
    join/retire scheduling to chunk boundaries; the serving analog of
    ``MXTPU_SUPERSTEP_K``."""
    return max(1, base.getenv("MXTPU_DECODE_CHUNK", _CHUNK_DEFAULT,
                              dtype=int))


def decode_max_new() -> int:
    """Default per-request new-token budget when ``submit`` doesn't
    pass ``max_new_tokens`` (``MXTPU_DECODE_MAX_NEW``, default 32)."""
    return max(1, base.getenv("MXTPU_DECODE_MAX_NEW", _MAX_NEW_DEFAULT,
                              dtype=int))


# ---------------------------------------------------------------------------
# on-device sampling
# ---------------------------------------------------------------------------

def sample_tokens(logits, key, temperature, top_k, top_p, greedy,
                  live=None):
    """Sample one token per row, entirely in-graph. ``logits`` is
    ``(B, V)``; every knob is a ``(B,)`` vector so each batch slot
    applies ITS OWN policy inside the shared executable:

    - ``greedy`` (bool): argmax of the raw logits (ignores the rest);
    - ``temperature`` (f32): logit scale before filtering;
    - ``top_k`` (i32): keep the k highest-scoring tokens (0 = off);
    - ``top_p`` (f32): nucleus — keep the smallest prefix of the
      sorted distribution with cumulative probability >= p (1.0 = off;
      the argmax always survives, so filtering can never empty a row).

    Filters compose (top-k first, then top-p) by masking to ``-inf``
    and drawing with ``jax.random.categorical``.

    The filter sorts the whole vocabulary, so it runs under a
    device-side ``lax.cond`` only when some ``live`` row (bool ``(B,)``;
    ``None`` = every row) is not greedy; otherwise the step takes the
    argmax and nothing else. A dead row's policy is whatever its last
    request left behind and never engages the filter."""
    import jax
    import jax.numpy as jnp

    def filter_and_draw():
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

    def argmax_only():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    draws = ~greedy if live is None else live & ~greedy
    return jax.lax.cond(jnp.any(draws), filter_and_draw, argmax_only)


# ---------------------------------------------------------------------------
# request/future plumbing (mirrors batcher._Request / ServeFuture)
# ---------------------------------------------------------------------------

class _GenRequest:
    __slots__ = ("prompt", "max_new", "temperature", "top_k", "top_p",
                 "greedy", "seed", "eos", "deadline", "rid", "t_submit",
                 "t_admit", "tokens", "t_first", "t_last", "pace0",
                 "device_s", "stall_s", "event", "result", "error",
                 "version", "claimed", "cancelled", "_state_lock")

    def __init__(self, prompt, max_new, temperature, top_k, top_p,
                 greedy, seed, eos, deadline):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.greedy = bool(greedy)
        self.seed = int(seed)
        self.eos = int(eos)
        self.deadline = deadline  # absolute perf_counter time, or None
        # the id its ``req*`` and ``gen.prefill`` spans share
        self.rid = _obs.tracer().new_span_id()
        self.t_submit = time.perf_counter()
        self.t_admit = None  # claim() won and its blocks are allocated
        self.tokens = []
        self.t_first = None
        self.t_last = None
        # where its pace went (``GenerationEngine._account``): the
        # engine's two sums as its prefill ended, then its chunks' wall
        # and the other requests' prefills until its last token
        self.pace0 = None
        self.device_s = 0.0
        self.stall_s = 0.0
        self.event = threading.Event()
        self.result = None
        self.error = None
        self.version = None
        self.claimed = False     # admission won the CAS
        self.cancelled = False
        self._state_lock = threading.Lock()

    def claim(self) -> bool:
        """Admission-side CAS: exactly one of {admit, cancel} wins."""
        with self._state_lock:
            if self.cancelled:
                return False
            self.claimed = True
            return True

    def cancel(self) -> bool:
        with self._state_lock:
            if self.claimed or self.event.is_set():
                return False
            self.cancelled = True
        self.error = RequestCancelled(
            "generation request cancelled while queued — never admitted")
        self.event.set()
        return True

    def finish(self, result=None, error=None, version=None):
        if self.event.is_set():
            return
        self.result = result
        self.error = error
        self.version = version
        self.event.set()


class GenerateFuture:
    """Client handle for a generation request. ``result()`` returns the
    generated token ids as ``np.int32`` (prompt NOT included; the EOS
    token, when hit, IS the last element)."""

    def __init__(self, req: _GenRequest):
        self._req = req

    def done(self) -> bool:
        return self._req.event.is_set()

    @property
    def version(self):
        return self._req.version

    def cancel(self) -> bool:
        """Withdraw a still-queued request (True iff it was never
        admitted to a slot — after admission the generation runs to
        completion and the original outcome stands)."""
        return self._req.cancel()

    def cancelled(self) -> bool:
        return self._req.cancelled

    def result(self, timeout=None):
        if not self._req.event.wait(timeout):
            raise TimeoutError(
                f"generation result not ready within {timeout}s (the "
                "request itself is still running; cancel() to withdraw "
                "a queued one)")
        if self._req.error is not None:
            raise self._req.error
        return self._req.result

    def token_times(self):
        """(t_first_token, t_last_token) perf_counter stamps, what a
        caller computes its inter-token latency from (None until the
        request finishes)."""
        return self._req.t_first, self._req.t_last


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

# A dispatch crosses to the device once: what the host knows of each slot
# goes up as one int32 row a slot. The chunk's row is these columns (the
# two floats bit-cast), then the slot's index row as the cache lays it
# out (``SequenceCache.write_row``: block table, state slot); the first
# four come back in the chunk's result.
(_LENS, _TOKEN, _ACTIVE, _REMAINING, _TOP_K, _GREEDY, _EOS, _TEMP,
 _TOP_P) = range(9)
_CARRY_COLS, _SLOT_COLS = 4, 9
# a prefill's one row: these, then the sequence's index row
_P_LENGTH, _P_SEED, _P_TOP_K, _P_GREEDY, _P_TEMP, _P_TOP_P = range(6)
_PREFILL_COLS = 6


def generation_programs(net, chunk):
    """The two programs the engine compiles for ``net``, as pure
    functions: ``chunk_fn`` (``chunk`` decode steps of the whole slot
    batch with sampling and the EOS/budget bookkeeping in-graph) and
    ``prefill_fn`` (one padded prompt and its first token). ``cache``
    is the cache's arrays as one pytree, donated and returned. Whatever
    else the host says goes up as ONE packed int32 operand, taken apart
    in the program:

    - ``chunk_fn(params, cache, rows, rng)``: ``rows`` is ``(slots,
      9 + W)``, a row a slot: ``lens, token, active, remaining, top_k,
      greedy, eos``, ``temperature`` and ``top_p`` as their float32
      bits, then the slot's ``W`` index columns (its block table and/or
      state slot, as the net's ``cache_spec()`` kinds have them:
      :func:`~.kvcache.split_index`). Returns ``(cache, rng, result)``,
      ``result`` ``(chunk + 4, slots)`` int32: a row a step of the
      token each slot emitted (-1 where it emitted none), then the
      final ``lens, token, active, remaining``. For a net with expert
      layers one thing more: the int32 ``[routed_pairs, experts_hit,
      load_max]`` summed over the chunk's decode steps and the expert
      layers, of the live slots' tokens.
    - ``prefill_fn(params, tokens, cache, row)``: ``row`` is
      ``(1, 6 + W)``: ``length, seed, top_k, greedy``, ``temperature``
      and ``top_p`` as bits, then the sequence's index row. Returns
      ``(first token, cache)``."""
    import jax
    import jax.numpy as jnp

    with_load = bool(getattr(net, "expert_layers", 0))
    step = net.decode_step_fn(with_load=True) if with_load \
        else net.decode_step_fn()
    prefill = net.prefill_fn()
    chunk_t = int(chunk)
    kinds = tuple(net.cache_spec()) if hasattr(net, "cache_spec") \
        else ("attention",)

    def floats(bits):
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    def chunk_fn(params, cache, rows, rng):
        lens, token = rows[:, _LENS], rows[:, _TOKEN]
        active, remaining = rows[:, _ACTIVE] != 0, rows[:, _REMAINING]
        top_k, greedy = rows[:, _TOP_K], rows[:, _GREEDY] != 0
        eos = rows[:, _EOS]
        temp, top_p = floats(rows[:, _TEMP]), floats(rows[:, _TOP_P])
        index = split_index(kinds, rows[:, _SLOT_COLS:])

        def body(carry, _):
            cache, lens, token, active, remaining, rng, *load = carry
            logits, *cache = step(params, token, lens, *cache, *index,
                                  active)
            if with_load:
                *cache, more = cache
                load = [load[0] + more]
            rng, sub = jax.random.split(rng)
            nxt = sample_tokens(logits, sub, temp, top_k, top_p,
                                greedy, active)
            emitted = active
            nxt = jnp.where(emitted, nxt, 0)
            lens = lens + active.astype(lens.dtype)
            remaining = remaining - active.astype(remaining.dtype)
            hit_eos = (nxt == eos) & (eos >= 0)
            active = active & ~hit_eos & (remaining > 0)
            return ((tuple(cache), lens, nxt, active, remaining, rng, *load),
                    (nxt, emitted))

        carry = (tuple(cache), lens, token, active, remaining, rng) \
            + ((jnp.zeros(3, jnp.int32),) if with_load else ())
        carry, (toks, flags) = jax.lax.scan(body, carry, None,
                                            length=chunk_t)
        cache, lens, token, active, remaining, rng, *load = carry
        result = jnp.concatenate([
            jnp.where(flags, toks, -1),
            jnp.stack([lens, token, active.astype(jnp.int32), remaining])])
        return (cache, rng, result, *load)

    def prefill_fn(params, tokens, cache, row):
        index = split_index(kinds, row[:, _PREFILL_COLS:])
        logits, *cache = prefill(params, tokens, *cache, *index,
                                 row[:, _P_LENGTH])
        key = jax.random.fold_in(jax.random.PRNGKey(0),
                                 row[0, _P_SEED])
        tok = sample_tokens(
            logits, key, floats(row[:, _P_TEMP]), row[:, _P_TOP_K],
            floats(row[:, _P_TOP_P]), row[:, _P_GREEDY] != 0)
        return tok, tuple(cache)

    return chunk_fn, prefill_fn


# an executable whose temporaries pass this share of one KV pool's bytes
# is taken to copy the pool (in place they are a few per cent of it)
_POOL_TEMP_SHARE_WARN = 0.25


class GenerationEngine:
    """Continuous-batching generation server over the net's cache (a
    paged KV pool, a state a slot, or both).

    ``shapes`` are PROMPT-LENGTH buckets (ints, or 1-tuples): each gets
    its own sealed prefill executable; the decode loop is ONE sealed
    executable for ``slots`` concurrent sequences regardless of length.

    >>> net = TransformerDecoderLM(vocab_size=64)
    >>> eng = GenerationEngine(net, [8, 16], slots=4, chunk=4)
    >>> toks = eng.predict(np.array([5, 3, 9]), max_new_tokens=12)

    Drop-in for the repository/fleet: same submit/predict/stats/
    lifecycle surface as :class:`InferenceEngine`."""

    # machine-checked lock protocol (mxtpu-lint thread-guard rule)
    _GUARDED_BY = {
        "_queue": "_lock",
        "_closing": "_lock",
        "_killed": "_lock",
        "_paused": "_lock",
    }

    def __init__(self, net, shapes, *, slots=None, chunk=None,
                 queue_cap=None, cache_blocks=None, cache_block_size=None,
                 max_new_default=None, seed=0, name="model", version="v1",
                 autostart=True, ctx=None, dtype=None):
        for attr in ("decode_step_fn", "prefill_fn", "params",
                     "decode_dims"):
            if not hasattr(net, attr):
                raise MXNetError(
                    f"{type(net).__name__} has no {attr} — generation "
                    "needs a decode-capable net (e.g. "
                    "serving.TransformerDecoderLM)")
        self._name = str(name)
        self._version = str(version)
        self._net = net
        dims = net.decode_dims()
        self.max_seq = int(dims["max_seq"])
        self.vocab_size = int(dims["vocab_size"])
        self._slots = int(slots) if slots is not None else decode_slots()
        self._chunk = int(chunk) if chunk is not None else decode_chunk()
        self._max_new_default = (int(max_new_default) if max_new_default
                                 is not None else decode_max_new())
        self._queue_cap = (int(queue_cap) if queue_cap is not None
                           else serve_queue_cap())
        self._buckets = self._normalize_buckets(shapes)
        # what the net's layers keep for a live sequence, a layer kind;
        # a net that says nothing keeps K/V in every layer
        spec = net.cache_spec() if hasattr(net, "cache_spec") else {
            "attention": {k: dims[k] for k in ("layers", "kv_heads",
                                                 "head_dim")}}
        self.cache = SequenceCache(
            spec, slots=self._slots, max_seq=self.max_seq,
            num_blocks=cache_blocks, block_size=cache_block_size,
            name=self._name,
            # the pool holds what the net's K/V projections emit: a
            # float32 pool under a bf16 net doubles the cache and hands
            # the decode kernel mixed operand dtypes
            dtype=dtype or getattr(net, "dtype", "float32"))
        self._lock = threading.Lock()
        self._queue = collections.deque()
        self._closing = False
        self._closed = False
        self._killed = False
        self._paused = False
        self._work = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        # engine-local SLO state (real numbers with telemetry off)
        self._tokens = 0
        self._chunks = 0
        self._filtered_chunks = 0  # chunks with a live slot that draws
        # a net with expert layers: [routed_pairs, experts_hit, load_max]
        # summed over every decode step and expert layer so far
        self._expert_load = _np.zeros(3, _np.int64) \
            if getattr(net, "expert_layers", 0) else None
        self._prefills = 0
        # arrays staged for and fetched from the served dispatches
        self._uploads = 0
        self._fetches = 0
        self._requests_ok = 0
        self._refused = 0
        self._shed = 0
        self._timeouts = 0
        self._failed = 0
        self._compiles = 0
        self._pool_temp_share = 0.0
        self._pool_temp_worst = None
        # where a live request's pace goes, summed on the clock of its
        # token stamps: every chunk's wall from staging to fetch, every
        # prefill's from entering ``_prefill`` to leaving it; both as
        # they stood at the last chunk's delivery; and over the requests
        # finished so far [decode_s, device_s, stall_s, intervals]
        # (``_account``)
        self._chunk_wait_s = 0.0
        self._prefill_s = 0.0
        self._delivered = (0.0, 0.0)
        self._pace = (0.0, 0.0, 0.0, 0)
        self._sealed = False
        # slot state (scheduler-thread-private after start): the chunk's
        # packed operand, kept on the host as the scheduler's truth. Its
        # columns are the mirrors that admission, shedding and retiring
        # read and write (``_active`` and ``_greedy`` hold 0 or 1), so a
        # chunk stages this one buffer as it stands
        n = self._slots
        self._slot_req = [None] * n
        self._slot_seqs = [None] * n
        self._rows = _np.zeros((n, _SLOT_COLS + self.cache.index_width),
                               _np.int32)
        (self._lens, self._token, self._active, self._remaining, self._topk,
         self._greedy, self._eos) = (self._rows[:, c] for c in (
             _LENS, _TOKEN, _ACTIVE, _REMAINING, _TOP_K, _GREEDY, _EOS))
        self._temp = self._rows[:, _TEMP].view(_np.float32)
        self._topp = self._rows[:, _TOP_P].view(_np.float32)
        self._temp[:] = self._topp[:] = 1.0
        self._greedy[:] = 1
        self._eos[:] = -1
        for row in self._rows:  # every slot empty: null blocks, null state
            self.cache.write_row(row[_SLOT_COLS:], None)
        self._deploy(seed)
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"mxtpu-genserve-{self._name}")
        if autostart:
            self._thread.start()

    @staticmethod
    def _normalize_buckets(shapes):
        if base.is_int(shapes):
            shapes = [shapes]
        out = set()
        for s in shapes:
            if isinstance(s, (tuple, list)):
                if len(s) != 1:
                    raise MXNetError(
                        "generation buckets are PROMPT LENGTHS (ints or "
                        f"1-tuples); got {s!r}")
                s = s[0]
            out.add(int(s))
        buckets = sorted(out)
        if not buckets or buckets[0] <= 0:
            raise MXNetError(f"invalid prompt buckets {shapes!r}")
        return buckets

    # -- deploy: build + AOT-compile + warm + seal -------------------------
    def _deploy(self, seed):
        import jax
        import jax.numpy as jnp

        chunk_fn, prefill_fn = generation_programs(self._net, self._chunk)
        params = self._net.params()
        self._params = params
        self._rng = jax.random.PRNGKey(int(seed))
        chunk_args = (params, self.cache.arrays(), jnp.asarray(self._rows),
                      self._rng)
        jfn = jax.jit(chunk_fn, donate_argnums=(1,))
        t0 = time.perf_counter()
        self._chunk_exe = jfn.lower(*chunk_args).compile()
        self._record_compile("decode_chunk", t0, self._chunk_exe)
        if _obs.introspect.ENABLED \
                and not _obs.introspect.registered("decode_chunk"):
            _obs.introspect.register_jit(
                "decode_chunk", jfn,
                _obs.introspect.avals_of(chunk_args), donated=True)
        # warm run: all slots inactive -> writes land in the null block
        # and the null state, lens unchanged, rng advances; adopts the
        # returned arrays
        out = self._chunk_exe(*chunk_args)
        jax.block_until_ready(out[0])
        self.cache.adopt(out[0])
        self._rng = out[1]

        self._prefill_exes = {}
        jpf = jax.jit(prefill_fn, donate_argnums=(2,))
        for tb in self._buckets:
            if tb > self.max_seq:
                raise MXNetError(
                    f"prompt bucket {tb} exceeds the net's max_seq "
                    f"{self.max_seq}")
            args = (params, jnp.zeros((1, tb), jnp.int32),
                    self.cache.arrays(),
                    jnp.asarray(self._prefill_row(None)))
            t0 = time.perf_counter()
            exe = jpf.lower(*args).compile()
            self._prefill_exes[tb] = exe
            self._record_compile(f"decode_prefill[{tb}]", t0, exe)
            site = f"decode_prefill[{self._name}:{tb}]"
            if _obs.introspect.ENABLED \
                    and not _obs.introspect.registered(site):
                _obs.introspect.register_jit(
                    site, jpf, _obs.introspect.avals_of(args),
                    donated=True)
            # warm run: length 0 -> every write goes to the null block,
            # and the null state is left as it was
            tok, arrays = exe(*args)
            jax.block_until_ready(tok)
            self.cache.adopt(arrays)
        if self._pool_temp_share > _POOL_TEMP_SHARE_WARN:
            logging.getLogger(__name__).warning(
                "generation engine %s:%s: executable %s holds temporaries "
                "of %.2f times a cache array's bytes (%d blocks, %d bytes): "
                "at a deployment's cache that is a copy of it come back, "
                "and the executable's time will follow the cache's size",
                self._name, self._version, self._pool_temp_worst,
                self._pool_temp_share, self.cache.num_blocks,
                self.cache.array_bytes())
        self._sealed = True

    def _record_compile(self, what, t0, exe):
        """Counts the compile and checks that ``exe`` works on the
        cache in place: its temporaries over the bytes of the largest
        array it threads through (a KV pool, the retention layers'
        states). A copy of that array is a temporary of its size (the
        share passes 1); in place it is a few per cent (logits, the
        dense prefill's scores). ``stats()["pool_temp_share"]`` keeps
        the largest."""
        self._compiles += 1
        share = (exe.memory_analysis().temp_size_in_bytes
                 / self.cache.array_bytes())
        if share > self._pool_temp_share:
            self._pool_temp_share, self._pool_temp_worst = share, what
        if _obs.ENABLED:
            _obs.SERVE_COMPILE_TOTAL.inc(1, model=self._name)
            _obs.tracer().record(
                "serving.compile", cat="serving", ts=t0,
                dur=time.perf_counter() - t0,
                args={"model": self._name, "version": self._version,
                      "bucket": str(what), "pool_temp_share": share})

    # -- submit path -------------------------------------------------------
    def _bucket_for(self, plen):
        for tb in self._buckets:
            if plen <= tb:
                return tb
        return None

    def submit(self, x, max_new_tokens=None, temperature=1.0, top_k=0,
               top_p=1.0, greedy=True, seed=None, eos=None,
               deadline_ms=None, **_ignored) -> GenerateFuture:
        """Queue one prompt (1-D int token array; a leading singleton
        batch axis is squeezed). Typed refusals mirror the one-shot
        engine: :class:`EngineClosed`, :class:`ServerOverloaded` (queue
        full), :class:`RetraceForbidden` (no prompt bucket fits —
        sealed, never compiles). ``max_new_tokens`` is clipped so
        ``prompt + generated <= max_seq``."""
        prompt = _np.asarray(x)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size == 0:
            raise ServingError(
                "generation takes ONE 1-D prompt of token ids per "
                f"submit; got shape {prompt.shape}")
        prompt = prompt.astype(_np.int32)
        plen = int(prompt.size)
        bucket = self._bucket_for(plen)
        if bucket is None or plen >= self.max_seq:
            self._refused += 1
            if _obs.ENABLED:
                _obs.record_serve_request(self._name, "error")
            raise RetraceForbidden(
                f"sealed generation engine {self._name}:{self._version} "
                f"has no prefill bucket for prompt length {plen} "
                f"(cause: shape; retrace budget is 0 after warmup). "
                f"Known buckets: {self._buckets}, max_seq {self.max_seq}. "
                "Truncate the prompt, or add a bucket and redeploy.")
        max_new = int(max_new_tokens) if max_new_tokens else \
            self._max_new_default
        max_new = max(1, min(max_new, self.max_seq - plen))
        deadline = (time.perf_counter() + float(deadline_ms) / 1e3
                    if deadline_ms else None)
        req = _GenRequest(
            prompt, max_new, temperature, top_k, top_p, greedy,
            seed if seed is not None else _np.random.randint(1 << 30),
            eos if eos is not None else -1, deadline)
        with self._lock:
            if self._closing or self._killed or self._paused:
                if _obs.ENABLED:
                    _obs.record_serve_request(self._name, "closed")
                raise EngineClosed(
                    f"generation engine {self._name}:{self._version} is "
                    "not accepting requests "
                    f"({'paused' if self._paused else 'closed'})")
            if len(self._queue) >= self._queue_cap:
                self._shed += 1
                if _obs.ENABLED:
                    _obs.record_serve_request(self._name, "shed")
                raise ServerOverloaded(
                    f"generation queue full ({self._queue_cap}) on "
                    f"{self._name}:{self._version} — retry with backoff")
            self._queue.append(req)
            self._idle.clear()
        self._work.set()
        if _obs.ENABLED:
            _obs.SERVE_QUEUE_DEPTH.set(self.queue_depth(),
                                       model=self._name)
        return GenerateFuture(req)

    def predict(self, x, timeout=None, **kwargs):
        """Synchronous generation: submit + wait; returns np.int32
        generated token ids."""
        return self.submit(x, **kwargs).result(timeout)

    # -- scheduler loop ----------------------------------------------------
    def _loop(self):
        while True:
            with self._lock:
                killed = self._killed
            if killed:
                self._abort_all(ReplicaDead(
                    f"generation engine {self._name}:{self._version} was "
                    "killed (host-death simulation) — request failed over "
                    "by the fleet router"))
                return
            self._admit()
            if self._active.any():
                self._step_chunk()
                continue
            with self._lock:
                drained = not self._queue
                closing = self._closing
            if drained:
                self._idle.set()
                if closing:
                    return
            with _obs.span("gen.idle", cat="generation"):
                self._work.wait(0.02)
            self._work.clear()

    def _fail(self, req, err, code):
        self._failed += 1
        if _obs.ENABLED:
            _obs.record_serve_request(self._name, code)
        self._account(req)
        self._trace_request(req, code)
        req.finish(error=err, version=self._version)

    def _trace_request(self, req, outcome):
        """One finished or failed request's phases into the ring, from
        the stamps on it. They cross threads (submitted on the client's,
        finished here), so they go to the ring with explicit times and
        not to the profiler. ``req`` is submit -> its last token's
        stamp (now, for one that failed), ``req.queue`` submit ->
        admitted, ``req.prefill`` -> first token, ``req.decode`` -> last
        token; all four under one ``rid``. ``req.decode`` carries the
        split of its pace (``_account``): ``tokens`` after the first,
        ``device_us`` and ``stall_us``."""
        if not _obs.watching():
            return
        ring = _obs.tracer()
        end = req.t_last if outcome == "ok" else time.perf_counter()
        rid = req.rid
        ring.record("req", cat="request", ts=req.t_submit,
                    dur=end - req.t_submit, span_id=rid,
                    args={"rid": rid, "outcome": outcome,
                          "prompt_len": len(req.prompt),
                          "tokens": len(req.tokens)})
        phases = [("req.queue", req.t_submit, req.t_admit or end)]
        if req.t_admit is not None and req.t_first is not None:
            phases += [("req.prefill", req.t_admit, req.t_first),
                       ("req.decode", req.t_first, req.t_last)]
        for name, t0, t1 in phases:
            args = {"rid": rid, "parent": rid}
            if name == "req.decode":
                args.update(tokens=len(req.tokens) - 1,
                            device_us=req.device_s * 1e6,
                            stall_us=req.stall_s * 1e6)
            ring.record(name, cat="request", ts=t0, dur=t1 - t0, args=args)

    def _account(self, req):
        """Splits the pace of a request that is leaving, ``t_last -
        t_first``: ``device_s``, the wall of the chunks it was live in
        (a live slot is in every chunk, so the difference of the sum
        is exact), ``stall_s``, the other requests' prefills between
        its first and last token; the rest is the scheduler's host
        turn. The sums are read as they stood at the last chunk's
        delivery, its last token's stamp: a request failed after it
        takes none of what ran since."""
        n = len(req.tokens) - 1
        if req.pace0 is None or n < 1:
            return
        chunks, prefills = self._delivered
        req.device_s = chunks - req.pace0[0]
        req.stall_s = prefills - req.pace0[1]
        decode_s = req.t_last - req.t_first
        decode, device, stall, intervals = self._pace
        # one new tuple: ``stats()`` on another thread reads all four
        # of one moment
        self._pace = (decode + decode_s, device + req.device_s,
                      stall + req.stall_s, intervals + n)
        if _obs.ENABLED:
            _obs.DECODE_ITL_SECONDS.observe(decode_s / n, model=self._name)

    def _admit(self):
        """Join queued requests to idle slots (iteration-level
        scheduling): sweep deadlines, then prefill into free slots
        while the cache can back the prompt."""
        with _obs.span("gen.admit", cat="generation") as sp:
            sp.set(admitted=self._admit_queued())

    def _admit_queued(self) -> int:
        """``_admit``'s work; returns how many requests it prefilled."""
        admitted = 0
        now = time.perf_counter()
        with self._lock:
            q = list(self._queue)
        for req in q:
            if req.deadline is not None and now > req.deadline \
                    and not req.claimed:
                with self._lock:
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        continue
                self._timeouts += 1
                self._fail(req, RequestTimeout(
                    "generation deadline expired before a slot opened"),
                    "timeout")
        while True:
            # between chunks a slot is live or holds no request
            free = _np.flatnonzero(self._active == 0)
            if not free.size:
                return admitted
            with self._lock:
                req = self._queue.popleft() if self._queue else None
            if req is None:
                return admitted
            if not req.claim():  # lost to cancel()
                continue
            try:
                seq = self.cache.allocate(len(req.prompt))
            except KVCacheOOM as e:
                if self._active.any():
                    # blocks free as running sequences retire: put the
                    # request back and retry after the next chunk
                    with req._state_lock:
                        req.claimed = False
                    with self._lock:
                        self._queue.appendleft(req)
                    return admitted
                self._fail(req, e, "shed")
                continue
            req.t_admit = time.perf_counter()
            admitted += 1
            try:
                self._prefill(req, seq, int(free[0]))
            except BaseException as e:  # noqa: BLE001 - typed to waiter
                self.cache.release(seq)
                self._fail(req, e if isinstance(e, ServingError) else
                           ServingError(f"prefill failed: {e}"), "error")

    def _prefill(self, req, seq, slot):
        t_in = time.perf_counter()
        plen = len(req.prompt)
        tb = self._bucket_for(plen)
        try:
            with _obs.span("gen.prefill", cat="generation", rid=req.rid,
                           bucket=tb, prompt_len=plen, slot=slot):
                self._prefill_traced(req, seq, slot, plen, tb)
        finally:
            # failed or not, every request decoding waited through it
            self._prefill_s += time.perf_counter() - t_in
            req.pace0 = (self._chunk_wait_s, self._prefill_s)

    def _prefill_row(self, seq, req=None):
        """A prefill's packed operand, one row: the request's scalars,
        then ``seq``'s index row (``None``: the deploy's warm run, a
        prompt of no tokens into the null block and the null state)."""
        row = _np.zeros((1, _PREFILL_COLS + self.cache.index_width),
                        _np.int32)
        bits = row.view(_np.float32)
        row[0, _P_GREEDY] = bits[0, _P_TEMP] = bits[0, _P_TOP_P] = 1
        if req is not None:
            row[0, _P_LENGTH] = len(req.prompt)
            row[0, _P_SEED] = req.seed
            row[0, _P_TOP_K] = req.top_k
            row[0, _P_GREEDY] = req.greedy
            bits[0, _P_TEMP] = max(req.temperature, 1e-6)
            bits[0, _P_TOP_P] = req.top_p
        self.cache.write_row(row[0, _PREFILL_COLS:], seq)
        return row

    def _prefill_traced(self, req, seq, slot, plen, tb):
        import jax

        padded = _np.zeros((1, tb), _np.int32)
        padded[0, :plen] = req.prompt
        row = self._prefill_row(seq, req)
        t0 = time.perf_counter()  # dt: staging, the call and the sync
        tokens, staged = jax.device_put((padded, row))
        self._uploads += 2
        with _obs.span("gen.prefill.device", cat="generation", bucket=tb):
            tok, arrays = self._prefill_exes[tb](
                self._params, tokens, self.cache.arrays(), staged)
            self.cache.adopt(arrays)
            # the ONE deliberate per-request sync: the first token
            # decides retire-or-seat before the next chunk can include
            # this slot
            first = int(jax.device_get(tok)[0])  # mxtpu-lint: host-sync-ok
            self._fetches += 1
        dt = time.perf_counter() - t0
        self.cache.written(seq, plen)
        self._prefills += 1
        now = time.perf_counter()
        req.tokens.append(first)
        req.t_first = req.t_last = now
        self._tokens += 1
        if _obs.ENABLED:
            _obs.record_xla_dispatch("decode_prefill")
            _obs.DECODE_PREFILL_SECONDS.observe(dt, model=self._name)
            _obs.DECODE_TOKENS_TOTAL.inc(1, model=self._name)
        done = (req.max_new <= 1
                or (req.eos >= 0 and first == req.eos))
        if done:
            self._retire(req, seq)
            return
        self._slot_req[slot] = req
        self._slot_seqs[slot] = seq
        self._rows[slot, _SLOT_COLS:] = row[0, _PREFILL_COLS:]
        self._lens[slot] = plen  # next decode step writes position plen
        self._token[slot] = first
        self._active[slot] = True
        self._remaining[slot] = req.max_new - 1
        self._temp[slot] = max(req.temperature, 1e-6)
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._greedy[slot] = req.greedy
        self._eos[slot] = req.eos
        if _obs.ENABLED:
            _obs.DECODE_ACTIVE_SLOTS.set(
                int(self._active.sum()),  # host numpy mirror  # mxtpu-lint: host-sync-ok
                model=self._name)

    def _step_chunk(self):
        """One decode dispatch: every active slot advances up to
        ``chunk`` tokens; retirements free their slots and cache blocks
        at the boundary (where the NEXT _admit can seat a newcomer)."""
        with _obs.span("gen.chunk", cat="generation") as sp:
            with _obs.span("gen.chunk.prep", cat="generation"):
                live = self._grow_sequences()
                if not live.size:
                    return
                # the sampler's own predicate, before the chunk
                if (self._greedy[live] == 0).any():
                    self._filtered_chunks += 1
                t0 = time.perf_counter()  # dt: staging, the call and the sync
                operands = self._chunk_operands()
            if sp is not _obs.NO_SPAN:
                # read after the chunk's growth, where the cache is fullest
                sp.set(blocks_used=self.cache.blocks_used())
            with _obs.span("gen.chunk.device", cat="generation",
                           steps=self._chunk):
                toks = self._run_chunk(operands)
            dt = time.perf_counter() - t0
            with _obs.span("gen.chunk.deliver", cat="generation") as out:
                emitted, retired = self._deliver(live, toks, dt)
                out.set(emitted=emitted, retired=retired)

    def _grow_sequences(self):
        """Backs the chunk's cache growth, live slot by live slot
        (blocks of the pool; a state grows by nothing), and rewrites the
        index row of a slot whose sequence took a block; the slots left
        to step."""
        live = _np.flatnonzero(self._active)
        need = _np.minimum(
            self._lens[live] + _np.minimum(self._chunk,
                                           self._remaining[live]),
            self.max_seq)
        for s, tokens in zip(live.tolist(), need.tolist()):
            seq = self._slot_seqs[s]
            try:
                if self.cache.ensure(seq, tokens):
                    self.cache.write_row(self._rows[s, _SLOT_COLS:], seq)
            except KVCacheOOM as e:
                # a pool too full to grow a sequence retires that
                # request early (typed OOM)
                req = self._slot_req[s]
                self.cache.release(seq)
                self._clear_slot(s)
                self._fail(req, e, "shed")
        return live[self._active[live] != 0]

    def _chunk_operands(self):
        """Stages the chunk's operands: the slots' rows are the one
        upload (the cache's arrays and the key are resident)."""
        import jax

        self._uploads += 1
        return self.cache.arrays(), jax.device_put(self._rows), self._rng

    def _run_chunk(self, operands):
        """The chunk's executable, to the last byte the scheduler needs
        of it: the token each slot emitted a step, ``(chunk, slots)``,
        -1 where it emitted none. The slots' ``lens, token, active,
        remaining`` are written back into their mirrors."""
        import jax

        arrays, self._rng, result, *load = self._chunk_exe(self._params,
                                                           *operands)
        self.cache.adopt(arrays)
        jax.block_until_ready(result)
        with _obs.span("gen.chunk.fetch", cat="generation"):
            # ONE host sync per chunk: everything the scheduler needs
            result, *load = jax.device_get(  # mxtpu-lint: host-sync-ok
                (result, *load))
        self._fetches += 1
        if load:
            self._expert_load += load[0]
        self._rows[:, :_CARRY_COLS] = result[self._chunk:].T
        return result[:self._chunk]

    def _deliver(self, live, toks, dt):
        """Hands each of the ``live`` slots its tokens of a chunk that
        took ``dt`` seconds and retires what finished; ``(tokens
        emitted, requests retired)``."""
        self._chunk_wait_s += dt
        self._chunks += 1
        now = time.perf_counter()  # the stamp of every token it delivers
        self._delivered = (self._chunk_wait_s, self._prefill_s)
        emitted_total = retired = 0
        for s in live.tolist():
            req = self._slot_req[s]
            new = toks[:, s]
            new = new[new >= 0].tolist()
            n = len(new)
            if n:
                req.tokens.extend(new)
                req.t_last = now
                emitted_total += n
            if not self._active[s]:
                seq = self._slot_seqs[s]
                self._clear_slot(s)
                self._retire(req, seq)
                retired += 1
        self._tokens += emitted_total
        if _obs.ENABLED:
            _obs.record_xla_dispatch("decode_chunk")
            _obs.DECODE_CHUNKS_TOTAL.inc(1, model=self._name)
            if emitted_total:
                _obs.DECODE_TOKENS_TOTAL.inc(emitted_total,
                                             model=self._name)
            _obs.DECODE_ACTIVE_SLOTS.set(
                int(self._active.sum()),  # host numpy mirror  # mxtpu-lint: host-sync-ok
                model=self._name)
        return emitted_total, retired

    def _clear_slot(self, s):
        """The slot holds nothing: no request, its index row the null
        block's and the null state's again (its policy stays behind)."""
        self._slot_req[s] = None
        self._slot_seqs[s] = None
        self._rows[s, :_CARRY_COLS] = 0  # lens, token, active, remaining
        self.cache.write_row(self._rows[s, _SLOT_COLS:], None)

    def _retire(self, req, seq):
        self.cache.release(seq)
        self._requests_ok += 1
        if _obs.ENABLED:
            _obs.record_serve_request(self._name, "ok")
            _obs.SERVE_LATENCY_SECONDS.observe(
                time.perf_counter() - req.t_submit, model=self._name)
        self._account(req)
        self._trace_request(req, "ok")
        req.finish(result=_np.asarray(req.tokens, _np.int32),
                   version=self._version)

    def _abort_all(self, err):
        with self._lock:
            queued = list(self._queue)
            self._queue.clear()
        for req in queued:
            self._fail(req, err, "closed")
        for s in range(self._slots):
            req = self._slot_req[s]
            if req is not None:
                if self._slot_seqs[s] is not None:
                    self.cache.release(self._slot_seqs[s])
                self._clear_slot(s)
                self._fail(req, err, "closed")
        self._idle.set()

    # -- introspection -----------------------------------------------------
    @property
    def version(self):
        return self._version

    @property
    def buckets(self):
        """Prompt-length buckets, 1-tuples (InferenceEngine shape)."""
        return [(b,) for b in self._buckets]

    @property
    def sealed(self):
        return self._sealed

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def active_slots(self) -> int:
        return int(self._active.sum())  # the column holds 0 or 1

    def stats(self) -> dict:
        """Engine-local snapshot (plain floats; telemetry-independent).
        ``retraces_after_warmup`` is structurally 0: every executable is
        AOT-sealed and slot liveness is an operand, never a shape."""
        dispatches = self._chunks + self._prefills
        return {
            "model": self._name,
            "version": self._version,
            "engine": "generation",
            "buckets": list(self._buckets),
            "slots": self._slots,
            "chunk": self._chunk,
            "requests_ok": self._requests_ok,
            "refused": self._refused,
            "shed": self._shed,
            "timeouts": self._timeouts,
            "failed": self._failed,
            "tokens_generated": self._tokens,
            "prefills": self._prefills,
            "decode_chunks": self._chunks,
            "filtered_chunks": self._filtered_chunks,
            "dispatches": dispatches,
            "host_transfers": {"uploads": self._uploads,
                               "fetches": self._fetches},
            "tokens_per_dispatch": self._tokens / max(1, dispatches),
            # the finished requests' pace, t_last - t_first summed, and
            # how much of it was chunks they were live in and other
            # requests' prefills (the rest: the scheduler's host turn);
            # over ``intervals``, their tokens after the first
            "pace": dict(zip(("decode_s", "device_s", "stall_s",
                              "intervals"), self._pace)),
            "queue_depth": self.queue_depth(),
            "active_slots": self.active_slots(),
            "compiles": self._compiles,
            "pool_temp_share": self._pool_temp_share,
            "retraces_after_warmup": 0 if self._sealed else None,
            "recompiles_after_warmup": 0 if self._sealed else None,
            "cache": self.cache.stats(),
            **({} if self._expert_load is None else {"experts": dict(zip(
                ("routed_pairs", "experts_hit", "load_max"),
                map(int, self._expert_load)))}),
        }

    def canary(self):
        """Deploy-time verification: a short greedy generation must
        return in-vocabulary token ids (the repository's staged-load
        veto for generation engines — finite-logits NaN screens ride
        the argmax: NaN logits produce out-of-range/degenerate ids)."""
        started = self._thread.is_alive()
        if not started:
            self._thread.start()
        toks = self.predict(_np.array([1, 2], _np.int32),
                            max_new_tokens=2, greedy=True, timeout=60.0)
        if len(toks) == 0 or _np.any(toks < 0) \
                or _np.any(toks >= self.vocab_size):
            raise ServingError(
                f"generation canary produced out-of-vocabulary ids "
                f"{toks!r} — refusing to serve this version")
        return toks

    # -- lifecycle ---------------------------------------------------------
    def pause(self):
        """Stop accepting work and drain: queued + in-flight
        generations complete, executables and pools stay resident
        (repository standby — resume() is a flag flip)."""
        with self._lock:
            if self._paused or self._closing:
                return
            self._paused = True
        self._work.set()
        self._idle.wait(timeout=120.0)

    def resume(self):
        with self._lock:
            if self._closing or self._killed:
                raise EngineClosed(
                    f"engine {self._name}:{self._version} was released; "
                    "reload instead of resume")
            self._paused = False

    def kill(self):
        """Abrupt host-death simulation: queued AND in-flight requests
        fail with typed :class:`ReplicaDead` (the fleet router fails
        them over); nothing drains. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._killed = True
            self._closing = True
        self._work.set()
        if self._thread.is_alive():
            self._thread.join(timeout=30.0)
        else:
            self._abort_all(ReplicaDead(
                f"generation engine {self._name}:{self._version} killed"))
        self._release()

    def close(self):
        """Drain queued + in-flight generations, then release
        executables, pools, and weight references. Idempotent."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
        self._work.set()
        if self._thread.is_alive():
            self._thread.join(timeout=120.0)
        self._abort_all(EngineClosed(
            f"generation engine {self._name}:{self._version} closed"))
        self._release()

    def _release(self):
        self._closed = True
        self._chunk_exe = None
        self._prefill_exes = {}
        self._params = None
        self.cache.release_arrays()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
