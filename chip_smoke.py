#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip, every phase
    python chip_smoke.py --chips 4   # four chips: the data-parallel leg only

One process, one attempt at the backend, no retry of a phase, no child
that needs the chip. The command line always runs the full widths below
and always requires a TPU: on any other backend it exits non-zero
before a phase starts and prints no result. Each phase drives the main
path through the entry points a user would call and checks what comes
out by the repository's own references; a phase that fails raises, and
the run ends non-zero.

Every phase prints one JSON line. The timings in those lines are SMOKE
READINGS (one cold run, five steps): they say the path ran, not how
fast the system is. The last line of standard output is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phases are functions that take shapes, so ``tests/test_chip_smoke.py``
runs each at a tiny size on the CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

# A bfloat16 logit keeps 8 significant bits. The decode path and the
# dense reference round differently, so where the reference ranks other
# tokens within this many bfloat16 steps of its top logit, any of them
# is the reference's answer. Seen at full width: 16 of 512 tokens, 8 of
# them exact ties, the widest 2.0 steps and three tokens down (CPU
# rehearsal), 1.7 steps (v5e, PR 22); the bound leaves one step of
# room. Zero for a float32 model.
BF16_TIE_STEPS = 3
# What a tie could hide, the logit tap shows: max|engine - reference| /
# max|reference| over each emitted token's logits. Twelve bf16 layers
# read 1.39e-2 at full width (CPU rehearsal, PR 22), and a decode that
# reads one KV row too few reads 1.39e-1 (four layers); the bound
# sits between.
LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-4}
# past this share of the device's memory a phase warns (stderr and its
# JSON line): the next allocation may not fit
MEMORY_WARN_SHARE = 0.9

# -- the full widths the command line runs ----------------------------------
RESNET50 = dict(batch=128, image=224, classes=1000, steps=5)
BERT_BASE = dict(batch=64, seq=128, vocab=30522, steps=5)
GPT2_SMALL = dict(vocab_size=50257, num_layers=12, d_model=768, num_heads=12,
                  d_ff=3072, max_seq=1024, dtype="bfloat16")
SERVE = dict(prompt_lens=(16, 40, 72, 128, 200, 288, 400, 512),
             buckets=(32, 128, 512), new_tokens=64, slots=8, chunk=8)
KERNELS = dict(
    flash=(((4, 12, 128, 64), False), ((1, 16, 4096, 64), True)),
    window=((1, 8, 8192, 64), 1024),
    # B, H, KVH, D, block_size, max_blocks per sequence
    decode=(32, 12, 12, 64, 16, 64))
RESNET50_DP4 = dict(batch=128, image=224, classes=1000, steps=3)


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def _require(cond, what):
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits included: a
    hit is still a new executable that the steady state must not need)."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class _FallbackLog(logging.Handler):
    """Collects ``fusedstep.log_fallback`` firings while attached."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.fired = []

    def emit(self, record):
        self.fired.append(record.getMessage())

    def __enter__(self):
        from mxnet_tpu import fusedstep

        fusedstep.reset_fallback_log()  # it logs once per reason
        logging.getLogger("mxnet_tpu.fusedstep").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("mxnet_tpu.fusedstep").removeHandler(self)


def _seed(seed):
    import mxnet_tpu as mx

    np.random.seed(seed)  # initializers draw from numpy's global stream
    mx.random.seed(seed)
    return np.random.RandomState(seed)


def _memory(device, phase):
    """The allocator's view after a phase. The peak is the process's
    high-water mark so far, not this phase's alone; past
    ``MEMORY_WARN_SHARE`` of the limit the phase says so."""
    stats = device.memory_stats() or {}  # the CPU backend reports none
    out = {k: stats.get(k) for k in
           ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")}
    if out["peak_bytes_in_use"] and out["bytes_limit"]:
        share = out["peak_bytes_in_use"] / out["bytes_limit"]
        out["peak_share_of_limit"] = round(share, 4)
        if share > MEMORY_WARN_SHARE:
            out["warning"] = (f"peak is {share:.1%} of the device's "
                              f"memory (warns past {MEMORY_WARN_SHARE:.0%})")
            print(f"chip_smoke: {phase}: {out['warning']}", file=sys.stderr)
    return out


def _finite(losses, what):
    _require(all(np.isfinite(v) for v in losses),
             f"{what}: a loss is not finite: {losses}")


def _param_platforms(net):
    return sorted({p.data().ctx.jax_device.platform
                   for p in net.collect_params().values()})


def _timed_steps(one_step, steps, compiles):
    """Run ``one_step() -> (loss, what_to_wait_for)`` ``steps`` times.
    Returns (losses, per-step ms closed by block_until_ready, compiles
    per step). The loss is read after the clock stops."""
    import jax

    losses, ms, compiled = [], [], []
    for _ in range(steps):
        c0, t0 = compiles.count, time.perf_counter()
        loss, pending = one_step()
        jax.block_until_ready(pending)
        ms.append((time.perf_counter() - t0) * 1e3)
        compiled.append(compiles.count - c0)
        losses.append(float(np.asarray(loss, np.float32).mean()))
    return losses, ms, compiled


# ---------------------------------------------------------------------------
# phase: the README's Gluon loop
# ---------------------------------------------------------------------------

def train_resnet50(compiles, *, batch, image, classes, steps, make_net=None,
                   dtype="bfloat16", seed=0, platform="tpu"):
    """``hybridize()`` + ``autograd.record()`` -> ``backward()`` ->
    ``Trainer.step()`` on one seeded batch: the fused plan must hold,
    only step 1 may compile, every parameter lives on ``platform``."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import vision

    rng = _seed(seed)
    net = (make_net or vision.resnet50_v1)()
    net.initialize(mx.initializer.Xavier(), ctx=mx.tpu())
    net.cast(dtype)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9,
                             "wd": 1e-4})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.nd.array(rng.rand(batch, 3, image, image).astype(np.float32),
                    ctx=mx.tpu()).astype(dtype)
    y = mx.nd.array(rng.randint(0, classes, (batch,)).astype(np.float32),
                    ctx=mx.tpu())
    params = list(net.collect_params().values())

    def one_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        return loss.data, [p.data().data for p in params]

    with _FallbackLog() as fallbacks:
        c0, t0 = compiles.count, time.perf_counter()
        # deferred shapes resolve in one eager forward (Gluon's own
        # rule); one row is enough, so step 1 is already hybridized
        with autograd.predict_mode():
            net(x[0:1]).wait_to_read()
        init_s = time.perf_counter() - t0
        losses, ms, compiled = _timed_steps(one_step, steps, compiles)
    out = {
        "phase": "train_resnet50", "path": "gluon.Trainer fused plan",
        "batch": batch, "image": image, "dtype": dtype, "steps": steps,
        "n_params": sum(int(np.prod(p.shape)) for p in params),
        "smoke_reading_compile_s": round(init_s + ms[0] / 1e3, 3),
        "smoke_reading_step_ms": [round(v, 3) for v in ms],
        "losses": losses, "compiles_by_step": compiled,
        "compiles_total": compiles.count - c0,
        "fused_fallbacks": fallbacks.fired,
        "param_platforms": _param_platforms(net),
        "memory": _memory(jax.devices()[0], "train_resnet50"),
    }
    _finite(losses, "train_resnet50")
    _require(not fallbacks.fired,
             f"the fused plan was declined: {fallbacks.fired}")
    _require(not any(compiled[1:]),
             f"steps after the first compiled: {compiled}")
    _require(out["param_platforms"] == [platform],
             f"parameters live on {out['param_platforms']}, "
             f"expected {platform!r}")
    return out


# ---------------------------------------------------------------------------
# phase: BERT through the one-executable SPMD step
# ---------------------------------------------------------------------------

def _mlm_loss():
    from mxnet_tpu import gluon

    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        logits = out[-1] if isinstance(out, (tuple, list)) else out
        return sce(logits, y)

    return mlm_loss


def train_bert_base(compiles, *, batch, seq, vocab, steps, make_net=None,
                    dtype="bfloat16", seed=0, platform="tpu"):
    """``parallel.SPMDTrainStep(net, mlm_loss, "adam", mesh=None)``.
    On a TPU the step's compiled HLO must hold a
    ``tpu_custom_call`` (else attention quietly took the jnp path)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.models import bert as bert_mod

    rng = _seed(seed)
    net = (make_net or (lambda: bert_mod.bert_base(
        dropout=0.0, use_pooler=False, use_classifier=False)))()
    net.initialize(init=mx.initializer.Normal(0.02))
    net.cast(dtype)
    step = parallel.SPMDTrainStep(net, _mlm_loss(), "adam", {"wd": 0.01},
                                  mesh=None)
    x = mx.nd.array(rng.randint(0, vocab, (batch, seq)), dtype="int32")
    y = mx.nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.float32))

    def one_step():
        loss = step(x, y, lr=1e-4, sync=False)
        return loss, loss

    with _FallbackLog() as fallbacks:
        c0 = compiles.count
        losses, ms, compiled = _timed_steps(one_step, steps, compiles)
    compiles_total = compiles.count - c0
    hlo = step._compile_step().as_text()  # lowers the step once more
    state_platforms = sorted({d.platform for leaf in step._state[0]
                              for d in leaf.devices()})
    out = {
        "phase": "train_bert_base", "path": "parallel.SPMDTrainStep",
        "batch": batch, "seq": seq, "vocab": vocab, "dtype": dtype,
        "steps": steps,
        "smoke_reading_compile_s": round(ms[0] / 1e3, 3),
        "smoke_reading_step_ms": [round(v, 3) for v in ms],
        "losses": losses, "compiles_by_step": compiled,
        "compiles_total": compiles_total,
        "tpu_custom_call_in_step_hlo": "tpu_custom_call" in hlo,
        "spmd_fallbacks": fallbacks.fired,
        "param_platforms": state_platforms,
        "memory": _memory(jax.devices()[0], "train_bert_base"),
    }
    _finite(losses, "train_bert_base")
    _require(not any(compiled[1:]),
             f"steps after the first compiled: {compiled}")
    if platform == "tpu":
        _require(out["tpu_custom_call_in_step_hlo"],
                 "no tpu_custom_call in the step's HLO: attention took "
                 "_jnp_flash_fwd")
    _require(state_platforms == [platform],
             f"step state lives on {state_platforms}, expected {platform!r}")
    return out


# ---------------------------------------------------------------------------
# phase: kernels against their jnp references
# ---------------------------------------------------------------------------

def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _flash_case(shape, causal, window, dtype, seed, grads):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(seed)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(4))
    scale = shape[-1] ** -0.5

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, window=window)

    def reference(q, k, v):
        return fa._jnp_flash_fwd(q, k, v, scale, causal or window > 0,
                                 window)[0]

    errs = {"fwd": _rel_err(jax.jit(kernel)(q, k, v),
                            jax.jit(reference)(q, k, v))}
    if grads:
        def loss_of(f):
            return lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

        got = jax.jit(jax.grad(loss_of(kernel), (0, 1, 2)))(q, k, v)
        ref = jax.jit(jax.grad(loss_of(reference), (0, 1, 2)))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, ref):
            errs[name] = _rel_err(a, b)
    return errs


def _decode_case(B, H, KVH, D, bs, mb, dtype, seed):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(seed)
    nb = B * mb + 1
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k_pool = jnp.asarray(rng.randn(nb, bs, KVH, D), dtype)
    v_pool = jnp.asarray(rng.randn(nb, bs, KVH, D), dtype)
    tables = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb),
                         jnp.int32)
    lens = rng.randint(1, mb * bs + 1, (B,))
    lens[:4] = (0, 1, bs, mb * bs)  # empty slot, one token, block edge, full
    lens = jnp.asarray(lens, jnp.int32)
    got = jax.jit(fa.paged_decode_attention)(q, k_pool, v_pool, tables, lens)
    ref = jax.jit(lambda q, k, v, t, l: fa._jnp_paged_decode(
        q, k.reshape(1, nb, bs, -1), v.reshape(1, nb, bs, -1), t, l,
        D ** -0.5))(q, k_pool, v_pool, tables, lens)
    _require(not np.asarray(got, np.float32)[0].any(),
             "paged decode: an empty slot did not return zeros")
    return {"out": _rel_err(got, ref)}


def kernels(*, flash, window, decode, dtype="bfloat16", seed=0, tol=None):
    """The public attention ops (the Pallas kernels on a TPU) against
    ``_jnp_flash_fwd`` / ``_jnp_paged_decode`` on the same device.
    ``tol`` bounds max|got - ref| / max|ref|; default is the bf16 bound
    the on-chip tests use (forward 2e-2, gradients 5e-2)."""
    import jax

    fwd_tol, grad_tol = (tol, tol) if tol is not None else (2e-2, 5e-2)
    cases = {}
    for shape, causal in flash:
        name = f"flash{'_causal' if causal else ''}_{'x'.join(map(str, shape))}"
        cases[name] = _flash_case(shape, causal, 0, dtype, seed, grads=True)
    wshape, w = window
    cases[f"window{w}_{'x'.join(map(str, wshape))}"] = _flash_case(
        wshape, True, w, dtype, seed, grads=False)
    cases["paged_decode_" + "x".join(map(str, decode))] = _decode_case(
        *decode, dtype, seed)
    for name, errs in cases.items():
        for what, err in errs.items():
            bound = fwd_tol if what in ("fwd", "out") else grad_tol
            _require(np.isfinite(err) and err <= bound,
                     f"kernels: {name} {what} off by {err:.4g} "
                     f"(bound {bound:g})")
    return {"phase": "kernels", "dtype": dtype, "max_rel_err": cases,
            "tol_fwd": fwd_tol, "tol_grad": grad_tol,
            "memory": _memory(jax.devices()[0], "kernels")}


# ---------------------------------------------------------------------------
# phase: the paged-decode server
# ---------------------------------------------------------------------------

def _tap_columns(vocab):
    """Every k-th logit, some 256 of them: what the tap hands the host."""
    return np.arange(0, vocab, max(1, vocab // 256))


def _tapped_decoder(taps):
    """``TransformerDecoderLM`` whose prefill and decode step also hand
    the host the logits the engine samples from: per sequence the top
    logit, its index and the ``_tap_columns`` (``jax.debug.callback``,
    some 1 KB per token). The engine is not touched and has no logits
    to give; this is the only way to hold them to the reference."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import TransformerDecoderLM

    def tap(kind, at, live, logits):
        cols = _tap_columns(logits.shape[-1])
        jax.debug.callback(
            lambda *a: taps.append((kind, *map(np.asarray, a))),
            at, live, logits.argmax(-1), logits.max(-1).astype(jnp.float32),
            logits[:, cols].astype(jnp.float32))

    class Tapped(TransformerDecoderLM):
        def prefill_fn(self):
            prefill = super().prefill_fn()

            def tapped(params, tokens, k_pool, v_pool, table, length):
                out = prefill(params, tokens, k_pool, v_pool, table, length)
                tap("prefill", length, length > 0, out[0])
                return out

            return tapped

        def decode_step_fn(self):
            step = super().decode_step_fn()

            def tapped(params, token, pos, k_pool, v_pool, tables, active):
                out = step(params, token, pos, k_pool, v_pool, tables,
                           active)
                tap("decode", pos, active, out[0])
                return out

            return tapped

    return Tapped


def _tapped_logits(taps, prompts, outputs):
    """Sort the tap's records by request: per request and emitted token
    (argmax, top logit, tapped columns). The prefill's record carries
    the prompt length; a decode record carries its slot and the
    position it wrote, and a slot's first position is its request's
    prompt length. So prompt lengths are distinct and no slot serves
    two requests, which the caller's shapes guarantee."""
    by_len = {len(p): i for i, p in enumerate(prompts)}
    _require(len(by_len) == len(prompts), "serve_decode: prompt lengths "
             "must differ for the tap to tell requests apart")
    rows = [dict() for _ in prompts]  # token index -> record
    slots = {}
    for kind, at, live, arg, top, cols in taps:
        for b in np.flatnonzero(live):
            rec = (int(arg[b]), float(top[b]), cols[b])
            if kind == "prefill":
                rows[by_len[int(at[b])]][0] = rec
            else:
                slots.setdefault(int(b), {})[int(at[b])] = rec
    for b, recs in slots.items():
        first = min(recs)
        _require(first in by_len and sorted(recs) == list(
            range(first, first + len(recs))),
            f"serve_decode: slot {b} served more than one request")
        for pos, rec in recs.items():
            rows[by_len[first]][pos - first + 1] = rec
    for i, (row, out) in enumerate(zip(rows, outputs)):
        _require(sorted(row) == list(range(len(out))),
                 f"serve_decode: the tap saw tokens {sorted(row)} of "
                 f"request {i}, the engine emitted {len(out)}")
    return [[row[k] for k in range(len(out))]
            for row, out in zip(rows, outputs)]


def _dense_reference(net, prompts, outputs):
    """The engine's dense-recompute reference (tests/test_generation.py):
    ONE causal forward of ``net.forward_fn()`` over prompt+generated
    must greedy-predict every generated token from its own prefix.
    Returns per request the reference's token, how many tokens it
    ranks above the one the engine produced, its top logit, its logit
    of the engine's token, its ``_tap_columns`` and max|logit|, each
    per generated token."""
    import jax
    import jax.numpy as jnp

    total = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    seqs = np.zeros((len(prompts), total), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        seqs[i, :len(p)] = p
        seqs[i, len(p):len(p) + len(o)] = o
    fwd = net.forward_fn()

    @jax.jit
    def ref(params, tokens):
        # the barrier keeps every reading below on the same rounded
        # logits (the TPU compiler otherwise recomputes the gathered
        # one from the head's column, at another precision)
        logits = jax.lax.optimization_barrier(fwd(params, tokens))
        logits = logits.astype(jnp.float32)[:, :-1]
        chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)
        return (logits.argmax(-1), (logits > chosen).sum(-1),
                logits.max(-1), chosen[..., 0],
                logits[..., _tap_columns(logits.shape[-1])],
                jnp.abs(logits).max(-1))

    arrays = [np.asarray(a) for a in ref(net.params(), jnp.asarray(seqs))]
    rows = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        at = slice(len(p) - 1, len(p) - 1 + len(o))
        rows.append(tuple(a[i, at] for a in arrays))
    return rows


def serve_decode(compiles, *, model, prompt_lens, buckets, new_tokens, slots,
                 chunk, seed=0, platform="tpu"):
    """``TransformerDecoderLM`` through ``GenerationEngine`` and
    ``PagedKVCache`` as examples/generate.py drives them: one greedy
    request per prompt length (all different, no more than ``slots``),
    all submitted at once. Nothing may compile after the engine's
    warm-up, and the tokens must equal the dense-recompute reference.

    In bfloat16 the reference's own top logits can sit within a
    rounding step or two of each other; there, and only there, the
    engine may emit another of them (``BF16_TIE_STEPS``), and nine
    tokens in ten must still be equal outright. Since a small KV fault
    could hide in such a tie, the logits the engine sampled from
    (``_tapped_decoder``) are also held to the reference's numerically,
    every emitted token, at ``LOGIT_TOL``. A float32 model must be
    token-exact. The tap's host callback is inside the gap readings."""
    import jax

    from mxnet_tpu.serving import GenerationEngine

    _require(len(prompt_lens) <= slots,
             "serve_decode: more requests than slots")
    rng = _seed(seed)
    taps = []
    net = _tapped_decoder(taps)(seed=seed, **model)
    block = 16
    per_seq = -(-model["max_seq"] // block)
    c0, t0 = compiles.count, time.perf_counter()
    eng = GenerationEngine(net, list(buckets), slots=slots, chunk=chunk,
                           cache_blocks=slots * per_seq + 1,
                           cache_block_size=block, name="chip-smoke")
    deploy_s = time.perf_counter() - t0
    deploy_compiles = compiles.count - c0
    try:
        prompts = [rng.randint(0, model["vocab_size"], (n,)).astype(np.int32)
                   for n in prompt_lens]
        c1, t0 = compiles.count, time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=new_tokens, greedy=True)
                for p in prompts]
        outputs = [f.result(timeout=600.0) for f in futs]
        served_compiles = compiles.count - c1
        ttft_ms = [(f.token_times()[0] - t0) * 1e3 for f in futs]
        gap_ms = [(f.token_times()[1] - f.token_times()[0]) * 1e3
                  / max(1, len(o) - 1) for f, o in zip(futs, outputs)]
        stats = eng.stats()
        pool_platforms = sorted({d.platform
                                 for d in eng.cache.k_pool.devices()})
    finally:
        eng.close()
    jax.effects_barrier()  # every tap has reached the host
    _require(all(len(o) == new_tokens for o in outputs),
             "serve_decode: a request came back short")
    dtype = model.get("dtype", "float32")
    steps = BF16_TIE_STEPS if dtype == "bfloat16" else 0
    exact, logit_err = 0, 0.0
    tied = []  # per tie: (bf16 steps below the reference's top, rank)
    beyond = []  # (engine's tokens, reference's) where no tie explains it
    for (want, rank, top, chosen, cols, scale), tapped, out in zip(
            _dense_reference(net, prompts, outputs),
            _tapped_logits(taps, prompts, outputs), outputs):
        _require([t[0] for t in tapped] == out.tolist(),
                 "serve_decode: the tapped logits' argmax is not the "
                 f"token the engine emitted: {tapped} vs {out.tolist()}")
        eng_top = np.array([t[1] for t in tapped])
        eng_cols = np.stack([t[2] for t in tapped])
        err = np.maximum(np.abs(eng_top - chosen),
                         np.abs(eng_cols - cols).max(-1)) / scale
        logit_err = max(logit_err, float(err.max()))
        same = out == want
        bf16_step = 2.0 ** (np.floor(np.log2(np.abs(top))) - 7)
        apart = (top - chosen) / bf16_step
        near = ~same & (apart <= steps)
        if not (same | near).all():
            beyond.append((out.tolist(), want.tolist()))
        exact += int(same.sum())
        tied += [(round(float(a), 3), int(r))
                 for a, r in zip(apart[near], rank[near])]
    out = {
        "phase": "serve_decode", "path": "GenerationEngine + PagedKVCache",
        "model": model, "requests": len(prompts),
        "prompt_lens": list(prompt_lens), "new_tokens": new_tokens,
        "slots": slots, "chunk": chunk, "buckets": list(buckets),
        "tokens_total": int(sum(len(o) for o in outputs)),
        "tokens_equal_reference": exact,
        "tokens_at_reference_rounding_tie": len(tied),
        "ties_steps_below_top_and_rank": tied,
        "tie_bound_bf16_steps": steps,
        "logits_max_rel_err": logit_err, "logits_tol": LOGIT_TOL[dtype],
        "smoke_reading_deploy_s": round(deploy_s, 3),
        "deploy_compiles": deploy_compiles,
        "compiles_after_warmup": served_compiles,
        "smoke_reading_ttft_ms": [round(v, 3) for v in ttft_ms],
        "smoke_reading_inter_token_gap_ms": [round(v, 3) for v in gap_ms],
        "dispatches": stats["dispatches"],
        "cache_platforms": pool_platforms,
        "memory": _memory(jax.devices()[0], "serve_decode"),
    }
    _require(np.isfinite(logit_err) and logit_err <= LOGIT_TOL[dtype],
             f"serve_decode: the engine's logits are off the dense "
             f"reference's by {logit_err:.4g} (bound {LOGIT_TOL[dtype]:g})")
    _require(not beyond,
             "serve_decode: tokens differ from the dense reference beyond "
             f"a rounding tie (engine, reference): {beyond[:1]}")
    _require(exact >= 0.9 * out["tokens_total"],
             f"serve_decode: only {exact} of {out['tokens_total']} tokens "
             "equal the dense reference")
    _require(served_compiles == 0,
             f"serve_decode: {served_compiles} compile(s) after warm-up")
    _require(pool_platforms == [platform],
             f"KV pool lives on {pool_platforms}, expected {platform!r}")
    return out


# ---------------------------------------------------------------------------
# phase (--chips 4): data parallel against one device, same seed
# ---------------------------------------------------------------------------

def train_resnet50_dp4(*, batch, image, classes, steps, make_net=None,
                       dtype="bfloat16", seed=0, devices=None, tol=5e-2):
    """ResNet-50 through ``SPMDTrainStep`` on ``make_mesh({"dp": 4})``
    and the same steps at ``mesh=None`` on device 0. The data-parallel
    leg normalises each BatchNorm over its own 32-image shard, the
    single-device leg over all 128, so the losses agree to ``tol``
    (relative), not bit for bit."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    devices = list(devices if devices is not None else jax.devices())[:4]
    _require(len(devices) == 4, f"need 4 devices, have {len(devices)}")

    def leg(mesh):
        rng = _seed(seed)
        net = (make_net or vision.resnet50_v1)()
        net.initialize(init=mx.initializer.Xavier())
        net.cast(dtype)
        step = parallel.SPMDTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"momentum": 0.9, "wd": 1e-4}, mesh=mesh)
        x = mx.nd.array(rng.rand(batch, 3, image, image)
                        .astype(np.float32)).astype(dtype)
        y = mx.nd.array(rng.randint(0, classes, (batch,))
                        .astype(np.float32))
        losses = [float(step(x, y, lr=0.05, sync=True))
                  for _ in range(steps)]
        return step, x, losses

    mesh = parallel.make_mesh({"dp": 4}, devices=devices)
    step, x, dp_losses = leg(mesh)
    batch_devices = sorted(
        d.id for d in parallel.shard_batch(x, mesh).sharding.device_set)
    param_devices = sorted({s.device.id for leaf in step._state[0]
                            for s in leaf.addressable_shards})
    hlo = step._compile_step().as_text()
    # None where the backend reports no memory stats (the CPU)
    bytes_in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                    for d in devices}
    _, _, one_losses = leg(None)
    out = {
        "phase": "train_resnet50_dp4", "path": "SPMDTrainStep dp=4",
        "mode": step._mode, "batch": batch, "image": image, "dtype": dtype,
        "steps": steps, "losses_dp4": dp_losses, "losses_one": one_losses,
        "batch_shard_devices": batch_devices,
        "param_shard_devices": param_devices,
        "grad_all_reduce_in_step_hlo": "all-reduce" in hlo,
        "bytes_in_use": bytes_in_use, "tol": tol,
    }
    _finite(dp_losses + one_losses, "train_resnet50_dp4")
    _require(len(set(batch_devices)) == 4 and len(param_devices) == 4,
             f"shards do not span four devices: batch {batch_devices}, "
             f"params {param_devices}")
    _require(all(v is None or v > 0 for v in bytes_in_use.values()),
             f"a device of the mesh holds nothing: {bytes_in_use}")
    _require(out["grad_all_reduce_in_step_hlo"],
             "no all-reduce in the dp=4 step: gradients are not reduced "
             "across the mesh")
    for a, b in zip(dp_losses, one_losses):
        _require(abs(a - b) <= tol * max(abs(b), 1e-6),
                 f"dp=4 and one-device losses differ: {dp_losses} vs "
                 f"{one_losses}")
    return out


# ---------------------------------------------------------------------------
# the command line: full widths, a TPU or nothing
# ---------------------------------------------------------------------------

def _emit(record):
    print(json.dumps(record), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel leg on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()  # one attempt; a backend error ends the run
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing ran", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from mxnet_tpu import observability as obs
    from mxnet_tpu import runtime
    from mxnet_tpu.observability import introspect

    # a cache placed from outside wins (setup_compile_cache then sets no
    # directory); else a fixed path in the checkout, never a temporary
    cache_dir = runtime.setup_compile_cache(os.path.join(ROOT, ".jax_cache"))
    peak_tflops, peak_hbm_gbs, why = introspect.device_peaks()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _emit({"phase": "start", "platform": dev.platform,
           "device_kind": dev.device_kind, "device_count": len(devices),
           "jax": jax.__version__, "chips": args.chips,
           "compile_cache_dir": cache_dir,
           "device_peaks": {"tflops": peak_tflops, "hbm_gbs": peak_hbm_gbs,
                            "unknown_because": why}})
    compiles = CompileCounter()
    t0 = time.perf_counter()
    if args.chips == 4:
        _emit(train_resnet50_dp4(**RESNET50_DP4))
    else:
        _emit(train_resnet50(compiles, **RESNET50))
        _emit(train_bert_base(compiles, **BERT_BASE))
        _emit(kernels(**KERNELS))
        _emit(serve_decode(compiles, model=GPT2_SMALL, **SERVE))
    _emit({"phase": "end", "compile_cache_dir": cache_dir,
           "compile_cache_hits": int(obs.COMPILE_CACHE_HITS.total()),
           "compile_cache_misses": int(obs.COMPILE_CACHE_MISSES.total()),
           "backend_compiles": compiles.count,
           "smoke_reading_wall_s": round(time.perf_counter() - t0, 1)})
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
