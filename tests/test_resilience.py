"""Fault-tolerant training (mxnet_tpu/resilience/): async checkpoint
commit protocol + manifest/checksum integrity, bit-exact resume parity
(sgd/adam x AMP off/fp16), subprocess SIGTERM kill-and-resume for the
fused loop AND the K-step superstep, elastic 2-device->1-device SPMD
restore, chaos fault injection (deterministic, zero dispatches when
off), SIGTERM handler chaining order, and the save_states/load_states
fused-state round-trip fixes."""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, fusedstep, gluon, resilience
from mxnet_tpu import observability as obs
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import chaos, checkpoint, resume

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    obs.set_enabled(False)
    obs.reset()
    yield
    chaos.reset()
    amp.disable()
    obs.set_enabled(False)
    obs.reset()


def _build(seed=0, optimizer="adam", fp16=False, lr=0.05):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(init=mx.initializer.Xavier())
    if fp16:
        amp.init("float16")
        amp.convert_model(net)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), optimizer,
                       {"learning_rate": lr, "multi_precision": fp16},
                       kvstore=None)
    if fp16:
        amp.init_trainer(tr)
        tr._amp_loss_scaler = amp.LossScaler(init_scale=1024.0)
    return net, tr


_X32 = mx.nd.ones((8, 8))
_Y = mx.nd.zeros((8,))


def _step(net, tr, fp16=False):
    X = _X32.astype("float16") if fp16 else _X32
    with autograd.record():
        l = loss_fn(net(X), _Y)
        if fp16:
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
    if not fp16:
        l.backward()
    tr.step(8)
    return float(jnp.mean(l.data).astype(jnp.float32))


# ---------------------------------------------------------------------------
# commit protocol / verify / retention
# ---------------------------------------------------------------------------

def test_interval_commits_retention_and_verify(tmp_path):
    net, tr = _build()
    mgr = resilience.CheckpointManager(
        tmp_path / "ck", every_n_steps=2, keep=2, net=net,
        trainer=tr).attach(tr)
    try:
        for _ in range(9):
            _step(net, tr)
            # drain the writer at every step boundary: the async queue
            # is latest-wins by design, so under host pressure a slow
            # writer may legally SKIP an intermediate interval commit
            # (observed flake: committed steps [2, 8] or [4, 8] instead
            # of [6, 8]). Flushing per step pins the schedule to step
            # counts — every interval boundary commits, deterministically
            assert mgr.flush(timeout=120), "checkpoint writer stuck"
        kept = resilience.list_checkpoints(tmp_path / "ck")
        assert [s for s, _ in kept] == [6, 8], kept  # keep=2 trimmed 2, 4
        # the lifetime count, not what retention left: a dropped
        # interval must not hide behind the trimming
        assert mgr.commits == 4
        assert resilience.verify(tmp_path / "ck") == []
        for _, d in kept:  # every retained step, not only the latest
            assert resilience.verify(d) == []
        assert resilience.latest_checkpoint(tmp_path / "ck").endswith(
            "step_0000000008")
        assert mgr.last_error is None
    finally:
        mgr.close()


def test_commit_is_atomic_no_partial_dirs(tmp_path):
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=1,
                                       net=net, trainer=tr).attach(tr)
    try:
        for _ in range(3):
            _step(net, tr)
        mgr.flush()
        for d in os.listdir(tmp_path / "ck"):
            assert not d.startswith(".tmp"), d  # no half-written dirs
            if d.startswith("step_"):
                assert os.path.exists(tmp_path / "ck" / d / "MANIFEST.json")
    finally:
        mgr.close()


def test_verify_catches_corruption_and_truncation(tmp_path):
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=2,
                                       net=net, trainer=tr).attach(tr)
    try:
        _step(net, tr), _step(net, tr)
        mgr.flush()
    finally:
        mgr.close()
    step_dir = resilience.latest_checkpoint(tmp_path / "ck")
    payload = os.path.join(step_dir, "data.bin")
    blob = bytearray(open(payload, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(payload, "wb") as f:
        f.write(blob)
    problems = resilience.verify(step_dir)
    assert problems and any("checksum mismatch" in p for p in problems)
    # the loader refuses corrupt payloads outright
    with pytest.raises(mx.MXNetError, match="checksum"):
        checkpoint.read_checkpoint(step_dir)
    # truncation
    with open(payload, "wb") as f:
        f.write(bytes(blob[: len(blob) // 2]))
    problems = resilience.verify(step_dir)
    assert any("payload" in p or "past the end" in p for p in problems)


def test_verify_catches_missing_opt_state_tensors(tmp_path):
    """Completeness: a manifest that declares fused OR eager opt state
    whose tensors are absent must fail the lint (the loader would
    KeyError on it — the linter must not certify what cannot load)."""
    net, tr = _build(0, "adam")
    prev = fusedstep.set_enabled(False)
    try:
        _step(net, tr)  # eager path: _opt_state attached
    finally:
        fusedstep.set_enabled(prev)
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=100,
                                       net=net, trainer=tr)
    try:
        mgr.save_sync()
    finally:
        mgr.close()
    step_dir = resilience.latest_checkpoint(tmp_path / "ck")
    man_path = os.path.join(step_dir, "MANIFEST.json")
    man = json.load(open(man_path))
    assert any(k == "eager" for k in man["extras"]["opt_kind"].values())
    # drop one eager tensor from the manifest -> completeness failure
    eager_keys = [k for k in man["tensors"] if k.startswith("eager::")]
    assert eager_keys
    del man["tensors"][eager_keys[0]]
    json.dump(man, open(man_path, "w"))
    problems = resilience.verify(step_dir)
    assert any("declared eager" in p and "missing" in p
               for p in problems), problems


def test_verify_checkpoint_cli(tmp_path):
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=2,
                                       net=net, trainer=tr).attach(tr)
    try:
        _step(net, tr), _step(net, tr)
        mgr.flush()
    finally:
        mgr.close()
    tool = os.path.join(ROOT, "tools", "verify_checkpoint.py")
    res = subprocess.run([sys.executable, tool, str(tmp_path / "ck")],
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
    # corrupt -> rc 1 with the problem named
    step_dir = resilience.latest_checkpoint(tmp_path / "ck")
    with open(os.path.join(step_dir, "data.bin"), "r+b") as f:
        f.seek(0)
        f.write(b"\xff\xff")
    res = subprocess.run([sys.executable, tool, str(step_dir)],
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 1
    assert "checksum" in res.stdout


# ---------------------------------------------------------------------------
# bit-exact resume parity: sgd/adam x AMP off/fp16 (in-process)
# ---------------------------------------------------------------------------

# tier-1 keeps the diagonal (plain sgd + adam-with-masters-and-scaler);
# the off-diagonal cells re-cross already-covered axes and run under -m slow
@pytest.mark.parametrize("fp16,optimizer", [
    pytest.param(False, "sgd", id="fp32-sgd"),
    pytest.param(False, "adam", id="fp32-adam", marks=pytest.mark.slow),
    pytest.param(True, "sgd", id="fp16-sgd", marks=pytest.mark.slow),
    pytest.param(True, "adam", id="fp16-adam"),
])
def test_resume_parity_bit_exact(tmp_path, optimizer, fp16):
    """Train 8 steps with a checkpoint at 4; restore the step-4
    checkpoint into a FRESH model and run 4 more: the loss trajectory,
    params, optimizer pytrees (masters included) and scaler state must
    all match the uninterrupted run BIT-EXACTLY."""
    netA, trA = _build(0, optimizer, fp16)
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=4,
                                       net=netA, trainer=trA).attach(trA)
    try:
        lossesA = [_step(netA, trA, fp16) for _ in range(8)]
        mgr.flush()
    finally:
        mgr.close()
    amp.disable()

    netB, trB = _build(1234, optimizer, fp16)  # different init: must not leak
    rep = resilience.load_checkpoint(
        str(tmp_path / "ck" / "step_0000000004"), net=netB, trainer=trB)
    assert rep.step == 4 and rep.kind == "trainer" and not rep.elastic
    lossesB = [_step(netB, trB, fp16) for _ in range(4)]
    assert lossesA[4:] == lossesB, (lossesA[4:], lossesB)
    for p, p2 in zip(trA._params, trB._params):
        assert jnp.array_equal(p.data().data, p2.data().data), p.name
        assert p.data().data.dtype == p2.data().data.dtype
    for n, n2 in zip(sorted(trA._fused_states), sorted(trB._fused_states)):
        for a, b in zip(trA._fused_states[n], trB._fused_states[n2]):
            assert jnp.array_equal(a, b), (n, a, b)
    if fp16:
        assert trA._amp_loss_scaler.loss_scale == \
            trB._amp_loss_scaler.loss_scale
        assert trA._amp_loss_scaler.overflow_total == \
            trB._amp_loss_scaler.overflow_total
    assert trA._optimizer._index_update_count == \
        trB._optimizer._index_update_count


def test_resume_without_net_fails_loudly_not_silently_fresh(tmp_path):
    """A checkpoint saved with net= uses structural param names; a
    trainer-only restore cannot resolve them and must RAISE — not
    return success having restored nothing (silently training on from
    fresh weights + reset momentum is the worst possible outcome)."""
    net, tr = _build(0, "adam")
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=2,
                                       net=net, trainer=tr).attach(tr)
    try:
        _step(net, tr), _step(net, tr)
        assert mgr.flush()
    finally:
        mgr.close()
    net2, tr2 = _build(5, "adam")
    with pytest.raises(mx.MXNetError, match="net="):
        resilience.load_checkpoint(str(tmp_path / "ck"), trainer=tr2)


def test_resume_restores_rng_stream(tmp_path):
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=2,
                                       net=net, trainer=tr).attach(tr)
    try:
        _step(net, tr), _step(net, tr)
        mgr.flush()
    finally:
        mgr.close()
    a = mx.nd.random.uniform(shape=(4,)).asnumpy()
    net2, tr2 = _build(99)
    resilience.load_checkpoint(str(tmp_path / "ck"), net=net2, trainer=tr2)
    b = mx.nd.random.uniform(shape=(4,)).asnumpy()
    np.testing.assert_array_equal(a, b)  # same post-restore key stream


def test_cursor_rides_checkpoint_and_skip_batches(tmp_path):
    from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher

    pf = DevicePrefetcher(iter([np.ones((2, 8), np.float32)
                                for _ in range(6)]))
    it = iter(pf)
    next(it), next(it), next(it)
    assert pf.cursor == 3
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=1,
                                       net=net, trainer=tr,
                                       ring=pf).attach(tr)
    try:
        _step(net, tr)
        mgr.flush()
    finally:
        mgr.close()
    man, _ = checkpoint.read_checkpoint(str(tmp_path / "ck"))
    assert man["extras"]["cursor"] == 3
    rest = list(resume.skip_batches(range(10), man["extras"]["cursor"]))
    assert rest == [3, 4, 5, 6, 7, 8, 9]


# ---------------------------------------------------------------------------
# subprocess kill-and-resume: the acceptance path
# ---------------------------------------------------------------------------

_CHILD = """
import hashlib, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {root!r})
import numpy as np
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, resilience
from mxnet_tpu.gluon import nn

MODE = {mode!r}            # "full" | "resume"
SUPERSTEP = {superstep!r}  # 0 or K
FP16 = {fp16!r}
OPT = {opt!r}
KILL_MID = {kill_mid!r}    # arm a timer to SIGTERM ourselves MID-scan
STEPS = {steps!r}

np.random.seed(0)  # initializers draw from np.random (conftest seeds
mx.random.seed(0)  # it for in-process tests; a bare child must too)
net = nn.HybridSequential()
net.add(nn.Dense(16, activation="relu", in_units=8))
net.add(nn.Dense(4, in_units=16))
net.initialize(init=mx.initializer.Xavier())
if FP16:
    amp.init("float16")
    amp.convert_model(net)
net.hybridize()
tr = gluon.Trainer(net.collect_params(), OPT,
                   {{"learning_rate": 0.05, "multi_precision": FP16}},
                   kvstore=None)
if FP16:
    amp.init_trainer(tr)
    tr._amp_loss_scaler = amp.LossScaler(init_scale=1024.0)
mgr = resilience.maybe_checkpointing(net, tr)
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
X = mx.nd.ones((8, 8)).astype("float16" if FP16 else "float32")
Y = mx.nd.zeros((8,))

start = 0
if MODE == "resume":
    rep = resilience.load_checkpoint(os.environ["MXTPU_CHECKPOINT"]
                                     .rsplit(":", 1)[0], net=net, trainer=tr)
    start = rep.step
    if mgr is not None:
        mgr.restore_step(start)

def one_step():
    with autograd.record():
        l = loss_fn(net(X), Y)
        if FP16:
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
    if not FP16:
        l.backward()
    tr.step(8)
    return float(jnp.mean(l.data).astype(jnp.float32))

losses = []
if SUPERSTEP:
    import signal as _signal
    import threading as _threading
    import time as _time
    sstep = gluon.Superstep(net, loss_fn, tr, k=SUPERSTEP)
    from mxnet_tpu.gluon.data.prefetcher import stack_batches
    xs = stack_batches([X] * SUPERSTEP)
    ys = stack_batches([Y] * SUPERSTEP)
    for g in range(start // SUPERSTEP, STEPS // SUPERSTEP):
        if KILL_MID and g == start // SUPERSTEP + 2:
            # SIGTERM aimed MID-superstep: a watcher thread fires the
            # instant the main thread is inside the step's critical
            # section (checkpoint._CRITICAL > 0 — typically while the
            # K-iteration scan dispatch executes), so the handler MUST
            # defer the final checkpoint to the completed K-boundary —
            # never a half-applied carry
            from mxnet_tpu.resilience import checkpoint as _ckm
            def _watch():
                while _ckm._CRITICAL[0] == 0:
                    _time.sleep(0.0002)
                os.kill(os.getpid(), _signal.SIGTERM)
            _threading.Thread(target=_watch, daemon=True).start()
        ls = sstep.step(xs, ys, 8)
        losses.extend(float(v) for v in
                      np.asarray(ls.data, dtype=np.float32))
else:
    for i in range(start, STEPS):
        losses.append(one_step())

h = hashlib.sha1()
for _, p in sorted(net.collect_params().items()):
    h.update(np.asarray(p.data().data).tobytes())
for n in sorted(tr._fused_states):
    for leaf in tr._fused_states[n]:
        h.update(np.asarray(leaf).tobytes())
print("LOSSES " + " ".join(repr(l) for l in losses[-4:]))
print("HASH " + h.hexdigest())
print("DONE steps", start, "->", STEPS)
"""


def _run_child(tmp_path, mode, ckpt_env, superstep=0, fp16=False,
               opt="adam", chaos_spec=None, expect_rc=0, kill_mid=0,
               steps=12):
    env = {k: v for k, v in os.environ.items() if k != "MXTPU_CHAOS"}
    env["MXTPU_CHECKPOINT"] = ckpt_env
    if chaos_spec:
        env["MXTPU_CHAOS"] = chaos_spec
    res = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(root=ROOT, mode=mode, superstep=superstep,
                       fp16=fp16, opt=opt, kill_mid=kill_mid,
                       steps=steps)],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == expect_rc, (
        f"child rc={res.returncode} (wanted {expect_rc})\n"
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-2000:]}")
    return res


def _parse(res):
    losses = hashv = None
    for ln in res.stdout.splitlines():
        if ln.startswith("LOSSES "):
            losses = ln[len("LOSSES "):].split()
        if ln.startswith("HASH "):
            hashv = ln.split()[1]
    return losses, hashv


@pytest.mark.parametrize("superstep,fp16,opt", [
    # fused one-step loop, fp16 AMP + masters: same kill/resume drill
    # through a second subprocess pair (~12 s) — slow tier keeps it
    pytest.param(0, True, "adam", marks=pytest.mark.slow),
    (3, False, "sgd"),   # K-step superstep capture
], ids=["fused_adam_fp16", "superstep_sgd"])
def test_kill_and_resume_subprocess(tmp_path, superstep, fp16, opt):
    """SIGTERM (via a deterministic chaos fault) a live training loop
    mid-run; the final checkpoint commits on the way down; a fresh
    process resumes from it and must reproduce the uninterrupted run's
    loss tail and final params+opt-state hash BIT-EXACTLY."""
    ck = f"{tmp_path}/ck:3"
    # leg 1: uninterrupted reference
    full = _run_child(tmp_path, "full", f"{tmp_path}/ref:100",
                      superstep, fp16, opt)
    # leg 2: killed mid-run (chaos SIGTERM re-raises -> rc -SIGTERM)
    spec = "term@superstep:3" if superstep else "term@trainer:7"
    _run_child(tmp_path, "full", ck, superstep, fp16, opt,
               chaos_spec=spec, expect_rc=-signal.SIGTERM)
    assert resilience.verify(f"{tmp_path}/ck") == []
    # leg 3: resume from the committed checkpoint
    res = _run_child(tmp_path, "resume", ck, superstep, fp16, opt)
    losses_full, hash_full = _parse(full)
    losses_res, hash_res = _parse(res)
    assert losses_full == losses_res, (losses_full, losses_res)
    assert hash_full == hash_res


# kill_and_resume_subprocess[superstep_sgd] certifies chaos-SIGTERM ->
# k-boundary commit -> resume parity every tier-1 round; this twin
# re-proves the commit half only
@pytest.mark.slow
def test_sigterm_mid_superstep_commits_at_k_boundary(tmp_path):
    """ISSUE 11 satellite: SIGTERM arriving MID-``Superstep`` scan (a
    self-armed timer fires while the K-iteration dispatch executes, so
    the handler runs inside the step's critical section). The final
    checkpoint must commit at the last COMPLETED K-boundary — step
    divisible by K, params/opt-state/counts mutually consistent, never
    a half-applied carry — and a fresh process resuming from it must
    reproduce the uninterrupted run's loss tail bit-exactly."""
    k, steps = 4, 20
    ck = f"{tmp_path}/ck:1000"  # interval never fires; only the final
    # leg 1: uninterrupted reference
    full = _run_child(tmp_path, "full", f"{tmp_path}/ref:1000",
                      superstep=k, steps=steps)
    # leg 2: killed mid-scan by the in-child timer
    _run_child(tmp_path, "full", ck, superstep=k, steps=steps,
               kill_mid=1, expect_rc=-signal.SIGTERM)
    assert resilience.verify(f"{tmp_path}/ck") == []
    ckpts = resilience.list_checkpoints(f"{tmp_path}/ck")
    assert len(ckpts) == 1, ckpts
    committed_step = ckpts[0][0]
    # the contract under test: a K-boundary commit, not mid-carry —
    # and an INTERIOR one (the timer aimed at superstep 3 of 4), so
    # the resume leg has real steps left to reproduce
    assert committed_step % k == 0, (committed_step, k)
    assert 0 < committed_step < steps, (committed_step, steps)
    man = json.load(open(os.path.join(ckpts[0][1], "MANIFEST.json")))
    assert man["reason"] == "sigterm"
    # leg 3: resume; the loss tail and final state hash must match
    res = _run_child(tmp_path, "resume", ck, superstep=k, steps=steps)
    losses_full, hash_full = _parse(full)
    losses_res, hash_res = _parse(res)
    assert losses_full == losses_res, (losses_full, losses_res)
    assert hash_full == hash_res


@pytest.mark.slow
def test_chaos_smoke_sigterm_commits_verifiable_checkpoint(tmp_path):
    """The tier-1 chaos smoke (ISSUE 8 satellite): SIGTERM a live
    training subprocess from OUTSIDE (a real preemption, not an
    injected fault) and assert a committed checkpoint exists that
    tools/verify_checkpoint.py certifies."""
    child = f"""
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {ROOT!r})
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, resilience
from mxnet_tpu.gluon import nn
net = nn.Dense(4, in_units=8)
net.initialize(); net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd",
                   {{"learning_rate": 0.1, "momentum": 0.9}}, kvstore=None)
mgr = resilience.maybe_checkpointing(net, tr)
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
X, Y = mx.nd.ones((8, 8)), mx.nd.zeros((8,))
i = 0
while True:
    with autograd.record():
        l = loss_fn(net(X), Y)
    l.backward(); tr.step(8)
    i += 1
    if i == 3:
        open({str(tmp_path / 'ready')!r}, "w").write("ready")
    time.sleep(0.001)
"""
    env = dict(os.environ)
    env["MXTPU_CHECKPOINT"] = f"{tmp_path}/ck:1000"  # interval never fires
    proc = subprocess.Popen([sys.executable, "-c", child], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        t0 = time.monotonic()
        while not os.path.exists(tmp_path / "ready"):
            if proc.poll() is not None:
                raise AssertionError(
                    f"child died early: "
                    f"{proc.stderr.read().decode()[-2000:]}")
            assert time.monotonic() - t0 < 120, "child never became ready"
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGTERM, proc.returncode
    # ONLY the SIGTERM final save can have produced a checkpoint
    ckpts = resilience.list_checkpoints(f"{tmp_path}/ck")
    assert len(ckpts) == 1 and ckpts[0][0] >= 3, ckpts
    man = json.load(open(os.path.join(ckpts[0][1], "MANIFEST.json")))
    assert man["reason"] == "sigterm"
    tool = os.path.join(ROOT, "tools", "verify_checkpoint.py")
    res = subprocess.run([sys.executable, tool, f"{tmp_path}/ck"],
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr


# ---------------------------------------------------------------------------
# elastic SPMD resume: 2-device-sharded -> 1 device
# ---------------------------------------------------------------------------

def _spmd_net():
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="ck_net_")
    net.add(nn.Dense(16, activation="relu", in_units=8, prefix="d0_"))
    net.add(nn.Dense(4, in_units=16, prefix="d1_"))
    net.initialize(init=mx.initializer.Xavier())
    return net


def test_elastic_spmd_2dev_to_1dev(tmp_path):
    from jax.sharding import Mesh

    from mxnet_tpu import parallel

    X = mx.nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    Y = mx.nd.array(np.random.RandomState(1).randint(0, 4, (8,))
                    .astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    stepA = parallel.SPMDTrainStep(_spmd_net(), loss_fn, "adam", {},
                                   mesh=mesh, shard_opt_states=True)
    for _ in range(3):
        stepA(X, Y, lr=0.05)
    resilience.save_spmd_checkpoint(tmp_path / "ck", stepA, step=3)
    assert resilience.verify(tmp_path / "ck") == []

    stepB = parallel.SPMDTrainStep(_spmd_net(), loss_fn, "adam", {},
                                   mesh=None)
    stepB(X, Y, lr=0.05)  # init + compile; state replaced by restore
    rep = resilience.load_checkpoint(str(tmp_path / "ck"), spmd_step=stepB)
    assert rep.kind == "spmd" and rep.elastic  # 2 mesh devices -> 1
    lA = stepA(X, Y, lr=0.05)
    lB = stepB(X, Y, lr=0.05)
    np.testing.assert_allclose(lA, lB, rtol=1e-6)


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------

def test_chaos_spec_parsing_and_reset():
    faults = chaos.configure("term:5,nan@superstep:2,stall:4:0.25,"
                             "collective:1,seed=7")
    assert chaos.ENABLED and len(faults) == 4
    kinds = {f["kind"] for f in faults}
    assert kinds == {"term", "nan", "stall", "collective"}
    nan = next(f for f in faults if f["kind"] == "nan")
    assert nan["site"] == "superstep" and nan["step"] == 2
    chaos.reset()
    assert not chaos.ENABLED and chaos.fired() == []
    with pytest.raises(mx.MXNetError, match="cannot parse"):
        chaos.configure("frobnicate:1")
    with pytest.raises(mx.MXNetError, match="needs a"):
        chaos.configure("nan")
    chaos.reset()


def test_chaos_raise_and_stall_fire_deterministically():
    chaos.configure("raise:3")
    net, tr = _build()
    _step(net, tr)
    _step(net, tr)
    with pytest.raises(chaos.ChaosInjectedError):
        _step(net, tr)
    assert chaos.fired() == [("raise", "trainer", 3)]
    chaos.configure("stall@trainer:1:0.2")
    t0 = time.perf_counter()
    _step(net, tr)
    assert time.perf_counter() - t0 >= 0.2
    assert chaos.fired() == [("stall", "trainer", 1)]


def test_chaos_probabilistic_is_seeded_deterministic():
    chaos.configure("raise:p0.5", seed=42)
    seq1 = []
    for _ in range(12):
        try:
            chaos.step_point("t")
            seq1.append(0)
        except chaos.ChaosInjectedError:
            seq1.append(1)
    chaos.configure("raise:p0.5", seed=42)
    seq2 = []
    for _ in range(12):
        try:
            chaos.step_point("t")
            seq2.append(0)
        except chaos.ChaosInjectedError:
            seq2.append(1)
    assert seq1 == seq2 and 0 < sum(seq1) < 12


def test_chaos_nan_poisons_prefetched_batch():
    from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher

    chaos.configure("nan@prefetch:2")
    batches = [np.ones((2, 4), np.float32) for _ in range(3)]
    out = list(DevicePrefetcher(iter(batches)))
    assert np.isfinite(np.asarray(out[0].data)).all()
    assert np.isnan(np.asarray(out[1].data)).all()   # the poisoned one
    assert np.isfinite(np.asarray(out[2].data)).all()


def test_chaos_nan_superstep_fp16_skips_one_iteration():
    """nan@superstep poisons SLOT 0 of the stacked block; under fp16
    AMP exactly that iteration overflows + skips, the other K-1 apply
    (the PR-6 robustness claim, now injectable on demand)."""
    from mxnet_tpu.gluon.data.prefetcher import stack_batches

    obs.set_enabled(True)
    net, tr = _build(0, "sgd", fp16=True)
    sstep = gluon.Superstep(net, loss_fn, tr, k=4)
    X = _X32.astype("float16")
    xs, ys = stack_batches([X] * 4), stack_batches([_Y] * 4)
    sstep.step(xs, ys, 8)  # warm, no fault
    chaos.configure("nan@superstep:1")
    sstep.step(xs, ys, 8)
    ovf = obs.superstep_series()["overflow"]
    assert ovf == [1.0, 0.0, 0.0, 0.0], ovf
    w = np.asarray(net._children["0"].weight.data().data,
                   dtype=np.float32)
    assert np.isfinite(w).all()  # the skip kept NaN out of the weights


def test_chaos_collective_one_shot_and_barrier_retry():
    from mxnet_tpu.kvstore.dist import _global_allreduce

    chaos.configure("collective:1")
    with pytest.raises(chaos.ChaosInjectedError):
        _global_allreduce(jnp.ones((4,)))
    # one-shot: the retry (same call pattern the barrier uses) succeeds
    out = _global_allreduce(jnp.ones((4,)))
    assert np.asarray(out).tolist() == [1, 1, 1, 1]

    from mxnet_tpu import runtime

    chaos.configure("collective:1")
    calls = []

    def attempt():
        calls.append(1)
        chaos.collective_point("barrier")

    runtime.retry_with_backoff(attempt, attempts=3, base_delay=0.01,
                               desc="test barrier")
    assert len(calls) == 2  # failed once, recovered on retry

    # a watchdog TIMEOUT is never retried: peers are gone, and waiting
    # retries x timeout would turn "fail loudly" back into a hang
    from mxnet_tpu.kvstore.dist import CollectiveTimeoutError

    n = []

    def timed_out():
        n.append(1)
        raise CollectiveTimeoutError("peer gone")

    with pytest.raises(CollectiveTimeoutError):
        runtime.retry_with_backoff(timed_out, attempts=3, base_delay=0.01,
                                   desc="t",
                                   no_retry=(CollectiveTimeoutError,))
    assert len(n) == 1  # surfaced immediately, no retries


def test_collective_timeout_raises_instead_of_hanging():
    from mxnet_tpu.kvstore.dist import _call_with_timeout

    t0 = time.perf_counter()
    with pytest.raises(mx.MXNetError, match="timed out"):
        _call_with_timeout(lambda: time.sleep(30), 0.3, "test barrier")
    assert time.perf_counter() - t0 < 5
    # errors inside the worker surface on the caller thread
    with pytest.raises(ValueError, match="boom"):
        _call_with_timeout(lambda: (_ for _ in ()).throw(ValueError("boom")),
                           5.0, "test")
    assert _call_with_timeout(lambda: 42, 5.0, "test") == 42
    assert _call_with_timeout(lambda: 43, 0, "test") == 43  # 0 = off


def test_chaos_off_adds_zero_dispatches():
    """The zero-cost-when-off contract (telemetry-overhead style): the
    per-step dispatch count of the fused loop is IDENTICAL with chaos
    never imported-armed, and with chaos armed-but-not-firing."""
    obs.set_enabled(True)

    def measure():
        net, tr = _build()
        _step(net, tr), _step(net, tr)  # warm: compile everything
        c0 = obs.XLA_DISPATCH_TOTAL.total()
        for _ in range(5):
            _step(net, tr)
        return (obs.XLA_DISPATCH_TOTAL.total() - c0) / 5

    base = measure()
    chaos.configure("term:999999999")  # armed but never firing
    armed = measure()
    chaos.reset()
    off = measure()
    assert base == armed == off, (base, armed, off)


# ---------------------------------------------------------------------------
# SIGTERM chaining order (checkpoint FIRST, flight bundle second)
# ---------------------------------------------------------------------------

def test_sigterm_order_checkpoint_before_flight(tmp_path, monkeypatch):
    from mxnet_tpu.observability import flight

    order = []
    flight.install(str(tmp_path))
    try:
        net, tr = _build()
        mgr = resilience.CheckpointManager(tmp_path / "ck",
                                           every_n_steps=100, net=net,
                                           trainer=tr).attach(tr)
        try:
            _step(net, tr)
            real_save = mgr.save_sync
            monkeypatch.setattr(
                mgr, "save_sync",
                lambda *a, **k: (order.append("checkpoint"),
                                 real_save(*a, **k))[1])
            monkeypatch.setattr(
                flight, "dump",
                lambda *a, **k: order.append("flight") or "x")
            # simulate the delivered signal with a chained prev handler
            # (so the test process survives the re-raise)
            flight._STATE["prev_signal"][signal.SIGTERM] = \
                lambda *a: order.append("prev")
            flight._signal_handler(signal.SIGTERM, None)
        finally:
            mgr.close()
    finally:
        flight._STATE["prev_signal"].pop(signal.SIGTERM, None)
        flight.uninstall()
    assert order == ["checkpoint", "flight", "prev"], order
    assert resilience.verify(tmp_path / "ck") == []  # the save was real


def test_sigterm_order_holds_with_reversed_install(tmp_path, monkeypatch):
    """Manager installed FIRST, recorder second: the outermost handler
    is flight's, whose pre-dump hook still runs the checkpoint before
    the bundle — and the manager's own chained handler no-ops (the
    once-per-death flag) instead of double-saving."""
    from mxnet_tpu.observability import flight

    order = []
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=100,
                                       net=net, trainer=tr).attach(tr)
    try:
        _step(net, tr)
        flight.install(str(tmp_path))  # AFTER the manager
        real_save = mgr.save_sync
        monkeypatch.setattr(
            mgr, "save_sync",
            lambda *a, **k: (order.append("checkpoint"),
                             real_save(*a, **k))[1])
        monkeypatch.setattr(
            flight, "dump", lambda *a, **k: order.append("flight") or "x")
        flight._STATE["prev_signal"][signal.SIGTERM] = \
            lambda *a: order.append("prev")
        flight._signal_handler(signal.SIGTERM, None)
    finally:
        flight._STATE["prev_signal"].pop(signal.SIGTERM, None)
        flight.uninstall()
        mgr.close()
    assert order.count("checkpoint") == 1, order
    assert order.index("checkpoint") < order.index("flight"), order


# ---------------------------------------------------------------------------
# save_states / load_states round-trip (satellite fix)
# ---------------------------------------------------------------------------

def test_save_states_roundtrip_fused_to_eager_momentum_survives(tmp_path):
    """Momentum/adam-t trained on the FUSED path must survive
    save->load->continue on the EAGER path (pre-fix: only eager
    _opt_state round-tripped; fused-trained trainers saved state the
    eager path then ignored)."""
    net, tr = _build(0, "adam")
    for _ in range(3):
        _step(net, tr)
    fname = str(tmp_path / "states.bin")
    tr.save_states(fname)
    saved_m = {n: np.asarray(st[0]) for n, st in tr._fused_states.items()}

    net2, tr2 = _build(7, "adam")
    tr2.load_states(fname)
    assert tr2._optimizer.num_update == 3
    prev = fusedstep.set_enabled(False)  # force the eager path
    try:
        _step(net2, tr2)
        # migration happened from the RESTORED fused store (not fresh)
        for i, p in enumerate(tr2._params):
            assert getattr(p, "_opt_state", None) is not None
    finally:
        fusedstep.set_enabled(prev)
    # and fused continuation also sees the restored state
    net3, tr3 = _build(8, "adam")
    tr3.load_states(fname)
    _step(net3, tr3)
    for n, m0 in zip(sorted(tr3._fused_states), sorted(saved_m)):
        t_leaf = tr3._fused_states[n][2]
        assert int(t_leaf) == 4  # adam t continued from 3, not reset


def test_load_states_clears_stale_eager_state(tmp_path):
    """A trainer that ALREADY trained eagerly must not keep its stale
    per-param _opt_state shadowing the restored fused states."""
    net, tr = _build(0, "adam")
    for _ in range(3):
        _step(net, tr)  # fused path: state lives in _fused_states
    fname = str(tmp_path / "states.bin")
    tr.save_states(fname)

    net2, tr2 = _build(9, "adam")
    prev = fusedstep.set_enabled(False)
    try:
        _step(net2, tr2)  # eager: attaches _opt_state
        assert all(hasattr(p, "_opt_state") for p in tr2._params)
        tr2.load_states(fname)
        # restored file carries fused state for every param -> stale
        # eager attributes are gone
        assert not any(hasattr(p, "_opt_state") for p in tr2._params)
        _step(net2, tr2)  # eager continue migrates from restored store
        for p in tr2._params:
            assert p._opt_state is not None
    finally:
        fusedstep.set_enabled(prev)


def test_save_states_survives_digit_boundary_name_order(tmp_path):
    """Trainer param order is the LEXICOGRAPHIC name sort, which flips
    layer order at digit boundaries (d10_* sorts before d9_*). The
    saved index<->layer mapping must align by CONSTRUCTION order, or a
    model whose global name counter crossed 9/10 loads another layer's
    momentum (caught live: shape-mismatch crash in a full-suite run)."""
    def build(p0, p1):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8, prefix=p0))
        net.add(nn.Dense(4, in_units=16, prefix=p1))
        net.initialize(init=mx.initializer.Xavier())
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.05}, kvstore=None)
        return net, tr

    # saver: lexicographic order REVERSES the layers (d10_* < d9_*)
    netA, trA = build("d9_", "d10_")
    assert [p.name for p in trA._params][0].startswith("d10_")
    for _ in range(3):
        _step(netA, trA)
    fname = str(tmp_path / "states.bin")
    trA.save_states(fname)

    # loader: plain order — same structure, different index meaning
    netB, trB = build("e0_", "e1_")
    trB.load_states(fname)
    # every restored state must sit on the param of ITS OWN shape
    # (pre-fix: the 4-wide output-bias state landed on the 16-wide
    # hidden bias and vice versa)
    for p in trB._params:
        assert trB._fused_states[p.name][0].shape == \
            tuple(p.data().shape), p.name
    # the eager path migrates restored states into per-param updates —
    # the misalignment crashed here with a broadcast TypeError
    prev = fusedstep.set_enabled(False)
    try:
        _step(netB, trB)
    finally:
        fusedstep.set_enabled(prev)
    _step(netB, trB)  # and the fused path continues adam t: 3 -> 5
    for p in trB._params:
        assert int(trB._fused_states[p.name][2]) == 5, p.name


def test_save_states_file_is_numpy_only(tmp_path):
    """format-2 files carry no device-array pickles (portable across
    hosts/backends)."""
    import pickle

    net, tr = _build(0, "sgd")
    prev = fusedstep.set_enabled(False)
    try:
        for _ in range(2):
            _step(net, tr)  # eager path: NDArray states
    finally:
        fusedstep.set_enabled(prev)
    fname = str(tmp_path / "states.bin")
    tr.save_states(fname)
    blob = pickle.load(open(fname, "rb"))
    assert blob["format"] == 2

    def walk(o):
        if isinstance(o, dict):
            return all(walk(v) for v in o.values())
        if isinstance(o, (tuple, list)):
            return all(walk(v) for v in o)
        return isinstance(o, (np.ndarray, np.generic, int, float,
                              str, bytes, type(None)))

    assert walk(blob["states"]) and walk(blob["fused_states"])


# ---------------------------------------------------------------------------
# checkpoint metrics (documented in docs/observability.md)
# ---------------------------------------------------------------------------

def test_checkpoint_metrics_recorded(tmp_path):
    obs.set_enabled(True)
    net, tr = _build()
    mgr = resilience.CheckpointManager(tmp_path / "ck", every_n_steps=2,
                                       net=net, trainer=tr).attach(tr)
    try:
        for _ in range(4):
            _step(net, tr)
            # the queue is latest-wins: on a loaded host step 4's snapshot
            # can replace step 2's before the writer takes it (one commit)
            mgr.flush()
    finally:
        mgr.close()
    assert obs.CHECKPOINT_TOTAL.total() == 2
    assert obs.CHECKPOINT_BYTES_TOTAL.total() > 0
    assert obs.CHECKPOINT_LAST_STEP.value() == 4.0
    text = obs.dump_prometheus()
    assert "mxtpu_checkpoint_total" in text
    assert "mxtpu_checkpoint_seconds" in text
