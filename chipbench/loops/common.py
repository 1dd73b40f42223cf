"""What the training loop kinds share: seeded weights and batches, the
three checked steps in set-up, the window, the comparison afterwards."""

from __future__ import annotations

import collections
import importlib
import time

import numpy as np

from ..harness import optim, train_check


def load_family(cfg):
    """The family's glue module and its plain reference."""
    model = importlib.import_module(f"chipbench.models.{cfg['family']}")
    ref = importlib.import_module(f"chipbench.references.{model.REFERENCE}")
    return model, ref


def make_ring(model, cfg, workload, seed, n):
    """``n`` batches drawn on the device in one jitted call. Every row of
    every batch differs; labels are float32, as MXNet feeds them."""
    import jax
    import jax.numpy as jnp

    batch = int(workload["batch"])
    shapes = workload["shapes"]
    xs = (n, batch) + tuple(model.sample_shape(cfg, shapes))
    ys = (n, batch) + tuple(model.label_shape(cfg, shapes))
    classes = model.label_range(cfg)

    @jax.jit
    def draw(key):
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, xs, 0, cfg["vocab_size"], jnp.int32)
        y = jax.random.randint(ky, ys, 0, classes, jnp.int32)
        return x, y.astype(jnp.float32)

    x, y = draw(jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), 7))
    return [(x[i], y[i]) for i in range(n)]


class TrainLoop:
    """A training cell. Subclasses give ``build_program``, ``one_step``,
    ``wait_for``, ``first_moment_norms``, ``change_norms`` and
    ``release_program``."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.workload, self.seed = ctx.cfg, ctx.workload, ctx.seed
        self.model, self.ref = load_family(self.cfg)
        self.names = self.model.program_names(self.cfg)
        self.lr = float(self.workload["lr"])
        # float32 master copies under low-precision weights, where the
        # cell states them: the reference then keeps them too
        self.has_master = bool(
            (self.workload.get("step_options") or {}).get("multi_precision")
            and self.workload["dtype"] in ("bfloat16", "float16"))
        self.counters = {"batch": int(self.workload["batch"])}

    def wait_last(self, out):
        """Waits until the step that returned ``out`` has wholly ended."""
        self.wait_for(out)

    # -- set-up -------------------------------------------------------------
    def draw_inputs(self):
        """The seed's weights and ring of batches, on the device."""
        w = self.workload
        self.params0 = self.ref.init_params(self.cfg, self.seed, w["dtype"])
        self.ring = make_ring(self.model, self.cfg, w, self.seed,
                              int(w.get("ring", 8)))
        self.first_batches = [(x, y) for x, y in
                              self.ring[:train_check.STEPS]]

    def setup(self):
        w = self.workload
        self.draw_inputs()
        self.build_program()
        # the first steps go through the window's own call and feed; what
        # the comparison needs of them stays on the device until the
        # window has closed
        losses, moment = [], None
        for i in range(train_check.STEPS):
            losses.append(self.one_step(i))
            if i == 0:
                moment = self.first_moment_norms()
        self.checked = {"losses": losses, "moment_norms": moment,
                        "change_norms": self.change_norms()}
        done = train_check.STEPS
        for i in range(done, done + int(w.get("warmup_steps", 3))):
            losses.append(self.one_step(i))
        self.wait_last(losses[-1])
        self.steps_done = len(losses)

    # -- the window ---------------------------------------------------------
    def window(self, seconds):
        """Steps until ``seconds`` have passed, then waits for the last.
        No step is waited for inside the window except to keep at most
        ``max_ahead`` of them in flight."""
        ahead = int(self.workload.get("max_ahead", 8))
        span, tick = self.ctx.span, self.ctx.tick
        pending = collections.deque()
        losses = []
        i = self.steps_done
        self.ctx.open_window()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with span("step"):
                out = self.one_step(i)
            i += 1
            losses.append(out)
            pending.append(out)
            if len(pending) > ahead:
                with span("wait_oldest"):
                    self.wait_for(pending.popleft())
            tick()
        with span("wait_last"):
            self.wait_last(losses[-1])
        t1 = time.perf_counter()
        self.ctx.close_window()
        self.steps_done = i
        self.window_losses = losses
        steps = len(losses)
        self.counters.update({
            "steps": steps, "window_s": t1 - t0,
            "samples": steps * int(self.workload["batch"]),
            "samples_per_s": steps * int(self.workload["batch"]) / (t1 - t0),
        })
        return t0, t1

    def outcome(self):
        """(attempted, failed) of the window: a step whose loss is not
        finite has failed."""
        vals = np.asarray([float(np.asarray(v, np.float32).mean())
                           for v in self.window_losses])
        self.counters["last_loss"] = float(vals[-1])
        return len(vals), int((~np.isfinite(vals)).sum())

    # -- afterwards ----------------------------------------------------------
    def release(self):
        """Reads what the checked steps left, then drops the program."""
        w = self.workload
        c = self.checked
        moment = {k: float(v) for k, v in c["moment_norms"].items()}
        self.program_reading = {
            "losses": [float(np.asarray(v, np.float32).mean())
                       for v in c["losses"]],
            "grad_norms": {k: optim.first_gradient_norm(
                w["optimizer"], w.get("optimizer_params") or {}, v, self.lr)
                for k, v in moment.items()},
            "change_norms": {k: float(v)
                             for k, v in c["change_norms"].items()},
        }
        self.checked = self.window_losses = None
        self.release_program()
        self.ring = None

    def reference_reading(self, **planted):
        w = self.workload
        batches = [(np.asarray(x), np.asarray(y).astype(np.int32))
                   for x, y in self.first_batches]
        return train_check.reference_run(
            self.ref, self.cfg, self.params0, batches, w["optimizer"],
            w.get("optimizer_params") or {}, self.lr,
            master=self.has_master, **planted)

    def check(self):
        """[(name, value, limit, where)]; ``correct`` is every value at
        or under its limit."""
        got = train_check.compare(self.program_reading,
                                  self.reference_reading())
        limits = self.workload["limits"]
        return [(name, value, float(limits[name]), where)
                for name, (value, where) in got.items() if name in limits]
