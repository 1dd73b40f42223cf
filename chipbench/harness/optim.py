"""Plain reference optimizers, as the configurations state them, and the
way back from an optimizer's state after one step to the gradient it
was given. Imports nothing of the program.

Both rules are MXNet 1.x's: weight decay is added to the gradient
(``g + wd * w``), the state is float32, and the weight is stored in the
configuration's own type after every update. With ``master`` (MXNet's
``multi_precision``) a float32 master copy of every weight is part of
the state: the rule updates the master, and the stored weight is the
master rounded to the configuration's type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_state(name, params, master=False):
    """{leaf: (master or None, moments...)}."""
    if name not in ("adam", "sgd"):
        raise ValueError(f"no reference rule for optimizer {name!r}")
    n = 2 if name == "adam" else 1
    return {k: ((v.astype(jnp.float32) if master else None,)
                + tuple(jnp.zeros(v.shape, jnp.float32) for _ in range(n)))
            for k, v in params.items()}


def masters(params, state):
    """The float32 weights the rule updates: the master copies where the
    state has them, else the stored weights."""
    return {k: (state[k][0] if state[k][0] is not None
                else params[k].astype(jnp.float32)) for k in params}


@jax.jit
def _adam(params, grads, state, t, lr, wd, b1, b2, eps):
    new_p, new_s, eff = {}, {}, {}
    for k, w in params.items():
        kept = state[k][0]
        w32 = w.astype(jnp.float32) if kept is None else kept
        g = grads[k].astype(jnp.float32) + wd * w32
        m = b1 * state[k][1] + (1 - b1) * g
        v = b2 * state[k][2] + (1 - b2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        w32 = w32 - lr_t * m / (jnp.sqrt(v) + eps)
        new_p[k] = w32.astype(w.dtype)
        new_s[k] = (None if kept is None else w32, m, v)
        eff[k] = g
    return new_p, new_s, eff


@jax.jit
def _sgd(params, grads, state, lr, wd, mom):
    new_p, new_s, eff = {}, {}, {}
    for k, w in params.items():
        kept = state[k][0]
        w32 = w.astype(jnp.float32) if kept is None else kept
        g = grads[k].astype(jnp.float32) + wd * w32
        m = mom * state[k][1] - lr * g
        w32 = w32 + m
        new_p[k] = w32.astype(w.dtype)
        new_s[k] = (None if kept is None else w32, m)
        eff[k] = g
    return new_p, new_s, eff


def update(name, hyper, params, grads, state, step, lr):
    """One step. Returns (params, state, the gradient as the rule got
    it: with weight decay added)."""
    wd = float(hyper.get("wd", 0.0))
    if name == "adam":
        return _adam(params, grads, state, jnp.float32(step), jnp.float32(lr),
                     wd, float(hyper.get("beta1", 0.9)),
                     float(hyper.get("beta2", 0.999)),
                     float(hyper.get("epsilon", 1e-8)))
    if name == "sgd":
        return _sgd(params, grads, state, jnp.float32(lr), wd,
                    float(hyper.get("momentum", 0.0)))
    raise ValueError(f"no reference rule for optimizer {name!r}")


def first_gradient_norm(name, hyper, momentum_norm, lr):
    """From the norm of the first-moment leaf after ONE step to the norm
    of the gradient the rule was given in that step."""
    if name == "adam":
        return momentum_norm / (1.0 - float(hyper.get("beta1", 0.9)))
    if name == "sgd":
        return momentum_norm / float(lr)
    raise ValueError(f"no reference rule for optimizer {name!r}")
