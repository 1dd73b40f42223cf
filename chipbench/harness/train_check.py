"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (driven in set-up through the window's
own call, on the window's own batches) against the plain reference's
first three from the same weights and batches. Three numbers:

``loss_gap``    worst of the three steps' |loss - ref| / |ref|
``grad_gap``    worst leaf's gap between the norm of the first gradient
                as the optimizer got it (read back from its state after
                one step) and the reference's
``change_gap``  worst leaf's gap between the norms of the parameters'
                change after the three steps

A leaf's gap is |program's norm - reference's norm| over the
reference's norm of that leaf or of the median leaf, whichever is
larger. Leaves whose reference gradient is under a thousandth of the
median leaf's are left out of ``change_gap``: Adam moves them by
round-off alone.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import optim

STEPS = 3
DEAD_LEAF = 1e-3  # of the median leaf's gradient norm


@jax.jit
def tree_norms(tree):
    return {k: jnp.linalg.norm(v.astype(jnp.float32).ravel())
            for k, v in tree.items()}


@jax.jit
def tree_change_norms(new, old):
    return {k: jnp.linalg.norm((new[k].astype(jnp.float32)
                                - old[k].astype(jnp.float32)).ravel())
            for k in old}


def reference_run(ref, cfg, params0, batches, opt, hyper, lr, master=False,
                  quant=None, drop_half=False, freeze=False):
    """The reference's first steps. ``quant`` / ``drop_half`` /
    ``freeze`` turn it into the control or into a planted fault (half of
    the batch left out; a step that returns its state unchanged); as
    the reference all three are off."""
    params = params0
    state = optim.init_state(opt, params0, master)
    losses, first = [], None
    for s, (x, y) in enumerate(batches[:STEPS]):
        loss, grads = ref.loss_and_grads(
            params, x, y, cfg, quant=quant, drop_half=drop_half)
        new_params, new_state, eff = optim.update(opt, hyper, params, grads,
                                                  state, s + 1, lr)
        if s == 0:
            first = tree_norms(eff)
        if not freeze:
            params, state = new_params, new_state
        losses.append(loss)
    # with master copies the change is theirs: the stored weight moves
    # only when its master crosses a rounding step
    change = tree_change_norms(optim.masters(params, state), params0)
    to_f = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
    return {"losses": [float(v) for v in losses], "grad_norms": to_f(first),
            "change_norms": to_f(change)}


def _leaf_gap(got, ref, keys):
    """(gap, leaf) of the worst of the leaves ``keys``."""
    med = statistics.median(ref[k] for k in ref)
    gaps = []
    for k in keys:
        gap = abs(got[k] - ref[k]) / max(ref[k], med, 1e-30)
        gaps.append((gap if np.isfinite(gap) else float("inf"), k))
    return max(gaps)


def compare(program, reference):
    """``program`` and ``reference`` are ``reference_run``-shaped.
    Returns {number: (value, the leaf or step it was read at)}."""
    lp, lr = program["losses"], reference["losses"]
    loss_gaps = [abs(a - b) / max(abs(b), 1e-30) if np.isfinite(a)
                 else float("inf") for a, b in zip(lp, lr)]
    gref = reference["grad_norms"]
    med = statistics.median(gref.values())
    live = [k for k in gref if gref[k] >= DEAD_LEAF * med]
    grad_gap, grad_at = _leaf_gap(program["grad_norms"], gref, list(gref))
    change_gap, change_at = _leaf_gap(program["change_norms"],
                                      reference["change_norms"], live)
    return {
        "loss_gap": (max(loss_gaps), f"step{int(np.argmax(loss_gaps)) + 1}"),
        "grad_gap": (grad_gap, grad_at),
        "change_gap": (change_gap, change_at),
    }
