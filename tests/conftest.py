"""Test configuration: force the XLA:CPU backend with 8 virtual devices.

Mirrors the reference's test strategy (SURVEY.md §4): the CPU suite is the
source of truth; TPU runs reuse it by flipping the default context. The
8-device host platform lets collective/sharding tests run without TPUs.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

# tier-1 runs on the CPU backend whatever JAX_PLATFORMS says
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


def natsort_key(name):
    """Natural sort key: ``dense2`` < ``dense10``. A PLAIN string sort
    swaps layers the moment the process-global gluon auto-name counter
    crosses a digit boundary mid-session (dense99 -> dense100 sorts
    before dense99's peers), silently pairing the wrong layers in any
    test that zips two sorted ``collect_params()`` views — a latent
    order-dependent flake (PR 10 hit it in test_overlap_zero)."""
    import re

    return [int(t) if t.isdigit() else t
            for t in re.split(r"(\d+)", name)]


def natsorted_items(items):
    """``(name, value)`` pairs sorted by NATURAL name order — the one
    way tests should order ``collect_params().items()`` / fused-state
    dicts (see :func:`natsort_key`)."""
    return sorted(items, key=lambda kv: natsort_key(kv[0]))


def sorts_by_conditional(hlo_text):
    """``(outside, inside)``: the ``sort`` instructions of a compiled
    module's HLO text that run whenever the program does, and those
    reached only through a ``conditional``'s branch computation. Walks
    the computations from ENTRY along every callee edge (``body=``,
    ``calls=``, ``to_apply=`` ...) but a conditional's branches."""
    import re

    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->.*\{\s*$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(re.sub(r'"[^"]*"', '""', line))
    assert entry is not None, "no ENTRY computation in the HLO text"

    def callees(line):
        branches = set()
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            branches.update(re.findall(r"[\w.\-]+", group))
        branches.update(re.findall(
            r"(?:true|false)_computation=%?([\w.\-]+)", line))
        rest = set(re.findall(
            r"(?:to_apply|calls|body|condition)=%?([\w.\-]+)", line))
        for group in re.findall(r"called_computations=\{([^}]*)\}", line):
            rest.update(re.findall(r"[\w.\-]+", group))
        return rest - branches, branches

    always, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in always or comp not in comps:
            continue
        always.add(comp)
        for line in comps[comp]:
            todo.extend(callees(line)[0])
    outside, inside = [], []
    for comp, lines in comps.items():
        for line in lines:
            if " sort(" in line:
                (outside if comp in always else inside).append(line.strip())
    return outside, inside


def pytest_configure(config):
    # XLA:CPU has no buffer donation; the fused step donates anyway
    # (no-op) and jax warns once per compiled function — pure noise here
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")
    # the 8 pre-existing multi-process failures (the container cannot
    # host spawned multi-process JAX workers): select with
    # `-m dist_baseline`, exclude with `-m 'not dist_baseline'` —
    # tier-1 triage without grepping test names
    config.addinivalue_line(
        "markers",
        "dist_baseline: known-environmental distributed multiprocess "
        "failures (launcher-spawned workers need real multi-core); "
        "diff tier-1 results against this set, not against zero")
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (process-replica spawn/compile, "
        "multi-second chaos drills) — excluded from tier-1 via "
        "`-m 'not slow'`")


@pytest.fixture(autouse=True)
def _seed_rngs():
    """Deterministic per-test RNG (reference: common.py:with_seed)."""
    import random

    import numpy as np

    import mxnet_tpu as mx

    np.random.seed(1234)
    random.seed(1234)
    mx.random.seed(1234)
    yield
