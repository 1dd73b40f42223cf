"""TPU-only test suite: runs on the real chip.

The main `tests/` suite forces XLA:CPU (reference test-strategy: CPU suite
is the source of truth, SURVEY.md §4). This directory is the GPU-suite
analog (`tests/python/gpu/`): it runs only where a TPU backend is live —
`python -m pytest tests_tpu/ -q -p no:xdist` on the machine with the
chip (one process: the chip belongs to one) — and every test skips
itself elsewhere.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import pytest


@pytest.fixture(scope="session")
def on_tpu():
    """Asked once, by the first test that runs — never at collection,
    which every xdist worker goes through."""
    return jax.default_backend() == "tpu"


@pytest.fixture(autouse=True)
def _seed_rngs(on_tpu):
    if not on_tpu:
        pytest.skip("no TPU backend live")
    import random

    import numpy as np

    import mxnet_tpu as mx

    np.random.seed(1234)
    random.seed(1234)
    mx.random.seed(1234)
    yield
