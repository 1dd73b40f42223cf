"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a device that is
described, not attached (``on-chip-measurement`` guide, section 2). Each
case lowers ONE Pallas kernel of the main path at a real width for a
described v5e and asserts the Mosaic call is in the HLO. A kernel that
passes interpret mode can still be refused here (block tiling, VMEM),
and that refusal costs no chip time.

Rules this file keeps: the topology is described inside a module-scoped,
non-autouse fixture (never at import, in a ``skipif`` or in
``parametrize`` arguments — every xdist worker imports this file, and
only one process may load libtpu); everything built from it (shardings,
shapes) is built in a fixture or a test; all compiles live in this one
file so one worker holds the library; the persistent compile cache is
off around them (such an entry cannot be read back without a chip).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import flash_attention as fa

# flash_attention's default block (what BERT and the decoder LMs run)
_BLOCK = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    """``on_chip(shape, dtype)`` -> a ShapeDtypeStruct placed on the
    described chip 0."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


def _compiles_to_kernel(fn, *shapes, names):
    """Compiles, and finds each kernel in the HLO as an instruction
    named after its ``pl.pallas_call(name=)``: the trace's operation
    lines are these lines, and the benchmark's per-kernel metrics match
    the names."""
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == len(names)
    for name in names:
        assert any(line.lstrip().startswith(f"%{name}.")
                   or line.lstrip().startswith(f"%{name} ")
                   for line in calls), (name, calls)


# (B, H, T, D), causal, window — BERT-base's attention, the r04 flash
# cell, and the sliding-window forward cell
_FLASH = {
    "bert_base": ((64, 12, 128, 64), False, 0),
    "causal_4k": ((4, 16, 4096, 64), True, 0),
    "window_32k": ((1, 8, 32768, 64), True, 1024),
}


@pytest.mark.parametrize("case", sorted(_FLASH))
def test_flash_fwd_compiles_for_v5e(on_chip, case):
    shape, causal, window = _FLASH[case]
    qkv = on_chip(shape, jnp.bfloat16)
    _compiles_to_kernel(
        lambda q, k, v: fa._pallas_flash_fwd(
            q, k, v, shape[-1] ** -0.5, causal, bq=_BLOCK, bk=_BLOCK,
            window=window),
        qkv, qkv, qkv, names=["mxtpu_flash_fwd"])


@pytest.mark.parametrize("case", ["bert_base", "causal_4k"])
def test_flash_bwd_split_compiles_for_v5e(on_chip, case):
    shape, causal, window = _FLASH[case]
    qkv = on_chip(shape, jnp.bfloat16)
    lse = on_chip(shape[:3], jnp.float32)
    _compiles_to_kernel(
        lambda q, k, v, o, l, g: fa._pallas_flash_bwd_split(
            q, k, v, o, l, g, shape[-1] ** -0.5, causal, bq=_BLOCK,
            bk=_BLOCK, window=window),
        qkv, qkv, qkv, qkv, lse, qkv,
        names=["mxtpu_flash_bwd_dq", "mxtpu_flash_bwd_dkv"])


# B, H, KVH, D, block_size, num_blocks, max_blocks per sequence
@pytest.mark.parametrize("B,H,KVH,D,bs,nb,mb", [
    (32, 12, 12, 64, 16, 4096, 64),     # GPT-2-small heads, 1024 ctx
    (32, 32, 8, 128, 16, 4096, 128),    # GQA 32Q/8KV at head_dim 128
], ids=["mha_h12_d64", "gqa_h32_kv8_d128"])
def test_paged_decode_compiles_for_v5e(on_chip, B, H, KVH, D, bs, nb, mb):
    q = on_chip((B, H, D), jnp.bfloat16)
    pool = on_chip((nb, bs, KVH, D), jnp.bfloat16)
    tables = on_chip((B, mb), jnp.int32)
    lens = on_chip((B,), jnp.int32)
    _compiles_to_kernel(
        lambda q, k, v, t, l: fa._pallas_paged_decode(q, k, v, t, l,
                                                      D ** -0.5),
        q, pool, pool, tables, lens, names=["mxtpu_paged_decode"])
