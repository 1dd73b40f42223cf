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
import math
import os
import re

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


def _pool_makers(hlo, pool):
    """The opcodes of the instructions whose result is an array of the
    pool's shape (``pool`` as ``"bf16[2,3073,16,768]"``): the parameter,
    the loop's and the calls' plumbing, and whatever else writes a
    pool."""
    return {re.search(r" ([a-z][a-z\-]*)\(", line).group(1)
            for line in hlo.splitlines()
            if re.match(rf"\s*(ROOT )?%\S+ = {re.escape(pool)}\{{", line)}


# what may hand a pool on without writing one of its own
_POOL_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while",
                  "bitcast"}


# B, H, KVH, D, block_size, layers, num_blocks, max_blocks per sequence:
# the kernel takes the whole pool where it lies (a token's heads side
# by side in its row) and copies the live blocks of a static layer
@pytest.mark.parametrize("append", [False, True], ids=["read", "append"])
@pytest.mark.parametrize("B,H,KVH,D,bs,L,nb,mb", [
    (32, 12, 12, 64, 16, 2, 4096, 64),    # GPT-2-small heads, 1024 ctx
    (32, 32, 8, 128, 16, 2, 4096, 128),   # GQA 32Q/8KV at head_dim 128
    (128, 12, 12, 64, 16, 12, 3073, 64),  # the serve cell's pool, whole
    (128, 12, 12, 64, 16, 12, 8193, 64),  # the pool an operator reserves
], ids=["mha_h12_d64", "gqa_h32_kv8_d128", "serve_chat_pool",
        "serve_chat_pool8193"])
def test_paged_decode_compiles_for_v5e(on_chip, B, H, KVH, D, bs, L, nb, mb,
                                      append):
    """With ``append`` the kernel also takes the step's new K/V rows
    and hands the pools back aliased: donated, they are written where
    they lie (no temporary, nothing but the kernel makes a pool)."""
    q = on_chip((B, H, D), jnp.bfloat16)
    pool = on_chip((L, nb, bs, KVH * D), jnp.bfloat16)
    tables = on_chip((B, mb), jnp.int32)
    lens = on_chip((B,), jnp.int32)
    if not append:
        _compiles_to_kernel(
            lambda q, k, v, t, l: fa._pallas_paged_decode(
                q, k, v, t, l, D ** -0.5, layer=L - 1),
            q, pool, pool, tables, lens, names=["mxtpu_paged_decode"])
        return
    rows = on_chip((B, KVH * D), jnp.bfloat16)
    exe = jax.jit(lambda q, k, v, t, l, kr, vr: fa._pallas_paged_decode(
        q, k, v, t, l, D ** -0.5, layer=L - 1, rows=(kr, vr)),
        donate_argnums=(1, 2)).lower(
            q, pool, pool, tables, lens, rows, rows).compile()
    hlo = exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < 2 * B * KVH * D * 64
    assert _pool_makers(hlo, f"bf16[{L},{nb},{bs},{KVH * D}]") \
        <= _POOL_PLUMBING | {"custom-call"}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def _gpt2_net(on_chip, layers, vocab):
    """GPT-2-small's widths; the parameters as shapes on the chip."""
    from mxnet_tpu.serving import TransformerDecoderLM

    net = TransformerDecoderLM(vocab_size=vocab, num_layers=layers,
                               d_model=768, num_heads=12, max_seq=1024,
                               dtype="bfloat16")
    return net, jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype), net.params())


@pytest.mark.parametrize("which", ["decode_step_fn", "prefill_fn"])
def test_decoder_works_on_the_pool_in_place_on_v5e(on_chip, monkeypatch,
                                                   which):
    """What the generation engine compiles, at the serve cell's pool
    (3,073 blocks of 16 tokens, 12 heads of 64; two layers): with the
    pools donated the executable's temporaries stay a small share of
    one pool, and the kernel is still its only custom call. With the
    heads on an axis of their own the device kept the pool in another
    axis order and every executable transposed it on the way in and on
    the way out: temporaries of five pools."""
    monkeypatch.setattr(fa, "_use_pallas", lambda d: True)  # no TPU here
    layers, slots, mb, bucket = 2, 128, 64, 64
    net, params = _gpt2_net(on_chip, layers, vocab=512)
    pool = on_chip((layers, 3073, 16, 768), jnp.bfloat16)
    if which == "decode_step_fn":
        args = (params, on_chip((slots,), jnp.int32),
                on_chip((slots,), jnp.int32), pool, pool,
                on_chip((slots, mb), jnp.int32), on_chip((slots,), bool))
        donate, kernels = (3, 4), layers
    else:
        args = (params, on_chip((1, bucket), jnp.int32), pool, pool,
                on_chip((1, mb), jnp.int32), on_chip((1,), jnp.int32))
        donate, kernels = (2, 3), 0
    exe = jax.jit(getattr(net, which)(),
                  donate_argnums=donate).lower(*args).compile()
    pool_bytes = 2 * math.prod(pool.shape)
    assert exe.memory_analysis().temp_size_in_bytes < 0.25 * pool_bytes
    assert exe.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernels
    if which == "decode_step_fn":
        # the kernels write the new rows: no scatter makes a pool
        assert _pool_makers(exe.as_text(), "bf16[2,3073,16,768]") \
            <= _POOL_PLUMBING | {"custom-call"}


# B slots, H query heads over KVH KV heads of D, layers: the store is
# (layers, B + 1, KVH, D, 65 * D) float32 and its normaliser, read and
# rewritten in place at a layer that may be traced (a scan over layers)
@pytest.mark.parametrize("B,H,KVH,D,L", [
    (16, 40, 8, 128, 8),   # the Brumby cell: 16 slots of 8 x 34 MB a layer
    (4, 8, 8, 128, 1),     # one query head a KV head
], ids=["brumby_16_slots", "mha_d128"])
def test_retention_decode_compiles_for_v5e(on_chip, B, H, KVH, D, L):
    from mxnet_tpu.ops import retention as R

    s_shape, z_shape = R.state_shapes(L, B, KVH, D)
    args = (on_chip((B, H, D), jnp.bfloat16),
            on_chip((B, KVH, D), jnp.bfloat16),
            on_chip((B, KVH, D), jnp.bfloat16),
            on_chip((B, KVH), jnp.float32), on_chip(s_shape, jnp.float32),
            on_chip(z_shape, jnp.float32), on_chip((B,), jnp.int32),
            on_chip((B,), bool), on_chip((), jnp.int32))
    exe = jax.jit(R._pallas_step, donate_argnums=(4, 5)).lower(
        *args).compile()
    calls = [line for line in exe.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].lstrip().startswith("%mxtpu_retention_decode")
    # in place: no temporary of a state's size, let alone the store's
    assert exe.memory_analysis().temp_size_in_bytes < D * 65 * D * 4


def _largest_array_bytes(hlo_text):
    """The bytes of the largest array any instruction of a compiled
    module's HLO text yields (a tuple's parts are their own lines)."""
    import re

    width = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
             "u8": 1, "pred": 1}
    sizes = [math.prod(int(n) for n in dims.split(",") if n) * width[dtype]
             for dtype, dims in re.findall(
                 r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]", hlo_text,
                 re.MULTILINE) if dtype in width]
    return max(sizes)


def test_retention_prefill_compiles_for_v5e(on_chip, monkeypatch):
    """Brumby's layer at its published widths (40 query heads over 8 KV
    heads of 128, chunks of 256), one sequence in an 8,192-token bucket:
    the chunked form is one Mosaic call, and no array of the program is
    as large as one chunk's ``phi(q)`` (``C x H x P`` bfloat16, 170 MB),
    which the jnp form's program holds."""
    from mxnet_tpu.ops import retention as R

    T, H, KVH, D, C = 8192, 40, 8, 128, 256
    args = (on_chip((T, H, D), jnp.bfloat16),
            on_chip((T, KVH, D), jnp.bfloat16),
            on_chip((T, KVH, D), jnp.bfloat16),
            on_chip((T, KVH), jnp.float32),
            (on_chip((KVH, D, R.feature_dim(D)), jnp.float32),
             on_chip((KVH, R.feature_rows(D), D), jnp.float32)),
            on_chip((), jnp.int32))
    phi_q = C * H * R.feature_dim(D) * 2

    def chunked(*a):  # a new function a path: jit caches what it traced
        return R.power_retention_chunked(*a, chunk=C)

    monkeypatch.setattr(R, "_use_pallas", lambda d: True)  # no TPU here
    hlo = jax.jit(chunked).lower(*args).compile().as_text()
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].lstrip().startswith("%mxtpu_retention_prefill")
    assert _largest_array_bytes(hlo) < phi_q
    monkeypatch.setattr(R, "_use_pallas", lambda d: False)
    plain = jax.jit(lambda *a: chunked(*a)).lower(*args).compile().as_text()
    assert _largest_array_bytes(plain) >= phi_q


def _brumby_net(on_chip, layers, vocab):
    """Brumby-14B's layer at its published widths, ``layers`` of them
    under the scan; 660 MB a layer: shapes only, held as the benchmark's
    glue holds its weights."""
    import json

    from chipbench.models import retention_lm as glue

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "brumby_14b.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": layers,
               "vocab_size": vocab}
    L, d, ff, hd, V = layers, 5120, 17408, 128, vocab
    assert (d, ff, hd) == (cfg["hidden_size"], cfg["intermediate_size"],
                           cfg["head_dim"])
    leaves = {"ln1_g": (L, d), "ln2_g": (L, d), "wq": (L, d, 40 * hd),
              "wk": (L, d, 8 * hd), "wv": (L, d, 8 * hd),
              "wo": (L, 40 * hd, d), "q_norm": (L, hd), "k_norm": (L, hd),
              "wg": (L, d, 8), "bg": (L, 8), "w_gate": (L, d, ff),
              "w_up": (L, d, ff), "w_down": (L, ff, d)}
    assert set(glue.LAYER_LEAVES) == set(leaves)
    net = glue.build_net(cfg, {
        "embed": on_chip((V, d), jnp.bfloat16),
        "head": on_chip((d, V), jnp.bfloat16),
        "lnf_g": on_chip((d,), jnp.bfloat16),
        **{k: on_chip(shape, jnp.bfloat16) for k, shape in leaves.items()},
    }, "bfloat16")
    return net, net.params()


@pytest.mark.parametrize("which", ["decode_step_fn", "prefill_fn"])
def test_retention_decoder_works_on_the_states_in_place_on_v5e(
        on_chip, monkeypatch, which):
    """The Brumby layer at its published widths (two layers under the
    scan, a small vocabulary), as the generation engine compiles it:
    with the states donated the executable's temporaries stay a small
    share of the state array, and each program's one kernel is its
    retention kernel (the decode step's, or the chunked prefill's)."""
    from mxnet_tpu.ops import retention as R

    monkeypatch.setattr(R, "_use_pallas", lambda d: True)  # no TPU here
    layers, slots, bucket, hd = 2, 16, 512, 128
    net, params = _brumby_net(on_chip, layers, vocab=512)
    s_shape, z_shape = R.state_shapes(layers, slots, 8, hd)
    state = (on_chip(s_shape, jnp.float32), on_chip(z_shape, jnp.float32))
    if which == "decode_step_fn":
        args = (params, on_chip((slots,), jnp.int32),
                on_chip((slots,), jnp.int32), *state,
                on_chip((slots,), jnp.int32), on_chip((slots,), bool))
        donate = (3, 4)
    else:
        args = (params, on_chip((1, bucket), jnp.int32), *state,
                on_chip((1,), jnp.int32), on_chip((1,), jnp.int32))
        donate = (2, 3)
    exe = jax.jit(getattr(net, which)(),
                  donate_argnums=donate).lower(*args).compile()
    # prefill's phi(q) is made in the kernel's VMEM, a row at a time
    assert exe.memory_analysis().temp_size_in_bytes \
        < 0.25 * 4 * math.prod(s_shape)
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1


# the serve cells' engines: vocabulary, slots, a prefill bucket (the
# compiler takes 20 s over a sort of such a vocabulary, so prefill, whose
# sampler is the same call on one row, sorts a small one)
_ENGINES = {
    "gpt2_small": (50257, 128, 64),
    "brumby_14b": (151936, 16, 512),
}


@pytest.mark.parametrize("which", ["chunk_fn", "prefill_fn"])
@pytest.mark.parametrize("family", sorted(_ENGINES))
def test_engine_sorts_the_vocabulary_only_under_a_conditional_on_v5e(
        on_chip, monkeypatch, family, which):
    """The two programs ``GenerationEngine`` compiles, at the serve
    cells' slots and, the chunk, their vocabularies (two layers): the
    sampler's sort of the vocabulary is in the compiled program, and
    only inside a conditional's branch, so a step whose live slots are
    all greedy does not run it."""
    from conftest import sorts_by_conditional
    from mxnet_tpu.ops import retention as R
    from mxnet_tpu.serving import generation

    monkeypatch.setattr(fa, "_use_pallas", lambda d: True)  # no TPU here
    monkeypatch.setattr(R, "_use_pallas", lambda d: True)
    vocab, slots, bucket = _ENGINES[family]
    layers, vocab = 2, vocab if which == "chunk_fn" else 512
    if family == "gpt2_small":
        net, params = _gpt2_net(on_chip, layers, vocab)
        pool = on_chip((layers, 3073, 16, 768), jnp.bfloat16)
        cache, index = (pool, pool), 64  # a block table a row
    else:
        net, params = _brumby_net(on_chip, layers, vocab)
        cache = tuple(on_chip(shape, jnp.float32)
                      for shape in R.state_shapes(layers, slots, 8, 128))
        index = 1  # a state's slot a row
    chunk_fn, prefill_fn = generation.generation_programs(net, 8)

    if which == "chunk_fn":
        # the slots' packed rows and the key are all the host sends
        args = (params, cache,
                on_chip((slots, generation._SLOT_COLS + index), jnp.int32),
                on_chip((2,), jnp.uint32))
        exe = jax.jit(chunk_fn, donate_argnums=(1,)).lower(*args).compile()
        assert jax.eval_shape(chunk_fn, *args)[2].shape == (8 + 4, slots)
    else:
        args = (params, on_chip((1, bucket), jnp.int32), cache,
                on_chip((1, generation._PREFILL_COLS + index), jnp.int32))
        exe = jax.jit(prefill_fn, donate_argnums=(2,)).lower(*args).compile()
    outside, inside = sorts_by_conditional(exe.as_text())
    # (the retention step sorts its 16 slots by liveness, always)
    assert [line for line in outside if f"{vocab}]" in line] == []
    assert any(f"{vocab}]" in line for line in inside), inside


# ---------------------------------------------------------------------------
# latent attention over the latent pool, and the expert layer's grouped
# products, at JoyAI-LLM-Flash's published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("append", [False, True], ids=["read", "append"])
def test_latent_decode_compiles_for_v5e(on_chip, append):
    """The serve cell's shapes: 64 slots, 32 query heads against one
    row of 576 numbers in 640 lanes, blocks of 16 tokens, 288 a table,
    the whole pool of 5 layers x 18,433 blocks, the layer an operand;
    with ``append`` the step's rows too, the pool donated and written
    where it lies."""
    shapes = (on_chip((1,), jnp.int32), on_chip((64, 288), jnp.int32),
              on_chip((64,), jnp.int32), on_chip((64, 32, 640), jnp.bfloat16),
              on_chip((5, 18433, 16, 640), jnp.bfloat16))
    if not append:
        _compiles_to_kernel(
            lambda layer, t, l, q, pool: fa._pallas_latent_decode(
                layer, t, l, q, pool, scale=192 ** -0.5)[..., :512],
            *shapes, names=["mxtpu_latent_decode"])
        return
    exe = jax.jit(lambda layer, t, l, q, pool, row: fa._pallas_latent_decode(
        layer, t, l, q, pool, row, scale=192 ** -0.5),
        donate_argnums=(4,)).lower(
            *shapes, on_chip((64, 640), jnp.bfloat16)).compile()
    assert exe.memory_analysis().temp_size_in_bytes < 64 * 640 * 2 * 64
    assert _pool_makers(exe.as_text(), "bf16[5,18433,16,640]") \
        <= _POOL_PLUMBING | {"custom-call"}


@pytest.mark.parametrize("tokens", [64, 4096], ids=["decode", "prefill"])
def test_experts_product_compiles_for_v5e(on_chip, monkeypatch, tokens):
    """A layer's 256 experts of 2,048 x 768 under 8 pairs a token: the
    three grouped products compile to the kernel ``mxtpu_experts_gmm``
    (row tiles of 64 for the decode batch's 512 pairs, 256 for a 4,096
    prompt's 32,768), and nothing copies a stack of experts."""
    from mxnet_tpu.ops import experts as ex

    monkeypatch.setattr(ex, "_use_pallas", lambda: True)  # no TPU here
    d, E, ff, k = 2048, 256, 768, 8
    exe = jax.jit(ex.experts_apply).lower(
        on_chip((tokens, d), jnp.bfloat16), on_chip((tokens, k), jnp.float32),
        on_chip((tokens, k), jnp.int32), on_chip((E, d, ff), jnp.bfloat16),
        on_chip((E, d, ff), jnp.bfloat16), on_chip((E, ff, d), jnp.bfloat16),
        on_chip((tokens,), bool)).compile()
    calls = [line.lstrip() for line in exe.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 3 and all(
        c.startswith("%mxtpu_experts_gmm") for c in calls), calls
    assert [line for line in exe.as_text().splitlines()
            if ("256,2048,768]" in line or "256,768,2048]" in line)
            and (" copy(" in line or " transpose(" in line)] == []


def _joyai_net(on_chip, layers, vocab):
    """JoyAI-LLM-Flash's layers at their published widths (the first
    dense), the parameters as shapes on the chip, held as the
    benchmark's glue holds them."""
    import json

    from chipbench.models import latent_experts_lm as glue
    from chipbench.references import latent_experts_lm as ref

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "joyai_llm_flash.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": layers,
               "vocab_size": vocab, "served_positions": 4608}
    shapes = jax.eval_shape(lambda: ref.init_params(cfg, 1, "bfloat16"))
    net = glue.build_net(cfg, {k: on_chip(v.shape, v.dtype)
                               for k, v in shapes.items()}, "bfloat16")
    return net, net.params()


@pytest.mark.parametrize("which", ["chunk_fn", "prefill_fn"])
def test_latent_engine_works_on_the_pool_in_place_on_v5e(on_chip,
                                                         monkeypatch, which):
    """The two programs ``GenerationEngine`` compiles for the latent
    net (the dense layer and one expert layer, a small vocabulary) over
    the serve cell's pool: with the pool donated the temporaries stay a
    small share of it and nothing of its size is copied (the 4,096
    bucket's own activations are a third of it); the chunk's kernels
    are the two latent decodes and the expert layer's three products."""
    from mxnet_tpu.ops import experts as ex
    from mxnet_tpu.serving import generation

    monkeypatch.setattr(fa, "_use_pallas", lambda d: True)  # no TPU here
    monkeypatch.setattr(ex, "_use_pallas", lambda: True)
    layers, slots, mb = 2, 64, 288
    net, params = _joyai_net(on_chip, layers, vocab=512)
    pool = on_chip((layers, 18433, 16, 640), jnp.bfloat16)
    chunk_fn, prefill_fn = generation.generation_programs(net, 8)

    if which == "chunk_fn":
        args = (params, (pool,),
                on_chip((slots, generation._SLOT_COLS + mb), jnp.int32),
                on_chip((2,), jnp.uint32))
        exe = jax.jit(chunk_fn, donate_argnums=(1,)).lower(*args).compile()
        share, kernels = 0.1, 2 + 3
        # the counters ride out beside the chunk's packed result
        out = jax.eval_shape(chunk_fn, *args)
        assert out[2].shape == (8 + 4, slots) and out[-1].shape == (3,)
    else:
        args = (params, on_chip((1, 4096), jnp.int32), (pool,),
                on_chip((1, generation._PREFILL_COLS + mb), jnp.int32))
        exe = jax.jit(prefill_fn, donate_argnums=(2,)).lower(*args).compile()
        share, kernels = 1.0, 3
    pool_bytes = 2 * math.prod(pool.shape)
    assert exe.memory_analysis().temp_size_in_bytes < share * pool_bytes
    hlo = exe.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == kernels
    assert [line for line in hlo.splitlines() if "18433,16,640]" in line
            and (" copy(" in line or " transpose(" in line)] == []
    if which == "chunk_fn":  # the kernels write the new rows
        assert _pool_makers(hlo, "bf16[2,18433,16,640]") \
            <= _POOL_PLUMBING | {"custom-call"}
