"""The serve cell's engine works on its KV pool in place, on the chip.

``gpt2_small.serve_chat``'s engine at its own size (read from the
benchmark's files, nothing of them edited), and the same engine with
the 8,193-block pool that cell was meant to have: after deploy
``stats()["pool_temp_share"]`` is under 0.25, and the optimised HLO of
the chunk executable and of the 64-bucket prefill holds no operation
whose result is the pool, or one layer of it, other than the in-place
scatters (counted by elements, so ``bf16[12,3073,16,12,64]``,
``bf16[12,3073,16,768]`` and ``bf16[590016,768]`` are all the pool).

    chiprun -- python -m pytest tests_tpu/test_pool_in_place.py -q -s -p no:xdist

``-s`` shows the JSON line each case prints (what PERF.md quotes).
"""

import json
import math
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# opcodes that make no buffer of their own
_FREE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
         "conditional", "call"}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<shape>\S+) (?P<op>[\w\-]+)\(")


def pool_shaped_ops(hlo, layers, blocks, block_size, kv_heads, head_dim):
    """``{(opcode, shape): count}`` over the instructions of ``hlo``
    (every computation but the bodies of fusions) whose result holds as
    many elements as the whole pool or as one layer of it, whatever its
    axes (XLA also spells the pool ``[layers*blocks*block_size,
    width]``), and that is not an in-place scatter: a ``scatter``, or a
    fusion whose root is one."""
    layer = blocks * block_size * kv_heads * head_dim
    sizes = {layer, layers * layer}

    def computations():
        comp = None
        for line in hlo.splitlines():
            head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
            if head:
                comp = head.group(1)
            m = _INSTR.match(line)
            if m:
                yield comp, line, m

    roots, fused = {}, set()
    for comp, line, m in computations():
        if line.lstrip().startswith("ROOT "):
            roots[comp] = m.group("op")
        if m.group("op") == "fusion":
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    found = {}
    for comp, line, m in computations():
        dims = re.match(r"^\w+\[([\d,]+)\]", m.group("shape"))
        op = m.group("op")
        if comp in fused or not dims or op in _FREE or op == "scatter":
            continue
        if math.prod(int(d) for d in dims.group(1).split(",")) not in sizes:
            continue
        if op == "fusion" and all(
                roots.get(c) == "scatter"
                for c in re.findall(r"calls=%([\w.\-]+)", line)):
            continue
        key = (op, m.group("shape").split("{")[0])
        found[key] = found.get(key, 0) + 1
    return found


def cell_engine(cache_blocks=None):
    """The serve cell's engine, at its own size but for the pool's."""
    from mxnet_tpu.serving import GenerationEngine, TransformerDecoderLM

    with open(os.path.join(_ROOT, "chipbench", "workloads",
                           "gpt2_small.serve_chat.json")) as f:
        cell = json.load(f)
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "gpt2_small.json")) as f:
        cfg = json.load(f)
    net = TransformerDecoderLM(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"],
        d_ff=cfg["n_inner"], max_seq=cfg["n_positions"],
        dtype=cell["dtype"])
    e = cell["engine"]
    return GenerationEngine(
        net, list(e["buckets"]), slots=e["slots"], chunk=e["chunk"],
        cache_block_size=e["cache_block_size"],
        cache_blocks=cache_blocks or e["cache_blocks"],
        name="pool-in-place", autostart=False)


def report(eng):
    """What the test asserts on, as one JSON-able dict."""
    import jax

    c = eng.cache.pool  # the manager's paged part
    geometry = (c.layers, c.num_blocks, c.block_size, c.kv_heads,
                c.head_dim)
    out = {"cache_blocks": c.num_blocks,
           "pool_bytes": int(c.k_pool.nbytes),
           "pool_temp_share": eng.stats().get("pool_temp_share"),
           "peak_bytes_in_use": jax.devices()[0].memory_stats().get(
               "peak_bytes_in_use")}
    exes = {"chunk": eng._chunk_exe,
            "prefill64": eng._prefill_exes[min(eng._prefill_exes)]}
    for name, exe in exes.items():
        hlo = exe.as_text()
        out[name] = {
            "temp_over_pool": (exe.memory_analysis().temp_size_in_bytes
                               / c.k_pool.nbytes),
            "pool_shaped_ops": sorted(
                [op, shape, n] for (op, shape), n in
                pool_shaped_ops(hlo, *geometry).items()),
            "tpu_custom_calls": sum(
                "custom_call_target=\"tpu_custom_call\"" in line
                for line in hlo.splitlines()),
            "kernel_named": "%mxtpu_paged_decode" in hlo,
        }
    return out


@pytest.mark.parametrize("cache_blocks", [None, 8193],
                         ids=["cell_pool", "pool8193"])
def test_serve_cell_engine_holds_its_pool_in_place(cache_blocks):
    eng = cell_engine(cache_blocks)
    try:
        got = report(eng)
    finally:
        eng.close()
    print("\n" + json.dumps({"pool_in_place": got}))
    assert got["pool_temp_share"] < 0.25, got
    for name in ("chunk", "prefill64"):
        assert got[name]["pool_shaped_ops"] == [], got[name]
    # the kernel is the served path's only custom call, a layer each
    assert got["chunk"]["tpu_custom_calls"] == eng.cache.layers
    assert got["chunk"]["kernel_named"]
    assert got["prefill64"]["tpu_custom_calls"] == 0


def latent_cell_engine():
    """``joyai_llm_flash.serve_assist``'s engine over its whole latent
    pool's blocks, with the dense layer and one expert layer at their
    published widths, a vocabulary of 8,192 and the 256 bucket alone
    (the full net is the benchmark's to build: 11 GB)."""
    from chipbench.models import latent_experts_lm as glue
    from chipbench.references import latent_experts_lm as ref
    from mxnet_tpu.serving import GenerationEngine

    with open(os.path.join(_ROOT, "chipbench", "workloads",
                           "joyai_llm_flash.serve_assist.json")) as f:
        cell = json.load(f)
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "joyai_llm_flash.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": 2, "vocab_size": 8192,
               "served_positions": 4608}
    net = glue.build_net(cfg, ref.init_params(cfg, 1, cell["dtype"]),
                         cell["dtype"])
    e = cell["engine"]
    return GenerationEngine(
        net, [min(e["buckets"])], slots=e["slots"], chunk=e["chunk"],
        cache_block_size=e["cache_block_size"],
        cache_blocks=e["cache_blocks"], name="latent-in-place",
        autostart=False)


def test_latent_cell_engine_holds_its_pool_in_place():
    eng = latent_cell_engine()
    try:
        c = eng.cache.pool
        assert len(c.pools()) == 1 and c.width == 640
        out = {"cache_blocks": c.num_blocks,
               "pool_bytes": int(c.k_pool.nbytes),
               "pool_temp_share": eng.stats()["pool_temp_share"]}
        for name, exe in (("chunk", eng._chunk_exe),
                          ("prefill256", eng._prefill_exes[256])):
            hlo = exe.as_text()
            out[name] = {
                "temp_over_pool": (exe.memory_analysis().temp_size_in_bytes
                                   / c.k_pool.nbytes),
                "pool_shaped_ops": sorted(
                    [op, shape, n] for (op, shape), n in pool_shaped_ops(
                        hlo, c.layers, c.num_blocks, c.block_size, 1,
                        c.width).items()),
                "kernel_named": "%mxtpu_latent_decode" in hlo}
    finally:
        eng.close()
    print("\n" + json.dumps({"latent_pool_in_place": out}))
    assert out["pool_temp_share"] < 0.25, out
    assert out["chunk"]["kernel_named"]
    for name in ("chunk", "prefill256"):
        assert out[name]["pool_shaped_ops"] == [], out[name]
