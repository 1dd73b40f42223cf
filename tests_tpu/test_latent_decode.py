"""The latent-decode kernel and the expert layer's grouped products on
the chip, alone, at ``joyai_llm_flash.serve_assist``'s shapes: 64 slots,
32 query heads against one row of 576 numbers (640 lanes) a token, 288
blocks of 16 tokens a sequence, the whole pool of 5 layers x 18,433
blocks; 256 experts of 2,048 x 768, 8 a token. Against the gather oracle
and the every-expert sum on the device, then timed: ms a call and GB/s
of what the algorithm needs (a live token's 1,152 bytes; the three
matrices of every distinct expert hit), and one grouped product alone by
the kernel ``mxtpu_experts_gmm`` and by the compiler's ``ragged_dot``.
The latent kernel also as the decode step calls it, appending each live
slot's new row: the pool against the oracle's scatter bit for bit, and
ms a call beside the call that only reads and beside the XLA scatter a
layer the append replaced.

    chiprun -- python -m pytest tests_tpu/test_latent_decode.py -q -s -p no:xdist

``-s`` shows the JSON lines (what PERF.md quotes).
"""

import json
import time

import numpy as np
import pytest

B, H, RANK, ROPE, LANES, BS, MB, LAYERS, NB = 64, 32, 512, 64, 640, 16, 288, \
    5, 18433
# live slots and live tokens: about the cell's window mean, then every
# slot at a full table
LOADS = {"serve_assist": (40, 52_000), "full": (B, B * MB * BS)}


def _operands(slots_live, tokens_live, seed=0):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    share = rng.dirichlet(np.full(slots_live, 4.0))
    lens = np.zeros(B, np.int32)
    live = rng.permutation(B)[:slots_live]
    lens[live] = np.clip(np.round(share * tokens_live), 1, MB * BS)
    tables = np.zeros((B, MB), np.int32)
    free = list(1 + rng.permutation(NB - 1))
    for row in live:
        for j in range(-(-int(lens[row]) // BS)):
            tables[row, j] = free.pop()
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    pool = jax.random.normal(kp, (LAYERS, NB, BS, LANES), jnp.bfloat16)
    pool = pool.at[..., RANK + ROPE:].set(0)
    q = jax.random.normal(kq, (B, H, RANK + ROPE), jnp.bfloat16) * 0.2
    return q, pool, jnp.asarray(tables), jnp.asarray(lens)


def _call_ms(fn, *args, n=40):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def _donated_ms(fn, pool, *rest, n=40):
    """``fn(pool, *rest)`` takes the pool donated and returns it first:
    each call takes the pool the last one returned."""
    import jax

    res = jax.block_until_ready(fn(pool, *rest))
    t0 = time.perf_counter()
    for _ in range(n):
        res = fn(res[0], *rest)
    jax.block_until_ready(res)
    return (time.perf_counter() - t0) / n * 1e3


@pytest.mark.parametrize("load", list(LOADS))
def test_latent_kernel_agrees_with_the_gather_oracle_and_its_time(load):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    q, pool, tables, lens = _operands(*LOADS[load])
    scale = (128 + ROPE) ** -0.5
    q_lat, q_rope = q[..., :RANK], q[..., RANK:]
    layer = LAYERS - 1
    got = np.asarray(jax.jit(lambda *a: fa.latent_decode_attention(
        *a, scale, layer=layer))(q_lat, q_rope, pool, tables, lens),
        np.float32)
    if load != "full":  # the oracle gathers 64 x 4,608 rows in float32
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, LANES - RANK - ROPE)))
        want = np.asarray(jax.jit(lambda *a: fa._jnp_latent_decode(
            jnp.asarray([layer], jnp.int32), *a, scale=scale))(
            tables, lens, qp, pool), np.float32)[..., :RANK]
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert np.isfinite(got).all()
    assert not got[np.asarray(lens) == 0].any()

    def step(q_lat, q_rope, pool, tables, lens):  # a decode step's five
        out = q_lat
        for li in range(LAYERS):
            out = fa.latent_decode_attention(out, q_rope, pool, tables, lens,
                                             scale, layer=li)
        return out

    ms = _call_ms(jax.jit(step), q_lat, q_rope, pool, tables, lens) / LAYERS
    need = int(np.asarray(lens).sum()) * (RANK + ROPE) * 2

    # the decode step's call: each live slot's new row appended
    row = jax.random.normal(jax.random.PRNGKey(9), (B, LANES), pool.dtype)
    row = row.at[:, RANK + ROPE:].set(0)
    if load != "full":
        at = jnp.asarray([layer], jnp.int32)
        want_out, want = jax.jit(lambda *a: fa._jnp_latent_decode(
            at, *a, scale=scale))(tables, lens, qp, pool, row)
        got_out, got = jax.jit(lambda *a: fa.latent_decode_attention(
            *a, scale, layer=layer, new=row))(q_lat, q_rope, pool, tables,
                                              lens)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(got_out, np.float32),
            np.asarray(want_out, np.float32)[..., :RANK], rtol=2e-2,
            atol=2e-2)
        del got, want

    def append_step(pool, q_lat, q_rope, tables, lens, row):
        out = q_lat
        for li in range(LAYERS):
            out, pool = fa.latent_decode_attention(
                out, q_rope, pool, tables, lens, scale, layer=li, new=row)
        return pool, out

    def scatter_step(pool, row, blk, off):
        for li in range(LAYERS):
            pool = pool.at[li, blk, off].set(row)
        return (pool,)

    from mxnet_tpu.serving.kvcache import slot_coords

    blk, off = slot_coords(tables, lens - 1, BS, lens > 0)
    scatter_ms = _donated_ms(jax.jit(scatter_step, donate_argnums=(0,)),
                             jnp.copy(pool), row, blk, off) / LAYERS
    append_ms = _donated_ms(jax.jit(append_step, donate_argnums=(0,)),
                            pool, q_lat, q_rope, tables, lens, row) / LAYERS
    print(json.dumps({
        "load": load, "tokens_live": int(np.asarray(lens).sum()),
        "latent_decode_call_ms": ms, "latent_decode_append_call_ms":
        append_ms, "latent_scatter_ms_a_layer": scatter_ms,
        "needed_bytes": need,
        "GB_per_s": need / ms / 1e6,
        "share_of_819_GB_per_s": need / ms / 1e6 / 819}))
    assert need / ms / 1e6 < 1.05 * 819


@pytest.mark.parametrize("tokens", [40, 64, 1024, 4096])
def test_experts_product_agrees_with_every_expert_and_its_time(tokens):
    """Random routing of ``tokens`` tokens, 8 experts each."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import experts as ex

    d, E, ff, k = 2048, 256, 768, 8
    keys = jax.random.split(jax.random.PRNGKey(tokens), 6)
    h = jax.random.normal(keys[0], (tokens, d), jnp.bfloat16)
    w_r = jax.random.normal(keys[1], (d, E), jnp.bfloat16) * 0.02
    mats = [jax.random.normal(kk, shape, jnp.bfloat16) * 0.02
            for kk, shape in zip(keys[2:5], ((E, d, ff), (E, d, ff),
                                             (E, ff, d)))]
    bias = jax.random.normal(keys[5], (E,), jnp.float32) * 0.1
    w, chosen = jax.jit(lambda *a: ex.route(*a, k, 2.5))(h, w_r, bias)
    apply = jax.jit(ex.experts_apply)
    y, load = apply(h, w, chosen, *mats)
    assert int(load.sum()) == tokens * k
    if tokens <= 64:  # every expert on every token, in float32
        h32 = h.astype(jnp.float32)
        dense = jnp.zeros((tokens, E)).at[
            jnp.arange(tokens)[:, None], chosen].set(w)
        with jax.default_matmul_precision("highest"):
            want = sum(dense[:, e][:, None] * ex.gated_mlp(
                h32, *(m[e].astype(jnp.float32) for m in mats))
                for e in range(E))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(want), rtol=3e-2, atol=3e-3)
    ms = _call_ms(apply, h, w, chosen, *mats, n=20)
    hit = int((load > 0).sum())
    need = hit * 3 * d * ff * 2
    flops = tokens * k * 2 * 3 * d * ff
    # one product alone, the kernel and the compiler's own grouped
    # product, on the same sorted rows
    x = h[jnp.argsort(chosen.reshape(-1)) // k]
    ragged = jax.jit(lambda x, m, g: jax.lax.ragged_dot(
        x, m, g, preferred_element_type=jnp.float32))
    got, want = ex.grouped_matmul(x, mats[0], load), ragged(x, mats[0], load)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-3)
    print(json.dumps({
        "tokens": tokens, "experts_hit": hit, "load_max": int(load.max()),
        "experts_apply_ms": ms, "needed_bytes": need,
        "GB_per_s": need / ms / 1e6, "TFLOP_per_s": flops / ms / 1e9,
        "one_product_ms": {
            "mxtpu_experts_gmm": _call_ms(jax.jit(ex.grouped_matmul), x,
                                          mats[0], load, n=20),
            "ragged_dot": _call_ms(ragged, x, mats[0], load, n=20)},
        "one_product_least_ms": max(hit * d * ff * 2 / 819e6,
                                    tokens * k * 2 * d * ff / 197e9)}))
    assert need / ms / 1e6 < 1.05 * 819 and flops / ms / 1e9 < 1.05 * 197
