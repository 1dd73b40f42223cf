"""Paged KV cache (``mxnet_tpu.serving.kvcache``): the block-table
allocator (free list + refcounts, typed OOM, fork/copy-on-write) and
the pure in-graph paging helpers the decode model compiles against
(null-block routing for inactive slots / pad positions, scatter +
gather round-trips through the table indirection)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.serving import BlockTable, KVCacheOOM, PagedKVCache
from mxnet_tpu.serving.kvcache import (
    Sequence,
    SequenceCache,
    paged_gather,
    paged_prefill_write,
    paged_write,
    slot_coords,
)


@pytest.fixture(autouse=True)
def _telemetry_state():
    obs.set_enabled(False)
    obs.reset()
    yield
    obs.set_enabled(False)
    obs.reset()


def _cache(num_blocks=16, block_size=4, layers=2, kv_heads=2, head_dim=3,
           max_seq=32):
    return PagedKVCache(layers, kv_heads, head_dim, max_seq=max_seq,
                        num_blocks=num_blocks, block_size=block_size)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_allocate_release_round_trip():
    c = _cache()
    assert c.blocks_used() == 0
    t = c.allocate(10)  # 3 blocks of 4
    assert len(t.blocks) == 3
    assert c.blocks_used() == 3
    assert 0 not in t.blocks  # the null block is never handed out
    c.release(t)
    assert c.blocks_used() == 0
    assert t.blocks == [] and t.length == 0
    c.release(t)  # idempotent
    assert c.blocks_used() == 0


def test_zero_token_allocation_is_empty():
    c = _cache()
    t = c.allocate(0)
    assert t.blocks == []
    c.release(t)


def test_oom_is_typed_and_non_destructive():
    c = _cache(num_blocks=4)  # 3 usable
    t = c.allocate(12)
    with pytest.raises(KVCacheOOM, match="exhausted"):
        c.allocate(1)
    # the failed take mutated nothing: the held table still frees fully
    c.release(t)
    assert c.blocks_free() == 3
    t2 = c.allocate(12)
    c.release(t2)


def test_ensure_grows_in_place():
    c = _cache()
    t = c.allocate(4)  # exactly 1 block
    t.length = 4
    c.ensure(t, 5)
    assert len(t.blocks) == 2
    c.ensure(t, 5)  # already covered: no growth
    assert len(t.blocks) == 2
    c.release(t)
    assert c.blocks_used() == 0


def test_fork_is_free_until_divergence():
    c = _cache()
    t = c.allocate(6)  # 2 blocks, second one partial (len 6, bs 4)
    t.length = 6
    used = c.blocks_used()
    f = c.fork(t)
    assert c.blocks_used() == used  # refcount bump only
    assert f.blocks == t.blocks and f is not t
    assert c.forks == 1
    # release one holder: blocks stay (the other still references them)
    c.release(f)
    assert c.blocks_used() == used
    c.release(t)
    assert c.blocks_used() == 0


def test_fork_copy_on_write_copies_exactly_one_block():
    c = _cache()
    t = c.allocate(6)
    t.length = 6
    f = c.fork(t)
    used = c.blocks_used()
    shared_tail = t.blocks[-1]
    # the WRITER appending into the shared partial block gets a private
    # copy of that one block; the reader keeps the original
    c.ensure(f, 7)
    assert c.cow_copies == 1
    assert c.blocks_used() == used + 1
    assert f.blocks[-1] != shared_tail
    assert t.blocks[-1] == shared_tail
    assert f.blocks[:-1] == t.blocks[:-1]  # full blocks still shared
    # appending at a block boundary is NOT a divergence (no shared
    # partial block to split) — plain growth
    c.release(f)
    f2 = c.fork(t)
    f2.length = t.length = 8
    c.ensure(f2, 9)
    assert c.cow_copies == 1  # unchanged
    c.release(f2)
    c.release(t)
    assert c.blocks_used() == 0


def test_fork_free_round_trip_interleaved():
    """Fork chains release in arbitrary order without leaking or
    double-freeing blocks."""
    c = _cache(num_blocks=32)
    t = c.allocate(10)
    t.length = 10
    forks = [c.fork(t) for _ in range(3)]
    c.release(t)                      # parent first
    assert c.blocks_used() == 3      # children keep the blocks alive
    c.ensure(forks[0], 11)            # COW under surviving forks
    for f in forks:
        c.release(f)
    assert c.blocks_used() == 0
    assert c.blocks_free() == 31
    # every block is reusable after the churn
    t2 = c.allocate(31 * 4)
    assert len(t2.blocks) == 31
    c.release(t2)


def test_occupancy_accounting_and_gauges():
    c = _cache(num_blocks=11)  # 10 usable
    obs.set_enabled(True)
    t = c.allocate(20)  # 5 blocks
    assert c.occupancy() == pytest.approx(0.5)
    assert c.stats()["blocks_used"] == 5
    assert obs.KVCACHE_BLOCKS_USED.value(model=c.name) == 5
    assert obs.KVCACHE_OCCUPANCY.value(model=c.name) == pytest.approx(0.5)
    assert c.can_allocate(20) and not c.can_allocate(21)
    c.release(t)
    assert obs.KVCACHE_BLOCKS_USED.value(model=c.name) == 0


def test_oom_counter_increments():
    c = _cache(num_blocks=3)
    obs.set_enabled(True)
    t = c.allocate(8)
    with pytest.raises(KVCacheOOM):
        c.allocate(4)
    assert obs.KVCACHE_OOM_TOTAL.value(model=c.name) == 1
    c.release(t)


def test_block_table_device_row_pads_with_null():
    """A sequence's index row as the device sees it: its blocks, then
    the null block, over whatever a longer table left in the row; an
    empty slot's row is all null blocks. ``ensure`` says when a row is
    stale."""
    c = SequenceCache({"attention": {"layers": 1, "kv_heads": 1,
                                     "head_dim": 4}},
                      slots=2, max_seq=24, num_blocks=8, block_size=4)
    assert c.index_width == 6
    row = np.full(6, 7, np.int32)  # a stale, longer table
    c.write_row(row, Sequence(table=BlockTable([5, 9, 2], 0)))
    assert row.tolist() == [5, 9, 2, 0, 0, 0]
    seq = c.allocate(5)
    assert not c.ensure(seq, 8)   # covered by its two blocks
    assert c.ensure(seq, 9)       # took a third
    (tables,) = c.rows([seq, None])
    assert tables.dtype == np.int32
    assert tables.tolist() == [seq.table.blocks + [0] * 3, [0] * 6]
    c.write_row(row, None)
    assert row.tolist() == [0] * 6


# ---------------------------------------------------------------------------
# pure in-graph helpers (the decode model compiles these)
# ---------------------------------------------------------------------------

def test_slot_coords_routes_inactive_to_null_block():
    tables = np.array([[3, 7], [4, 6]], np.int32)
    pos = np.array([5, 1], np.int32)
    blk, off = slot_coords(tables, pos, 4,
                           active=np.array([True, False]))
    blk, off = np.asarray(blk), np.asarray(off)
    assert blk.tolist() == [7, 0]  # slot 1 inactive -> null sink
    assert off.tolist() == [1, 1]
    blk2, _ = slot_coords(tables, pos, 4)  # no mask: all live
    assert np.asarray(blk2).tolist() == [7, 4]


def test_paged_write_then_gather_round_trip():
    bs, kvh, d = 4, 2, 3
    pool = jnp.zeros((8, bs, kvh, d), jnp.float32)
    tables = np.array([[2, 5], [3, 0]], np.int32)
    vals = np.arange(2 * kvh * d, dtype=np.float32).reshape(2, kvh, d)
    blk, off = slot_coords(tables, np.array([5, 2], np.int32), bs)
    pool = np.asarray(paged_write(pool, blk, off, vals))
    # slot 0 pos 5 -> table[0][1]=5, offset 1; slot 1 pos 2 -> blk 3
    assert np.array_equal(pool[5, 1], vals[0])
    assert np.array_equal(pool[3, 2], vals[1])
    gathered = np.asarray(paged_gather(pool, tables))
    assert gathered.shape == (2, 2 * bs, kvh, d)
    assert np.array_equal(gathered[0, 5], vals[0])
    assert np.array_equal(gathered[1, 2], vals[1])


def test_paged_prefill_write_masks_pad_positions():
    bs, kvh, d = 4, 1, 2
    pool = jnp.zeros((6, bs, kvh, d), jnp.float32)
    table_row = np.array([2, 4], np.int32)
    vals = np.ones((8, kvh, d), np.float32)  # padded prompt of bucket 8
    pool = np.asarray(paged_prefill_write(pool, table_row, 5, vals))
    # 5 real positions land through the table...
    assert pool[2].sum() == 4 * kvh * d
    assert pool[4, 0].sum() == kvh * d
    assert pool[4, 1:].sum() == 0.0
    # ...and the 3 pad positions hit ONLY the null sink (block 0)
    assert pool[[1, 3, 5]].sum() == 0.0


def _decode_writer(writer, q, k_pool, v_pool, tables, lens, rows, layer=0):
    """One decode call that appends ``rows``: through the public call
    (the gather path on this backend) or the kernel in the Pallas
    interpreter. Returns ``(out, k_pool, v_pool)``."""
    from mxnet_tpu.ops import flash_attention as fa

    scale = q.shape[-1] ** -0.5
    if writer == "gather":
        return fa.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                         scale=scale, layer=layer, new=rows)
    return fa._pallas_paged_decode(
        q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
        jnp.asarray(lens, jnp.int32), scale, layer=layer, rows=rows,
        interpret=True)


@pytest.mark.parametrize("writer", ["scatter", "gather", "kernel"])
def test_null_block_absorbs_inactive_writes(writer):
    """An inactive slot's write: the scatter (``paged_write`` at
    ``slot_coords``) lands it in block 0, and ``paged_gather`` of a
    real table never reads it back; the decode call's append (its
    gather path, and the kernel) writes nothing for it, so the null
    block stays as it was too."""
    bs, kvh, d = 2, 1, 2
    pool = jnp.zeros((4, bs, kvh, d), jnp.float32)
    tables = np.array([[1], [2]], np.int32)
    vals = np.full((2, kvh, d), 7.0, np.float32)
    if writer == "scatter":
        blk, off = slot_coords(tables, np.array([0, 0], np.int32), bs,
                               active=np.array([True, False]))
        pool = np.asarray(paged_write(pool, blk, off, vals))
        sink = kvh * d * 7.0                   # absorbed by the sink
    else:
        # position 0 written, so the live slot's context is 1 and the
        # inactive one's 0
        q = jnp.ones((2, kvh, d), jnp.float32)
        pool = pool.reshape(1, 4, bs, kvh * d)
        out, pool, _ = _decode_writer(writer, q, pool, pool, tables,
                                      np.array([1, 0], np.int32),
                                      (vals, vals))
        pool = np.asarray(pool).reshape(4, bs, kvh, d)
        assert np.array_equal(np.asarray(out)[0], vals[0])  # its own row
        assert not np.asarray(out)[1].any()
        sink = 0.0                             # never written
    assert pool[1, 0].sum() == kvh * d * 7.0   # the live slot's write
    assert pool[2].sum() == 0.0                # inactive slot's block clean
    assert pool[0, 0].sum() == sink
    got = np.asarray(paged_gather(pool, tables))
    assert got[1].sum() == 0.0  # the sink never leaks into a real read


def _append_case(lens, bs, mb, nb, width, dtype, seed):
    """A pool of random rows, a table a sequence of distinct shuffled
    blocks (the null block past its use), and a new row a sequence."""
    rng = np.random.RandomState(seed)
    pool = np.asarray(rng.randn(2, nb, bs, width), dtype)
    tables = np.zeros((len(lens), mb), np.int32)
    free = list(1 + rng.permutation(nb - 1))
    for b, n in enumerate(lens):
        tables[b, :-(-n // bs)] = [free.pop() for _ in range(-(-n // bs))]
    rows = np.asarray(rng.randn(len(lens), width), dtype)
    return pool, tables, rows, rng


def _scattered(pool, layer, tables, lens, rows, bs):
    """What the decode step's scatter wrote: each live sequence's row at
    its last token, through its table; nothing for an empty one."""
    want = np.array(pool)
    for b, n in enumerate(lens):
        if n:
            want[layer, tables[b, (n - 1) // bs], (n - 1) % bs] = rows[b]
    return want


# live and dead slots in turn; a row at offset 0 of a fresh block (17,
# 33), at a block's last offset (16, 32, 64), the first token (1), in
# either block of a later group (the group is 2 blocks here: 33, 47, 64)
_APPEND_LENS = [17, 0, 16, 1, 0, 32, 33, 47, 64]


@pytest.mark.parametrize("writer", ["gather", "kernel"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_decode_append_equals_the_scatter(writer, dtype, monkeypatch):
    """One decode step through ``paged_decode_attention`` with the new
    rows: both pools equal the XLA scatter's bit for bit (live rows at
    their coordinates, every other row untouched, the null block and
    the dead slots' blocks included), and the output equals the
    oracle's over the scattered pool, the new row in its context."""
    from mxnet_tpu.ops import flash_attention as fa

    H, KVH, D, bs, mb = 4, 2, 16, 16, 4
    width = KVH * D
    monkeypatch.setattr(fa, "_PAGED_BUFFER_BYTES",
                        2 * 4 * bs * width * jnp.dtype(dtype).itemsize)
    assert fa._paged_group_blocks(bs, width, jnp.dtype(dtype).itemsize,
                                  mb) == 2
    lens = _APPEND_LENS
    nb = sum(-(-n // bs) for n in lens) + 3
    k_pool, tables, k_rows, rng = _append_case(lens, bs, mb, nb, width,
                                               dtype, 1)
    v_pool, _, v_rows, _ = _append_case(lens, bs, mb, nb, width, dtype, 2)
    q = jnp.asarray(rng.randn(len(lens), H, D), dtype)
    out, k_new, v_new = _decode_writer(
        writer, q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
        np.asarray(lens, np.int32), (jnp.asarray(k_rows),
                                     jnp.asarray(v_rows)), layer=1)
    k_want = _scattered(k_pool, 1, tables, lens, k_rows, bs)
    v_want = _scattered(v_pool, 1, tables, lens, v_rows, bs)
    assert np.array_equal(np.asarray(k_new), k_want)
    assert np.array_equal(np.asarray(v_new), v_want)
    assert not np.array_equal(k_want, k_pool)  # the rows did land
    want = fa._jnp_paged_decode(q, jnp.asarray(k_want), jnp.asarray(v_want),
                                jnp.asarray(tables),
                                jnp.asarray(lens, jnp.int32), D ** -0.5,
                                layer=1)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert not np.asarray(out, np.float32)[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("writer", ["gather", "kernel"])
def test_latent_append_equals_the_scatter(writer, monkeypatch):
    """The same for the latent pool (one pool, rows of 640 lanes, the
    kernel's body with ``latent``): the pool equals the scatter's bit
    for bit and the output the oracle's over it."""
    from mxnet_tpu.ops import flash_attention as fa

    bs, mb, width, heads = 16, 4, 640, 8
    monkeypatch.setattr(fa, "_use_pallas", lambda d: writer == "kernel")
    monkeypatch.setattr(fa, "_PAGED_BUFFER_BYTES", 2 * 4 * bs * width * 2)
    assert fa._paged_group_blocks(bs, width, 2, mb) == 2
    if writer == "kernel":  # the interpreter, steered here
        real = fa._pallas_latent_decode
        monkeypatch.setattr(fa, "_pallas_latent_decode",
                            lambda *a, **k: real(*a, **k, interpret=True))
    lens = _APPEND_LENS
    nb = sum(-(-n // bs) for n in lens) + 3
    pool, tables, rows, rng = _append_case(lens, bs, mb, nb, width,
                                           jnp.bfloat16, 3)
    pool[..., 576:] = 0
    rows[:, 576:] = 0
    q = jnp.asarray(rng.randn(len(lens), heads, 576) * 0.2, jnp.bfloat16)
    out, got = fa.latent_decode_attention(
        q[..., :512], q[..., 512:], jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), 0.07, layer=1, new=jnp.asarray(rows))
    want_pool = _scattered(pool, 1, tables, lens, rows, bs)
    assert np.array_equal(np.asarray(got), want_pool)
    want = fa._jnp_latent_decode(
        jnp.asarray([1], jnp.int32), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32),
        jnp.concatenate([q, jnp.zeros((len(lens), heads, 64), q.dtype)], -1),
        jnp.asarray(want_pool), scale=0.07)[..., :512]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    assert not np.asarray(out, np.float32)[np.asarray(lens) == 0].any()


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_env_knob_defaults_and_floors(monkeypatch):
    from mxnet_tpu.serving import kvcache_block_size, kvcache_blocks

    monkeypatch.delenv("MXTPU_KVCACHE_BLOCKS", raising=False)
    monkeypatch.delenv("MXTPU_KVCACHE_BLOCK_SIZE", raising=False)
    assert kvcache_blocks() == 512
    assert kvcache_block_size() == 16
    monkeypatch.setenv("MXTPU_KVCACHE_BLOCKS", "1")
    assert kvcache_blocks() == 2  # block 0 is the sink: need >= 1 usable
    monkeypatch.setenv("MXTPU_KVCACHE_BLOCKS", "64")
    monkeypatch.setenv("MXTPU_KVCACHE_BLOCK_SIZE", "8")
    c = PagedKVCache(1, 1, 2, max_seq=32)
    assert c.num_blocks == 64 and c.block_size == 8
    assert c.max_blocks_per_seq == 4


# ---------------------------------------------------------------------------
# the Pallas decode kernel against the gather oracle (interpret mode)
# ---------------------------------------------------------------------------

def _group_lens(gb, bs, mb):
    """Contexts around a group's edge: inside the first group, exactly
    at its end, one block past it, one token past it, the full table."""
    return [gb * bs - 3, gb * bs, (gb + 1) * bs, gb * bs + 1, mb * bs]


# H, KVH, D, block_size, max_blocks, dtype, tolerance, then the
# contexts (a list, or a function of the kernel's group of blocks),
# whether the tables' unused entries name the null block, and the group
# of blocks a smaller buffer budget is to force (several a sequence)
_DECODE_CASES = {
    "mha_f32": (4, 4, 16, 8, 6, jnp.float32, 1e-5, None, False, None),
    "gqa_f32": (8, 2, 32, 8, 5, jnp.float32, 1e-5, None, False, None),
    "mha_bf16_h12_d64": (12, 12, 64, 16, 4, jnp.bfloat16, 2e-2, None, False,
                         None),
    "group_edges": (4, 4, 16, 8, 12, jnp.float32, 1e-5, _group_lens, False,
                    4),
    "group_edges_gqa": (8, 2, 32, 8, 8, jnp.float32, 1e-5, _group_lens, True,
                        2),
    "groups_of_one_block": (4, 4, 16, 8, 6, jnp.float32, 1e-5, None, False,
                            1),
    "all_empty": (4, 4, 16, 8, 6, jnp.float32, 1e-5, [0, 0, 0, 0, 0], True,
                  None),
    "one_full_among_empty": (4, 2, 16, 8, 6, jnp.float32, 1e-5,
                             [0, 0, 48, 0, 0], True, 2),
    "live_and_empty_interleaved": (4, 4, 16, 8, 6, jnp.float32, 1e-5,
                                   [5, 0, 47, 0, 9], False, 2),
    "gqa_h32_kv8_d128": (32, 8, 128, 16, 4, jnp.bfloat16, 2e-2,
                         [0, 17, 64, 33, 0], True, None),
    "null_block_tail": (4, 4, 16, 8, 6, jnp.float32, 1e-5, None, True, None),
}


@pytest.mark.parametrize("append", [False, True], ids=["read", "append"])
@pytest.mark.parametrize("case", list(_DECODE_CASES))
def test_pallas_paged_decode_matches_gather_oracle(case, append,
                                                   monkeypatch):
    """The kernel the TPU path takes, run by the Pallas interpreter
    (steered here, not by a program option), against
    ``_jnp_paged_decode`` on ragged contexts: an empty slot, one token,
    a block boundary, a full table and a partial last block; contexts
    on either side of a group's edge; nothing live, one slot live,
    live and empty slots in turn; tables that end in the null block.
    With ``append`` each sequence's new rows go in at its last token,
    in whichever group and block of the group that falls, and the
    pools come back equal to the oracle's scatter bit for bit."""
    from mxnet_tpu.ops import flash_attention as fa

    H, KVH, D, bs, mb, dtype, tol, lens, null_tail, gb = _DECODE_CASES[case]
    itemsize = jnp.dtype(dtype).itemsize
    if gb is not None:
        monkeypatch.setattr(fa, "_PAGED_BUFFER_BYTES",
                            gb * 4 * bs * KVH * D * itemsize)
        assert gb < mb
        assert fa._paged_group_blocks(bs, KVH * D, itemsize, mb) == gb
    gb = fa._paged_group_blocks(bs, KVH * D, itemsize, mb)
    if lens is None:
        lens = [0, 1, bs, mb * bs, mb * bs - 3]
    elif callable(lens):
        lens = lens(gb, bs, mb)
    B = len(lens)
    rng = np.random.RandomState(0)
    nb = B * mb + 1
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k_pool = jnp.asarray(rng.randn(nb, bs, KVH, D), dtype)
    v_pool = jnp.asarray(rng.randn(nb, bs, KVH, D), dtype)
    # every sequence owns distinct, shuffled pool blocks (never null 0)
    tables = 1 + rng.permutation(B * mb).reshape(B, mb)
    if null_tail:  # as the cache hands a table over: null past its use
        used = -(-np.asarray(lens) // bs)
        tables = np.where(np.arange(mb)[None, :] < used[:, None], tables, 0)
    tables = jnp.asarray(tables, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    scale = D ** -0.5
    # the helpers take the whole pool, a token's heads side by side in
    # its row: one layer is a leading 1
    k_pool = k_pool.reshape(1, nb, bs, KVH * D)
    v_pool = v_pool.reshape(1, nb, bs, KVH * D)
    rows = (tuple(jnp.asarray(rng.randn(B, KVH * D), dtype)
                  for _ in range(2)) if append else None)
    ref = fa._jnp_paged_decode(q, k_pool, v_pool, tables, lens, scale,
                               rows=rows)
    out = fa._pallas_paged_decode(q, k_pool, v_pool, tables, lens, scale,
                                  rows=rows, interpret=True)
    if append:
        for got, want in zip(out[1:], ref[1:]):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        ref, out = ref[0], out[0]
    assert out.shape == (B, H, D) and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    # empty slots: zeros
    assert not np.asarray(out, np.float32)[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("bs,width,itemsize,mb,want", [
    (16, 768, 2, 64, 8),     # the serve cells: 128 rows of 768 lanes
    (16, 1024, 2, 128, 8),   # 8 kv heads of 128
    (16, 256, 2, 64, 32),    # a narrow row: more blocks in the budget
    (16, 768, 2, 4, 4),      # no more than a table holds
    (16, 65536, 4, 64, 1),   # a row the budget cannot hold twice: one
], ids=["gpt2", "kv8_d128", "narrow", "short_table", "wide"])
def test_paged_group_follows_the_shapes(bs, width, itemsize, mb, want):
    from mxnet_tpu.ops import flash_attention as fa

    assert fa._paged_group_blocks(bs, width, itemsize, mb) == want


# ---------------------------------------------------------------------------
# the pool's hand-off: one scatter into the whole pool, the kernel reads
# the whole pool by layer index (no layer's slice on either side)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket,length", [(16, 5), (8, 8), (4, 7)],
                         ids=["bucket_longer", "bucket_exact",
                              "bucket_shorter"])
def test_whole_pool_prefill_write_equals_layer_by_layer(bucket, length):
    """``paged_prefill_write_all`` (what prefill runs) gives bit for
    bit the pool that ``paged_prefill_write`` gives layer by layer; pad
    positions (and a bucket shorter than the stated length has none)
    land in the null block only."""
    from mxnet_tpu.serving.kvcache import paged_prefill_write_all

    layers, nb, bs, width = 3, 9, 4, 6
    rng = np.random.RandomState(bucket)
    pool = jnp.asarray(rng.randn(layers, nb, bs, width), jnp.float32)
    table_row = np.array([5, 2, 7, 3], np.int32)
    vals = jnp.asarray(rng.randn(layers, bucket, width), jnp.float32)
    got = np.asarray(paged_prefill_write_all(pool, table_row, length, vals))
    want = np.stack([
        np.asarray(paged_prefill_write(pool[li], table_row, length,
                                       vals[li]))
        for li in range(layers)])
    assert np.array_equal(got, want)
    real = min(bucket, length)
    for t in range(real):  # every real position through the table
        assert np.array_equal(got[:, table_row[t // bs], t % bs],
                              np.asarray(vals[:, t]))
    # blocks the table does not name are untouched, but for the sink
    # when there was padding
    others = [b for b in range(1, nb) if b not in table_row[:-(-real // bs)]]
    assert np.array_equal(got[:, others], np.asarray(pool)[:, others])
    assert np.array_equal(got[:, 0], np.asarray(pool)[:, 0]) \
        == (bucket <= length)


@pytest.mark.parametrize("append", [False, True], ids=["read", "append"])
@pytest.mark.parametrize("path", ["gather", "kernel"])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)],
                         ids=["group1", "group4"])
def test_whole_pool_decode_equals_one_layer_call(H, KVH, path, append):
    """``paged_decode_attention`` on the whole pool with ``layer=li``
    is the one-layer call on that layer's pool, for every layer, with
    and without GQA, through the gather path and through the kernel
    (Pallas interpreter); an empty slot returns zeros. With ``append``
    (the decode step's writer since the kernel writes its rows) the
    whole pool comes back with layer ``li`` as the one-layer call left
    its pool and every other layer untouched."""
    from mxnet_tpu.ops import flash_attention as fa

    layers, B, D, bs, mb = 3, 4, 16, 8, 3
    rng = np.random.RandomState(KVH)
    nb = B * mb + 1
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    k_pool = jnp.asarray(rng.randn(layers, nb, bs, KVH * D), jnp.float32)
    v_pool = jnp.asarray(rng.randn(layers, nb, bs, KVH * D), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb),
                         jnp.int32)
    lens = jnp.asarray([0, 1, bs, mb * bs - 3], jnp.int32)
    rows = (tuple(jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
                  for _ in range(2)) if append else None)
    for li in range(layers):
        one_layer = fa.paged_decode_attention(
            q, k_pool[li].reshape(nb, bs, KVH, D),
            v_pool[li].reshape(nb, bs, KVH, D), tables, lens, new=rows)
        if path == "gather":
            got = fa.paged_decode_attention(q, k_pool, v_pool, tables,
                                            lens, layer=li, new=rows)
        else:
            got = fa._pallas_paged_decode(
                q, k_pool, v_pool, tables, lens, D ** -0.5, layer=li,
                rows=rows and tuple(r.reshape(B, -1) for r in rows),
                interpret=True)
        if append:
            for whole, pool, one in zip(got[1:], (k_pool, v_pool),
                                        one_layer[1:]):
                want = np.array(pool)
                want[li] = np.asarray(one).reshape(nb, bs, KVH * D)
                assert np.array_equal(np.asarray(whole), want)
            got, one_layer = got[0], one_layer[0]
        if path == "gather":
            assert np.array_equal(np.asarray(got), np.asarray(one_layer))
        else:
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(one_layer),
                                       rtol=1e-5, atol=1e-5)
        assert not np.asarray(got)[0].any()
    # layers differ, so a wrong index could not pass
    assert not np.array_equal(
        np.asarray(fa.paged_decode_attention(q, k_pool, v_pool, tables,
                                             lens, layer=0)),
        np.asarray(fa.paged_decode_attention(q, k_pool, v_pool, tables,
                                             lens, layer=1)))


def test_paged_decode_attention_refuses_a_layer_outside_the_pool():
    from mxnet_tpu.ops import flash_attention as fa

    q = jnp.zeros((2, 4, 8), jnp.float32)
    pool = jnp.zeros((2, 5, 4, 16), jnp.float32)
    tables, lens = jnp.zeros((2, 2), jnp.int32), jnp.zeros(2, jnp.int32)
    with pytest.raises(ValueError, match="layer"):
        fa.paged_decode_attention(q, pool, pool, tables, lens, layer=2)
    with pytest.raises(ValueError, match="kv heads"):
        fa.paged_decode_attention(q, pool[..., :12], pool[..., :12],
                                  tables, lens, layer=0)


def _pool_shaped_outputs(jaxpr, pool_shape):
    """(primitive, shape) of every equation output, through nested
    jaxprs, that holds as many elements as the pool or as one layer of
    it, whatever its axes."""
    import math

    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if math.prod(shape) in sizes and len(shape) > 1:
                    found.append((eqn.primitive.name, tuple(shape)))

    walk(jaxpr)
    return found


@pytest.mark.parametrize("which", ["decode_step_fn", "prefill_fn"])
def test_served_functions_never_slice_or_copy_the_pool(which):
    """Structure of what the engine compiles: no equation of the decode
    step or of prefill produces one layer's slice of the pool, and only
    the scatters produce a value of the pool's size: prefill's, and in
    the decode step the gather path's append (on the chip the kernel
    writes the rows and its aliased call is the only such value:
    ``tests/test_chip_compile.py``). The gather path reads ``pool[layer,
    tables]`` in one step, so this holds on a CPU as it does for the
    kernel."""
    from mxnet_tpu.serving import TransformerDecoderLM

    net = TransformerDecoderLM(vocab_size=32, num_layers=3, d_model=16,
                               num_heads=4, kv_heads=2, max_seq=32)
    # sizes no activation shares: 3 layers x 23 blocks x 4 x (2*4)
    pool = jnp.zeros((3, 23, 4, 8), jnp.float32)
    if which == "decode_step_fn":
        jaxpr = jax.make_jaxpr(net.decode_step_fn())(
            net.params(), jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
            pool, pool, jnp.zeros((2, 8), jnp.int32), jnp.ones(2, bool))
        scatters = 2 * net.num_layers
    else:
        jaxpr = jax.make_jaxpr(net.prefill_fn())(
            net.params(), jnp.zeros((1, 8), jnp.int32), pool, pool,
            jnp.zeros((1, 8), jnp.int32), jnp.ones(1, jnp.int32))
        scatters = 2
    found = _pool_shaped_outputs(jaxpr.jaxpr, pool.shape)
    assert found == [("scatter", pool.shape)] * scatters, found
