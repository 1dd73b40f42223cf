"""Paged KV cache: fixed-size blocks in one preallocated device pool,
per-request block tables (vLLM/PagedAttention-style).

The decode batch packs requests of wildly different lengths into one
dispatch, so per-request contiguous KV buffers would fragment device
memory and force reallocation every time a sequence grows. Instead the
cache owns ONE pool per projection, shaped

    ``(layers, num_blocks, block_size, kv_heads * head_dim)``

— a token's row holds all its kv heads side by side. The two minor
axes are what the TPU tiles: ``(block_size, kv_heads * head_dim)`` =
``(16, 768)`` fills bf16 tiles of ``(16, 128)`` exactly, so the
device's own layout of the pool is the row-major one that the scatters
and the decode kernel address in place. With the heads on an axis of
their own the minor axes were ``(12, 64)``: padded to ``(16, 128)``
(2.67 times the bytes) in the layout the kernel and the scatters need,
so the device kept the pool in another axis order and every
executable transposed the whole pool on the way in and on the way out
(PERF.md, PR 26). Every request holds a :class:`BlockTable` — the list of pool block
ids that back its tokens, in order. Growing a sequence is appending a
block id to a host-side list; no device copy, no reallocation, zero
external fragmentation (internal waste is bounded by one partial block
per sequence). Block 0 is reserved as the NULL block: in-graph writes
for inactive batch slots are routed there, so the compiled decode step
never branches on slot liveness — dead slots scatter into a sink that
nothing ever reads.

Allocation is a free-list with per-block refcounts. ``fork()`` shares
a prefix between sequences by bumping refcounts (O(blocks) host ints,
no device traffic) — copy-on-write triggers only when a writer must
append into a shared partial block, and copies exactly that one block.

The pool arrays are FUNCTIONAL values threaded through the compiled
prefill/decode executables (donated in, returned out); the cache
object carries the current arrays between dispatches plus the host
allocator state. The hand-off is IN PLACE: an executable only ever
scatters rows into the whole pool (``.at[layer, blk, off]``) and reads
it through the decode kernel's copies from ``(layer, table)``, so no
operation produces a value of the pool's shape or of one layer's slice
of it (``GenerationEngine.stats()["pool_temp_share"]`` says when a
copy has come back). Everything device-side (gather/scatter through the
table) lives in the pure helpers at the bottom so the decode model and
the tests target the same code.

Three kinds of layer keep something for a live sequence
(:data:`CACHE_ARRAYS`). ``attention`` layers keep K and V rows: two
pools of ``kv_heads * head_dim`` lanes. ``latent`` layers (latent
attention) keep ONE row a token for all heads, the compressed key-value
latent and the shared rotary key: one pool of that width, in whole lane
tiles; the same :class:`PagedKVCache`, which is a tuple of pools of a
stated row width over one allocator. ``retention`` layers (power
retention) keep a fixed recurrent state a sequence: :class:`StateStore`,
one state a decode slot, no table, nothing that grows.
:class:`SequenceCache` is the one manager the generation engine talks
to; the net's ``cache_spec()`` says which parts it has.

Knobs: ``MXTPU_KVCACHE_BLOCKS`` (pool size), ``MXTPU_KVCACHE_BLOCK_SIZE``
(tokens per block). Gauges: ``mxtpu_kvcache_blocks_used`` /
``mxtpu_kvcache_occupancy_ratio``; counters ``mxtpu_kvcache_forks_total``
/ ``mxtpu_kvcache_oom_total`` (docs/observability.md).
"""

from __future__ import annotations

import threading

import numpy as np

from .. import base
from .. import observability as _obs
from .errors import KVCacheOOM

# device arrays a layer kind keeps for its sequences, in the order the
# net's faces take the kinds: K and V pools; a state and its
# normaliser; one pool of latent rows
CACHE_ARRAYS = {"attention": 2, "retention": 2, "latent": 1}
_LANES = 128  # the TPU tiles an array's minor axis in lanes of 128


def kvcache_blocks() -> int:
    """Pool capacity in blocks (``MXTPU_KVCACHE_BLOCKS``, default 512).
    Block 0 is the reserved null sink, so usable capacity is one less.
    Sizing rule: ``blocks ~= slots * ceil(max_seq / block_size)`` plus
    headroom for forks; the allocator sheds (typed
    :class:`~.errors.KVCacheOOM`) rather than oversubscribe."""
    return max(2, base.getenv("MXTPU_KVCACHE_BLOCKS", 512, dtype=int))


def kvcache_block_size() -> int:
    """Tokens per cache block (``MXTPU_KVCACHE_BLOCK_SIZE``, default
    16). Larger blocks cut table-indirection overhead but raise
    internal waste (one partial block per sequence) and make
    copy-on-write forks copy more."""
    return max(1, base.getenv("MXTPU_KVCACHE_BLOCK_SIZE", 16, dtype=int))


class BlockTable:
    """One sequence's view into the pool: ordered block ids + how many
    tokens are written. Host-side bookkeeping only — the device sees a
    padded ``int32`` row (:meth:`SequenceCache.write_row`) with the null
    block in unused slots."""

    __slots__ = ("blocks", "length")

    def __init__(self, blocks=None, length=0):
        self.blocks = list(blocks or [])
        self.length = int(length)

    def __repr__(self):
        return f"BlockTable(blocks={self.blocks}, length={self.length})"


class PagedKVCache:
    """Device block pool + host free-list allocator (thread-safe).

    >>> cache = PagedKVCache(layers=2, kv_heads=2, head_dim=8,
    ...                      max_seq=128)
    >>> t = cache.allocate(17)          # ceil(17/16) = 2 blocks
    >>> child = cache.fork(t)           # refcount bump, no copy
    >>> cache.ensure(child, 18)         # COW copies ONE shared block
    >>> cache.release(t); cache.release(child)

    ``arrays`` pools of ``width`` lanes a token's row: K and V of
    ``kv_heads * head_dim`` by default; ``width=640, arrays=1`` is the
    latent kind's one pool."""

    # machine-checked lock protocol (mxtpu-lint thread-guard rule)
    _GUARDED_BY = {
        "_free": "_lock",
        "_ref": "_lock",
    }

    def __init__(self, layers, kv_heads=None, head_dim=None, *, width=None,
                 arrays=2, max_seq=None, num_blocks=None, block_size=None,
                 dtype="float32", name="model"):
        import jax.numpy as jnp

        self.layers = int(layers)
        if width is None:
            self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
            width = self.kv_heads * self.head_dim
        self.width = int(width)
        self.block_size = int(block_size or kvcache_block_size())
        self.num_blocks = int(num_blocks or kvcache_blocks())
        if self.num_blocks < 2:
            raise ValueError("PagedKVCache needs >= 2 blocks "
                             "(block 0 is the reserved null sink)")
        self.name = str(name)
        self._dtype = np.dtype(dtype)
        self.max_blocks_per_seq = (
            -(-int(max_seq) // self.block_size) if max_seq
            else self.num_blocks - 1)
        shape = (self.layers, self.num_blocks, self.block_size, self.width)
        self._pools = tuple(jnp.zeros(shape, dtype=self._dtype)
                            for _ in range(int(arrays)))
        self._lock = threading.Lock()
        self._free = list(range(self.num_blocks - 1, 0, -1))  # pop() -> 1
        self._ref = np.zeros((self.num_blocks,), dtype=np.int64)
        self._ref[0] = 1  # the null block is permanently resident
        self.forks = 0
        self.cow_copies = 0

    # -- pool threading ----------------------------------------------------
    def pools(self):
        """The current device arrays, ``(k_pool, v_pool)`` or the one
        latent pool — the operands to hand the next prefill/decode
        dispatch (which donates them)."""
        return self._pools

    def update_pools(self, *pools):
        """Adopt the pool arrays a dispatch returned (the donated
        inputs are dead after the call — this is the hand-over)."""
        if len(pools) != len(self._pools):
            raise ValueError(f"this cache holds {len(self._pools)} pool(s); "
                             f"got {len(pools)}")
        self._pools = tuple(pools)

    # the names a SequenceCache's parts share
    arrays, adopt = pools, update_pools

    @property
    def k_pool(self):
        """The first pool: K, or the latent rows."""
        return self._pools[0]

    @property
    def v_pool(self):
        return self._pools[1]

    # -- allocator ---------------------------------------------------------
    def _blocks_for(self, num_tokens: int) -> int:
        return -(-max(0, int(num_tokens)) // self.block_size)

    def _take(self, n: int):
        """Pop ``n`` free blocks (caller holds ``_lock``); raises typed
        OOM without mutating anything when the pool can't supply them."""
        if n > len(self._free):
            if _obs.ENABLED:
                _obs.KVCACHE_OOM_TOTAL.inc(1, model=self.name)
            raise KVCacheOOM(
                f"KV cache pool exhausted: need {n} block(s), "
                f"{len(self._free)} free of {self.num_blocks - 1} usable "
                f"(MXTPU_KVCACHE_BLOCKS={self.num_blocks}, "
                f"block_size={self.block_size})")
        return [self._free.pop() for _ in range(n)]

    def allocate(self, num_tokens: int) -> BlockTable:
        """Blocks for a fresh sequence of ``num_tokens`` tokens."""
        n = self._blocks_for(num_tokens)
        with self._lock:
            blocks = self._take(n)
            for b in blocks:
                self._ref[b] = 1
        self._gauges()
        return BlockTable(blocks, 0)

    def ensure(self, table: BlockTable, num_tokens: int):
        """Grow ``table`` to cover ``num_tokens`` tokens, triggering
        copy-on-write first if new tokens would land in a shared
        partial block. True when the table's blocks changed (its row on
        the device is then stale)."""
        need = self._blocks_for(num_tokens) - len(table.blocks)
        will_append = num_tokens > table.length
        copy = None
        with self._lock:
            if (will_append and table.blocks
                    and table.length % self.block_size != 0
                    and self._ref[table.blocks[-1]] > 1):
                # COW: the writer gets a private copy of the one shared
                # partial block; readers keep the original.
                (dst,) = self._take(1)
                self._ref[dst] = 1
                src = table.blocks[-1]
                self._ref[src] -= 1
                table.blocks[-1] = dst
                copy = (src, dst)
            if need > 0:
                grown = self._take(need)
                for b in grown:
                    self._ref[b] = 1
                table.blocks.extend(grown)
        if copy is not None:
            self._copy_block(*copy)
            self.cow_copies += 1
        self._gauges()
        return need > 0 or copy is not None

    def fork(self, table: BlockTable) -> BlockTable:
        """Share ``table``'s prefix with a new sequence: refcount bump
        only — no device traffic until a writer appends into the shared
        partial block (then exactly that block is copied)."""
        with self._lock:
            for b in table.blocks:
                self._ref[b] += 1
        self.forks += 1
        if _obs.ENABLED:
            _obs.KVCACHE_FORKS_TOTAL.inc(1, model=self.name)
        return BlockTable(list(table.blocks), table.length)

    def release(self, table: BlockTable):
        """Return the table's blocks (refcounted — a block frees only
        when its last holder releases). Idempotent per table."""
        blocks, table.blocks, table.length = table.blocks, [], 0
        with self._lock:
            for b in blocks:
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    self._free.append(b)
        self._gauges()

    def _copy_block(self, src: int, dst: int):
        """Device-copy one block (all layers, every pool) — the COW
        path. One fused dispatch a pool per copy; copies are rare (only
        shared partial blocks on first divergence)."""
        self._pools = tuple(p.at[:, dst].set(p[:, src]) for p in self._pools)

    # -- accounting --------------------------------------------------------
    def blocks_used(self) -> int:
        with self._lock:
            return self.num_blocks - 1 - len(self._free)

    def blocks_free(self) -> int:
        with self._lock:
            return len(self._free)

    def occupancy(self) -> float:
        usable = max(1, self.num_blocks - 1)
        return self.blocks_used() / usable

    def can_allocate(self, num_tokens: int) -> bool:
        """Admission check: could a fresh sequence of this length be
        backed right now? (Advisory — allocate() stays the authority.)"""
        with self._lock:
            return self._blocks_for(num_tokens) <= len(self._free)

    def _gauges(self):
        if _obs.ENABLED:
            used = self.blocks_used()
            _obs.KVCACHE_BLOCKS_USED.set(used, model=self.name)
            _obs.KVCACHE_OCCUPANCY.set(
                used / max(1, self.num_blocks - 1), model=self.name)

    def stats(self) -> dict:
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "blocks_used": self.blocks_used(),
            "occupancy": self.occupancy(),
            "forks": self.forks,
            "cow_copies": self.cow_copies,
        }


class StateStore:
    """A fixed recurrent state a live sequence, for layers that keep one
    (power retention: ``S`` and its normaliser ``z``, float32). Two
    device arrays hold every layer's states, ``(layers, slots + 1,
    kv_heads, head_dim, P)`` and ``(layers, slots + 1, kv_heads, R,
    head_dim)`` (:func:`mxnet_tpu.ops.retention.state_shapes`); the
    last slot is the NULL slot, where steps of slots that are not live
    are routed. A state never grows and has no table: a sequence holds
    one slot id from :meth:`allocate` to :meth:`release`. ``allocate``
    zeroes the slot on the device (in place, the arrays donated), since
    prefill starts from the state its slot holds.

    The surface is the pool's, with a block read as one sequence's
    state: ``num_blocks`` (slots and the null slot), ``blocks_used()``,
    ``stats()``."""

    _GUARDED_BY = {"_free": "_lock"}

    def __init__(self, layers, kv_heads, head_dim, *, slots, name="model",
                 gauges=True):
        import jax
        import jax.numpy as jnp

        from ..ops.retention import state_shapes

        # beside a paged pool the ``mxtpu_kvcache_*`` gauges are the pool's
        self._own_gauges = bool(gauges)

        self.layers = int(layers)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.slots = int(slots)
        self.num_blocks = self.slots + 1
        self.null = self.slots
        self.name = str(name)
        s_shape, z_shape = state_shapes(self.layers, self.slots,
                                        self.kv_heads, self.head_dim)
        self.state = jnp.zeros(s_shape, jnp.float32)
        self.norm = jnp.zeros(z_shape, jnp.float32)
        self.bytes_per_state = (self.state.nbytes + self.norm.nbytes) \
            // self.num_blocks
        self._lock = threading.Lock()
        self._free = list(range(self.slots - 1, -1, -1))  # pop() -> 0

        def zero(state, norm, slot):
            return (state.at[:, slot].set(0.0), norm.at[:, slot].set(0.0))

        # compiled here, on the null slot: never inside a served window
        self._zero = jax.jit(zero, donate_argnums=(0, 1))
        self._clear(self.null)

    def _clear(self, slot):
        self.state, self.norm = self._zero(
            self.state, self.norm, np.int32(slot))

    def arrays(self):
        return self.state, self.norm

    def adopt(self, state, norm):
        self.state, self.norm = state, norm

    def allocate(self) -> int:
        """A zeroed slot for a fresh sequence; typed OOM when every
        slot is held."""
        with self._lock:
            if not self._free:
                if _obs.ENABLED:
                    _obs.KVCACHE_OOM_TOTAL.inc(1, model=self.name)
                raise KVCacheOOM(
                    f"state store exhausted: all {self.slots} sequence "
                    "states are held")
            slot = self._free.pop()
        self._clear(slot)
        self._gauges()
        return slot

    def release(self, slot: int):
        with self._lock:
            self._free.append(int(slot))
        self._gauges()

    def _gauges(self):
        if _obs.ENABLED and self._own_gauges:
            used = self.blocks_used()
            _obs.KVCACHE_BLOCKS_USED.set(used, model=self.name)
            _obs.KVCACHE_OCCUPANCY.set(used / max(1, self.slots),
                                       model=self.name)

    def blocks_used(self) -> int:
        with self._lock:
            return self.slots - len(self._free)

    def stats(self) -> dict:
        used = self.blocks_used()
        return {
            "num_blocks": self.num_blocks,
            "blocks_used": used,
            "occupancy": used / max(1, self.slots),
            "state_bytes_reserved": self.bytes_per_state * self.num_blocks,
            "state_bytes_in_use": self.bytes_per_state * used,
        }


class Sequence:
    """What one live sequence holds of a :class:`SequenceCache`: a
    block table where the net has attention or latent layers, a state's
    slot where it has retention layers."""

    __slots__ = ("table", "state")

    def __init__(self, table=None, state=None):
        self.table, self.state = table, state


class SequenceCache:
    """The generation engine's cache manager: whatever the net's layers
    keep for a live sequence, configured by the net's ``cache_spec()``
    (a layer kind -> its geometry). ``attention`` layers get a
    :class:`PagedKVCache` of K and V pools that grows block by block;
    ``latent`` layers the same allocator over one pool of their row's
    ``width`` (in whole lane tiles: 576 -> 640); ``retention`` layers a
    :class:`StateStore` of one fixed state a decode slot. A sequence is
    admitted when every part can hold it, grows only where a part
    grows, and is released from all of them at once.

    The engine threads ``arrays()`` through its executables as one
    donated pytree and hands back what they return (``adopt``). What
    goes beside them is an index row a sequence, ``index_width`` int32
    wide (its block table and/or its state's slot): ``write_row`` puts
    one into a row of the engine's packed operand, and
    :func:`split_index` takes the rows apart again, in the program, into
    the index operands the net's faces take (``rows`` does both on the
    host); arrays and indices both in :data:`CACHE_ARRAYS`'s order of
    kinds, each kind as many arrays as it says there. ``num_blocks`` /
    ``blocks_used()`` / ``occupancy()`` are the paged pool's where there
    is one, else the state store's (a block is then one sequence's
    state); ``stats()`` gives both."""

    def __init__(self, spec, *, slots, max_seq=None, num_blocks=None,
                 block_size=None, dtype="float32", name="model"):
        unknown = set(spec) - set(CACHE_ARRAYS)
        if unknown or not spec or {"attention", "latent"} <= set(spec):
            raise ValueError(
                f"a cache spec names layer kinds of {tuple(CACHE_ARRAYS)}, "
                "'attention' or 'latent' but not both (a sequence has one "
                f"block table); got {sorted(spec)}")
        self.name = str(name)
        self.pool = self.states = None
        paged = dict(max_seq=max_seq, num_blocks=num_blocks,
                     block_size=block_size, dtype=dtype, name=name)
        if "attention" in spec:
            a = spec["attention"]
            self.pool = PagedKVCache(a["layers"], a["kv_heads"],
                                     a["head_dim"], **paged)
        if "latent" in spec:
            c = spec["latent"]
            self.pool = PagedKVCache(
                c["layers"], width=-(-int(c["width"]) // _LANES) * _LANES,
                arrays=CACHE_ARRAYS["latent"], **paged)
        if "retention" in spec:
            r = spec["retention"]
            self.states = StateStore(r["layers"], r["kv_heads"],
                                     r["head_dim"], slots=slots, name=name,
                                     gauges=self.pool is None)
        self._main = self.pool if self.pool is not None else self.states
        # the kinds in the faces' order, and the part that serves each
        self.kinds = tuple(kind for kind in CACHE_ARRAYS if kind in spec)
        self._parts = [{"retention": self.states}.get(kind, self.pool)
                       for kind in self.kinds]
        self.index_width = sum(
            1 if part is self.states else part.max_blocks_per_seq
            for part in self._parts)

    # -- what the pool's readers read --------------------------------------
    @property
    def k_pool(self):
        return self.pool.k_pool if self.pool is not None else None

    @property
    def layers(self):
        return self._main.layers

    @property
    def num_blocks(self):
        return self._main.num_blocks

    def blocks_used(self) -> int:
        return self._main.blocks_used()

    def occupancy(self) -> float:
        return self.blocks_used() / max(1, self.num_blocks - 1)

    def array_bytes(self) -> int:
        """Bytes of the largest array an executable threads through:
        what a copy of the cache inside one would cost."""
        return max(a.nbytes for a in self.arrays())

    # -- the arrays, as the executables take them ---------------------------
    def arrays(self):
        """Every part's arrays side by side: ``(k_pool, v_pool)``,
        ``(state, norm)``, ``(latent_pool,)`` or two of them; the net's
        faces take them in this order."""
        return tuple(a for part in self._parts for a in part.arrays())

    def adopt(self, arrays):
        """The arrays a dispatch returned (the donated ones are dead)."""
        arrays = tuple(arrays)
        for part in self._parts:
            n = len(part.arrays())
            part.adopt(*arrays[:n])
            arrays = arrays[n:]

    def write_row(self, row, seq):
        """``seq``'s index row into ``row`` (int32, ``index_width``
        long): its table's blocks with the null block behind them, its
        state's slot. ``None`` is an empty slot's row: a table of null
        blocks, the null state."""
        at = 0
        for part in self._parts:
            if part is self.states:
                row[at] = part.null if seq is None else seq.state
                at += 1
                continue
            mb = part.max_blocks_per_seq
            blocks = () if seq is None else seq.table.blocks[:mb]
            row[at:at + len(blocks)] = blocks
            row[at + len(blocks):at + mb] = 0
            at += mb

    def rows(self, sequences):
        """The index operands for a batch of sequences (``None`` for an
        empty slot): block tables ``(B, max_blocks)`` and/or state
        slots ``(B,)``, int32, in ``arrays()``'s order of kinds."""
        packed = np.empty((len(sequences), self.index_width), np.int32)
        for row, seq in zip(packed, sequences):
            self.write_row(row, seq)
        return split_index(self.kinds, packed)

    def release_arrays(self):
        """Drops the device arrays (the engine was released)."""
        for part in self._parts:
            part.adopt(*(None,) * len(part.arrays()))

    # -- a sequence's life ---------------------------------------------------
    def allocate(self, num_tokens: int) -> Sequence:
        """Room for a fresh sequence of ``num_tokens`` tokens in every
        part, or typed OOM with nothing held: this is where the engine
        learns whether a request can be admitted."""
        seq = Sequence()
        if self.pool is not None:
            seq.table = self.pool.allocate(num_tokens)
        if self.states is not None:
            try:
                seq.state = self.states.allocate()
            except KVCacheOOM:
                if seq.table is not None:
                    self.pool.release(seq.table)
                raise
        return seq

    def ensure(self, seq: Sequence, num_tokens: int) -> bool:
        """Room for ``seq`` to grow to ``num_tokens`` tokens: blocks of
        the pool; a state needs none. True when its index row changed
        with it."""
        return self.pool is not None \
            and self.pool.ensure(seq.table, num_tokens)

    def written(self, seq: Sequence, num_tokens: int):
        """``seq`` now holds ``num_tokens`` tokens (the pool's
        copy-on-write looks at a table's length)."""
        if seq.table is not None:
            seq.table.length = int(num_tokens)

    def release(self, seq: Sequence):
        """Gives back everything ``seq`` holds. Idempotent."""
        if seq.table is not None:
            self.pool.release(seq.table)
        if seq.state is not None:
            self.states.release(seq.state)
            seq.state = None

    def stats(self) -> dict:
        out = dict(self._main.stats())
        if self.pool is not None and self.states is not None:
            out["states"] = self.states.stats()
        return out


# ---------------------------------------------------------------------------
# pure in-graph helpers (used under jit by the decode model AND the tests —
# one implementation of the table indirection, exercised from both sides)
# ---------------------------------------------------------------------------

def split_index(kinds, rows):
    """Packed index rows ``(B, W)`` (:meth:`SequenceCache.write_row`) as
    the index operands the net's faces take, for a net whose layers are
    of ``kinds``: in :data:`CACHE_ARRAYS`'s order a ``(B,)`` column of
    state slots for retention layers, the ``(B, max_blocks)`` table for
    attention or latent layers. ``rows`` is a numpy array or a traced
    one."""
    out, at = (), 0
    table = rows.shape[1] - ("retention" in kinds)
    for kind in CACHE_ARRAYS:
        if kind == "retention" and kind in kinds:
            out += (rows[:, at],)
            at += 1
        elif kind in kinds:
            out += (rows[:, at:at + table],)
            at += table
    return out


def slot_coords(tables, pos, block_size, active=None):
    """``(block_id, offset)`` pool coordinates for writing each batch
    slot's token at position ``pos``. ``tables`` is ``(B, max_blocks)``
    int32, ``pos`` is ``(B,)`` int32. Inactive slots are routed to the
    null block (id 0) so the compiled step is branch-free in liveness.
    """
    import jax.numpy as jnp

    idx = jnp.clip(pos // block_size, 0, tables.shape[1] - 1)
    blk = jnp.take_along_axis(tables, idx[:, None], axis=1)[:, 0]
    off = pos % block_size
    if active is not None:
        blk = jnp.where(active, blk, 0)
    return blk.astype(jnp.int32), off.astype(jnp.int32)


def paged_write(pool_layer, blk, off, values):
    """Scatter one token's K (or V) per batch slot into a single
    layer's pool ``(num_blocks, block_size, ...)``; ``values`` is
    ``(B, ...)`` with the pool's trailing axes. (The decode step writes
    the whole pool the same way, one axis up: ``pool.at[layer, blk,
    off]``.)"""
    return pool_layer.at[blk, off].set(values)


def _prefill_coords(table_row, length, num_tokens, block_size):
    """``(block_id, offset)`` of each of a padded prompt's positions:
    positions ``>= length`` (bucket padding) go to the null block."""
    import jax.numpy as jnp

    pos = jnp.arange(num_tokens, dtype=jnp.int32)
    idx = jnp.clip(pos // block_size, 0, table_row.shape[0] - 1)
    blk = jnp.where(pos < length, table_row[idx], 0)
    return blk, pos % block_size


def paged_prefill_write(pool_layer, table_row, length, values):
    """Scatter a whole prompt's K (or V) into one layer's pool
    ``(num_blocks, block_size, ...)``. ``table_row`` ``(max_blocks,)``
    int32, ``values`` ``(T, ...)`` with the pool's trailing axes;
    positions ``>= length`` (bucket padding) go to the null block."""
    blk, off = _prefill_coords(table_row, length, values.shape[0],
                               pool_layer.shape[1])
    return pool_layer.at[blk, off].set(values)


def paged_prefill_write_all(pool, table_row, length, values):
    """Every layer's rows of a prompt into the WHOLE pool ``(layers,
    num_blocks, block_size, ...)`` in one scatter at ``(layer, block,
    offset)``: what prefill runs, and the same pool bit for bit as
    :func:`paged_prefill_write` layer by layer, without a layer's slice
    taken out and written back. ``values`` is ``(layers, T, ...)``."""
    import jax.numpy as jnp

    blk, off = _prefill_coords(table_row, length, values.shape[1],
                               pool.shape[2])
    li = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[li, blk[None], off[None]].set(values)


def paged_gather(pool_layer, tables):
    """Gather each slot's K (or V) context from one layer's pool
    ``(num_blocks, block_size, ...)`` through its block table: ``(B,
    max_blocks * block_size, ...)``. Padding rows gather the null block
    — callers mask by context length."""
    b, mb = tables.shape
    g = pool_layer[tables]  # (B, max_blocks, block_size, ...)
    return g.reshape(b, mb * pool_layer.shape[1], *pool_layer.shape[2:])
