"""Paged KV cache: block-table attention for serving decode.

Reference analog: paddle/incubate/nn/functional/block_multihead_attention.py
(paged "Block Multi-head attention": the KV cache is a POOL of fixed-size
blocks, each sequence owns a list of block ids — its block table — so cache
memory is allocated block-at-a-time, sequences of very different lengths
don't reserve max_len each, and finished sequences return their blocks).
The reference implements it as a CUDA serving kernel
(fluid/operators/fused/block_multi_head_attention_op.cu); TPU-first
redesign: the pool is a [num_blocks, block_size, kv_heads, head_dim] array
and the block table drives jnp scatters for the writes. V rows may have
their own width, a pool may be stored flat ([num_blocks, block_size,
kv_heads * dim]: the grouped-query kernel's layout), and a pager may be the
cache of sliding-window layers, whose rows hand back the blocks behind their
window (``release_behind``); a model with several kinds of attention layer
keeps one ``PagedKVCache`` a kind. The decode attention over a bf16/f32 pool
is a Pallas kernel on the TPU (ops/pallas/paged_attention.py: it walks each
lane's table row itself, from the window's first block to the position's,
once, in the pool's dtype; ``paged_attention`` for one query row a KV head,
``paged_attention_gqa`` for grouped queries over flat pools); elsewhere —
CPU runs, widths the kernels do not tile, the int8 pool — it is the plain
path: the table drives a jnp gather of the whole row and XLA fuses gather ->
attention -> reduce. ``paged_attention_decode`` picks from its inputs alone.

Layout note: the reference kernel stores [max_blocks, kv_heads, block_size,
head_dim]; here blocks are [block_size, kv_heads, head_dim]-major so the
gathered view reshapes straight to the [B, S, H, D] attention layout with
no transpose, and one block is one contiguous DMA for the kernel.

Everything is functional and jit-compatible: cache arrays in, cache arrays
out (donate-friendly), shapes static, per-sequence lengths as data.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis import faultinject as _fi

__all__ = ["PagedKVCache", "CowPoolExhausted", "alloc_blocks",
           "read_blocks",
           "paged_write_decode", "paged_write_prefill", "paged_write_mixed",
           "paged_attention_decode", "paged_attention_decode_plain",
           "paged_write_decode_int8",
           "paged_write_prefill_int8", "paged_write_mixed_int8",
           "paged_attention_decode_int8"]


class CowPoolExhausted(RuntimeError):
    """Copy-on-write ran out of free blocks. Copies that were already
    remapped before the pool ran dry ARE applied (their table rows point
    at initialized private blocks), and — because the copy DONATES the
    pools it was handed — the replacement pool list travels on ``.pools``
    so a caller may reclaim blocks and retry against live buffers."""

    def __init__(self, msg, pools):
        super().__init__(msg)
        self.pools = pools

_MON = None  # (state, free-blocks gauge, CoW counter, exhaustion counter)


def _mon():
    global _MON
    if _MON is None:
        from .. import monitor as _m

        _MON = (_m._state,
                _m.gauge("paddle_tpu_kv_free_blocks"),
                _m.counter("paddle_tpu_kv_cow_copies_total"),
                _m.counter("paddle_tpu_kv_pool_exhausted_total"))
    return _MON


class StateSlots:
    """The cache manager's pool for ONE set of recurrent layers (a
    ``StateKind``): a sequence's cache of this kind is not blocks addressed
    by position but ONE slot, row ``b``'s is slot ``b``, and the last slot
    (``batch``) is the NULL slot, where padding lanes go as unassigned table
    entries go to block 0. Per layer ``state`` ``[batch + 1, heads / pack,
    key_dim, pack * value_dim]`` float32 (``pack`` heads side by side along a
    row: ``ops/pallas/gated_delta_rule.py``) and ``conv`` ``[batch + 1,
    conv_kernel - 1, conv_width]`` in the activations' dtype.

    Nothing is granted step by step, so the allocator's calls are no-ops
    here, and a freed slot is not cleared on the host: a sequence's first
    run starts at position 0, which the program takes from zeros whatever
    the slot holds. ``slot_bytes`` is what one sequence holds in one layer."""

    window = None
    block_tables = None                 # no table: the slot is the row

    def __init__(self, num_layers, batch, kind, dtype=jnp.bfloat16):
        from ..ops.pallas.gated_delta_rule import pack_of

        self.batch = int(batch)
        self.slots = self.batch + 1
        pack = pack_of(kind.num_heads)
        shape = (self.slots, kind.num_heads // pack, kind.key_dim,
                 pack * kind.value_dim)
        cshape = (self.slots, kind.conv_kernel - 1, kind.conv_width)
        self.state = [jnp.zeros(shape, jnp.float32)
                      for _ in range(num_layers)]
        self.conv = [jnp.zeros(cshape, dtype) for _ in range(num_layers)]
        self.slot_bytes = int(np.prod(shape[1:])) * 4 \
            + int(np.prod(cshape[1:])) * jnp.dtype(dtype).itemsize

    def ensure_capacity(self, seq_lens_next):
        """A slot holds any length."""

    def free_sequence(self, b):
        """The slot's next sequence resets it in the program."""


class PagedKVCache:
    """Host-side block allocator + the device block pools for ONE layer set.

    The allocator (free-list) is host logic — block grant/free decisions are
    control flow, not device math (the reference's BlockManager is host C++
    too). The pools and tables live on device and flow through jit.
    """

    def __init__(self, num_layers, num_blocks, block_size, kv_heads, head_dim,
                 batch, max_blocks_per_seq, dtype=jnp.bfloat16,
                 quantized=False, v_head_dim=None, flat=False, window=None):
        """``v_head_dim`` (default ``head_dim``) gives V rows their own
        width. ``flat`` stores a block as ``[block_size, kv_heads * dim]``
        (heads merged into the minor dim: the layout of the grouped-query
        kernel, whose head dims need not fill 128 lanes each). ``window``
        makes this the cache of sliding-window layers: a row keeps only the
        blocks that hold its last ``window`` positions
        (``release_behind``), so its table is sparse at the head."""
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.quantized = bool(quantized)
        self.window = None if window is None else int(window)
        v_head_dim = head_dim if v_head_dim is None else v_head_dim
        if quantized and (flat or v_head_dim != head_dim
                          or window is not None):
            raise ValueError("the int8 pool is one [block, kv_heads, "
                             "head_dim] shape for K and V, without a window")
        shape = (num_blocks, block_size, kv_heads, head_dim)
        vshape = (num_blocks, block_size, kv_heads, v_head_dim)
        if flat:
            shape = shape[:2] + (kv_heads * head_dim,)
            vshape = vshape[:2] + (kv_heads * v_head_dim,)
        if quantized:
            # int8 blocks + per-(token, head) fp32 absmax scales: the same
            # halved-KV-bandwidth lever as the dense int8 cache, paged
            sshape = shape[:-1]
            self.k = [jnp.zeros(shape, jnp.int8) for _ in range(num_layers)]
            self.v = [jnp.zeros(shape, jnp.int8) for _ in range(num_layers)]
            self.k_scale = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
            self.v_scale = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
        else:
            self.k = [jnp.zeros(shape, dtype) for _ in range(num_layers)]
            self.v = [jnp.zeros(vshape, dtype) for _ in range(num_layers)]
        # block 0 is the permanently-reserved NULL block: unassigned table
        # slots point at it, so gathers stay in-bounds without masking reads
        self._free = list(range(num_blocks - 1, 0, -1))
        self.batch = int(batch)
        self._tables_np = np.zeros((batch, max_blocks_per_seq), np.int32)
        self.block_tables = jnp.asarray(self._tables_np)
        # per-block reference counts: >1 after fork_rows (beam search shares
        # prompt blocks); writes go copy-on-write via make_tail_exclusive
        self._refs = np.zeros(num_blocks, np.int32)
        # window cache only: per row, the first block index still kept
        self._first = np.zeros(batch, np.int64)

    def _note_free(self, mon):
        """The free-blocks gauge follows the whole-length cache; a window
        cache beside it has its own pool and does not overwrite it."""
        if self.window is None:
            mon[1].set(len(self._free))

    @property
    def blocks_in_use(self):
        """Blocks granted to rows or pinned by a cache (the null block is
        neither)."""
        return self.num_blocks - 1 - len(self._free)

    # -- host-side allocator -------------------------------------------------
    def ensure_capacity(self, seq_lens_next):
        """Grant blocks so every sequence can hold seq_lens_next[b] tokens.

        The table lives host-side (numpy mirror); the device copy is
        re-uploaded ONLY when a grant actually happened — most decode steps
        grant nothing (blocks change once per block_size tokens), and a
        per-token host->device upload would sit in the serving hot loop.
        The nothing-to-grant case is detected vectorized up front: it IS
        the serving steady state, and a per-row python loop there costs
        more than the compiled step saves."""
        _sp = _fi.fire("paged_kv.ensure")
        if _sp is not None and _sp.action == "flag":
            # chaos drill: the allocator's typed exhaustion error without
            # touching the free list — the engine's eviction/spill relief
            # must absorb it (a delay spec just slept inside fire())
            raise RuntimeError(
                "paged KV pool exhausted: no free blocks (injected fault; "
                f"pool={self.num_blocks}, block={self.block_size})")
        tables = self._tables_np
        if self.window is None:
            owned = (tables > 0).sum(axis=1)
        else:
            # released blocks leave holes at a row's head: what counts is
            # how far the row has been granted
            held = tables > 0
            owned = np.where(held.any(axis=1), tables.shape[1]
                             - np.argmax(held[:, ::-1], axis=1), 0)
        need_arr = np.asarray(seq_lens_next)
        needed = -(-np.maximum(need_arr.astype(np.int64), 0)
                   // self.block_size)
        mon = _mon()
        if (needed <= owned).all():
            if mon[0].on:
                self._note_free(mon)
            return
        changed = False
        for b, need_tok in enumerate(need_arr):
            need = int(-(-int(need_tok) // self.block_size))  # ceil
            while owned[b] < need:
                if not self._free:
                    if mon[0].on:
                        mon[3].inc()
                    if changed:
                        # blocks already granted to earlier rows must reach
                        # the device even on the failure path — a caller
                        # that catches this would otherwise decode against
                        # a stale device table (writes landing in the null
                        # block) while the host mirror says all is granted
                        self.block_tables = jnp.asarray(tables.copy())
                    raise RuntimeError(
                        "paged KV pool exhausted: no free blocks "
                        f"(pool={self.num_blocks}, block={self.block_size})")
                blk = self._free.pop()
                tables[b, owned[b]] = blk
                self._refs[blk] = 1
                owned[b] += 1
                changed = True
        if mon[0].on:
            self._note_free(mon)
        if changed:
            # upload a COPY: jnp.asarray of an aligned numpy array may be
            # zero-copy on CPU, and an in-flight async step could still be
            # reading the previous device view while the host mirror mutates
            self.block_tables = jnp.asarray(tables.copy())

    def release_behind(self, seq_lens):
        """Window cache: free every block that lies wholly behind the
        window of a row's NEXT query (position ``seq_lens[b]``, which reads
        positions ``seq_lens[b] - window + 1 ..``); later queries read
        later positions only. Returns the number of blocks freed. The
        freed table slots point at the null block; the attention never
        reads below a lane's first window block."""
        first = np.maximum(np.asarray(seq_lens, np.int64) - self.window + 1,
                           0) // self.block_size
        rows = np.flatnonzero(first > self._first)
        if not len(rows):
            return 0
        tables, freed = self._tables_np, 0
        for b in rows:
            for i in range(int(self._first[b]), int(first[b])):
                blk = int(tables[b, i])
                if blk > 0:
                    tables[b, i] = 0
                    self._refs[blk] -= 1
                    if self._refs[blk] == 0:
                        self._free.append(blk)
                        freed += 1
            self._first[b] = first[b]
        self.block_tables = jnp.asarray(tables.copy())
        mon = _mon()
        if mon[0].on:
            self._note_free(mon)
        return freed

    def free_sequence(self, b):
        """Drop sequence b's block references; blocks return to the pool
        when their last referencing row lets go."""
        self._first[b] = 0
        tables = self._tables_np
        for blk in tables[b]:
            if blk > 0:
                self._refs[blk] -= 1
                if self._refs[blk] == 0:
                    self._free.append(int(blk))
        tables[b] = 0
        self.block_tables = jnp.asarray(tables.copy())
        mon = _mon()
        if mon[0].on:
            self._note_free(mon)

    # -- external references (radix/prefix cache) ----------------------------
    def retain_blocks(self, blocks):
        """Take one extra reference on each block (the prefix cache's pin):
        a retained block survives :meth:`free_sequence` of its original
        owner and only returns to the pool when released."""
        for blk in blocks:
            blk = int(blk)
            if not 0 < blk < self.num_blocks:
                raise ValueError(f"block {blk} out of range")
            if self._refs[blk] <= 0:
                raise ValueError(f"block {blk} is free; cannot retain")
            self._refs[blk] += 1

    def release_blocks(self, blocks):
        """Drop one reference per block (undo of retain_blocks); blocks
        whose last reference goes return to the free pool."""
        freed = 0
        for blk in blocks:
            blk = int(blk)
            self._refs[blk] -= 1
            if self._refs[blk] == 0:
                self._free.append(blk)
                freed += 1
        mon = _mon()
        if mon[0].on:
            self._note_free(mon)
        return freed

    def adopt_blocks(self, b, blocks):
        """Map shared ``blocks`` into the HEAD of row b's block table (one
        new reference each) — the prefix-cache admission path: row b's
        first ``len(blocks) * block_size`` positions read the shared KV.
        Row b must hold no blocks yet (adoption happens at admission)."""
        tables = self._tables_np
        if (tables[b] > 0).any():
            raise ValueError(f"row {b} already holds blocks")
        if len(blocks) > self.max_blocks_per_seq:
            raise ValueError("shared prefix longer than max_blocks_per_seq")
        for i, blk in enumerate(blocks):
            blk = int(blk)
            if self._refs[blk] <= 0:
                raise ValueError(f"block {blk} is free; cannot adopt")
            tables[b, i] = blk
            self._refs[blk] += 1
        self.block_tables = jnp.asarray(tables.copy())

    # -- host-RAM spill/restore (serving resilience) -------------------------
    def take_blocks(self, n):
        """Pop ``n`` free blocks for a restore (spilled radix prefixes,
        preempted-request KV): each comes back with one reference — the
        restorer owns it. Returns None (taking nothing) when the pool
        lacks headroom, so a restore can degrade to a recompute instead
        of starving live sequences."""
        n = int(n)
        if n <= 0 or len(self._free) < n:
            return None
        blks = [self._free.pop() for _ in range(n)]
        for blk in blks:
            self._refs[blk] = 1
        mon = _mon()
        if mon[0].on:
            self._note_free(mon)
        return blks

    def place_blocks(self, b, blocks):
        """Map ``blocks`` (owned by the caller via :meth:`take_blocks`)
        into the HEAD of empty row ``b`` — the restore path of a
        preempted request: its spilled KV re-uploads into these blocks
        at the same in-block offsets, so the continuation is bit-exact."""
        tables = self._tables_np
        if (tables[b] > 0).any():
            raise ValueError(f"row {b} already holds blocks")
        if len(blocks) > self.max_blocks_per_seq:
            raise ValueError("restore longer than max_blocks_per_seq")
        for i, blk in enumerate(blocks):
            tables[b, i] = int(blk)
        self.block_tables = jnp.asarray(tables.copy())

    def write_block_contents(self, pools, blocks, contents):
        """Upload host-RAM block contents into pool ``blocks`` (one
        donated scatter): ``contents`` is a per-layer list of pool-leaf
        tuples — ``(k, v)`` for bf16 pools, ``(kq, ks, vq, vs)`` for the
        quantized layout — each numpy array shaped ``[n, block_size,
        ...]`` (block-major on axis 0, exactly like the pools). Index
        vectors pad to a power-of-two length (padding writes zeros into
        the null block — benign) so the jitted upload compiles for
        O(log) distinct shapes, exactly like the CoW copy."""
        n = len(blocks)
        if n == 0:
            return pools
        m = 1
        while m < n:
            m *= 2
        blks = np.zeros(m, np.int32)
        blks[:n] = np.asarray(blocks, np.int32)
        padded = []
        for entry in contents:
            leaves = []
            for arr in entry:
                if m != n:
                    pad = ((0, m - n),) + ((0, 0),) * (arr.ndim - 1)
                    arr = np.pad(arr, pad)
                leaves.append(arr)
            padded.append(tuple(leaves))
        fn = getattr(self, "_restore_jit", None)
        if fn is None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def fn(pools, blks, vals):
                return [tuple(pl.at[blks].set(c.astype(pl.dtype))
                              for pl, c in zip(entry, cs))
                        for entry, cs in zip(pools, vals)]

            self._restore_jit = fn
        return fn(pools, jnp.asarray(blks), padded)

    def make_positions_exclusive(self, rows, positions, pools):
        """Copy-on-write for the mixed serving step: before row ``rows[i]``
        writes at ``positions[i]``, any targeted block that is SHARED
        (refs > 1 — prefix-cache hits, beam forks) is replaced by a private
        copy in one donated gather/scatter. The generalized, per-row form
        of :meth:`make_tail_exclusive`; plain unshared decode takes the
        cheap all-refs<=1 early exit."""
        _sp = _fi.fire("paged_kv.cow")
        if _sp is not None and _sp.action == "flag":
            # chaos drill: a REAL CowPoolExhausted carrying the live
            # (unconsumed) pools, raised before any copy — the caller's
            # adopt-pools-evict-retry path runs against valid buffers
            raise CowPoolExhausted(
                "paged KV pool exhausted during copy-on-write (injected "
                f"fault; pool={self.num_blocks})", pools)
        if (self._refs <= 1).all():
            return pools
        mon = _mon()
        t = self._tables_np
        rows = np.asarray(rows, np.int64)
        positions = np.asarray(positions, np.int64)
        bidxs = positions // self.block_size
        targets = t[rows, bidxs]
        hot = np.flatnonzero((targets > 0) & (self._refs[targets] > 1))
        pairs = []
        exhausted = False
        for i in hot:
            b, bidx = int(rows[i]), int(bidxs[i])
            phys = int(t[b, bidx])
            if phys > 0 and self._refs[phys] > 1:
                if not self._free:
                    if mon[0].on:
                        mon[3].inc()
                    # raise only AFTER applying the pairs already
                    # remapped: their tables/refs mutations are in, so
                    # skipping their data copy would leave a retrying
                    # caller (they now look unshared) reading
                    # uninitialized KV
                    exhausted = True
                    break
                new = self._free.pop()
                self._refs[new] = 1
                self._refs[phys] -= 1
                t[b, bidx] = new
                pairs.append((phys, new))
        if pairs:
            if mon[0].on:
                mon[2].inc(len(pairs))
                self._note_free(mon)
            pools = self._cow_apply(pools, pairs)
            self.block_tables = jnp.asarray(t.copy())
        if exhausted:
            raise CowPoolExhausted(
                "paged KV pool exhausted during copy-on-write "
                f"(pool={self.num_blocks})", pools)
        return pools

    # -- copy-on-write sharing (beam search) ---------------------------------
    def fork_rows(self, parent_rows):
        """Every row adopts parent_rows[b]'s block table (shared blocks,
        refcounted) — the paged form of the dense cache's batch-axis beam
        reorder. Writes afterwards must go through make_tail_exclusive."""
        parent_rows = np.asarray(parent_rows, np.int64)
        t = self._tables_np
        new = t[parent_rows].copy()
        if np.array_equal(new, t):
            return   # identity fork (EOS-frozen beams): nothing changes
        # vectorized refcount delta (this runs once per decoded token)
        self._refs -= np.bincount(t[t > 0].ravel(),
                                  minlength=self.num_blocks).astype(np.int32)
        self._refs += np.bincount(new[new > 0].ravel(),
                                  minlength=self.num_blocks).astype(np.int32)
        # blocks nobody references anymore go back to the pool
        for blk in np.unique(t[t > 0]):
            if self._refs[blk] == 0:
                self._free.append(int(blk))
        self._tables_np = new
        self.block_tables = jnp.asarray(new.copy())
        mon = _mon()
        if mon[0].on:
            self._note_free(mon)

    def _cow_copy_fn(self):
        fn = getattr(self, "_cow_jit", None)
        if fn is None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def fn(pools, olds, news):
                # donated: XLA scatters the copied blocks in place instead
                # of duplicating every layer's whole pool per CoW event.
                # tree_map covers both pool layouts ((k, v) and the int8
                # (kq, ks, vq, vs)) — every leaf is block-major on axis 0
                return jax.tree_util.tree_map(
                    lambda a: a.at[news].set(a[olds]), pools)

            self._cow_jit = fn
        return fn

    def _cow_apply(self, pools, pairs):
        """Run the donated CoW copy for ``pairs`` of (old, new) blocks.
        The index vectors pad to a power-of-two length so the jitted copy
        compiles for O(log) distinct shapes, not one per batch size —
        padding entries copy the null block onto itself (benign)."""
        n = 1
        while n < len(pairs):
            n *= 2
        olds = np.zeros(n, np.int32)
        news = np.zeros(n, np.int32)
        for i, (o, w) in enumerate(pairs):
            olds[i] = o
            news[i] = w
        return self._cow_copy_fn()(pools, jnp.asarray(olds),
                                   jnp.asarray(news))

    def make_tail_exclusive(self, pos, pools):
        """Copy-on-write: before writing at position `pos`, any row whose
        tail block (pos // block_size) is SHARED gets its own copy of it
        (one donated gather/scatter over the pools). No-op (and cheap)
        when nothing is shared — plain decoding always takes that path."""
        if (self._refs <= 1).all():
            return pools
        mon = _mon()
        bidx = int(pos) // self.block_size
        t = self._tables_np
        pairs = []
        exhausted = False
        for b in range(len(t)):
            phys = int(t[b, bidx])
            if phys > 0 and self._refs[phys] > 1:
                if not self._free:
                    if mon[0].on:
                        mon[3].inc()
                    # apply-then-raise, as in make_positions_exclusive:
                    # already-remapped rows must get their data copy
                    exhausted = True
                    break
                new = self._free.pop()
                self._refs[new] = 1
                self._refs[phys] -= 1
                t[b, bidx] = new
                pairs.append((phys, new))
        if pairs:
            if mon[0].on:
                mon[2].inc(len(pairs))
                self._note_free(mon)
            pools = self._cow_apply(pools, pairs)
            self.block_tables = jnp.asarray(t.copy())
        if exhausted:
            raise CowPoolExhausted(
                "paged KV pool exhausted during copy-on-write "
                f"(pool={self.num_blocks})", pools)
        return pools


def alloc_blocks(batch, max_len, block_size):
    """Static shape helper: blocks per sequence for a max_len budget."""
    return -(-max_len // block_size)


def read_blocks(pools, blocks):
    """Download pool ``blocks`` to host RAM (the SPILL read): a per-layer
    list of pool-leaf tuples of numpy arrays ``[n, block_size, ...]`` —
    ``(k, v)`` for bf16 pools, the 4-leaf ``(kq, ks, vq, vs)`` for the
    quantized layout. This is a deliberate device→host transfer on the
    resilience path (pool pressure / preemption), never the serving hot
    loop — the spilled bits round-trip exactly, which is what makes
    restore-then-decode bit-identical."""
    blks = jnp.asarray(np.asarray(blocks, np.int32))
    out = []
    for entry in pools:
        out.append(tuple(
            np.asarray(jax.device_get(leaf[blks]))    # graftlint: disable=GL002
            for leaf in entry))
    return out


def _decode_scatter_idx(block_tables, seq_lens, bs):
    """(phys block, in-block offset) for writing one token at seq_lens[b]."""
    pos = seq_lens.astype(jnp.int32)
    blk_idx = pos // bs
    off = pos % bs
    rows = jnp.arange(block_tables.shape[0])
    return block_tables[rows, blk_idx], off


def _rows_for(pool, new):
    """``new`` [..., kv_heads, dim] as the pool stores a position: unchanged
    for a [blocks, block_size, kv_heads, dim] pool, heads merged into the
    minor dim for a flat one; in the pool's dtype."""
    if pool.ndim == 3:
        new = new.reshape(new.shape[:-2] + (-1,))
    return new.astype(pool.dtype)


def paged_write_decode(cache_k, cache_v, block_tables, seq_lens, k_new, v_new):
    """Write ONE new token per sequence into its current tail block.

    k_new/v_new: [B, kv_heads, head_dim]; position = seq_lens[b].
    Returns (cache_k, cache_v) with the writes applied (functional)."""
    phys, off = _decode_scatter_idx(block_tables, seq_lens, cache_k.shape[1])
    cache_k = cache_k.at[phys, off].set(_rows_for(cache_k, k_new))
    cache_v = cache_v.at[phys, off].set(_rows_for(cache_v, v_new))
    return cache_k, cache_v


def paged_write_mixed(cache_k, cache_v, row_tables, positions, valid,
                      k_new, v_new):
    """Write one token per LANE of a mixed (decode + chunked-prefill) pack.

    ``row_tables`` is the per-lane view ``block_tables[slot_ids]`` — two
    lanes of the same prefill chunk carry the same table row at different
    ``positions``. Padding lanes (``valid`` False) are redirected at an
    out-of-bounds block and DROPPED by the scatter, exactly like prefill
    padding rows (any real block id would clobber its owner)."""
    phys, off = _decode_scatter_idx(row_tables, positions, cache_k.shape[1])
    phys = jnp.where(valid, phys, cache_k.shape[0])
    cache_k = cache_k.at[phys, off].set(_rows_for(cache_k, k_new),
                                        mode="drop")
    cache_v = cache_v.at[phys, off].set(_rows_for(cache_v, v_new),
                                        mode="drop")
    return cache_k, cache_v


def paged_write_prefill(cache_k, cache_v, block_tables, seq_lens,
                        k_new, v_new):
    """Write a full prompt per sequence: k_new/v_new [B, S, kv_heads, D],
    token t of sequence b lands at block_tables[b, t // bs] offset t % bs
    (only t < seq_lens[b] rows are written; the rest target the null block
    but are masked by never being read — seq_lens bounds every gather)."""
    phys, off = _prefill_scatter_idx(cache_k, block_tables, seq_lens,
                                     k_new.shape[1])
    cache_k = cache_k.at[phys, off].set(
        _rows_for(cache_k, _flat_rows(k_new)), mode="drop")
    cache_v = cache_v.at[phys, off].set(
        _rows_for(cache_v, _flat_rows(v_new)), mode="drop")
    return cache_k, cache_v


def _prefill_scatter_idx(pool, block_tables, seq_lens, S):
    """Flattened (phys, offset) for writing a [B, S, ...] prompt. Padding
    rows target an OUT-OF-BOUNDS block and are DROPPED by the scatter —
    redirecting them at any real block id (block 0 included) would clobber
    whichever sequence owns that block."""
    B = block_tables.shape[0]
    nb, bs = pool.shape[0], pool.shape[1]
    t = jnp.arange(S)
    blk_idx = t // bs                                   # [S]
    off = t % bs
    phys = block_tables[:, blk_idx]                     # [B, S]
    valid = t[None, :] < seq_lens[:, None]              # [B, S]
    phys = jnp.where(valid, phys, nb)
    return phys.reshape(-1), jnp.tile(off, B)


def _flat_rows(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def paged_write_decode_int8(kq, ks, vq, vs, block_tables, seq_lens,
                            k_new_q, k_new_s, v_new_q, v_new_s):
    """int8 form of paged_write_decode: values [B, kv, D] int8 plus their
    per-(token, head) scales [B, kv] — same scatter indices, four pools."""
    phys, off = _decode_scatter_idx(block_tables, seq_lens, kq.shape[1])
    return (kq.at[phys, off].set(k_new_q), ks.at[phys, off].set(k_new_s),
            vq.at[phys, off].set(v_new_q), vs.at[phys, off].set(v_new_s))


def paged_write_mixed_int8(kq, ks, vq, vs, row_tables, positions, valid,
                           k_new_q, k_new_s, v_new_q, v_new_s):
    """int8 form of paged_write_mixed: one quantized token per LANE of a
    mixed (decode + chunked-prefill + draft-verify) pack — values
    [T, kv, D] int8 plus per-(token, head) scales [T, kv], the same
    per-lane scatter indices across four pools. Padding lanes (``valid``
    False) redirect at an out-of-bounds block and DROP."""
    phys, off = _decode_scatter_idx(row_tables, positions, kq.shape[1])
    phys = jnp.where(valid, phys, kq.shape[0])
    return (kq.at[phys, off].set(k_new_q, mode="drop"),
            ks.at[phys, off].set(k_new_s, mode="drop"),
            vq.at[phys, off].set(v_new_q, mode="drop"),
            vs.at[phys, off].set(v_new_s, mode="drop"))


def paged_write_prefill_int8(kq, ks, vq, vs, block_tables, seq_lens,
                             k_new_q, k_new_s, v_new_q, v_new_s):
    """int8 form of paged_write_prefill (values [B, S, kv, D] int8 + scales
    [B, S, kv]); padding rows drop via the shared out-of-bounds scatter."""
    phys, off = _prefill_scatter_idx(kq, block_tables, seq_lens,
                                     k_new_q.shape[1])

    def w(pool, new):
        return pool.at[phys, off].set(_flat_rows(new), mode="drop")

    return w(kq, k_new_q), w(ks, k_new_s), w(vq, v_new_q), w(vs, v_new_s)


@jax.named_scope("paged_attention")
def paged_attention_decode_int8(q, kq, ks, vq, vs, block_tables, seq_lens,
                                scale=None):
    """One decode step against the int8 paged cache WITHOUT materializing a
    dequantized copy: the per-(token, head) scales fold into the score and
    value einsums. Arithmetic MIRRORS the dense engine's _attend_int8
    op-for-op (QK/PV einsums in q.dtype, fp32 scale fold, divide by
    sqrt(D)) so dense-int8 and paged-int8 stay bit-comparable in bf16 too,
    not just fp32."""
    B, n_q, D = q.shape
    nb, bs, n_kv, _ = kq.shape
    groups = n_q // n_kv
    T = block_tables.shape[1] * bs

    k = kq[block_tables].reshape(B, T, n_kv, D)
    k_s = ks[block_tables].reshape(B, T, n_kv)
    v = vq[block_tables].reshape(B, T, n_kv, D)
    v_s = vs[block_tables].reshape(B, T, n_kv)

    qg = q.reshape(B, n_kv, groups, D)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(q.dtype))
    ct = jnp.promote_types(q.dtype, jnp.float32)
    logits = (logits.astype(ct)
              * jnp.transpose(k_s, (0, 2, 1))[:, :, None, :].astype(ct)
              / (np.sqrt(D) if scale is None else 1.0 / scale))
    t = jnp.arange(T)[None, None, None, :]
    mask = t <= seq_lens[:, None, None, None]
    logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    pv = (probs * jnp.transpose(v_s, (0, 2, 1))[:, :, None, :].astype(ct)
          ).astype(q.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", pv, v.astype(q.dtype))
    return out.reshape(B, n_q, D).astype(q.dtype)


def _kernel_applies(q, pool, v_pool=None):
    """Whether ``paged_attention_decode`` runs a Pallas kernel for this
    query and these pools: on a TPU, where the kernel's blocks tile, the
    dtypes are ones it loads, and its four block buffers (K and V, double
    buffered) fit half of a core's 16 MiB of scoped VMEM. A
    ``[blocks, block_size, kv_heads, head_dim]`` pool goes to the
    one-row-a-head kernel (``paged_attention``): the head dim has to fill
    whole 128-lane rows and the KV heads whole sublane tiles of 8, or one
    small tile of 2 or 4 (30 heads are refused by the compiler: a model
    with such a count keeps flat pools); block_size sits on a dim it does
    not tile. A flat ``[blocks, block_size, kv_heads * dim]`` pool goes to the
    grouped-query kernel (``paged_attention_gqa``), which wants the MERGED
    rows of K and of V to fill whole 128-lane rows (192-wide K heads beside
    128-wide V heads do) and a block_size its dtype's sublane tile divides.
    Everything read here is visible in the inputs: no flag picks the path.
    The serving engine asks it too, to count the blocks a step's attention
    reads."""
    v_pool = pool if v_pool is None else v_pool
    fits = (jax.devices()[0].platform == "tpu"
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and pool.dtype in (jnp.bfloat16, jnp.float32)
            and 2 * (int(np.prod(pool.shape[1:]))
                     + int(np.prod(v_pool.shape[1:]))) * pool.dtype.itemsize
            <= 8 * 2 ** 20)
    if pool.ndim == 3:
        return (fits and pool.shape[-1] % 128 == 0
                and v_pool.shape[-1] % 128 == 0 and pool.shape[1] % 16 == 0)
    # (the kernel slices a block's [kv_heads, head_dim] rows out of HBM: the
    # heads sit on sublanes, in whole tiles of 8 or one small tile of 2 or 4;
    # the compiler refuses 1, 3, 6, 12, 20, 30)
    heads = pool.shape[2]
    return fits and q.shape[-1] % 128 == 0 \
        and (heads % 8 == 0 or heads in (2, 4))


def kernel_applies(q, cache_k, cache_v):
    """``_kernel_applies`` for a layer's pool pair (a [blocks, block_size,
    kv_heads, head_dim] pool's V has its K's shape, so K alone decides)."""
    if cache_k.ndim == 3:
        return _kernel_applies(q, cache_k, cache_v)
    return _kernel_applies(q, cache_k)


@jax.named_scope("paged_attention")
def paged_attention_decode(q, cache_k, cache_v, block_tables, seq_lens,
                           scale=None, window=None, sink=None, rows=None):
    """One decode step of attention against the paged cache.

    q: [B, q_heads, head_dim] (GQA: q_heads a multiple of kv_heads); row b
    attends to positions 0..seq_lens[b] INCLUSIVE of its block-table row
    (the current token was just written at position seq_lens), or, with
    ``window``, to the last ``window`` of them. ``sink`` [q_heads] is a
    logit per query head that joins the softmax's denominator and adds no
    value. The pools may be flat and V's head dim its own (see
    ``PagedKVCache``); the result is [B, q_heads, v_head_dim]. On a TPU
    this is a Pallas kernel (``_kernel_applies``), which reads only the
    blocks from the window's first to the position's; elsewhere
    ``paged_attention_decode_plain``, the kernels' reference. Same
    arithmetic either way: float32 scores, probabilities and accumulation.

    ``rows`` [B] int32 says which rows of ``block_tables`` are the SAME row
    (equal ids; a mixed step's slot ids): the kernels then serve lanes on
    one row at consecutive positions (a prefill chunk) as query tiles that
    walk the row once (``ops.pallas.paged_attention.plan_tiles``). It is
    data about the step, and changes no result; the gather paths ignore it.

    The whole of it runs under ``jax.named_scope("paged_attention")``, so
    a device trace can tell the serving programs' attention from the rest
    of a layer by name."""
    plain = cache_k.ndim == 4 and (window is not None or sink is not None)
    if not plain and kernel_applies(q, cache_k, cache_v):
        from ..ops.pallas import paged_attention as _k

        if cache_k.ndim == 3:
            return _k.paged_attention_gqa(q, cache_k, cache_v, block_tables,
                                          seq_lens, scale, window, sink, rows)
        return _k.paged_attention(q, cache_k, cache_v, block_tables,
                                  seq_lens, scale, rows)
    return paged_attention_decode_plain(q, cache_k, cache_v, block_tables,
                                        seq_lens, scale, window, sink)


def paged_attention_decode_plain(q, cache_k, cache_v, block_tables, seq_lens,
                                 scale=None, window=None, sink=None):
    """The gather path of ``paged_attention_decode``: every row reads its
    WHOLE table. Gathers each sequence's blocks into a [B, T_max, kv, D]
    view (T_max = max_blocks_per_seq * block_size) and masks
    t <= seq_lens[b] (and, with ``window``, t > seq_lens[b] - window).

    QK and PV are written as multiply + reduce, not as einsums: every
    (lane, kv head) pair has its OWN gathered K and V, so as a matmul each
    is ``groups`` rows tall — one row for MHA — and the TPU compiler pads
    that to 8 sublanes and materializes the padded f32 operand
    ([8, B, T_max, kv, D]: 18 GB at 136 lanes x 1024 x 32 x 128, refused
    outright for a v5e). The fused multiply-reduce reads the gathered
    bf16 blocks once and keeps nothing wider than the logits."""
    B, n_q, D = q.shape
    bs = cache_k.shape[1]
    n_kv = cache_k.shape[2] if cache_k.ndim == 4 else cache_k.shape[2] // D
    groups = n_q // n_kv
    T = block_tables.shape[1] * bs

    k = cache_k[block_tables].reshape(B, T, n_kv, D)
    v = cache_v[block_tables].reshape(B, T, n_kv, -1)

    if scale is None:
        scale = 1.0 / np.sqrt(D)
    # promote, don't demote: bf16 -> f32 for a stable softmax, f64 stays f64
    ct = jnp.promote_types(q.dtype, jnp.float32)
    qg = q.reshape(B, 1, n_kv, groups, D).astype(ct)
    logits = (qg * k.astype(ct)[:, :, :, None, :]).sum(-1) * scale  # B,T,h,g
    t = jnp.arange(T)[None, :, None, None]
    mask = t <= seq_lens[:, None, None, None]
    if window is not None:
        mask = mask & (t > seq_lens[:, None, None, None] - window)
    logits = jnp.where(mask, logits, -1e30)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=1)
    else:
        # the sink is one more logit in the denominator, with no value row
        sk = sink.astype(ct).reshape(1, 1, n_kv, groups)
        m = jnp.maximum(logits.max(axis=1, keepdims=True), sk)
        e = jnp.exp(logits - m)
        probs = e / (e.sum(axis=1, keepdims=True) + jnp.exp(sk - m))
    out = (probs[..., None] * v.astype(ct)[:, :, :, None, :]).sum(1)  # B,h,g,Dv
    return out.reshape(B, n_q, -1).astype(q.dtype)
