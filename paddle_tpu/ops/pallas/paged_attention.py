"""Paged attention for serving decode as Pallas TPU kernels.

Reference analog: fluid/operators/fused/block_multi_head_attention_op.cu (the
reference's CUDA page-table kernel behind block_multihead_attention).
TPU-first redesign: one query token per LANE; the K/V pools stay in HBM in
the repo's own layout and the kernels walk block-table rows themselves, block
by block, double buffered, in the pool's dtype. Tables, positions and the
plan below are scalar-prefetched DATA: one compile serves every mix of
lengths, and every mix of decode lanes and prefill chunks.

**A lane of its own** (a decode lane, a burst, lockstep decode) walks ITS row
for ``positions[lane] // block_size + 1`` blocks and no further. In
``paged_attention`` (pools [num_blocks, block_size, kv_heads, head_dim], head
dim a multiple of 128) there is one query row per (lane, kv head), no matmul
to feed the MXU: scores are a multiply + lane reduce on the VPU, replicated
along the lanes so that the values product needs no relayout; K and V are
promoted to float32 on chip, scores, probabilities and the accumulator are
float32 (online softmax over the blocks), as on the plain path
(models/paged_kv.py). ``paged_attention_gqa`` is the kernel for grouped
queries (16 query heads a KV head would be 16 passes of the VPU's multiply +
reduce over one block): flat pools [num_blocks, block_size, kv_heads * dim],
K rows wider than V rows (192 beside 128), scores and values on the MXU, a
per-lane FIRST block (a sliding-window lane starts at ``(position - window +
1) // block_size``) and an optional sink logit a head in the softmax's
denominator.

**A query tile** (PR 33). The lanes of a prefill chunk sit on ONE table row
at consecutive positions. Told which lanes share a row (``rows``: the mixed
step's slot ids), both kernels serve such a run as tiles of ``tile_lanes``
lanes (``plan_tiles``): each K/V block from the tile's lowest first block to
its highest last block comes into VMEM ONCE, the scores of all the tile's
queries against it are one MXU product a KV head, each query is masked by ITS
OWN ``lo <= position <= pos`` (causal inside the chunk by absolute position,
the window and the sink as for a lane), and the online softmax runs a query.
A tile's queries lie ACROSS the 128 lanes of a vector register (scores
[block_size, queries], running maximum and sum [1, queries]): the softmax's
reductions run down the sublanes and no [queries, 1] column is ever held.
The lanes no tile serves go to the per-lane kernel through an ORDER (the
grid is as long as there are such lanes), so a step without ``rows`` (the
burst, lockstep decode) runs the per-lane kernel over every lane as before
and alone. Draft-verify lanes behind their decode lane are a run too: three
or more drafts make a tile, fewer stay per lane (``MIN_RUN``). The same
arithmetic either way: float32 scores, statistics and accumulator;
``paged_attention`` multiplies float32 operands, ``paged_attention_gqa``
rounds the probabilities to the pool's dtype for the values product.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NEG_INF, _i32, _interpret

# positions of one block reduced per inner iteration. Measured on the v5e at
# the serve cell's shape (PERF.md, PR 30): 16 is a tenth faster than 8 (half
# the loop iterations for the same vector work) and a third faster than 4
_CHUNK = 16


def _lane_order(refs, ordered):
    """The lane grid step ``g`` walks and the lane of any step. Without an
    order every step walks its own lane; with one (``order_ref``: the lanes
    no tile serves, in lane order) the grid is as long as the order."""
    g = pl.program_id(0)
    if not ordered:
        return refs, g, lambda step: step
    return refs[1:], refs[0][g], lambda step: refs[0][step]


def _kernel(tables_ref, pos_ref, *rest, scale, width, chunk, ordered=False):
    """One lane: walk its row of ``tables_ref`` (flattened [T * width]) up
    to its position. The block for the NEXT iteration — this lane's next
    block, or the next walked lane's first — is always in flight while the
    current one is reduced; ``slot_ref`` carries the buffer parity across
    lanes."""
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem, slot_ref), t, \
        lane_at = _lane_order(rest, ordered)
    g = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    bs = kbuf.shape[1]
    groups, n_kv, d = q_ref.shape[1:]
    # held inside the table: a position past it reads the whole row, as the
    # plain path's mask does, and every lane waits for the block that the
    # lane before it started
    pos = jnp.clip(pos_ref[t], _i32(0), _i32(width * bs - 1))
    n_blocks = pos // _i32(bs) + _i32(1)

    def copies(lane, j, slot):
        blk = tables_ref[lane * _i32(width) + j]
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot],
                                      vsem.at[slot]))

    def start(lane, j, slot):
        for c in copies(lane, j, slot):
            c.start()

    @pl.when(g == 0)
    def _():
        slot_ref[0] = _i32(0)
        start(t, _i32(0), _i32(0))

    slot0 = slot_ref[0]
    q = q_ref[0].astype(jnp.float32) * np.float32(scale)   # (groups, kv, D)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, n_kv, d), 0)

    def block(j, carry):
        slot = jax.lax.rem(slot0 + j, _i32(2))
        other = _i32(1) - slot
        last = j + _i32(1) == n_blocks

        @pl.when(jnp.logical_not(last))
        def _():
            start(t, j + _i32(1), other)

        @pl.when(jnp.logical_and(last, g + _i32(1) < n_lanes))
        def _():
            start(lane_at(g + _i32(1)), _i32(0), other)

        for c in copies(t, j, slot):
            c.wait()
        base = j * _i32(bs)
        # the lane's last block is reduced up to the chunk holding pos
        n_chunks = jnp.where(last, (pos - base) // _i32(chunk) + _i32(1),
                             _i32(bs // chunk))

        def rows(c, carry):
            m, l, acc = carry
            r0 = pl.multiple_of(c * _i32(chunk), chunk)
            kf = kbuf[slot, pl.ds(r0, chunk)].astype(jnp.float32)
            vf = vbuf[slot, pl.ds(r0, chunk)].astype(jnp.float32)
            live = row + (base + r0) <= pos
            m_n, l_n, acc_n = [], [], []
            for i in range(groups):
                s = jnp.sum(kf * q[i][None], axis=-1, keepdims=True)
                s = jnp.where(live, jnp.broadcast_to(s, kf.shape), _NEG_INF)
                m_new = jnp.maximum(m[i], jnp.max(s, axis=0))
                alpha = jnp.exp(m[i] - m_new)
                p = jnp.exp(s - m_new[None])
                m_n.append(m_new)
                l_n.append(l[i] * alpha + jnp.sum(p, axis=0))
                acc_n.append(acc[i] * alpha + jnp.sum(p * vf, axis=0))
            return tuple(m_n), tuple(l_n), tuple(acc_n)

        return jax.lax.fori_loop(_i32(0), n_chunks, rows, carry)

    init = (tuple(jnp.full((n_kv, d), _NEG_INF, jnp.float32)
                  for _ in range(groups)),
            tuple(jnp.zeros((n_kv, d), jnp.float32) for _ in range(groups)),
            tuple(jnp.zeros((n_kv, d), jnp.float32) for _ in range(groups)))
    _, l, acc = jax.lax.fori_loop(_i32(0), n_blocks, block, init)
    slot_ref[0] = jax.lax.rem(slot0 + n_blocks, _i32(2))
    for i in range(groups):
        o_ref[0, i] = (acc[i] / l[i]).astype(o_ref.dtype)


# --------------------------------------------------------------------------- #
# query tiles: the lanes of one request, one walk of their shared row
# --------------------------------------------------------------------------- #
# a run shorter than this stays on the per-lane walk: a tile pays for a whole
# tile's rows a block, which two or three lanes' own walks undercut (a decode
# lane with one or two draft-verify lanes behind it is such a run; with three
# drafts or more it forms a tile)
MIN_RUN = 4


def tile_lanes(groups, flat):
    """Lanes a query tile holds. Its queries (lanes x the query heads a KV
    head) lie across the 128 lanes of a vector register, so they come in
    whole 128s: for [blocks, block_size, kv_heads, D] pools the fewest lanes
    that do (128 for MHA: a block's per-head relayout is paid once a tile),
    for flat pools 32 lanes at least (512 queries a KV head at 16 groups;
    the block's DMA is a tenth of the tile's arithmetic already, and a
    window layer's tile should span few blocks)."""
    fewest = 128 // math.gcd(groups, 128)
    return max(fewest, 32) if flat else fewest


def plan_tiles(rows, positions, tq, xp=jnp):
    """Which lanes of a step form query tiles. ``rows`` [T]: the table row a
    lane sits on (lanes with equal ids have equal rows), ``positions`` [T].
    A RUN is a stretch of neighbouring lanes on one row at consecutive
    positions (a prefill chunk); a run of ``MIN_RUN`` lanes or more is cut
    into tiles of ``tq`` lanes, in lane order, up to ``T // tq + 2`` tiles
    (a step with more keeps the rest per lane). Returns a dict of int32
    arrays: ``tiled`` [T] bool; ``slot`` [T], a tiled lane's place among the
    tiles' ``n_tiles * tq`` queries; ``lane0`` / ``n`` [n_tiles], a tile's
    first lane and lane count (0 past the ``tiles`` [] in use). The same code
    plans on the device (``xp`` jnp, inside the step's program) and counts on
    the host (``xp`` numpy, ``blocks_walked``), so what is counted is what is
    walked."""
    T = rows.shape[0]
    n_tiles = T // tq + 2
    i32 = xp.int32
    idx = xp.arange(T, dtype=i32)
    rows, positions = rows.astype(i32), positions.astype(i32)
    cont = xp.concatenate([
        xp.zeros((1,), bool),
        (rows[1:] == rows[:-1]) & (positions[1:] == positions[:-1] + 1)])
    ends = xp.concatenate([~cont[1:], xp.ones((1,), bool)])
    first = _running(xp, xp.where(cont, xp.zeros_like(idx), idx), True)
    last = _running(xp, xp.where(ends, idx, xp.full_like(idx, T - 1)), False,
                    reverse=True)
    k = idx - first                                  # place in the run
    long = last - first + 1 >= MIN_RUN
    heads = xp.cumsum((long & (k % tq == 0)).astype(i32))
    tile = heads - 1                # of a lane in a long run: its own tile
    tiled = long & (tile < n_tiles)
    which = xp.arange(n_tiles, dtype=i32)
    tiles = xp.minimum(heads[-1], n_tiles)
    lane0 = xp.minimum(_nth(xp, heads, which), T - 1)
    n = xp.where(which < tiles, xp.minimum(tq, last[lane0] - lane0 + 1),
                 xp.zeros_like(which))
    slot = xp.clip(tile * tq + k % tq, 0, n_tiles * tq - 1)
    return {"tiled": tiled, "slot": slot.astype(i32), "lane0": lane0,
            "n": n.astype(i32), "tiles": tiles.astype(i32)}


def _lanes_left(tiled):
    """``((order,), count)``: the lanes no tile serves, in lane order (then
    padded with the last lane), as the per-lane kernel's one more scalar
    operand, and how many they are: its grid."""
    T = tiled.shape[0]
    left = jnp.cumsum((~tiled).astype(jnp.int32))
    order = _nth(jnp, left, jnp.arange(T, dtype=jnp.int32))
    return (jnp.minimum(order, np.int32(T - 1)),), left[-1]


def _running(xp, x, largest, reverse=False):
    """Running maximum (or minimum) along ``x``, from its end if ``reverse``."""
    if xp is jnp:
        return (jax.lax.cummax if largest else jax.lax.cummin)(
            x, reverse=reverse)
    x = x[::-1] if reverse else x
    out = (np.maximum if largest else np.minimum).accumulate(x)
    return out[::-1] if reverse else out


def _nth(xp, counts, which):
    """Index of the element that brings the running count ``counts`` to
    ``which + 1`` (``len(counts)`` where none does)."""
    return xp.sum(counts[None, :] <= which[:, None], axis=1).astype(xp.int32)


def blocks_walked(positions, block_size, valid=None, plan=None, window=None):
    """Host-side count (numpy) of the K/V blocks the kernels' DMAs bring for
    a step's first ``valid`` lanes (all, by default), one layer once, and of
    the lanes among them that tiles serve: a lane of its own walks from its
    window's first block (block 0 without ``window``) to its position's; a
    tile of ``plan`` (``plan_tiles(rows, positions, tq, np)`` over ALL the
    lanes, as the device plans) walks from its first lane's first block to
    its last lane's last, once. Returns ``(blocks, tiled_lanes)``."""
    positions = np.asarray(positions, np.int64).reshape(-1)
    valid = len(positions) if valid is None else valid

    def span(pos, last):
        lo = 0 if window is None else np.maximum(pos - window + 1, 0)
        return last // block_size + 1 - lo // block_size

    walked = span(positions, positions)[:valid]
    if plan is None:
        return int(walked.sum()), 0
    tiled = plan["tiled"][:valid]
    pos0, n = positions[plan["lane0"]], plan["n"]
    tiles = np.where(n > 0, span(pos0, pos0 + n - 1), 0)
    return int(walked[~tiled].sum() + tiles.sum()), int(tiled.sum())


def _tile_span(i, lane0_ref, n_ref, pos_ref, *, queries, groups, bs, width,
               window):
    """What tile ``i`` walks: each query's own position and lowest visible
    position ([1, queries]; query c is lane c // groups of the tile, a lane
    past the tile's count repeats its last lane), and the blocks from the
    lowest first block to the highest last one."""
    top = _i32(width * bs - 1)
    pos0 = jnp.clip(pos_ref[lane0_ref[i]], _i32(0), top)
    hi = jnp.minimum(pos0 + n_ref[i] - _i32(1), top)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, queries), 1) \
        // _i32(groups)
    pos = jnp.minimum(pos0 + lane, hi)
    if window is None:
        lo, lo0 = jnp.zeros_like(pos), _i32(0)
    else:
        lo = jnp.maximum(pos - _i32(window - 1), _i32(0))
        lo0 = jnp.maximum(pos0 - _i32(window - 1), _i32(0))
    return pos, lo, lo0 // _i32(bs), hi // _i32(bs) + _i32(1)


def _tile_walk(i, tables_ref, lane0_ref, first, stop, k_hbm, v_hbm, kbuf,
               vbuf, ksem, vsem, width, reduce_block):
    """Bring each block of the tile's row from ``first`` to ``stop`` into
    VMEM once, the next in flight while ``reduce_block(j, slot)`` runs."""
    base = lane0_ref[i] * _i32(width)

    def copies(j, slot):
        blk = tables_ref[base + j]
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot],
                                      vsem.at[slot]))

    for c in copies(first, _i32(0)):
        c.start()

    def block(j, carry):
        slot = jax.lax.rem(j - first, _i32(2))

        @pl.when(j + _i32(1) < stop)
        def _():
            for c in copies(j + _i32(1), _i32(1) - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        reduce_block(j, slot)
        return carry

    jax.lax.fori_loop(first, stop, block, _i32(0))


def _tile_reduce(h, s, live, v, m_ref, l_ref, acc_ref, **dot):
    """One block's scores ``s`` [block_size, queries] of KV head ``h`` into
    the head's running softmax ([1, queries] maximum and sum, [dv, queries]
    accumulator): every query masked by its own ``live`` column; ``v``
    [block_size, dv] in the dtype the probabilities are rounded to."""
    s = jnp.where(live, s, _NEG_INF)
    m = m_ref[h]
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, **dot)
    m_ref[h] = m_new


def _tile_start(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _tile_kernel(tables_ref, lane0_ref, n_ref, pos_ref, q_ref, k_hbm, v_hbm,
                 o_ref, kbuf, vbuf, ksem, vsem, m_ref, l_ref, acc_ref, *,
                 scale, width, groups, n_kv):
    """One query tile over [blocks, block_size * kv_heads, D] pools (a
    block's rows position-major, as the pool holds them): ``q_ref`` [kv_heads,
    D, queries] holds the tile's queries a KV head (query = lane x group). A
    head's [block_size, D] slice of a block is every kv_heads-th row: a
    strided load (two heads at a time from a bfloat16 block, whose rows pair
    up in 32-bit words). Scores and values are float32 products on the
    MXU."""
    i = pl.program_id(0)
    queries = q_ref.shape[2]
    bs = kbuf.shape[1] // n_kv
    exact = dict(precision=jax.lax.Precision.HIGHEST)
    packed = kbuf.dtype == jnp.bfloat16
    pos, _lo, first, stop = _tile_span(
        i, lane0_ref, n_ref, pos_ref, queries=queries, groups=groups, bs=bs,
        width=width, window=None)
    _tile_start(m_ref, l_ref, acc_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (bs, queries), 0)

    def heads(buf, slot, c):
        """The float32 [block_size, D] slices of the heads that load ``c``
        brings: one, or the two that share 32-bit words."""
        if not packed:
            return [buf[slot, pl.ds(c, bs, stride=n_kv), :]
                    .astype(jnp.float32)]
        w = buf.at[slot].bitcast(jnp.uint32)[
            pl.ds(c, bs, stride=n_kv // 2), :]
        return [pltpu.bitcast(w << 16, jnp.float32),
                pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)]

    def reduce_block(j, slot):
        live = row + j * _i32(bs) <= pos

        def load(c, carry):
            ks, vs = heads(kbuf, slot, c), heads(vbuf, slot, c)
            for e, (kf, vf) in enumerate(zip(ks, vs)):
                h = c * _i32(len(ks)) + _i32(e)
                q = q_ref[h].astype(jnp.float32) * np.float32(scale)
                s = jax.lax.dot_general(
                    kf, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, **exact)
                _tile_reduce(h, s, live, vf, m_ref, l_ref, acc_ref, **exact)
            return carry

        jax.lax.fori_loop(_i32(0), _i32(n_kv // (2 if packed else 1)), load,
                          _i32(0))

    _tile_walk(i, tables_ref, lane0_ref, first, stop, k_hbm, v_hbm, kbuf,
               vbuf, ksem, vsem, width, reduce_block)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _tile_queries(q, plan, tq, n_kv):
    """``q`` [T, q_heads, dk] as the tiles take it: [kv_heads, n_tiles * tq *
    groups, dk], a tile's queries together, query = (lane, group); a lane
    past its tile's count is some lane, its result is dropped."""
    T, n_q, dk = q.shape
    src = plan["lane0"][:, None] + jnp.arange(tq, dtype=jnp.int32)[None]
    qt = q[jnp.minimum(src.reshape(-1), np.int32(T - 1))]
    qt = qt.reshape(src.size, n_kv, n_q // n_kv, dk)
    return jnp.swapaxes(qt, 0, 1).reshape(n_kv, -1, dk)


def _tile_results(ot, out, plan):
    """The tiles' results ``ot`` [kv_heads, n_tiles * tq * groups, dv] back
    on their lanes, beside the per-lane kernel's ``out`` [T, q_heads, dv]."""
    n_kv, _, dv = ot.shape
    n_q = out.shape[1]
    ot = jnp.swapaxes(ot.reshape(n_kv, -1, n_q // n_kv, dv), 0, 1)
    ot = ot.reshape(-1, n_q, dv)[plan["slot"]]
    return jnp.where(plan["tiled"][:, None, None], ot, out)


def tiles_apply(k_pool, n_kv):
    """Whether lanes on a shared row may form tiles over this pool: a
    bfloat16 [blocks, block_size, kv_heads, D] block's head slices are cut
    from 32-bit words that hold two heads."""
    return k_pool.ndim == 3 or k_pool.dtype != jnp.bfloat16 or n_kv % 2 == 0


def _vmem_bytes(shape, dtype):
    """A VMEM buffer's bytes, its minor dims padded to whole (8, 128) tiles."""
    return (int(np.prod(shape[:-2])) * -(-shape[-2] // 8) * 8
            * -(-shape[-1] // 128) * 128 * jnp.dtype(dtype).itemsize)


def _tile_call(kernel, name, plan, tables, positions, qt, extra, k_pool,
               v_pool, dv, dtype):
    """The tile kernels' ``pallas_call``: a grid step a tile IN USE; the
    tile's queries ``qt`` [heads, width, n_tiles * queries] and its results
    [heads, dv, ...] as blocks of one tile's queries; ``extra``: (array,
    BlockSpec) operands between them and the pools; K/V double buffers and
    the running softmax's state in VMEM, whose scoped limit is what these
    take twice over (the pipeline's second copies, the compiler's
    temporaries), the default 16 MiB at least."""
    n_tiles = plan["n"].shape[0]
    heads, wq = qt.shape[:2]
    queries = qt.shape[2] // n_tiles

    def tile_block(i, *_):
        return (np.int32(0), np.int32(0), i)

    blocks = [((heads, wq, queries), qt.dtype), ((heads, dv, queries), dtype)]
    blocks += [(spec.block_shape, a.dtype) for a, spec in extra]
    scratch = [((2,) + k_pool.shape[1:], k_pool.dtype),
               ((2,) + v_pool.shape[1:], v_pool.dtype),
               ((heads, 1, queries), jnp.float32),
               ((heads, 1, queries), jnp.float32),
               ((heads, dv, queries), jnp.float32)]
    need = 2 * sum(_vmem_bytes(*b) for b in blocks + scratch) \
        + 2 * _vmem_bytes(*scratch[-1])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(plan["tiles"],),
            in_specs=[pl.BlockSpec(blocks[0][0], tile_block)]
            + [spec for _, spec in extra]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(blocks[1][0], tile_block),
            scratch_shapes=[pltpu.VMEM(*b) for b in scratch[:2]] + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,))]
            + [pltpu.VMEM(*b) for b in scratch[2:]]),
        out_shape=jax.ShapeDtypeStruct((heads, dv, n_tiles * queries), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(need, 16 * 2 ** 20), 96 * 2 ** 20))),
        interpret=_interpret(),
        name=name,
    )(tables, plan["lane0"], plan["n"], positions, qt,
      *(a for a, _ in extra), k_pool, v_pool)


def paged_attention(q, k_pool, v_pool, block_tables, positions, scale=None,
                    rows=None):
    """q [T, q_heads, D]; k_pool / v_pool [num_blocks, block_size, kv_heads,
    D]; block_tables [T, W] int32; positions [T] int32. Lane ``t`` attends
    to positions ``0..positions[t]`` INCLUSIVE of its table row; returns
    [T, q_heads, D] in ``q.dtype``. Same meaning as
    ``models.paged_kv.paged_attention_decode``'s plain path.

    ``rows`` [T] int32 names the table row a lane sits on (equal ids: equal
    rows; the mixed step's slot ids). With it, lanes on one row at
    consecutive positions are served as query tiles (``plan_tiles``), the
    others by the per-lane walk; without it every lane walks its own row."""
    T, n_q, D = q.shape
    _, bs, n_kv, _ = k_pool.shape
    groups = n_q // n_kv
    width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    chunk = _CHUNK if bs % _CHUNK == 0 else bs
    tables = block_tables.astype(jnp.int32).reshape(-1)
    positions = positions.astype(jnp.int32)
    # q head h * groups + g sits on row h of group g: a group's rows line
    # up with the kv heads on K's sublanes
    qg = jnp.swapaxes(q.reshape(T, n_kv, groups, D), 1, 2)
    tq = tile_lanes(groups, False)
    plan, left = None, ((), T)
    if rows is not None and tiles_apply(k_pool, n_kv):
        plan = plan_tiles(rows, positions, tq)
        left = _lanes_left(plan["tiled"])
    order, count = left

    def lane_block(t, tables, pos, *order):
        lane = order[0][t] if order else t
        return (lane, np.int32(0), np.int32(0), np.int32(0))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), width=width,
                          chunk=chunk, ordered=plan is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(order),
            grid=(count,),
            in_specs=[
                pl.BlockSpec((1, groups, n_kv, D), lane_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, groups, n_kv, D), lane_block),
            scratch_shapes=[
                pltpu.VMEM((2, bs, n_kv, D), k_pool.dtype),
                pltpu.VMEM((2, bs, n_kv, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, groups, n_kv, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="paged_attention",
    )(tables, positions, *order, qg, k_pool, v_pool)
    out = jnp.swapaxes(out, 1, 2).reshape(T, n_q, D)
    if plan is None:
        return out

    ot = _tile_call(
        functools.partial(_tile_kernel, scale=float(scale), width=width,
                          groups=groups, n_kv=n_kv),
        "paged_attention_tile", plan, tables, positions,
        jnp.swapaxes(_tile_queries(q, plan, tq, n_kv), 1, 2), [],
        k_pool.reshape(-1, bs * n_kv, D), v_pool.reshape(-1, bs * n_kv, D),
        D, q.dtype)
    return _tile_results(jnp.swapaxes(ot, 1, 2), out, plan)


# --------------------------------------------------------------------------- #
# grouped queries on the MXU, over flat pools
# --------------------------------------------------------------------------- #
def _gqa_kernel(tables_ref, pos_ref, lo_ref, *rest, scale, width, groups,
                has_sink, ordered=False):
    """One lane of ``paged_attention_gqa``: walk its table row from the block
    that holds ``lo`` to the one that holds ``pos``, double buffered as in
    ``_kernel`` (the next block, this lane's or the next walked lane's first,
    is in flight while the current one is reduced).

    ``q_ref`` holds the lane's query heads BLOCK-DIAGONALLY: row r = (kv
    head h, group g) is zero outside the lanes [h * dk, (h + 1) * dk) of
    the merged K row, so ONE [n_q, kv * dk] x [kv * dk, block_size]
    product gives every head's scores against its own KV head; the values
    product is taken against the whole merged V row and each head's own
    [groups, dv] block is cut out of the accumulator at the end. The MXU
    multiplies kv times more than it needs to, which is cheap beside the
    block's DMA; nothing is sliced at a lane offset that is not a multiple
    of 128."""
    rest, t, lane_at = _lane_order(rest, ordered)
    q_ref, rest = rest[0], rest[1:]
    if has_sink:
        sink_ref, rest = rest[0], rest[1:]
    k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem, slot_ref, acc_ref = rest
    g = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    bs = kbuf.shape[1]
    n_q = q_ref.shape[1]
    n_kv = n_q // groups
    dv = vbuf.shape[2] // n_kv
    top = _i32(width * bs - 1)

    def span(lane):
        pos = jnp.clip(pos_ref[lane], _i32(0), top)
        lo = jnp.clip(lo_ref[lane], _i32(0), pos)
        return pos, lo, lo // _i32(bs), pos // _i32(bs) + _i32(1)

    pos, lo, first, stop = span(t)

    def copies(lane, j, slot):
        blk = tables_ref[lane * _i32(width) + j]
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot],
                                      vsem.at[slot]))

    def start(lane, j, slot):
        for c in copies(lane, j, slot):
            c.start()

    @pl.when(g == 0)
    def _():
        slot_ref[0] = _i32(0)
        start(t, first, _i32(0))

    slot0 = slot_ref[0]
    q = q_ref[0]                                          # (n_q, kv * dk)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_q, bs), 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(j, carry):
        m, l = carry                                      # (n_q, 1) each
        i = j - first
        slot = jax.lax.rem(slot0 + i, _i32(2))
        other = _i32(1) - slot
        last = j + _i32(1) == stop

        @pl.when(jnp.logical_not(last))
        def _():
            start(t, j + _i32(1), other)

        @pl.when(jnp.logical_and(last, g + _i32(1) < n_lanes))
        def _():
            nxt = lane_at(g + _i32(1))
            start(nxt, span(nxt)[2], other)

        for c in copies(t, j, slot):
            c.wait()
        s = jax.lax.dot_general(
            q, kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        at = col + j * _i32(bs)
        s = jnp.where(jnp.logical_and(at >= lo, at <= pos), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new

    init = (jnp.full((n_q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((n_q, 1), jnp.float32))
    m, l = jax.lax.fori_loop(first, stop, block, init)
    slot_ref[0] = jax.lax.rem(slot0 + stop - first, _i32(2))
    if has_sink:
        # one more logit a head in the denominator, with no value row
        b = sink_ref[...][:, :1]
        m_new = jnp.maximum(m, b)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.exp(b - m_new)
    else:
        alpha = None
    for h in range(n_kv):
        rows = slice(h * groups, (h + 1) * groups)
        out = acc_ref[rows, h * dv:(h + 1) * dv]
        if alpha is not None:
            out = out * alpha[rows]
        o_ref[0, rows, :] = (out / l[rows]).astype(o_ref.dtype)


def _gqa_tile_kernel(tables_ref, lane0_ref, n_ref, pos_ref, q_ref, *rest,
                     scale, width, groups, window, k_at, v_at, has_sink):
    """One query tile of ``paged_attention_gqa``. ``q_ref`` [kv_heads, wq,
    queries] holds the tile's queries a KV head (query = lane x group), each
    head's padded with zeros to the 128-aligned stretch ``k_at[h] .. k_at[h]
    + wq`` of the merged K row that holds the head's own lanes: a product a
    head, [block_size, wq] x [wq, queries], multiplies no other head's lanes
    but those zeros. The values product is taken against the aligned stretch
    ``v_at[h] .. + wv`` of the merged V row; the head's own rows are cut
    from the result outside."""
    if has_sink:
        sink_ref, rest = rest[0], rest[1:]
    k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)
    n_kv, wq, queries = q_ref.shape
    wv = o_ref.shape[1]
    bs = kbuf.shape[1]
    pos, lo, first, stop = _tile_span(
        i, lane0_ref, n_ref, pos_ref, queries=queries, groups=groups, bs=bs,
        width=width, window=window)
    _tile_start(m_ref, l_ref, acc_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (bs, queries), 0)

    def reduce_block(j, slot):
        at = row + j * _i32(bs)
        live = jnp.logical_and(at >= lo, at <= pos)
        for h in range(n_kv):
            s = jax.lax.dot_general(
                kbuf[slot, :, k_at[h]:k_at[h] + wq], q_ref[h],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * np.float32(scale)
            _tile_reduce(h, s, live, vbuf[slot, :, v_at[h]:v_at[h] + wv],
                         m_ref, l_ref, acc_ref)

    _tile_walk(i, tables_ref, lane0_ref, first, stop, k_hbm, v_hbm, kbuf,
               vbuf, ksem, vsem, width, reduce_block)
    for h in range(n_kv):
        l, out = l_ref[h], acc_ref[h]
        if has_sink:
            # one more logit a head in the denominator, with no value row
            b, m = sink_ref[h][:1], m_ref[h]
            m_new = jnp.maximum(m, b)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.exp(b - m_new)
            out = out * alpha
        o_ref[h] = (out / l).astype(o_ref.dtype)


def _aligned(n_kv, dim):
    """Where each head's ``dim`` lanes of a merged row lie among 128-lane
    stretches: ``(starts, width, offsets)``: head h's lanes are ``offsets[h]
    .. + dim`` of the stretch ``starts[h] .. + width``, every start a
    multiple of 128, one width for all heads. (A merged row that is no
    whole number of 128s, which only interpret mode takes: the head's own
    lanes.)"""
    if n_kv * dim % 128:
        return [h * dim for h in range(n_kv)], dim, [0] * n_kv
    lo = [h * dim // 128 * 128 for h in range(n_kv)]
    width = max(-(-(h * dim + dim) // 128) * 128 - lo[h]
                for h in range(n_kv))
    lo = [min(a, n_kv * dim - width) for a in lo]
    return lo, width, [h * dim - a for h, a in enumerate(lo)]


def paged_attention_gqa(q, k_pool, v_pool, block_tables, positions, scale=None,
                        window=None, sink=None, rows=None):
    """Grouped-query paged attention over FLAT pools, scores and values on
    the MXU. q [T, q_heads, dk]; k_pool [num_blocks, block_size, kv_heads *
    dk], v_pool [num_blocks, block_size, kv_heads * dv] (dv may differ from
    dk); block_tables [T, W] int32; positions [T] int32. Lane ``t`` attends
    to positions ``max(0, positions[t] - window + 1) .. positions[t]`` of
    its table row (from 0 without ``window``) and reads only the blocks
    that hold them; ``sink`` [q_heads] adds ``exp(sink_h)`` to head h's
    softmax denominator. Returns [T, q_heads, dv] in ``q.dtype``. Same
    meaning as ``models.paged_kv.paged_attention_decode_plain``; the
    probabilities are rounded to the pool's dtype for the values product (a
    float32 pool loses nothing). ``rows`` as in ``paged_attention``: lanes
    on one row at consecutive positions form query tiles."""
    T, n_q, dk = q.shape
    _, bs, kdk = k_pool.shape
    n_kv = kdk // dk
    dv = v_pool.shape[2] // n_kv
    groups = n_q // n_kv
    width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(dk)
    tables = block_tables.astype(jnp.int32).reshape(-1)
    positions = positions.astype(jnp.int32)
    lo = jnp.zeros_like(positions) if window is None \
        else jnp.maximum(positions - np.int32(window - 1), np.int32(0))
    # q head (h, g) on row h * groups + g, zero outside KV head h's lanes
    # (pads and a concatenation: a copy at the HBM's speed, where a product
    # with an identity ran as a convolution at a third of it)
    qd = jnp.concatenate([
        jnp.pad(q[:, h * groups:(h + 1) * groups],
                ((0, 0), (0, 0), (h * dk, kdk - (h + 1) * dk)))
        for h in range(n_kv)], axis=1).astype(k_pool.dtype)
    tq = tile_lanes(groups, True)
    plan, left = None, ((), T)
    if rows is not None:
        plan = plan_tiles(rows, positions, tq)
        left = _lanes_left(plan["tiled"])
    order, count = left

    def lane_block(t, tables, pos, lo, *order):
        return (order[0][t] if order else t, np.int32(0), np.int32(0))

    def whole(t, *_):
        return (np.int32(0), np.int32(0))

    in_specs = [pl.BlockSpec((1, n_q, kdk), lane_block)]
    args = [qd]
    if sink is not None:
        in_specs.append(pl.BlockSpec((n_q, 128), whole))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (n_q, 128)))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, scale=float(scale), width=width,
                          groups=groups, has_sink=sink is not None,
                          ordered=plan is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(order),
            grid=(count,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_q, dv), lane_block),
            scratch_shapes=[
                pltpu.VMEM((2, bs, kdk), k_pool.dtype),
                pltpu.VMEM((2, bs, n_kv * dv), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((n_q, n_kv * dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, n_q, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="paged_attention_gqa",
    )(tables, positions, lo, *order, *args, k_pool, v_pool)
    if plan is None:
        return out

    queries = tq * groups
    k_at, wq, k_off = _aligned(n_kv, dk)
    v_at, wv, v_off = _aligned(n_kv, dv)
    # the tiles' queries a KV head, in the pool's dtype, each head's lanes
    # where its stretch of the merged K row has them
    qt = _tile_queries(q, plan, tq, n_kv)
    qt = jnp.stack([jnp.pad(qt[h], ((0, 0), (k_off[h], wq - dk - k_off[h])))
                    for h in range(n_kv)]).astype(k_pool.dtype)
    extra = []
    if sink is not None:
        extra.append((jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(n_kv, 1, 1, groups),
            (n_kv, 8, tq, groups)).reshape(n_kv, 8, queries), pl.BlockSpec(
                (n_kv, 8, queries),
                lambda i, *_: (np.int32(0), np.int32(0), np.int32(0)))))
    ot = _tile_call(
        functools.partial(_gqa_tile_kernel, scale=float(scale), width=width,
                          groups=groups, window=window, k_at=tuple(k_at),
                          v_at=tuple(v_at), has_sink=sink is not None),
        "paged_attention_gqa_tile", plan, tables, positions,
        jnp.swapaxes(qt, 1, 2), extra, k_pool, v_pool, wv, q.dtype)
    ot = jnp.stack([ot[h, v_off[h]:v_off[h] + dv] for h in range(n_kv)])
    return _tile_results(jnp.swapaxes(ot, 1, 2), out, plan)
