"""Paged attention for serving decode as a Pallas TPU kernel.

Reference analog: fluid/operators/fused/block_multi_head_attention_op.cu (the
reference's CUDA page-table kernel behind block_multihead_attention).
TPU-first redesign: one query token per LANE; the K/V pools stay in HBM in
the repo's own [num_blocks, block_size, kv_heads, head_dim] layout and the
kernel walks each lane's block-table row itself — block by block, double
buffered, for ``positions[lane] // block_size + 1`` blocks and no further —
so what is read is what the lane's length needs, once, in the pool's dtype.
Tables and positions are scalar-prefetched DATA: one compile serves every
mix of lengths.

The arithmetic is the plain path's (models/paged_kv.py): K and V are promoted
to float32 on chip, scores, probabilities and the accumulator are float32
(online softmax over the blocks), the result is cast once at the end. With
one query row per (lane, kv head) there is no matmul to feed the MXU (a
``groups``-row product per head): scores are a multiply + lane reduce on the
VPU, and they stay replicated along the lanes so that the values product
needs no relayout. That is ``paged_attention`` (pools [num_blocks,
block_size, kv_heads, head_dim], head dim a multiple of 128).

``paged_attention_gqa`` is the kernel for grouped queries (16 query heads a KV
head would be 16 passes of the VPU's multiply + reduce over one block): flat
pools [num_blocks, block_size, kv_heads * dim], K rows wider than V rows
(192 beside 128), scores and values on the MXU, a per-lane FIRST block (a
sliding-window lane starts at ``(position - window + 1) // block_size``) and
an optional sink logit a head in the softmax's denominator.
"""
from __future__ import annotations

import functools

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


def _kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, ksem, vsem, slot_ref, *, scale, width, chunk):
    """One lane: walk its row of ``tables_ref`` (flattened [T * width]) up
    to its position. The block for the NEXT iteration — this lane's next
    block, or the next lane's first — is always in flight while the current
    one is reduced; ``slot_ref`` carries the buffer parity across lanes."""
    t = pl.program_id(0)
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

    @pl.when(t == 0)
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

        @pl.when(jnp.logical_and(last, t + _i32(1) < n_lanes))
        def _():
            start(t + _i32(1), _i32(0), other)

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
            for g in range(groups):
                s = jnp.sum(kf * q[g][None], axis=-1, keepdims=True)
                s = jnp.where(live, jnp.broadcast_to(s, kf.shape), _NEG_INF)
                m_new = jnp.maximum(m[g], jnp.max(s, axis=0))
                alpha = jnp.exp(m[g] - m_new)
                p = jnp.exp(s - m_new[None])
                m_n.append(m_new)
                l_n.append(l[g] * alpha + jnp.sum(p, axis=0))
                acc_n.append(acc[g] * alpha + jnp.sum(p * vf, axis=0))
            return tuple(m_n), tuple(l_n), tuple(acc_n)

        return jax.lax.fori_loop(_i32(0), n_chunks, rows, carry)

    init = (tuple(jnp.full((n_kv, d), _NEG_INF, jnp.float32)
                  for _ in range(groups)),
            tuple(jnp.zeros((n_kv, d), jnp.float32) for _ in range(groups)),
            tuple(jnp.zeros((n_kv, d), jnp.float32) for _ in range(groups)))
    _, l, acc = jax.lax.fori_loop(_i32(0), n_blocks, block, init)
    slot_ref[0] = jax.lax.rem(slot0 + n_blocks, _i32(2))
    for g in range(groups):
        o_ref[0, g] = (acc[g] / l[g]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, positions, scale=None):
    """q [T, q_heads, D]; k_pool / v_pool [num_blocks, block_size, kv_heads,
    D]; block_tables [T, W] int32; positions [T] int32. Lane ``t`` attends
    to positions ``0..positions[t]`` INCLUSIVE of its table row; returns
    [T, q_heads, D] in ``q.dtype``. Same meaning as
    ``models.paged_kv.paged_attention_decode``'s plain path."""
    T, n_q, D = q.shape
    _, bs, n_kv, _ = k_pool.shape
    groups = n_q // n_kv
    width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    chunk = _CHUNK if bs % _CHUNK == 0 else bs
    # q head h * groups + g sits on row h of group g: a group's rows line
    # up with the kv heads on K's sublanes
    qg = jnp.swapaxes(q.reshape(T, n_kv, groups, D), 1, 2)

    def lane_block(t, tables, pos):
        return (t, np.int32(0), np.int32(0), np.int32(0))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), width=width,
                          chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
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
    )(block_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), qg, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2).reshape(T, n_q, D)


# --------------------------------------------------------------------------- #
# grouped queries on the MXU, over flat pools
# --------------------------------------------------------------------------- #
def _gqa_kernel(tables_ref, pos_ref, lo_ref, q_ref, *rest, scale, width,
                groups, has_sink):
    """One lane of ``paged_attention_gqa``: walk its table row from the block
    that holds ``lo`` to the one that holds ``pos``, double buffered as in
    ``_kernel`` (the next block, this lane's or the next lane's first, is in
    flight while the current one is reduced).

    ``q_ref`` holds the lane's query heads BLOCK-DIAGONALLY: row r = (kv
    head h, group g) is zero outside the lanes [h * dk, (h + 1) * dk) of
    the merged K row, so ONE [n_q, kv * dk] x [kv * dk, block_size]
    product gives every head's scores against its own KV head; the values
    product is taken against the whole merged V row and each head's own
    [groups, dv] block is cut out of the accumulator at the end. The MXU
    multiplies kv times more than it needs to, which is cheap beside the
    block's DMA; nothing is sliced at a lane offset that is not a multiple
    of 128."""
    if has_sink:
        sink_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem, slot_ref, \
            acc_ref = rest
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem, slot_ref, acc_ref = rest
    t = pl.program_id(0)
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

    @pl.when(t == 0)
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

        @pl.when(jnp.logical_and(last, t + _i32(1) < n_lanes))
        def _():
            start(t + _i32(1), span(t + _i32(1))[2], other)

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


def paged_attention_gqa(q, k_pool, v_pool, block_tables, positions, scale=None,
                        window=None, sink=None):
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
    float32 pool loses nothing)."""
    T, n_q, dk = q.shape
    _, bs, kdk = k_pool.shape
    n_kv = kdk // dk
    dv = v_pool.shape[2] // n_kv
    groups = n_q // n_kv
    width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(dk)
    positions = positions.astype(jnp.int32)
    lo = jnp.zeros_like(positions) if window is None \
        else jnp.maximum(positions - np.int32(window - 1), np.int32(0))
    # q head (h, g) on row h * groups + g, zero outside KV head h's lanes
    eye = jnp.eye(n_kv, dtype=q.dtype)
    qd = (q.reshape(T, n_kv, groups, 1, dk)
          * eye[None, :, None, :, None]).reshape(T, n_q, kdk)
    qd = qd.astype(k_pool.dtype)

    def lane_block(t, tables, pos, lo):
        return (t, np.int32(0), np.int32(0))

    def whole(t, tables, pos, lo):
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
                          groups=groups, has_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T,),
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
    )(block_tables.astype(jnp.int32).reshape(-1), positions, lo, *args,
      k_pool, v_pool)
    return out
