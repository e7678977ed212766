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
needs no relayout.
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
