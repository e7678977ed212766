"""A grouped matmul for the experts a serving chip holds, as Pallas TPU kernels.

Reference analog: the grouped GEMM behind the reference's fused MoE
(fluid/operators/fused/fused_moe: one GEMM a routed expert over the rows
sorted to it). TPU-first redesign: at serving sizes an expert gets a handful
of rows (~10 of a 320-lane mixed step, ~2 of a burst), so the product is
memory-bound by far and the kernel is a WEIGHT STREAMER: what it has to do is
bring each expert that got a row through VMEM once, at HBM speed, and no
expert that got none.

**Row tiles.** The rows are the (token, expert) pairs sorted by expert, and
each expert's group is PADDED to whole row tiles (``plan_row_tiles``: a tile
never holds two experts' rows, so no expert is streamed twice because its
rows straddle a boundary). A row tile is the dtype's sublane tile (16 rows of
bfloat16, 8 of float32). A tile's expert, its first sorted row and how many
rows it serves, and each sorted row's TOKEN, are scalar-prefetched DATA
(``expert``, ``row0``, ``n`` [tiles at most], ``token`` [sorted rows]) that
the index maps and the kernels read, and the grid's tile dimension is the
number of tiles IN USE, a dynamic bound: an expert without a row and the
rows behind the last group cost neither a DMA nor a product, and one compile
serves every routing.

**Rows in and out by token.** Nothing outside the kernels holds the padded
layout. ``gmm_up`` holds the activations ``h`` [T, hidden] as ONE float32
VMEM block (its index never changes, so the pipeline brings it once a call)
and copies row ``r`` of tile ``i`` out of it at the sublane of token
``token[row0[i] + r]`` (float32: Mosaic reads one row at a dynamic offset
only from an unpacked dtype, and no DMA moves a single row of a tiled HBM
array). ``gmm_down`` adds ``weight[row0[i] + r] * (x @ w2[e])[r]``, from the
float32 accumulator with the row's routing weight (in SMEM), into the row of
its token in a float32 output panel [T, tn] that stays in VMEM across the
tile dimension: zeroed at its first tile, written back once. A padding row
(``r >= n[i]``) is neither read nor added, so what it holds reaches no
output; the activations between the two calls ([rows padded, width], a tile
at rows ``i * tm``) are written and read only in tiles in use.

**Weight panels.** A grid step is one (output panel, row tile), panels
outermost: ``x`` [tm, K] against ``w[expert]`` [K, tn], the WHOLE contraction
in one block, float32 accumulation, the panel written in the activations'
dtype. So an expert's neighbouring row tiles name the SAME weight block and
the pipeline brings it once: an expert with 40 rows streams its matrices once
like one with 4 (contraction slabs [tk, N], contiguous in HBM, are a twentieth
faster while every expert fits one tile and re-stream the expert for every
further tile; measured, PERF.md PR 35). ``held_experts_gmm_up`` streams the
gate and the up matrix side by side, reads the rows once and applies
``silu(a) * b`` to the two float32 products in VMEM (the unfused path rounds
both to the activations' dtype first); ``held_experts_gmm_down`` is the plain
product. Operands bfloat16 or float32, never anything lower; float32 operands
multiply at the highest precision.

**VMEM** (``_vmem_need``): the pipeline double-buffers every block. ``up``
holds 2 x (h [T, K] float32 + a panel [K, tn] a streamed matrix + out [tm,
tn]) and the tile's rows [tm, K] float32 once; ``down`` 2 x (x [tm, K] + a
panel + out [T, tn] float32). A panel is as wide as ``_PANEL_BYTES`` (8 MiB)
allow, 128 lanes at least: at the MiMo-V2-Flash cell's shapes (hidden 4096,
width 2048, bfloat16, T = 320 lanes) 2 x (5 MiB + 2 x 8 MiB + 32 KiB) + 256
KiB = 42.3 MiB for ``up`` (tn 1024) and 2 x (64 KiB + 8 MiB + 2.5 MiB) =
21.1 MiB for ``down`` (tn 2048).
``vmem_limit_bytes`` is asked for explicitly: the need and 16 MiB more for
the compiler's temporaries (the float32 products of a panel); ``fits`` is
the dispatch rule's bound on it, read from T as well as the widths.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret
from .paged_attention import _vmem_bytes

__all__ = ["row_tile", "plan_row_tiles", "padded_rows", "fits", "gmm_up",
           "gmm_down"]

# bytes of ONE streamed weight block [K, tn], at most. Measured on the v5e at
# the cell's shapes, up + down a layer (PERF.md, PR 35): panels of 1 MiB take
# 1.20 ms, of 2 MiB 1.29, of 4 MiB 1.21, of 8 MiB 1.13 (fewer grid steps,
# longer contiguous runs; the first block of a call is never hidden, so
# whole 16 MiB matrices would lose again)
_PANEL_BYTES = 8 * 2 ** 20
_VMEM_SPARE = 16 * 2 ** 20


def row_tile(dtype):
    """Rows of a row tile: the dtype's sublane tile."""
    return 32 // jnp.dtype(dtype).itemsize


def padded_rows(rows, experts, tm):
    """Rows the padded layout has to hold at most, a whole number of tiles:
    ``rows`` sorted rows spread over ``experts`` groups leave at most
    ``tm - 1`` rows of padding a group."""
    return (rows + experts * (tm - 1)) // tm * tm


def plan_row_tiles(sizes, tm, rows):
    """Where each expert's rows lie once every group is padded to whole
    tiles of ``tm`` rows. ``sizes`` [E] int32: rows sorted to expert ``e``
    (the groups lie one behind the other from sorted row 0); ``rows``: how
    many sorted rows there can be at most. Returns a dict of int32 arrays
    over the ``padded_rows(rows, E, tm) // tm`` tiles there can be:
    ``expert`` (0 past the tiles in use: some expert, never read), ``row0``
    (the first sorted row a tile serves) and ``n`` (how many it serves, 0
    past the tiles in use), and the scalar ``tiles`` in use. With ``tm`` 1
    the layout is the sorted rows themselves. (A tile's expert's numbers are
    picked by comparison, not by indexing: XLA runs a gather of a few hundred
    scalars as a loop on the TPU.)"""
    E = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    per = (sizes + np.int32(tm - 1)) // np.int32(tm)
    ends = jnp.cumsum(per)
    tile = jnp.arange(padded_rows(rows, E, tm) // tm, dtype=jnp.int32)
    expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile[:, None], axis=1), E - 1
    ).astype(jnp.int32)
    used = tile < ends[-1]
    expert = jnp.where(used, expert, 0)
    mine = expert[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]

    def pick(v):                        # v[expert], a tile each
        return jnp.sum(jnp.where(mine, v[None, :], 0), axis=1)

    k = (tile - pick(ends - per)) * np.int32(tm)       # rows before, in group
    row0 = pick(jnp.cumsum(sizes) - sizes) + k
    n = jnp.where(used, jnp.clip(pick(sizes) - k, 0, tm), 0)
    return {"expert": expert, "row0": row0.astype(jnp.int32),
            "n": n.astype(jnp.int32), "tiles": ends[-1].astype(jnp.int32)}


def _panel(K, N, dtype):
    """Lanes of a weight panel [K, tn]: the widest multiple of 128 that
    divides ``N`` and keeps the panel within ``_PANEL_BYTES``; 128 where none
    does."""
    most = _PANEL_BYTES // (K * jnp.dtype(dtype).itemsize)
    return max((t for t in range(128, N + 1, 128)
                if N % t == 0 and t <= most), default=128)


def _vmem_need(blocks, once=()):
    """Bytes of VMEM a call holds: every block ``(shape, dtype)`` twice (the
    pipeline's double buffers), every scratch buffer in ``once`` once."""
    return (2 * sum(_vmem_bytes(s, d) for s, d in blocks)
            + sum(_vmem_bytes(s, d) for s, d in once))


def _up_need(T, tm, K, tn, dtype):
    return _vmem_need([((T, K), jnp.float32), ((K, tn), dtype),
                       ((K, tn), dtype), ((tm, tn), dtype)],
                      [((tm, K), jnp.float32)])


def _down_need(T, tm, K, tn, dtype):
    return _vmem_need([((tm, K), dtype), ((K, tn), dtype),
                       ((T, tn), jnp.float32)])


def fits(T, hidden, width, dtype):
    """Whether both calls' blocks at their narrowest panels, ``T`` rows of
    activations held in ``up`` and of output in ``down``, and the spare, stay
    inside the 96 MiB a call may ask for."""
    tm = row_tile(dtype)
    return max(_up_need(T, tm, hidden, 128, dtype),
               _down_need(T, tm, width, 128, dtype)
               ) + _VMEM_SPARE <= 96 * 2 ** 20


def _dot(x, w):
    """The product of a tile, accumulated in float32; float32 operands at
    the highest precision."""
    exact = ({"precision": jax.lax.Precision.HIGHEST}
             if x.dtype == jnp.float32 else {})
    return jnp.dot(x, w, preferred_element_type=jnp.float32, **exact)


def _real_rows(row0_ref, n_ref, token_ref, tm, body):
    """``body(r, row, token)`` for each real row ``r`` of this grid step's
    tile, ``row`` its sorted row."""
    i = pl.program_id(1)
    for r in range(tm):
        @pl.when(np.int32(r) < n_ref[i])
        def _():
            row = row0_ref[i] + np.int32(r)
            body(r, row, token_ref[row])


def _up_kernel(expert_ref, row0_ref, n_ref, token_ref, h_ref, w1_ref, w3_ref,
               o_ref, x_ref):
    """One (output panel, row tile): the tile's real rows copied from ``h``
    at their tokens, then ``silu(x @ w1) * (x @ w3)`` over the whole
    contraction. Padding rows keep what the scratch held: their products are
    never added."""
    del expert_ref                      # read by the weights' index maps

    def gather(r, row, t):
        x_ref[r:r + 1, :] = h_ref[pl.ds(t, 1), :]

    _real_rows(row0_ref, n_ref, token_ref, x_ref.shape[0], gather)
    x = x_ref[...].astype(w1_ref.dtype)
    o_ref[...] = (jax.nn.silu(_dot(x, w1_ref[...]))
                  * _dot(x, w3_ref[...])).astype(o_ref.dtype)


def _down_kernel(expert_ref, row0_ref, n_ref, token_ref, x_ref, w_ref,
                 weight_ref, o_ref):
    """One (output panel, row tile): ``weight * (x @ w2)`` of each real row
    added into its token's row of the resident float32 output panel."""
    del expert_ref

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    y = _dot(x_ref[...], w_ref[...])

    def combine(r, row, t):
        o_ref[pl.ds(t, 1), :] += y[r:r + 1] * weight_ref[row]

    _real_rows(row0_ref, n_ref, token_ref, x_ref.shape[0], combine)


def _call(name, kernel, plan, token, operands, in_specs, out_shape,
          out_spec, tn, need, scratch=()):
    """A grouped product's ``pallas_call``: a grid step an (output panel of
    ``tn`` lanes, row tile IN USE), panels outermost; ``expert``, ``row0``
    and ``n`` of ``plan`` and the sorted rows' ``token`` scalar-prefetched."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # a step without a pair on a held expert still runs one tile (of
            # expert 0, no real row): a grid is never empty
            grid=(out_shape.shape[1] // tn, jnp.maximum(plan["tiles"], 1)),
            in_specs=in_specs, out_specs=out_spec,
            scratch_shapes=list(scratch)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need + _VMEM_SPARE),
        interpret=_interpret(),
        name=name,
    )(plan["expert"], plan["row0"], plan["n"], token.astype(jnp.int32),
      *operands)


def _weights_spec(K, tn):
    zero = np.int32(0)
    return pl.BlockSpec((None, K, tn),
                        lambda n, i, expert, *_: (expert[i], zero, n))


def gmm_up(h, w1, w3, plan, token):
    """``silu(x @ w1[e]) * (x @ w3[e])`` a row tile, ``e`` the tile's expert
    and ``x`` its rows: real row ``r`` of tile ``i`` is ``h[token[row0[i] +
    r]]``. ``h`` [T, hidden]; ``w1`` / ``w3`` [E, hidden, width]; ``plan``
    the row tiles of ``plan_row_tiles`` at ``tm = row_tile(h.dtype)``;
    ``token`` [sorted rows] int32, the token of each sorted row. Float32
    accumulation and SwiGLU; the result
    [rows padded, width] in ``h.dtype``, tile ``i`` at rows ``i * tm``,
    written in the tiles in use and meaningful in their real rows only."""
    T, K = h.shape
    N = w1.shape[2]
    tiles = plan["expert"].shape[0]
    tm = row_tile(h.dtype)
    tn = _panel(K, N, h.dtype)
    zero = np.int32(0)
    return _call(
        "held_experts_gmm_up", _up_kernel, plan, token,
        (h.astype(jnp.float32), w1, w3),
        [pl.BlockSpec((T, K), lambda n, i, *_: (zero, zero)),
         _weights_spec(K, tn), _weights_spec(K, tn)],
        jax.ShapeDtypeStruct((tiles * tm, N), h.dtype),
        pl.BlockSpec((tm, tn), lambda n, i, *_: (i, n)), tn,
        _up_need(T, tm, K, tn, h.dtype),
        [pltpu.VMEM((tm, K), jnp.float32)])


def gmm_down(x, w2, plan, token, weight, T):
    """The weighted sum, a token, of its rows' products with ``w2``: ``x``
    [rows padded, width] as ``gmm_up`` leaves it, ``w2`` [E, width, hidden],
    ``token`` / ``weight`` [sorted rows] each sorted row's token and routing
    weight (float32). Real row ``r`` of tile ``i``, sorted row ``row =
    row0[i] + r``, adds ``weight[row] * (x[i * tm + r] @ w2[e])``, from the
    float32 accumulator, into row ``token[row]`` of the result [T, hidden]
    float32; a token that no real row names stays zero."""
    K = x.shape[1]
    N = w2.shape[2]
    tm = row_tile(x.dtype)
    tn = _panel(K, N, x.dtype)
    zero = np.int32(0)
    return _call(
        "held_experts_gmm_down", _down_kernel, plan, token,
        (x, w2, weight.astype(jnp.float32)),
        [pl.BlockSpec((tm, K), lambda n, i, *_: (i, zero)),
         _weights_spec(K, tn),
         pl.BlockSpec(weight.shape, lambda n, i, *_: (zero,),
                      memory_space=pltpu.SMEM)],
        jax.ShapeDtypeStruct((T, N), jnp.float32),
        pl.BlockSpec((T, tn), lambda n, i, *_: (zero, n)), tn,
        _down_need(T, tm, K, tn, x.dtype))
