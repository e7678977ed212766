"""A grouped matmul for the experts a serving chip holds, as Pallas TPU kernels.

Reference analog: the grouped GEMM behind the reference's fused MoE
(fluid/operators/fused/fused_moe: one GEMM a routed expert over the rows
sorted to it). TPU-first redesign: at serving sizes an expert gets a handful
of rows (~10 of a 320-lane mixed step, ~2 of a burst), so the product is
memory-bound by far and the kernel is a WEIGHT STREAMER: what it has to do is
bring each expert that got a row through VMEM once, at HBM speed, and no
expert that got none.

**Row tiles.** The rows arrive sorted by expert and PADDED per expert to whole
row tiles (``plan_row_tiles``; the caller gathers them so: a tile never holds
two experts' rows, so no store is masked and no expert is streamed twice
because its rows straddle a boundary). A row tile is the dtype's sublane tile
(16 rows of bfloat16, 8 of float32). Which expert a tile belongs to is
scalar-prefetched DATA (``expert`` [tiles at most]) that the weights' index
map reads, and the grid's tile dimension is the number of tiles IN USE, a
dynamic bound: an expert without a row and the rows behind the last group
cost neither a DMA nor a product, and one compile serves every routing.

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

**VMEM** (``_vmem_need``): the pipeline double-buffers every block, so a call
holds 2 x (x [tm, K] + a panel [K, tn] a streamed matrix + out [tm, tn]). A
panel is as wide as ``_PANEL_BYTES`` (8 MiB) allow, 128 lanes at least: at
the MiMo-V2-Flash cell's shapes (hidden 4096, width 2048, bfloat16) 2 x (128
KiB + 2 x 8 MiB + 32 KiB) = 32.3 MiB for ``up`` (tn 1024) and 2 x (64 KiB + 8
MiB + 64 KiB) = 16.3 MiB for ``down`` (tn 2048). ``vmem_limit_bytes`` is
asked for explicitly: the need and 16 MiB more for the compiler's temporaries
(the float32 products of a panel); ``fits`` is the dispatch rule's bound on
it.
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
    the layout is the sorted rows themselves."""
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
    k = (tile - (ends - per)[expert]) * np.int32(tm)   # rows before, in group
    row0 = (jnp.cumsum(sizes) - sizes)[expert] + k
    n = jnp.where(used, jnp.clip(sizes[expert] - k, 0, tm), 0)
    return {"expert": expert, "row0": row0.astype(jnp.int32),
            "n": n.astype(jnp.int32), "tiles": ends[-1].astype(jnp.int32)}


def _panel(K, N, dtype):
    """Lanes of a weight panel [K, tn]: the widest multiple of 128 that
    divides ``N`` and keeps the panel within ``_PANEL_BYTES``; 128 where none
    does."""
    most = _PANEL_BYTES // (K * jnp.dtype(dtype).itemsize)
    return max((t for t in range(128, N + 1, 128)
                if N % t == 0 and t <= most), default=128)


def _vmem_need(tm, K, tn, dtype, streams):
    """Bytes of VMEM a call holds: every block twice (the pipeline's double
    buffers)."""
    blocks = [(tm, K)] + [(K, tn)] * streams + [(tm, tn)]
    return 2 * sum(_vmem_bytes(b, dtype) for b in blocks)


def fits(hidden, width, dtype):
    """Whether both calls' blocks at their narrowest panels, and the spare,
    stay inside the 96 MiB a call may ask for."""
    tm = row_tile(dtype)
    return max(_vmem_need(tm, hidden, 128, dtype, 2),
               _vmem_need(tm, width, 128, dtype, 1)
               ) + _VMEM_SPARE <= 96 * 2 ** 20


def _kernel(expert_ref, x_ref, *refs):
    """One (output panel, row tile): ``x @ w`` for each streamed matrix over
    the whole contraction; the panel is the product, or ``silu(a) * b`` where
    two matrices stream."""
    del expert_ref                      # read by the weights' index maps
    *ws, o_ref = refs
    exact = ({"precision": jax.lax.Precision.HIGHEST}
             if x_ref.dtype == jnp.float32 else {})
    x = x_ref[...]
    parts = [jnp.dot(x, w[...], preferred_element_type=jnp.float32, **exact)
             for w in ws]
    out = parts[0] if len(ws) == 1 else jax.nn.silu(parts[0]) * parts[1]
    o_ref[...] = out.astype(o_ref.dtype)


def _call(name, xp, ws, plan):
    """``xp`` [rows padded, K] against each of ``ws`` [E, K, N] by the row
    tiles of ``plan``; [rows padded, N] in ``xp.dtype``. Rows of tiles not in
    use are left as they were allocated."""
    Mp, K = xp.shape
    N = ws[0].shape[2]
    tm = Mp // plan["expert"].shape[0]
    tn = _panel(K, N, xp.dtype)
    need = _vmem_need(tm, K, tn, xp.dtype, len(ws))
    zero = np.int32(0)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # a step without a pair on a held expert still runs one tile (of
            # expert 0, rows that count nowhere): a grid is never empty
            grid=(N // tn, jnp.maximum(plan["tiles"], 1)),
            in_specs=[pl.BlockSpec((tm, K), lambda n, i, expert: (i, zero))]
            + [pl.BlockSpec((None, K, tn),
                            lambda n, i, expert: (expert[i], zero, n))
               ] * len(ws),
            out_specs=pl.BlockSpec((tm, tn), lambda n, i, expert: (i, n))),
        out_shape=jax.ShapeDtypeStruct((Mp, N), xp.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need + _VMEM_SPARE),
        interpret=_interpret(),
        name=name,
    )(plan["expert"], xp, *ws)


def gmm_up(xp, w1, w3, plan):
    """``silu(xp @ w1[e]) * (xp @ w3[e])`` a row tile, ``e`` the tile's
    expert: ``xp`` [rows padded, hidden] in the padded layout of ``plan``
    (``plan_row_tiles``), ``w1`` / ``w3`` [E, hidden, width]. Float32
    accumulation and SwiGLU; the result [rows padded, width] in
    ``xp.dtype``."""
    return _call("held_experts_gmm_up", xp, (w1, w3), plan)


def gmm_down(xp, w2, plan):
    """``xp @ w2[e]`` a row tile: ``xp`` [rows padded, width], ``w2`` [E,
    width, hidden]; [rows padded, hidden] in ``xp.dtype``."""
    return _call("held_experts_gmm_down", xp, (w2,), plan)
