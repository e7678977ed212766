"""The gated delta rule of a linear-attention layer, as Pallas TPU kernels.

Reference analog: none in the reference framework (its attention is softmax
attention over keys and values); the recurrence is Gated DeltaNet's (Yang,
Kautz, Hatamizadeh, arXiv:2412.06464). Per head, with the state ``S`` in
``[dk, dv]`` float32, for a token with ``q``, ``k`` in ``[dk]``, ``v`` in
``[dv]``, a log decay ``g <= 0`` and a write strength ``beta``::

    S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

The state is not addressed by position: a sequence has ONE, in the slot the
cache manager gave it, and a token can only be run from the state its
predecessor left. A serving step hands this file one token a LANE:

- ``rows`` None (a decode burst, lockstep decoding): lane ``i`` is the next
  token of slot ``i``. Every lane is a run of one: ``gated_delta_step``.
- ``rows`` [T] (the mixed step; lockstep prefill): lanes of one row at
  consecutive positions form a RUN (a prefill chunk) that has to go through
  the recurrence in order from the slot's state; a decode lane is a run of
  one; padding lanes (``valid`` False) touch nothing but the null slot.
  ``plan_runs`` cuts runs of two lanes or more into chunks of ``CHUNK``
  tokens for ``gated_delta_chunk`` and sends the runs of one, in lane order,
  to ``gated_delta_step``.

**The chunked form** (the paper's section 3, the WY representation) of
``C`` tokens from the state ``S``: with ``c`` the running sum of ``g`` in the
chunk, ``D[i, j] = exp(c_i - c_j)`` for ``i >= j``, ``L`` the strictly lower
part of ``((beta k) k^T) * D`` and ``T = (I + L)^-1``::

    v' = T (beta v - (beta k * exp(c)) S)
    o  = (q * exp(c)) S + ((q k^T) * D) v'
    S <- exp(c_C) S + (k * exp(c_C - c))^T v'

It is the same function of its inputs as ``C`` single steps. Rows of zeros
(``k``, ``v``, ``g``, ``beta`` all 0) change nothing, so a run's last chunk
is padded with them.

**A decay per key channel** (Kimi Delta Attention, arXiv:2510.26692): ``g``
is ``[dk]`` a token and the step's first factor is ``Diag(exp g) S``. Every
op below takes it where it takes ``g``: ``g`` ``[T, H, dk]`` in place of ``[T,
H]`` says which rule runs, and the kernels are ``%kda_step.N`` and
``%kda_chunk.N``. In the chunked form ``c`` is a running sum a channel and
``D`` has a channel axis: ``(q k^T) * D`` becomes ``sum_d q_id k_jd exp(c_id
- c_jd)``. Unbounded decays make ``exp(-c_j)`` overflow float32 within a few
tokens, so that sum is never split into ``exp(c_i)`` and ``exp(-c_j)``. The
chunk is cut into sub-chunks of ``SUB`` rows: a pair in different sub-chunks
is split at the first row ``r`` of ``i``'s, ``exp(c_i - c_r) exp(c_r - c_j)``,
both factors at most 1, and one MXU product a sub-chunk covers them all; a
pair in one sub-chunk is taken whole, a diagonal ``i - j = o`` at a time, from
``k`` and ``c`` shifted down ``o`` rows.

**Layouts.** The state pool is ``[slots, H / P, dk, P * dv]``: ``P``
(``pack``) heads side by side along the lanes, 2 where the heads pair up, so
that a row fills whole 128-lane registers where one head's ``dv`` would not
(192 -> 384: no lane is padded in HBM or in VMEM), and 1 where one head's
``dv`` fills whole registers itself (128); ``pack_state`` /
``unpack_state`` convert from and to ``[slots, H, dk, dv]``. A kernel scores
``k`` [.., dk] against a pair's state by a product over all ``P * dv`` lanes
per head and keeps each head's own lanes (a select by the lane's index).

**The kernels.** ``%gated_delta_step.N``: a grid step a lane of the order;
the lane's slot is scalar-prefetched data that the state's index map reads;
the state block is read, updated on the VPU (``k`` and ``q`` arrive
transposed, ``[dk, H]``, so that a head's column broadcasts along the lanes)
and written back to the SAME pool (aliased). ``%gated_delta_chunk.N``: a grid
step a (head pair, chunk), pairs outermost; the chunks of a run follow each
other and name the same state block, which the pipeline brings in once and
writes once; ``T`` is made by forward substitution, row by row (64 unrolled
steps on a [64, 64] tile: the products of powers of ``L`` that would run on
the MXU lose digits once ``beta k_i . k_j`` is large), everything else is
MXU products at the highest precision. The grids' lengths are the chunks and
lanes IN USE (dynamic). ``_kernel_applies`` reads its inputs only; everywhere
else the ``jax.numpy`` forms below run, which are the kernels' references.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["CHUNK", "pack_of", "pack_state", "unpack_state", "plan_runs",
           "max_chunks", "step_reference", "chunk_reference",
           "kda_chunk_reference", "gated_delta"]

CHUNK = 64
SUB = 8                 # rows of a sub-chunk of the per-channel chunked form
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def pack_of(heads, value_dim):
    """Heads side by side along a state row's lanes: two where they pair up
    and one head's ``value_dim`` fills no whole 128-lane register."""
    return 2 if heads % 2 == 0 and value_dim % 128 else 1


def pack_state(s, pack):
    """``[N, H, dk, dv]`` -> the pool's ``[N, H / P, dk, P * dv]``."""
    n, h, dk, dv = s.shape
    s = s.reshape(n, h // pack, pack, dk, dv)
    return jnp.swapaxes(s, 2, 3).reshape(n, h // pack, dk, pack * dv)


def unpack_state(s, pack):
    """The pool's ``[N, H / P, dk, P * dv]`` -> ``[N, H, dk, dv]``."""
    n, hp, dk, w = s.shape
    s = s.reshape(n, hp, dk, pack, w // pack)
    return jnp.swapaxes(s, 2, 3).reshape(n, hp * pack, dk, w // pack)


# ---------------------------------------------------------------------------
# the plain forms: the kernels' references, and what runs off the TPU
# ---------------------------------------------------------------------------
def step_reference(q, k, v, g, beta, s):
    """One token a lane. ``q``, ``k`` [N, H, dk], ``v`` [N, H, dv], ``g``
    [N, H] (or a decay a key channel, [N, H, dk]), ``beta`` [N, H], ``s`` [N,
    H, dk, dv], all float32. Returns ``(o [N, H, dv], s')``."""
    s = s * (jnp.exp(g)[..., None] if g.ndim == 3
             else jnp.exp(g)[..., None, None])
    u = beta[..., None] * (v - jnp.einsum("nhd,nhdv->nhv", k, s,
                                          precision=_HI))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("nhd,nhdv->nhv", q, s, precision=_HI), s


def chunk_reference(q, k, v, g, beta, s):
    """``C`` tokens of ONE sequence in the chunked form. ``q``, ``k`` [C, H,
    dk], ``v`` [C, H, dv], ``g``, ``beta`` [C, H], ``s`` [H, dk, dv], all
    float32. Returns ``(o [C, H, dv], s')``."""
    C = q.shape[0]
    q, k, v = (jnp.swapaxes(x, 0, 1) for x in (q, k, v))     # [H, C, .]
    g, beta = g.T, beta.T                                    # [H, C]
    c = jnp.cumsum(g, axis=-1)
    i = jnp.arange(C, dtype=jnp.int32)
    low = i[:, None] >= i[None, :]
    d = jnp.exp(jnp.where(low, c[:, :, None] - c[:, None, :], -1e30))
    kb = k * beta[..., None]
    mm = functools.partial(jnp.einsum, precision=_HI)
    lmat = jnp.where(i[:, None] > i[None, :],
                     mm("hid,hjd->hij", kb, k) * d, 0.0)
    t = jax.scipy.linalg.solve_triangular(
        lmat + jnp.eye(C, dtype=_F32), jnp.broadcast_to(
            jnp.eye(C, dtype=_F32), lmat.shape), lower=True,
        unit_diagonal=True)
    ec = jnp.exp(c)[..., None]
    vn = mm("hij,hjv->hiv", t,
            v * beta[..., None] - mm("hid,hdv->hiv", kb * ec, s))
    o = mm("hid,hdv->hiv", q * ec, s) \
        + mm("hij,hjv->hiv", mm("hid,hjd->hij", q, k) * d, vn)
    last = c[:, -1]
    s = s * jnp.exp(last)[:, None, None] + mm(
        "hid,hiv->hdv", k * jnp.exp(last[:, None] - c)[..., None], vn)
    return jnp.swapaxes(o, 0, 1), s


def kda_chunk_reference(q, k, v, g, beta, s):
    """``chunk_reference`` with a decay a key channel: ``g`` [C, H, dk]. The
    decays between every two tokens are taken whole, ``exp(c_i - c_j)`` of
    each channel, never split."""
    C = q.shape[0]
    q, k, v, g = (jnp.swapaxes(x, 0, 1) for x in (q, k, v, g))   # [H, C, .]
    beta = beta.T                                            # [H, C]
    c = jnp.cumsum(g, axis=1)
    i = jnp.arange(C, dtype=jnp.int32)
    low = (i[:, None] >= i[None, :])[None, :, :, None]
    d = jnp.exp(jnp.where(low, c[:, :, None] - c[:, None], -1e30))
    kb = k * beta[..., None]
    mm = functools.partial(jnp.einsum, precision=_HI)
    lmat = jnp.where(i[:, None] > i[None, :], mm("hid,hjd,hijd->hij", kb, k, d),
                     0.0)
    t = jax.scipy.linalg.solve_triangular(
        lmat + jnp.eye(C, dtype=_F32), jnp.broadcast_to(
            jnp.eye(C, dtype=_F32), lmat.shape), lower=True,
        unit_diagonal=True)
    ec = jnp.exp(c)
    vn = mm("hij,hjv->hiv", t,
            v * beta[..., None] - mm("hid,hdv->hiv", kb * ec, s))
    o = mm("hid,hdv->hiv", q * ec, s) \
        + mm("hij,hjv->hiv", mm("hid,hjd,hijd->hij", q, k, d), vn)
    last = c[:, -1]                                          # [H, dk]
    s = s * jnp.exp(last)[..., None] + mm(
        "hid,hiv->hdv", k * jnp.exp(last[:, None] - c), vn)
    return jnp.swapaxes(o, 0, 1), s


# ---------------------------------------------------------------------------
# the plan of a step's lanes
# ---------------------------------------------------------------------------
def max_chunks(lanes, slots):
    """Chunks a step of ``lanes`` lanes over ``slots`` slots (the null one
    included) can hold at most: a run of two lanes or more leaves one partial
    chunk at most, and a slot has one run."""
    return max(min(slots - 1, lanes // 2) + lanes // CHUNK, 1)


def plan_runs(rows, positions, valid, slots):
    """Which lanes of a step form runs, and how they are cut. ``rows`` [T]
    the slot a lane sits on, ``positions`` [T], ``valid`` [T] bool; ``slots``
    the pool's slots, the last the null slot. A RUN is a stretch of
    neighbouring valid lanes on one row at consecutive positions. Returns a
    dict of arrays (int32 where not said):

    ``rows`` [T] as given; ``off`` [T] a lane's place in its run; ``fresh`` [T] bool: the lane's run
    starts its sequence (position 0: from the zero state, whatever the slot
    holds); ``last`` [T] bool: the lane ends its run; ``single`` [T] bool: a
    valid run of one; ``chunked`` [T] bool: a lane of a longer run;
    ``chunk`` / ``place`` [T]: a chunked lane's chunk and row in it;
    ``lane0`` / ``n`` / ``slot`` / ``code`` [chunks at most]: a chunk's first
    lane, lanes (0 past the ``chunks`` [] in use), slot (the null slot past
    them) and 0 where it goes on from the chunk before, 1 where it starts
    from the slot's state, 2 where from zero; ``order`` [T]: the single
    lanes in lane order (then lane 0), ``order_slot`` / ``order_fresh`` [T]
    theirs (the null slot, fresh, past the ``singles`` [] in use)."""
    T = rows.shape[0]
    i32 = jnp.int32
    null = np.int32(slots - 1)
    idx = jnp.arange(T, dtype=i32)
    rows, positions = rows.astype(i32), positions.astype(i32)
    cont = jnp.concatenate([
        jnp.zeros((1,), bool),
        (rows[1:] == rows[:-1]) & (positions[1:] == positions[:-1] + 1)
        & valid[1:] & valid[:-1]])
    ends = jnp.concatenate([~cont[1:], jnp.ones((1,), bool)])
    first = lax.cummax(jnp.where(cont, jnp.zeros_like(idx), idx))
    last = lax.cummin(jnp.where(ends, idx, jnp.full_like(idx, T - 1)),
                      reverse=True)
    off = idx - first
    length = last - first + 1
    single = valid & (length == 1)
    chunked = valid & (length > 1)
    fresh = positions[first] == 0
    nc = max_chunks(T, slots)
    heads = jnp.cumsum((chunked & (off % CHUNK == 0)).astype(i32))
    chunk = jnp.clip(heads - 1, 0, nc - 1)
    which = jnp.arange(nc, dtype=i32)
    chunks = jnp.minimum(heads[-1], nc)
    used = which < chunks
    lane0 = jnp.minimum(jnp.searchsorted(heads, which + 1).astype(i32),
                        T - 1)
    n = jnp.where(used, jnp.minimum(CHUNK, last[lane0] - lane0 + 1), 0)
    code = jnp.where(off[lane0] > 0, 0, jnp.where(fresh[lane0], 2, 1))
    ones = jnp.cumsum(single.astype(i32))
    order = jnp.minimum(jnp.searchsorted(ones, idx + 1).astype(i32), T - 1)
    in_order = idx < ones[-1]
    return {
        "rows": rows, "off": off, "fresh": fresh, "last": valid & ends, "single": single,
        "chunked": chunked, "chunk": chunk, "place": off % CHUNK,
        "lane0": lane0, "n": n.astype(i32),
        "slot": jnp.where(used, rows[lane0], null),
        "code": jnp.where(used, code, 2).astype(i32), "chunks": chunks,
        "order": jnp.where(in_order, order, 0),
        "order_slot": jnp.where(in_order, rows[order], null),
        "order_fresh": jnp.where(in_order, fresh[order], True).astype(i32),
        "singles": ones[-1]}


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _kernel_applies(q, v, state):
    """Whether the Pallas kernels run: on a TPU, a float32 state whose rows
    (``pack`` heads of ``dv``) fill whole 128-lane registers and whose ``dk``
    fills whole sublanes. Everything read here is visible in the inputs: no
    flag picks the path."""
    return (jax.devices()[0].platform == "tpu"
            and state.dtype == jnp.float32
            and state.shape[-1] % 128 == 0 and state.shape[-2] % 8 == 0
            and q.shape[-1] == state.shape[-2])


def _select(pack, dv, width):
    """``sel(a, b)``: a pair's row from what head a and head b give over all
    ``width`` lanes; head a's alone where heads do not pair."""
    if pack == 1:
        return lambda a, b=None: a
    lo = lax.broadcasted_iota(jnp.int32, (1, width), 1) < dv
    return lambda a, b: jnp.where(lo, a, b)


def _step_kernel(order_ref, slot_ref, fresh_ref, qt_ref, kt_ref, v_ref,
                 dec_ref, beta_ref, s_ref, o_ref, so_ref, *, pack, dv):
    del order_ref, slot_ref             # read by the index maps
    i = pl.program_id(0)
    keep = jnp.where(fresh_ref[i] > 0, np.float32(0), np.float32(1))
    pairs, _, width = s_ref.shape[1:]
    sel = _select(pack, dv, width)

    def col(ref, h):                    # a head's column, along the lanes
        return ref[0, :, h:h + 1]

    for p in range(pairs):
        a, b = pack * p, pack * p + pack - 1
        s = s_ref[0, p] * keep
        k = sel(col(kt_ref, a), col(kt_ref, b))              # [dk, width]
        s = s * sel(dec_ref[0, :, a:a + 1], dec_ref[0, :, b:b + 1])
        ks = jnp.sum(s * k, axis=0, keepdims=True)
        u = sel(beta_ref[0, :, a:a + 1], beta_ref[0, :, b:b + 1]) \
            * (v_ref[0, p:p + 1, :] - ks)
        s = s + k * u
        q = sel(col(qt_ref, a), col(qt_ref, b))
        o_ref[0, p:p + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)
        so_ref[0, p] = s


def _step_call(q, k, v, g, beta, state, order, slot, fresh, count):
    """``q``, ``k`` [T, H, dk], ``v`` [T, H, dv], ``g``, ``beta`` [T, H]
    float32 (``g`` [T, H, dk]: a decay a key channel, ``%kda_step``);
    ``state`` the pool; the lanes ``order[:count]`` run, each on ``slot[i]``,
    from zero where ``fresh[i]``. Returns ``(o [T, H, dv], rows of lanes not
    run left as allocated; state')``. The kernel reads a head's decay as a
    column down the state's key rows either way: one number, or one a row."""
    T, H, dk = q.shape
    slots, pairs, _, width = state.shape
    pack = H // pairs
    dv = width // pack
    zero = np.int32(0)
    lane3 = lambda i, order, slot, fresh: (order[i], zero, zero)  # noqa: E731
    pool = pl.BlockSpec((1, pairs, dk, width),
                        lambda i, order, slot, fresh: (slot[i], zero, zero,
                                                       zero))
    channel = g.ndim == 3
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, pack=pack, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a step without a run of one still runs one lane, on the null
            # slot: a grid is never empty
            grid=(jnp.maximum(count, 1),),
            in_specs=[pl.BlockSpec((1, dk, H), lane3),
                      pl.BlockSpec((1, dk, H), lane3),
                      pl.BlockSpec((1, pairs, width), lane3),
                      pl.BlockSpec((1, dk if channel else 1, H), lane3),
                      pl.BlockSpec((1, 1, H), lane3), pool],
            out_specs=[pl.BlockSpec((1, pairs, width), lane3), pool]),
        out_shape=[jax.ShapeDtypeStruct((T, pairs, width), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=_interpret(),
        name="kda_step" if channel else "gated_delta_step",
    )(order, slot, fresh, jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      v.reshape(T, pairs, width),
      jnp.swapaxes(jnp.exp(g), 1, 2) if channel else jnp.exp(g)[:, None, :],
      beta[:, None, :], state)
    return o.reshape(T, H, dv), state


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=_F32)


_AB = ((1,), (0,))          # a @ b
_ABT = ((1,), (1,))         # a @ b^T
_ATB = ((0,), (0,))         # a^T @ b


def _within(x, y, c, strict):
    """``[C, C]``: ``sum_d x_id y_jd exp(c_id - c_jd)`` for ``j <= i`` (``j <
    i`` where ``strict``), else 0, for ``x``, ``y``, ``c`` [C, dk] with
    ``c`` falling down each column. Pairs in different sub-chunks of ``SUB``
    rows are split at the first row ``r`` of ``i``'s, each factor's exponent
    at most 0 (one product a sub-chunk); pairs in one sub-chunk are taken a
    diagonal ``i - j = o`` at a time from ``y`` and ``c`` shifted down ``o``
    rows (a product with a shift matrix, which moves values exactly)."""
    C, dk = x.shape
    sub = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    lane = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    row = lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    out = jnp.zeros((C, C), _F32)
    for r in range(SUB, C, SUB):
        ref = c[r:r + 1, :]
        xs = x * jnp.exp(jnp.where((row >= r) & (row < r + SUB), c - ref,
                                   np.float32(-1e30)))
        ys = y * jnp.exp(jnp.where(row < r, ref - c, np.float32(-1e30)))
        out = out + _dot(xs, ys, _ABT)
    for o in range(int(strict), SUB):
        shift = (lane == sub - o).astype(_F32)
        e = jnp.exp(jnp.where((row & np.int32(SUB - 1)) >= o,
                              c - _dot(shift, c, _AB), np.float32(-1e30)))
        d = jnp.sum(x * _dot(shift, y, _AB) * e, axis=1, keepdims=True)
        out = out + jnp.where(lane == sub - o, d, np.float32(0))
    return out


def _kda_heads(q_ref, k_ref, kb_ref, c_ref, t_scr, row_id, pack):
    """What ``_chunk_kernel``'s pair takes of each head with a decay a key
    channel: ``c_ref`` holds the running sums [C, dk]."""
    C = q_ref.shape[2]
    eye = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(_F32)
    heads = []
    for a in range(pack):
        q, k, kb, c = q_ref[0, a], k_ref[0, a], kb_ref[0, a], c_ref[0, a]
        last = c[C - 1:C, :]                                 # [1, dk]
        dk = last.shape[1]
        # the decay of the state's rows, down its sublanes
        down = lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        across = lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
        decay = jnp.sum(jnp.where(down == across, jnp.exp(last),
                                  np.float32(0)), axis=1, keepdims=True)
        # lt[j, i] = L[i, j]
        _substitute(t_scr, a, _dot(eye, _within(kb, k, c, True), _ABT),
                    row_id)
        heads.append({"q": q * jnp.exp(c), "kb": kb * jnp.exp(c),
                      "aqk": _within(q, k, c, False), "t": a,
                      "k": k * jnp.exp(last - c), "decay": decay})
    return heads


def _substitute(t_scr, a, lt, row_id):
    """``t_scr[a] = (I + L)^-1`` by forward substitution, row by row, from
    ``lt[j, i] = L[i, j]`` (row ``i`` of ``L`` down the sublanes of column
    ``i``); ``row_id`` [1, C] the lanes' indices."""
    C = lt.shape[0]
    t_scr[a] = jnp.zeros((C, C), _F32)
    for i in range(C):
        e_i = (row_id == np.int32(i)).astype(_F32)
        if i == 0:
            t_scr[a, 0:1, :] = e_i
            continue
        top = -(-i // 8) * 8
        t_scr[a, i:i + 1, :] = e_i - jnp.sum(
            lt[:top, i:i + 1] * t_scr[a, :top, :], axis=0, keepdims=True)


def _chunk_kernel(slot_ref, code_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                  s_ref, o_ref, so_ref, t_scr, *, pack, dv, channel=False):
    del slot_ref
    c = pl.program_id(1)
    code = code_ref[c]

    @pl.when(code == 1)
    def _():
        so_ref[...] = s_ref[...]

    @pl.when(code == 2)
    def _():
        so_ref[...] = jnp.zeros_like(so_ref)

    C = q_ref.shape[2]
    width = so_ref.shape[-1]
    sel = _select(pack, dv, width)
    sub = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    lane = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    row_id = lax.broadcasted_iota(jnp.int32, (1, C), 1)
    s = so_ref[0, 0]                                         # [dk, width]

    heads = _kda_heads(q_ref, k_ref, kb_ref, g_ref, t_scr, row_id, pack) \
        if channel else []
    for a in range(0 if channel else pack):
        q, k, kb = q_ref[0, a], k_ref[0, a], kb_ref[0, a]    # [C, dk]
        g_row = g_ref[0, 0, a:a + 1, :]                      # [1, C]
        # the running sum as a column (down the sublanes) and as a row
        c_col = jnp.sum(jnp.where(lane <= sub, g_row, np.float32(0)), axis=1,
                        keepdims=True)
        c_row = jnp.sum(jnp.where(lane == sub, c_col, np.float32(0)), axis=0,
                        keepdims=True)
        last = c_row[:, C - 1:C]                             # [1, 1]
        # lt[j, i] = L[i, j]: row i of L down the sublanes of column i
        lt = _dot(k, kb, _ABT) * jnp.exp(
            jnp.where(lane > sub, c_row - c_col, np.float32(-1e30)))
        _substitute(t_scr, a, lt, row_id)
        aqk = _dot(q, k, _ABT) * jnp.exp(
            jnp.where(sub >= lane, c_col - c_row, np.float32(-1e30)))
        ec = jnp.exp(c_col)
        heads.append({"q": q * ec, "kb": kb * ec, "aqk": aqk, "t": a,
                      "k": k * jnp.exp(last - c_col), "decay": jnp.exp(last)})

    def both(f):
        """A pair's row: what ``f`` gives for each head, on its own lanes."""
        return sel(*(f(h) for h in heads))

    rhs = vb_ref[0] - both(lambda h: _dot(h["kb"], s, _AB))
    vn = both(lambda h: _dot(t_scr[h["t"]], rhs, _AB))
    o_ref[0] = both(lambda h: _dot(h["q"], s, _AB) + _dot(h["aqk"], vn, _AB))
    so_ref[0, 0] = s * both(lambda h: h["decay"]) \
        + both(lambda h: _dot(h["k"], vn, _ATB))


def _chunk_call(qc, kc, vc, gc, bc, state, slot, code, chunks):
    """``qc``, ``kc`` [NC, C, H, dk], ``vc`` [NC, C, H, dv], ``gc``, ``bc``
    [NC, C, H] float32: the chunks' lanes, rows past a chunk's lanes zeros;
    chunk ``c`` of the ``chunks`` in use runs on ``slot[c]``, going on from
    the chunk before it (``code[c]`` 0), from the slot's state (1) or from
    zero (2). Returns ``(o [NC, C, H, dv], state')``. ``gc`` [NC, C, H, dk]:
    a decay a key channel (``%kda_chunk``), whose running sums a chunk the
    kernel takes as it takes ``q``."""
    NC, C, H, dk = qc.shape
    slots, pairs, _, width = state.shape
    pack = H // pairs
    dv = width // pack
    zero = np.int32(0)
    heads = lambda x: jnp.swapaxes(x, 1, 2)                  # noqa: E731
    per_head = pl.BlockSpec((1, pack, C, dk),
                            lambda p, c, slot, code: (c, p, zero, zero))
    channel = gc.ndim == 4
    pool = pl.BlockSpec((1, 1, dk, width),
                        lambda p, c, slot, code: (slot[c], p, zero, zero))
    rows = pl.BlockSpec((1, C, width), lambda p, c, slot, code: (c, zero, p))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, pack=pack, dv=dv, channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pairs, jnp.maximum(chunks, 1)),
            in_specs=[per_head, per_head, per_head, rows,
                      per_head if channel else
                      pl.BlockSpec((1, 1, pack, C),
                                   lambda p, c, slot, code: (c, p, zero,
                                                             zero)),
                      pool],
            out_specs=[rows, pool],
            scratch_shapes=[pltpu.VMEM((pack, C, C), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((NC, C, H * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="kda_chunk" if channel else "gated_delta_chunk",
    )(slot, code, heads(qc), heads(kc), heads(kc * bc[..., None]),
      (vc * bc[..., None]).reshape(NC, C, H * dv),
      heads(jnp.cumsum(gc, axis=1)) if channel
      else jnp.swapaxes(gc, 1, 2).reshape(NC, pairs, pack, C), state)
    return o.reshape(NC, C, H, dv), state


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------
def _step_plain(q, k, v, g, beta, state, order, slot, fresh, count):
    """``_step_call`` in ``jax.numpy``: every lane of the order is run (those
    past ``count`` on the null slot, which is then zeroed again)."""
    pack = q.shape[1] // state.shape[1]
    null = state.shape[0] - 1
    take = lambda x: x[order]                                # noqa: E731
    s = unpack_state(state[slot], pack) \
        * (1.0 - fresh.astype(_F32))[:, None, None, None]
    o, s = step_reference(take(q), take(k), take(v), take(g), take(beta), s)
    state = state.at[slot].set(pack_state(s, pack)).at[null].set(
        jnp.zeros((), state.dtype))
    used = jnp.arange(order.shape[0], dtype=jnp.int32) < count
    return jnp.zeros_like(v).at[jnp.where(used, order, v.shape[0])].set(
        o, mode="drop"), state


def _chunk_plain(qc, kc, vc, gc, bc, state, slot, code, chunks):
    """``_chunk_call`` in ``jax.numpy``: a scan over the chunks, each from
    the pool's state of its slot and back into it."""
    pack = qc.shape[2] // state.shape[1]
    null = state.shape[0] - 1

    def one(state, x):
        q, k, v, g, b, sl, cd, used = x
        s = unpack_state(state[sl][None], pack)[0] * (cd != 2)
        o, s = (kda_chunk_reference if g.ndim == 3 else chunk_reference)(
            q, k, v, g, b, s)
        return state.at[jnp.where(used, sl, null)].set(
            pack_state(s[None], pack)[0]), o

    used = jnp.arange(qc.shape[0], dtype=jnp.int32) < chunks
    state, o = lax.scan(one, state, (qc, kc, vc, gc, bc, slot, code, used))
    return o, state.at[null].set(jnp.zeros((), state.dtype))


def gated_delta(q, k, v, g, beta, state, positions, plan=None):
    """One token a lane through the gated delta rule. ``q``, ``k`` [T, H,
    dk] (normalised and scaled), ``v`` [T, H, dv], ``g``, ``beta`` [T, H]
    (``g`` [T, H, dk]: a decay a key channel, the KDA rule), all float32;
    ``state`` the pool ``[slots, H / P, dk, P * dv]`` float32, its last slot
    the null slot; ``positions`` [T]. ``plan`` None: lane
    ``i`` is the next token of slot ``i`` (T < slots); else ``plan_runs``'
    of the step's lanes. A run that starts at position 0 starts from the
    zero state whatever its slot holds: that IS the slot's reset. Returns
    ``(o [T, H, dv] float32, state')``."""
    T = q.shape[0]
    kernel = _kernel_applies(q, v, state)
    step = _step_call if kernel else _step_plain
    if plan is None:
        lanes = jnp.arange(T, dtype=jnp.int32)
        return step(q, k, v, g, beta, state, lanes, lanes,
                    (positions == 0).astype(jnp.int32), np.int32(T))
    # runs of one, through the order
    o1, state = step(q, k, v, g, beta, state, plan["order"],
                     plan["order_slot"], plan["order_fresh"],
                     plan["singles"])
    # longer runs, cut into chunks: rows past a chunk's lanes are zeros
    at = plan["lane0"][:, None] + jnp.arange(CHUNK, dtype=jnp.int32)[None]
    real = jnp.arange(CHUNK, dtype=jnp.int32)[None] < plan["n"][:, None]
    at = jnp.where(real, at, T)

    def cut(x):
        return jnp.concatenate([x, jnp.zeros_like(x[:1])])[at]

    oc, state = (_chunk_call if kernel else _chunk_plain)(
        cut(q), cut(k), cut(v), cut(g), cut(beta), state, plan["slot"],
        plan["code"], plan["chunks"])
    o = jnp.where(plan["chunked"][:, None, None],
                  oc[plan["chunk"], plan["place"]],
                  jnp.where(plan["single"][:, None, None], o1, np.float32(0)))
    return o, state
