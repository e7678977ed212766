"""Flash attention (forward + backward) as Pallas TPU kernels.

Reference analog: the reference vendors third_party/flashattn (CUDA) behind
python/paddle/nn/functional/flash_attention.py. TPU-first redesign: an online-softmax
tiled kernel on the MXU — q blocks stream against k/v blocks held in VMEM, softmax state
(m, l) carried in fp32, O(S) memory instead of the O(S^2) probs matrix. Backward follows
the flash-attention-2 recomputation scheme (saved LSE + per-row delta), emitted as two
kernels (dq; dk/dv per q-head with a GQA group-sum outside).

Layout contract: paddle's (batch, seq, num_heads, head_dim); internally (B, H, S, D).
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = np.float32(-1e30)


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _cdiv_i32(a, b):
    # explicit int32 lax arithmetic: under x64 a python scalar in a jnp
    # expression would promote the kernel's index math to i64, which Mosaic
    # does not lower
    return jax.lax.div(jax.lax.add(a, _i32(b - 1)), _i32(b))


def _interpret():
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"):
        return True
    return jax.devices()[0].platform == "cpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_k, causal_offset=0):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (Bq, D)
    d = q.shape[-1]

    num_kv = seq_k // block_k
    if causal:
        # only blocks at or before the diagonal contribute
        hi = _cdiv_i32(jax.lax.add(
            jax.lax.mul(jax.lax.add(qi, _i32(1)), _i32(block_q)),
            _i32(causal_offset)), block_k)
        hi = jnp.minimum(hi, _i32(num_kv))
    else:
        hi = num_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(jax.lax.mul(j, _i32(block_k)), block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jax.lax.mul(j, _i32(block_k)), block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Bq, Bk)
        if causal:
            rows = jax.lax.mul(qi, _i32(block_q)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.mul(j, _i32(block_k)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + _i32(causal_offset) >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(_i32(0), _i32(hi), body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, np.float32(1e-30))
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # full 2-D store: a scalar-indexed lse_ref[0,0,:,0] store lowers through a
    # strided-store path that infinitely recurses in Mosaic (i64->i32 convert)
    lse_ref[0, 0] = (m + jnp.log(l_safe))[:, None]


def _fwd(q, k, v, scale, causal, block_q, block_k):
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    rep = Hq // Hkv
    grid = (B, Hq, Sq // block_q)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=Sk, causal_offset=Sk - Sq)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), np.int32(0), np.int32(0))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, np.int32(0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_q, block_k, seq_k, causal_offset=0):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0]                             # (Bq,)
    delta = delta_ref[0, 0][:, 0]                         # (Bq,)
    d = q.shape[-1]

    num_kv = seq_k // block_k
    if causal:
        hi = _cdiv_i32(jax.lax.add(
            jax.lax.mul(jax.lax.add(qi, _i32(1)), _i32(block_q)),
            _i32(causal_offset)), block_k)
        hi = jnp.minimum(hi, _i32(num_kv))
    else:
        hi = num_kv

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(jax.lax.mul(j, _i32(block_k)), block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jax.lax.mul(j, _i32(block_k)), block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.mul(qi, _i32(block_q)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.mul(j, _i32(block_k)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + _i32(causal_offset) >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                     # (Bq, Bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(_i32(0), _i32(hi), body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k, seq_q,
                    causal_offset=0):
    ki = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)                   # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    d = k.shape[-1]

    num_q = seq_q // block_q
    if causal:
        lo = jax.lax.div(
            jnp.maximum(jax.lax.sub(jax.lax.mul(ki, _i32(block_k)),
                                    _i32(causal_offset)), _i32(0)),
            _i32(block_q))
    else:
        lo = 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(jax.lax.mul(i, _i32(block_q)), block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(jax.lax.mul(i, _i32(block_q)), block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(jax.lax.mul(i, _i32(block_q)), block_q), :][:, 0]
        delta = delta_ref[0, 0, pl.ds(jax.lax.mul(i, _i32(block_q)), block_q), :][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.mul(i, _i32(block_q)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.mul(ki, _i32(block_k)) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + _i32(causal_offset) >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                     # (Bq, Bk)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(_i32(lo), _i32(num_q), body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    do = g
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    rep = Hq // Hkv

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=Sk,
                          causal_offset=Sk - Sq),
        grid=(B, Hq, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, np.int32(0))),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, np.int32(0))),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv computed per q-head, then group-summed over the GQA repeat factor
    dk_rep, dv_rep = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=Sq,
                          causal_offset=Sk - Sq),
        grid=(B, Hq, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, Sq, D), lambda b, h, i: (b, h, np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), i, np.int32(0))),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, rep=rep: (b, jax.lax.div(h, np.int32(rep)), i, np.int32(0))),
            pl.BlockSpec((1, 1, Sq, D), lambda b, h, i: (b, h, np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i: (b, h, np.int32(0), np.int32(0))),
            pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i: (b, h, np.int32(0), np.int32(0))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, np.int32(0))),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, np.int32(0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sk, D), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)

    if rep > 1:
        dk = dk_rep.reshape(B, Hkv, rep, Sk, D).sum(axis=2).astype(k.dtype)
        dv = dv_rep.reshape(B, Hkv, rep, Sk, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_rep, dv_rep
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP over the kernels)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, res, g):
    return _bwd(scale, causal, block_q, block_k, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=None, block_k=None):
    """(B, S, H, D) flash attention entry used by F.scaled_dot_product_attention.

    Differentiable (custom VJP); raises ValueError on unsupported shapes so the
    caller can fall back to the math path.
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    # 512x512 default tiles: bigger q/k tiles amortize the loop and fill the
    # MXU. Not re-measured since the port to jax 0.9.0 (PERF.md)
    if block_q is None:
        block_q = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_Q", "512"))
    if block_k is None:
        block_k = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_K", "512"))
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # shrink to a divisor rather than fail: Sq=1920 should still run flash at
    # block 128 instead of silently degrading to the O(S^2) math path
    while block_q > 16 and Sq % block_q != 0:
        block_q //= 2
    while block_k > 16 and Sk % block_k != 0:
        block_k //= 2
    if Sq % block_q != 0 or Sk % block_k != 0:
        raise ValueError(f"sequence lengths ({Sq},{Sk}) not divisible by "
                         f"blocks ({block_q},{block_k})")
    if Hq % Hkv != 0:
        raise ValueError(f"GQA head counts {Hq}/{Hkv} not divisible")
    if causal and Sq > Sk:
        # rows past Sk attend to nothing: forward would emit zeros and the
        # p=exp(s-lse) trick in the dk/dv kernel would add exp(0)=1 garbage terms
        raise ValueError(f"causal flash attention requires Sq<=Sk, got ({Sq},{Sk})")
    s = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, s, bool(causal), block_q, block_k)
    return jnp.swapaxes(out, 1, 2)
