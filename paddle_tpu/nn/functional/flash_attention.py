"""Attention functionals: scaled_dot_product_attention / flash_attention.

Reference analog: python/paddle/nn/functional/flash_attention.py (`_select_sdp_for_sdpa`
:309 dispatches flash / mem-efficient / math; `flash_attention` :358). TPU-first: the hot
path is a Pallas flash-attention kernel (ops/pallas/flash_attention.py) tiled for the MXU;
the math path is the jnp reference used for CPU tests and as the autodiff fallback.
Layout is paddle's (batch, seq, num_heads, head_dim).
"""
from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from ...framework import random as rng
from ...framework.core import Tensor
from ...ops._apply import defop


def _math_sdpa(q, k, v, attn_mask=None, causal=False, dropout_key=None, dropout_p=0.0,
               scale=None):
    # (B, S, H, D) -> (B, H, S, D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    # GQA: kv heads may be fewer
    hq, hk = qt.shape[1], kt.shape[1]
    if hq != hk:
        rep = hq // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    # promote, don't demote: bf16 -> f32 for stability, f64 stays f64
    ct = jnp.promote_types(qt.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(ct), axis=-1).astype(qt.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _use_pallas(q):
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS"):
        return False
    return jax.devices()[0].platform != "cpu" and q.shape[1] >= 128


def _flash(q, k, v, causal, scale):
    """The Pallas kernel, run per head shard where the trace has auto
    (GSPMD) mesh axes: a Mosaic kernel cannot be partitioned automatically,
    and under ``mesh.parallelize``'s dp-manual / mp-auto step the TP layers
    shard exactly the head dimension. Attention is independent per head, so
    a nested shard_map over the auto axes is exact whatever they shard."""
    from jax.sharding import PartitionSpec as P

    from ...ops.pallas.flash_attention import flash_attention_fwd

    def kernel(q, k, v):
        return flash_attention_fwd(q, k, v, causal=causal, scale=scale)

    am = jax.sharding.get_abstract_mesh()
    auto = tuple(a for a in am.axis_names
                 if a not in am.manual_axes and am.shape[a] > 1)
    if not auto:
        return kernel(q, k, v)
    heads = P(None, None, auto if len(auto) > 1 else auto[0], None)
    return jax.shard_map(kernel, in_specs=(heads, heads, heads),
                         out_specs=heads, axis_names=frozenset(auto),
                         check_vma=False)(q, k, v)


@defop("flash_attention", amp_category="white")
def _sdpa(q, k, v, attn_mask=None, dropout_key=None, dropout_p=0.0, causal=False,
          scale=None, use_pallas=False):
    if use_pallas and attn_mask is None and dropout_p == 0.0:
        try:
            return _flash(q, k, v, causal, scale)
        except ValueError:
            # documented fallback contract: unsupported shapes -> math path.
            # anything else (lowering/VMEM/compile errors) must surface, not
            # silently degrade to O(S^2) attention
            pass
    return _math_sdpa(q, k, v, attn_mask, causal, dropout_key, dropout_p, scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention (flash_attention.py:358 family)."""
    dk = rng.next_key() if (dropout_p > 0.0 and training) else None
    return _sdpa(query, key, value, attn_mask, dk,
                 dropout_p=float(dropout_p) if training else 0.0,
                 causal=bool(is_causal), use_pallas=_use_pallas(query))


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    if return_softmax:
        return out, None
    return out, None


@defop("flash_attn_varlen", amp_category="white")
def _varlen(q, k, v, seg_q, seg_k, scale=None, causal=False):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("qhd,khd->hqk", q, k) * s
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        mask = mask & (jnp.arange(q.shape[0])[:, None] >= jnp.arange(k.shape[0])[None, :])
    logits = jnp.where(mask[None], logits, -1e30)
    ct = jnp.promote_types(q.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(ct), -1).astype(q.dtype)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False,
                        return_softmax=False, fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Varlen flash attention: ragged batches packed as one sequence. Implemented by
    segment-masked attention (static shapes — TPU-friendly)."""
    cu_q = cu_seqlens_q.value
    total_q = query.value.shape[0]
    seg_q = jnp.cumsum(
        jnp.zeros(total_q, jnp.int32).at[cu_q[1:-1]].add(1)
    )
    cu_k = cu_seqlens_k.value
    total_k = key.value.shape[0]
    seg_k = jnp.cumsum(
        jnp.zeros(total_k, jnp.int32).at[cu_k[1:-1]].add(1)
    )

    out = _varlen(query, key, value, Tensor(seg_q), Tensor(seg_k),
                  scale=scale, causal=bool(causal))
    return out, None


def sdp_kernel(*args, **kwargs):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield

    return guard()
