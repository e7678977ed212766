"""Fused op surface (paddle.incubate.nn.functional).

Reference analog: python/paddle/incubate/nn/functional/{fused_rotary_position_embedding,
fused_rms_norm, fused_layer_norm, swiglu, fused_dropout_add, fused_linear}.py — hand-fused
CUDA kernels. TPU-first: each is ONE defop (a single jax-traceable function), so XLA fuses
it into neighbouring HLO; the per-op eager path still runs it as one cached executable.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ....nn.functional.activation import swiglu  # noqa: F401  (already fused)
from ....ops._apply import defop


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotate_every_two(x):
    # interleaved layout: rotation pairs are (2i, 2i+1)
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def _rope_tables(seq_len, head_dim, theta, dtype, position_ids=None, every_two=True):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if position_ids is None:
        t = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        t = position_ids.astype(jnp.float32)
    freqs = jnp.einsum("...s,d->...sd", t, inv_freq)
    if every_two:
        emb = jnp.repeat(freqs, 2, axis=-1)                  # [f0, f0, f1, f1, ...]
    else:
        emb = jnp.concatenate([freqs, freqs], axis=-1)       # [f0..f_{D/2-1}, f0..]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _normalize_rope_table(tbl):
    """Accept (S,D), (B,S,D), (1,S,1,D)/(B,S,1,D) layouts → (S,D) or (B,S,D)."""
    if tbl.ndim == 4:                                        # (B,S,1,D) head axis
        tbl = tbl.reshape(tbl.shape[0], tbl.shape[1], tbl.shape[3])
    if tbl.ndim == 3 and tbl.shape[0] == 1:
        tbl = tbl[0]
    return tbl


@defop("fused_rotary_position_embedding", amp_category="white")
def _fused_rope(q, k=None, v=None, sin=None, cos=None, position_ids=None,
                use_neox_rotary_style=True, rotary_theta=10000.0):
    """q/k/v: (B, S, H, D). RoPE applies to EVERY provided input (the reference
    kernel loops all of q/k/v: fused_rope_utils.h rotate_every_two iterates
    num_inputs). use_neox_rotary_style=True selects the interleaved rotate-every-two
    pairing, False the half-split rotate-half pairing — per the kernel dispatch at
    fused_rope_kernel.cu:188-190 (NOT the usual HF naming). Auto-generated tables use
    the pairing-consistent frequency layout for each style."""
    S, D = q.shape[1], q.shape[-1]
    if cos is None or sin is None:
        cos, sin = _rope_tables(S, D, rotary_theta, q.dtype, position_ids,
                                every_two=use_neox_rotary_style)
    else:
        cos = _normalize_rope_table(cos)
        sin = _normalize_rope_table(sin)
    # broadcast (…S,D) over batch/head axes of (B,S,H,D)
    if cos.ndim == 2:
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    else:  # (B,S,D) from position_ids
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]

    if use_neox_rotary_style:
        def rot(x):
            return x * cos_b + _rotate_every_two(x) * sin_b
    else:
        def rot(x):
            return x * cos_b + _rotate_half(x) * sin_b

    outs = tuple(rot(t) for t in (q, k, v) if t is not None)
    return outs[0] if len(outs) == 1 else outs


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    rotary_theta=10000.0, name=None):
    out = _fused_rope(q, k, v, sin=sin, cos=cos, position_ids=position_ids,
                      use_neox_rotary_style=use_neox_rotary_style,
                      rotary_theta=rotary_theta)
    if not isinstance(out, tuple):
        out = (out,)
    # fixed positional slots: None inputs yield None outputs in their own slot
    res, it = [], iter(out)
    for t in (q, k, v):
        res.append(next(it) if t is not None else None)
    return tuple(res)


@defop("fused_rms_norm", amp_category="fp32")
def _fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6, begin_norm_axis=-1):
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
    # promote, don't demote: bf16 -> f32 for stability, f64 stays f64
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    var = jnp.mean(xf * xf, axis=axes, keepdims=True)
    y = (xf * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if norm_weight is not None:
        y = y * norm_weight
    if norm_bias is not None:
        y = y + norm_bias
    return y


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6, begin_norm_axis=-1,
                   name=None):
    return _fused_rms_norm(x, norm_weight, norm_bias, epsilon=epsilon,
                           begin_norm_axis=begin_norm_axis)


@defop("fused_layer_norm", amp_category="fp32")
def _fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                      begin_norm_axis=-1, residual=None):
    if residual is not None:
        x = x + residual
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
    # promote, don't demote: bf16 -> f32 for stability, f64 stays f64
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if norm_weight is not None:
        y = y * norm_weight
    if norm_bias is not None:
        y = y + norm_bias
    return y


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, residual=None, name=None):
    return _fused_layer_norm(x, norm_weight, norm_bias, epsilon=epsilon,
                             begin_norm_axis=begin_norm_axis, residual=residual)


@defop("fused_dropout_add")
def _fused_dropout_add(x, y, key=None, p=0.5, training=True,
                       mode="upscale_in_train"):
    if not training or p == 0.0 or key is None:
        return x + y
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if mode == "upscale_in_train":
        dropped = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        dropped = jnp.where(keep, x, 0.0).astype(x.dtype)
    return dropped + y


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train", name=None):
    from ....framework import random as rng

    key = rng.next_key() if (training and p > 0.0) else None
    return _fused_dropout_add(x, y, key=key, p=p, training=training, mode=mode)


@defop("fused_linear")
def _fused_linear(x, weight, bias=None, transpose_weight=False):
    w = weight.T if transpose_weight else weight
    y = jnp.matmul(x, w)
    if bias is not None:
        y = y + bias
    return y


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    return _fused_linear(x, weight, bias, transpose_weight=transpose_weight)


@defop("fused_bias_act")
def _fused_bias_act(x, bias=None, act_method="gelu"):
    if bias is not None:
        x = x + bias
    if act_method in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=False)
    if act_method == "relu":
        return jax.nn.relu(x)
    if act_method in ("swiglu",):
        a, b = jnp.split(x, 2, axis=-1)
        return jax.nn.silu(a) * b
    if act_method in ("silu", "swish"):
        return jax.nn.silu(x)
    raise ValueError(f"unsupported act_method {act_method}")


def fused_bias_act(x, bias=None, act_method="gelu", name=None, **kwargs):
    return _fused_bias_act(x, bias, act_method=act_method)


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """fused_matmul_bias.py: matmul+bias in one op (XLA fuses the epilogue)."""
    from ....ops.linalg import matmul

    out = matmul(x, y, transpose_x=transpose_x, transpose_y=transpose_y)
    return out + bias if bias is not None else out


@defop("fused_gate_attention", amp_category="white")
def _fused_gate_attention(query, key=None, query_weight=None, key_weight=None,
                          value_weight=None, qkv_weight=None,
                          gate_linear_weight=None, gate_linear_bias=None,
                          out_linear_weight=None, out_linear_bias=None,
                          nonbatched_bias=None, attn_mask=None,
                          has_gating=True, merge_qkv=True):
    """reference fused_gate_attention.py:26 — AlphaFold-style gated MSA
    self-attention as ONE traced op (the reference fuses it as a CUDA
    kernel; here XLA fuses the einsum chain). Shapes per the reference:
    query [N, B, Q, A]; merged qkv_weight [3, H, D, A]; separate
    query/key/value weights [A, H, D]; gating [A, H, D] + [H, D]; output
    [H, D, A_out]; nonbatched_bias [N, H, Q, M] (unsqueezed over the msa
    axis); attn_mask [N, B, 1, 1, M] added as a bias."""
    if merge_qkv:
        qkv = jnp.einsum("nbqa,thda->tnbqhd", query, qkv_weight)
        q, k, v = qkv[0], qkv[1], qkv[2]
    else:
        kin = query if key is None else key
        q = jnp.einsum("nbqa,ahd->nbqhd", query, query_weight)
        k = jnp.einsum("nbka,ahd->nbkhd", kin, key_weight)
        v = jnp.einsum("nbka,ahd->nbkhd", kin, value_weight)
    head_dim = q.shape[-1]
    q = q * (head_dim ** -0.5)
    logits = jnp.einsum("nbqhd,nbkhd->nbhqk", q, k)
    if attn_mask is not None:
        logits = logits + attn_mask
    if nonbatched_bias is not None:
        logits = logits + nonbatched_bias[:, None]
    ct = jnp.promote_types(logits.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(ct), -1).astype(logits.dtype)
    out = jnp.einsum("nbhqk,nbkhd->nbqhd", probs, v)
    if has_gating:
        gate = jnp.einsum("nbqa,ahd->nbqhd", query, gate_linear_weight)
        if gate_linear_bias is not None:
            gate = gate + gate_linear_bias
        out = out * jax.nn.sigmoid(gate)
    out = jnp.einsum("nbqhd,hdo->nbqo", out, out_linear_weight)
    if out_linear_bias is not None:
        out = out + out_linear_bias
    return out


def fused_gate_attention(query, key=None, query_weight=None, key_weight=None,
                         value_weight=None, qkv_weight=None,
                         gate_linear_weight=None, gate_linear_bias=None,
                         out_linear_weight=None, out_linear_bias=None,
                         nonbatched_bias=None, attn_mask=None,
                         has_gating=True, merge_qkv=True,
                         use_flash_attn=False):
    """reference incubate/nn/functional/fused_gate_attention.py:26 public
    surface. ``use_flash_attn`` is accepted (the XLA fusion plays that
    role; the gate-attention shapes are small-res AlphaFold blocks, not
    long-sequence flash territory)."""
    if merge_qkv and key is not None:
        # the merged path is self-attention only (reference contract):
        # silently dropping `key` would return plausible-but-wrong numbers
        raise ValueError(
            "fused_gate_attention: merge_qkv=True is self-attention only "
            "(qkv projected from `query`); pass merge_qkv=False with "
            "query/key/value weights for cross-attention over `key`")
    if merge_qkv and qkv_weight is None:
        raise ValueError(
            "fused_gate_attention: merge_qkv=True needs qkv_weight "
            "([3, num_heads, head_dim, q_dim])")
    if not merge_qkv and (query_weight is None or key_weight is None
                          or value_weight is None):
        raise ValueError(
            "fused_gate_attention: merge_qkv=False needs query_weight, "
            "key_weight and value_weight ([dim, num_heads, head_dim])")
    if has_gating and gate_linear_weight is None:
        raise ValueError(
            "fused_gate_attention: has_gating=True needs "
            "gate_linear_weight (pass has_gating=False to skip gating)")
    if out_linear_weight is None:
        raise ValueError("fused_gate_attention: out_linear_weight is "
                         "required ([num_heads, head_dim, out_dim])")
    return _fused_gate_attention(
        query, key, query_weight, key_weight, value_weight, qkv_weight,
        gate_linear_weight, gate_linear_bias, out_linear_weight,
        out_linear_bias, nonbatched_bias, attn_mask,
        has_gating=bool(has_gating), merge_qkv=bool(merge_qkv))


def fused_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                is_causal=False, training=True,
                                scaling_factor=None, name=None):
    """fused_dot_product_attention.py: served by the sdp dispatcher (Pallas
    flash attention when shapes allow)."""
    from ....nn.functional.flash_attention import scaled_dot_product_attention

    return scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                        dropout_p=dropout_p,
                                        is_causal=is_causal,
                                        training=training)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               name=None):
    """variable_length_memory_efficient_attention.py: padding positions beyond
    kv_seq_lens are masked out (the reference kernel's varlen semantics)."""
    import jax.numpy as jnp

    from ....framework.core import Tensor
    from ....nn.functional.flash_attention import _sdpa, _use_pallas

    # (B, H, S, D) reference layout -> sdp's (B, S, H, D)
    from ....ops.manipulation import transpose

    q = transpose(query, [0, 2, 1, 3])
    k = transpose(key, [0, 2, 1, 3])
    v = transpose(value, [0, 2, 1, 3])

    sk = int(k.shape[1])
    kv_lens = kv_seq_lens if kv_seq_lens is not None else seq_lens
    if kv_lens is not None:
        lens = (kv_lens.value if isinstance(kv_lens, Tensor)
                else jnp.asarray(kv_lens)).reshape(-1)
        # keep key column j for batch b iff j < kv_len[b]; (B, 1, 1, Sk)
        keep = (jnp.arange(sk)[None, :] < lens[:, None])[:, None, None, :]
        if mask is None:
            mask = keep
        else:
            mv = mask.value if isinstance(mask, Tensor) else jnp.asarray(mask)
            if mv.dtype == jnp.bool_:
                mask = mv & keep
            else:
                mask = mv + jnp.where(keep, 0.0, -1e30).astype(mv.dtype)
    out = _sdpa(q, k, v, mask, None, dropout_p=0.0, causal=bool(causal),
                scale=scale, use_pallas=_use_pallas(q))
    return transpose(out, [0, 2, 1, 3])


def fused_moe(x, gate_weight, ffn1_weight, ffn2_weight, ffn1_bias=None,
              ffn2_bias=None, quant_method="None", moe_topk=2,
              norm_topk_prob=True, name=None):
    """fused_moe.py: token top-k routing + expert FFNs, einsum-dispatched so
    GSPMD can shard the expert axis.

    x: (B, S, D); gate_weight: (D, E); ffn1_weight: (E, D, I) (swiglu packs
    2*I); ffn2_weight: (E, I_or_I, D).
    """
    import jax
    import jax.numpy as jnp

    from ....framework.core import Tensor

    xv = x.value if isinstance(x, Tensor) else jnp.asarray(x)
    gw = gate_weight.value if isinstance(gate_weight, Tensor) \
        else jnp.asarray(gate_weight)
    w1 = ffn1_weight.value if isinstance(ffn1_weight, Tensor) \
        else jnp.asarray(ffn1_weight)
    w2 = ffn2_weight.value if isinstance(ffn2_weight, Tensor) \
        else jnp.asarray(ffn2_weight)
    B, S, D = xv.shape
    E = gw.shape[1]
    tokens = xv.reshape(B * S, D)
    logits = tokens @ gw
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe_topk)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # dense dispatch: weight each expert by its routed probability (0 when
    # not in the top-k) — einsums keep the E axis shardable
    weights = jnp.zeros((B * S, E), xv.dtype)
    weights = weights.at[jnp.arange(B * S)[:, None], top_e].set(
        top_p.astype(xv.dtype))
    h = jnp.einsum("td,edi->tei", tokens, w1)
    if ffn1_bias is not None:
        b1 = ffn1_bias.value if isinstance(ffn1_bias, Tensor) \
            else jnp.asarray(ffn1_bias)
        h = h + b1[None]
    inter = w2.shape[1]
    if h.shape[-1] == 2 * inter:  # swiglu-packed ffn1
        gate_h, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate_h) * up
    else:
        h = jax.nn.gelu(h, approximate=False)
    y = jnp.einsum("tei,eid->ted", h, w2)
    if ffn2_bias is not None:
        b2 = ffn2_bias.value if isinstance(ffn2_bias, Tensor) \
            else jnp.asarray(ffn2_bias)
        y = y + b2[None]
    out = jnp.einsum("ted,te->td", y, weights)
    return Tensor(out.reshape(B, S, D))


@defop("fused_linear_cross_entropy", amp_category="black")
def _fused_linear_cross_entropy(hidden, weight, labels, ignore_index=-100,
                                chunk_size=512):
    """Chunked LM-head matmul + softmax cross-entropy that never materializes
    the full [B, S, V] logits (at V=32k, B8 x S2048 that is >1 GB bf16 /
    >4 GB fp32 of HBM traffic). Sequence chunks run under jax.checkpoint
    inside lax.map: forward keeps only [B, C, V] live; backward recomputes
    each chunk's logits. The matmul stays in the input dtype (bf16 on the
    MXU); the softmax runs in fp32.

    Reference capability analog: fused_softmax_mask + c_softmax_with_
    cross_entropy family (fused_ops.yaml) — the TPU-first formulation is
    remat-chunking rather than a custom kernel, since the inner matmul and
    the online logsumexp are exactly what XLA already schedules well.
    Returns per-token loss [B, S] (0.0 at ignore_index positions).
    """
    B, S, H = hidden.shape
    C = min(int(chunk_size), S)
    pad = (-S) % C
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=ignore_index)
    sp = S + pad
    n = sp // C
    hs = jnp.moveaxis(hidden.reshape(B, n, C, H), 1, 0)   # [n, B, C, H]
    ls = jnp.moveaxis(labels.reshape(B, n, C), 1, 0)      # [n, B, C]

    @jax.checkpoint
    def chunk_fn(hc, lc):
        logits = jnp.einsum("bch,hv->bcv", hc, weight).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(lc == ignore_index, 0, lc).astype(jnp.int32)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.where(lc == ignore_index, 0.0, lse - picked)

    tok = jax.lax.map(lambda args: chunk_fn(*args), (hs, ls))  # [n, B, C]
    return jnp.moveaxis(tok, 0, 1).reshape(B, sp)[:, :S]


def fused_linear_cross_entropy(hidden, weight, labels, ignore_index=-100,
                               chunk_size=512, name=None):
    """Per-token causal-LM loss fused with the LM-head projection — see
    `_fused_linear_cross_entropy`. `weight` is [hidden, vocab]."""
    return _fused_linear_cross_entropy(hidden, weight, labels,
                                       ignore_index=int(ignore_index),
                                       chunk_size=int(chunk_size))


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation=None, name=None):
    """reference fused_ops fused_linear_activation: matmul + bias + act in
    one op (XLA fuses the epilogue into the matmul on TPU)."""
    out = fused_matmul_bias(x, y, bias, transpose_x=trans_x,
                            transpose_y=trans_y)
    if activation in (None, "", "none"):
        return out
    from ....nn import functional as F

    act = {"relu": F.relu, "gelu": F.gelu, "swish": F.silu,
           "silu": F.silu}.get(activation)
    if act is None:
        raise ValueError(f"unsupported activation {activation!r}")
    return act(out)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """reference fused_transformer.py fused_bias_dropout_residual_layer_norm:
    out = LN(residual + dropout(x + bias))."""
    from ....nn import functional as F

    y = x if bias is None else x + bias
    if dropout_rate:
        y = F.dropout(y, p=dropout_rate, training=training, mode=mode)
    y = residual + y
    norm_shape = [int(y.shape[-1])]
    return F.layer_norm(y, norm_shape, ln_scale, ln_bias, ln_epsilon)


def fused_multi_head_attention(
        x, qkv_weight, linear_weight, pre_layer_norm=False,
        pre_ln_scale=None, pre_ln_bias=None, ln_scale=None, ln_bias=None,
        pre_ln_epsilon=1e-5, qkv_bias=None, linear_bias=None, cache_kv=None,
        attn_mask=None, dropout_rate=0.5, attn_dropout_rate=0.5,
        ln_epsilon=1e-5, training=True, mode="upscale_in_train", ring_id=-1,
        add_residual=True, num_heads=-1, transpose_qkv_wb=False, name=None):
    """reference fused_transformer.py fused_multi_head_attention — the
    functional form of FusedMultiHeadAttention (packed [3, H, D, E] qkv
    weight; XLA fuses what the reference hand-fuses in CUDA)."""
    from ....nn import functional as F
    from ....ops import manipulation as m

    if transpose_qkv_wb:
        raise NotImplementedError(
            "transpose_qkv_wb=True is not implemented (packed [3, H, D, E] "
            "layout is — matches incubate.nn.FusedMultiHeadAttention)")
    three, heads, head_dim, embed = (int(s) for s in qkv_weight.shape)
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [embed], pre_ln_scale, pre_ln_bias,
                         pre_ln_epsilon)
    w = m.reshape(qkv_weight, [3 * embed, embed])
    qkv = fused_matmul_bias(
        x, w, None if qkv_bias is None else m.reshape(qkv_bias, [3 * embed]),
        transpose_y=True)
    qkv = m.reshape(qkv, [0, 0, 3, heads, head_dim])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    cache_out = None
    if cache_kv is not None:
        # reference contract: cache_kv [2, B, H, T, D] holds past K/V; the
        # new tokens append and the call returns (out, updated_cache)
        from ....framework.core import Tensor as _T
        from ....ops import manipulation as _m

        cv = cache_kv.value if isinstance(cache_kv, _T) \
            else jnp.asarray(cache_kv)
        past_k = _T(jnp.swapaxes(cv[0], 1, 2))  # -> (B, T, H, D)
        past_v = _T(jnp.swapaxes(cv[1], 1, 2))
        k = _m.concat([past_k, k], axis=1)
        v = _m.concat([past_v, v], axis=1)
        cache_out = _T(jnp.stack([jnp.swapaxes(k.value, 1, 2),
                                  jnp.swapaxes(v.value, 1, 2)]))
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate,
        is_causal=False, training=training)
    out = m.reshape(out, [0, 0, embed])
    out = fused_matmul_bias(out, linear_weight, linear_bias)
    if dropout_rate:
        out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, [embed], ln_scale, ln_bias, ln_epsilon)
    if cache_out is not None:
        return out, cache_out
    return out


def fused_feedforward(
        x, linear1_weight, linear2_weight, linear1_bias=None,
        linear2_bias=None, ln1_scale=None, ln1_bias=None, ln2_scale=None,
        ln2_bias=None, dropout1_rate=0.5, dropout2_rate=0.5,
        activation="relu", ln1_epsilon=1e-5, ln2_epsilon=1e-5,
        pre_layer_norm=False, training=True, mode="upscale_in_train",
        ring_id=-1, add_residual=True, name=None):
    """reference fused_transformer.py fused_feedforward — functional form of
    FusedFeedForward: [LN ->] linear1 -> act -> dropout -> linear2 ->
    dropout -> residual [-> LN]."""
    from ....nn import functional as F

    embed = int(x.shape[-1])
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [embed], ln1_scale, ln1_bias, ln1_epsilon)
    h = fused_linear_activation(x, linear1_weight, linear1_bias,
                                activation=activation)
    if dropout1_rate:
        h = F.dropout(h, p=dropout1_rate, training=training, mode=mode)
    h = fused_matmul_bias(h, linear2_weight, linear2_bias)
    if dropout2_rate:
        h = F.dropout(h, p=dropout2_rate, training=training, mode=mode)
    out = residual + h if add_residual else h
    if not pre_layer_norm:
        out = F.layer_norm(out, [embed], ln2_scale, ln2_bias, ln2_epsilon)
    return out


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, cache_kvs=None, pre_caches=None, seq_lens=None,
        rotary_embs=None, time_step=None, attn_mask=None,
        dropout_rate=0.0, activation="gelu", training=False,
        mode="upscale_in_train", trans_qkvw=True, ring_id=-1, name=None,
        **unused):
    """reference fused_transformer.py fused_multi_transformer — the whole
    decoder stack as one call: per layer, fused attention + fused FFN."""
    # Semantically significant rotary/varlen args must not be silently
    # dropped: a GPT-NeoX-style caller passing rotary_embs would get wrong
    # numerics without any signal (advisor r4).
    for arg_name, arg in (("rotary_embs", rotary_embs),
                          ("pre_caches", pre_caches),
                          ("seq_lens", seq_lens)):
        if arg is not None:
            raise NotImplementedError(
                f"fused_multi_transformer: {arg_name} is not supported by "
                "this build; apply rotary embeddings in the model (see "
                "models/llama.py) or use models.llama_decode."
                "LlamaDecodeEngine for cached decoding")
    if unused:
        raise TypeError(
            "fused_multi_transformer: unexpected keyword arguments "
            f"{sorted(unused)}")
    if not trans_qkvw:
        raise NotImplementedError(
            "fused_multi_transformer: trans_qkvw=False ([E, 3, H, D] weight "
            "layout) is not supported; pass the default transposed "
            "[3, H, D, E] layout")
    if cache_kvs is not None:
        if attn_mask is not None:
            raise NotImplementedError(
                "fused_multi_transformer: attn_mask with cache_kvs is not "
                "supported (the cached path masks by position only); for "
                "padded batches use models.serving.ContinuousBatchingEngine "
                "or left-trim the prompts")
        if training or (dropout_rate and mode == "downscale_in_infer"):
            # dropout_rate with training=False under the default
            # upscale_in_train mode is a no-op in the uncached path too, so
            # it is allowed; only combinations that would actually change
            # inference numerics are rejected
            raise ValueError(
                "fused_multi_transformer: the cached path is inference-only "
                "(training=False; downscale_in_infer dropout would change "
                "eval numerics and is not supported with cache_kvs)")
        return _fused_multi_transformer_cached(
            x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
            linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
            ffn1_biases, ffn2_weights, ffn2_biases,
            pre_layer_norm=pre_layer_norm, epsilon=epsilon,
            cache_kvs=cache_kvs, time_step=time_step,
            activation=activation)
    if time_step is not None:
        raise ValueError(
            "fused_multi_transformer: time_step needs cache_kvs (the "
            "preallocated [2, B, H, max_len, D] per-layer caches)")
    out = x
    for i in range(len(qkv_weights)):
        out = fused_multi_head_attention(
            out, qkv_weights[i], linear_weights[i],
            pre_layer_norm=pre_layer_norm,
            pre_ln_scale=ln_scales[i] if ln_scales else None,
            pre_ln_bias=ln_biases[i] if ln_biases else None,
            ln_scale=ln_scales[i] if ln_scales else None,
            ln_bias=ln_biases[i] if ln_biases else None,
            pre_ln_epsilon=epsilon, ln_epsilon=epsilon,
            qkv_bias=qkv_biases[i] if qkv_biases else None,
            linear_bias=linear_biases[i] if linear_biases else None,
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, training=training, mode=mode)
        out = fused_feedforward(
            out, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            ln2_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln2_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, ln1_epsilon=epsilon, ln2_epsilon=epsilon,
            pre_layer_norm=pre_layer_norm, training=training, mode=mode)
    return out


def _fused_multi_transformer_cached(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm, epsilon,
        cache_kvs, time_step, activation):
    """The reference's cached generation contract
    (fused_multi_transformer_op.cu): per-layer PREALLOCATED caches
    [2, B, H, max_len, D]; with ``time_step=None`` the call is the context/
    prefill phase (writes positions 0..S-1, causal attention within the
    prompt); with ``time_step=t`` it is one decode step (x is [B, 1, E],
    K/V written at position t, attention over positions <= t). Returns
    (out, updated_cache_kvs). Inference semantics: dropout off."""
    from ....framework.core import Tensor as _T
    from ....nn import functional as F
    from ....ops import manipulation as m

    def _v(t):
        return t.value if isinstance(t, _T) else jnp.asarray(t)

    xv = _v(x)
    B, S, E = xv.shape
    t0 = None if time_step is None else int(
        np.asarray(_v(time_step)).reshape(-1)[0])
    if t0 is not None and S != 1:
        raise ValueError(
            "fused_multi_transformer decode (time_step given) expects one "
            f"token per call, got S={S}")
    max_len = int(_v(cache_kvs[0]).shape[3])
    start0 = 0 if t0 is None else t0
    if start0 + S > max_len:
        # dynamic_update_slice would silently CLAMP an out-of-range write,
        # corrupting the last cache slot instead of failing
        raise ValueError(
            f"fused_multi_transformer: writing positions "
            f"{start0}..{start0 + S - 1} overflows the preallocated cache "
            f"(max_len={max_len}); allocate larger cache_kvs")

    out = x
    new_caches = []
    for i in range(len(qkv_weights)):
        residual = out
        h = out
        if pre_layer_norm:
            h = F.layer_norm(h, [E], ln_scales[i] if ln_scales else None,
                             ln_biases[i] if ln_biases else None, epsilon)
        three, heads, head_dim, _ = (int(s) for s in qkv_weights[i].shape)
        w = m.reshape(qkv_weights[i], [3 * E, E])
        qkv = fused_matmul_bias(
            h, w, None if not qkv_biases
            else m.reshape(qkv_biases[i], [3 * E]), transpose_y=True)
        qkv_v = _v(qkv).reshape(B, S, 3, heads, head_dim)
        q, k, v = qkv_v[:, :, 0], qkv_v[:, :, 1], qkv_v[:, :, 2]

        cv = _v(cache_kvs[i])                  # [2, B, H, max_len, D]
        k_btxd = jnp.swapaxes(k, 1, 2)         # [B, H, S, D]
        v_btxd = jnp.swapaxes(v, 1, 2)
        start = 0 if t0 is None else t0
        ck = jax.lax.dynamic_update_slice(cv[0], k_btxd.astype(cv.dtype),
                                          (0, 0, start, 0))
        cvv = jax.lax.dynamic_update_slice(cv[1], v_btxd.astype(cv.dtype),
                                           (0, 0, start, 0))
        new_caches.append(_T(jnp.stack([ck, cvv])))

        # attention over the cache with a position mask (dense decode-engine
        # semantics: static shapes, one compiled program per phase)
        positions = start + jnp.arange(S)                       # query pos
        tpos = jnp.arange(max_len)[None, None, :]
        pos_mask = tpos <= positions[None, :, None]             # [1, S, T]
        ct = jnp.promote_types(q.dtype, jnp.float32)
        logits = jnp.einsum("bshd,bhtd->bhst", q.astype(ct),
                            ck.astype(ct)) / np.sqrt(head_dim)
        logits = jnp.where(pos_mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        attn = jnp.einsum("bhst,bhtd->bshd", probs, cvv.astype(ct))
        attn = attn.reshape(B, S, heads * head_dim).astype(xv.dtype)

        o = fused_matmul_bias(_T(attn), linear_weights[i],
                              linear_biases[i] if linear_biases else None)
        o = residual + o
        if not pre_layer_norm:
            o = F.layer_norm(o, [E], ln_scales[i] if ln_scales else None,
                             ln_biases[i] if ln_biases else None, epsilon)
        out = fused_feedforward(
            o, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            ln2_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln2_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            dropout1_rate=0.0, dropout2_rate=0.0, activation=activation,
            ln1_epsilon=epsilon, ln2_epsilon=epsilon,
            pre_layer_norm=pre_layer_norm, training=False)
    return out, new_caches


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None,
                     name=None):
    """reference blha_get_max_len: the (max encoder len, max decoder len)
    pair the block-attention kernels size their launch by."""
    from ....framework.core import Tensor

    enc = seq_lens_encoder.value if isinstance(seq_lens_encoder, Tensor) \
        else jnp.asarray(seq_lens_encoder)
    dec = seq_lens_decoder.value if isinstance(seq_lens_decoder, Tensor) \
        else jnp.asarray(seq_lens_decoder)
    return (Tensor(jnp.max(enc).reshape(1)),
            Tensor(jnp.max(dec).reshape(1)))


def masked_multihead_attention(
        x, cache_kv=None, bias=None, src_mask=None, sequence_lengths=None,
        rotary_tensor=None, beam_cache_offset=None, qkv_out_scale=None,
        out_shift=None, out_smooth=None, seq_len=1, rotary_emb_dims=0,
        use_neox_rotary_style=False, compute_dtype="default",
        out_scale=-1.0, quant_round_type=1, quant_max_bound=127.0,
        quant_min_bound=-127.0, name=None):
    """reference masked_multihead_attention: ONE decode step of multi-head
    attention against a growing [2, B, H, T, D] cache — the generation-loop
    kernel. x is the packed qkv for the new token: (B, 3*H*D)."""
    from ....framework.core import Tensor

    # Reject (rather than silently ignore) args that change the attention
    # result: masking and the int8 quantization contract (advisor r4 —
    # mirrors the existing explicit rejections below).
    if rotary_emb_dims not in (0, 1):
        raise NotImplementedError(
            "masked_multihead_attention: rotary_emb_dims=2 (extra position "
            "ids) is not supported; the standard rotary_emb_dims=1 form is")
    if rotary_tensor is None and rotary_emb_dims:
        raise ValueError(
            "masked_multihead_attention: rotary_emb_dims=1 needs "
            "rotary_tensor ([2, B, max_seq, 1, head_dim] cos/sin tables)")
    if rotary_tensor is not None and not rotary_emb_dims:
        raise ValueError(
            "masked_multihead_attention: rotary_tensor given but "
            "rotary_emb_dims=0 (the reference kernel gates rotation on "
            "rotary_emb_dims; pass rotary_emb_dims=1)")
    if beam_cache_offset is not None:
        raise NotImplementedError(
            "masked_multihead_attention: beam_cache_offset (beam-search KV "
            "reordering) is not supported; use LlamaDecodeEngine's beam "
            "search for reordered-cache generation")
    if qkv_out_scale is not None or out_shift is not None \
            or out_smooth is not None or out_scale != -1.0:
        raise NotImplementedError(
            "masked_multihead_attention: int8 quantization params "
            "(qkv_out_scale/out_shift/out_smooth/out_scale) are not "
            "supported; use LlamaDecodeEngine(kv_cache_dtype='int8') for "
            "quantized-KV decoding")
    if cache_kv is None:
        raise ValueError("cache_kv is required (shape [2, B, H, T, D])")
    xv = x.value if isinstance(x, Tensor) else jnp.asarray(x)
    cv = cache_kv.value if isinstance(cache_kv, Tensor) \
        else jnp.asarray(cache_kv)
    if bias is not None:
        xv = xv + (bias.value if isinstance(bias, Tensor)
                   else jnp.asarray(bias)).reshape(-1)
    two, B, H, T, D = cv.shape
    qkv = xv.reshape(B, 3, H, D)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]        # (B, H, D)
    if sequence_lengths is None:
        raise ValueError(
            "sequence_lengths is required: it is the per-row cache write "
            "position — without it every step would overwrite slot 0 and "
            "decode with no history")
    sl = (sequence_lengths.value if isinstance(sequence_lengths, Tensor)
          else jnp.asarray(sequence_lengths)).reshape(-1)
    pos = sl.astype(jnp.int32)                        # write position per row
    if int(np.asarray(sl).max()) >= T:
        # the scatter would silently drop/clamp the write while the causal
        # mask opens the whole cache — plausible-but-wrong logits
        raise ValueError(
            f"masked_multihead_attention: write position "
            f"{int(np.asarray(sl).max())} exceeds the cache "
            f"(T={T}); allocate a longer cache_kv")
    bidx = jnp.arange(B)
    if rotary_tensor is not None and rotary_emb_dims:
        # reference mmha_util.cu.h:46: rotary_emb [2, B, max_seq, 1, D]
        # (cos at [0], sin at [1]); the kernel reads the row's CURRENT
        # position and rotates q and k with the same tables. The default
        # (use_neox_rotary_style=False) is the interleaved pairs-of-two
        # pairing; neox is the half-split pairing.
        rv = rotary_tensor.value if isinstance(rotary_tensor, Tensor) \
            else jnp.asarray(rotary_tensor)
        max_rot = int(rv.shape[2])
        if int(np.asarray(sl).max()) >= max_rot:
            # the gather would silently CLAMP to the last table row and
            # reuse its cos/sin for every later step
            raise ValueError(
                f"masked_multihead_attention: position "
                f"{int(np.asarray(sl).max())} exceeds the rotary table "
                f"(max_seq={max_rot}); build larger rotary_tensor tables")
        cos = rv[0][bidx, pos, 0].astype(q.dtype)[:, None, :]  # (B, 1, D)
        sin = rv[1][bidx, pos, 0].astype(q.dtype)[:, None, :]

        def _rot(t):
            rot = (_rotate_half(t) if use_neox_rotary_style
                   else _rotate_every_two(t))
            return t * cos + rot * sin

        q = _rot(q)
        k = _rot(k)
    ck = cv[0].at[bidx, :, pos].set(k)
    cvv = cv[1].at[bidx, :, pos].set(v)
    t = jnp.arange(T)[None, None, :]
    mask = t <= pos[:, None, None]                    # (B, 1, T)
    logits = jnp.einsum("bhd,bhtd->bht", q, ck) / jnp.sqrt(jnp.asarray(D, jnp.float32)).astype(q.dtype)
    logits = logits.astype(jnp.float32)
    if src_mask is not None:
        # reference kernel: qk += mask (additive, [B, 1, 1, T] broadcast
        # over heads — masked_multihead_attention_kernel.cu:385)
        sm = src_mask.value if isinstance(src_mask, Tensor) \
            else jnp.asarray(src_mask)
        if sm.shape[-1] != T or sm.shape[0] not in (1, B):
            raise ValueError(
                "masked_multihead_attention: src_mask must be "
                f"[B|1, 1, 1, T] with T={T} (the cache length); got "
                f"{tuple(sm.shape)}")
        logits = logits + sm.reshape(sm.shape[0], 1, T).astype(jnp.float32)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, -1).astype(q.dtype)
    out = jnp.einsum("bht,bhtd->bhd", probs, cvv).reshape(B, H * D)
    return Tensor(out), Tensor(jnp.stack([ck, cvv]))


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None, cache_k_quant_scales=None,
        cache_v_quant_scales=None, cache_k_dequant_scales=None,
        cache_v_dequant_scales=None, qkv_out_scale=None, qkv_bias=None,
        out_shift=None, out_smooth=None, max_enc_len_this_time=None,
        max_dec_len_this_time=None, rope_emb=None, mask=None, tgt_mask=None,
        max_seq_len=-1, block_size=64, use_neox_style=False,
        use_dynamic_cachekv_quant=False, quant_round_type=1,
        quant_max_bound=127.0, quant_min_bound=-127.0, out_scale=-1,
        compute_dtype="default", rope_theta=10000.0, name=None):
    """reference block_multihead_attention.py:33 — paged-KV (block-table)
    serving attention. The KV cache is a POOL of fixed-size blocks; each
    sequence's block_tables row lists the blocks it owns. TPU-first: the
    writes are jnp scatters, and the decode attention is
    models/paged_kv.paged_attention_decode — the Pallas page-table kernel
    on a TPU, the fused gather chain elsewhere.

    Layouts follow the reference contract: ``qkv`` is varlen-packed rows
    [token_num, (q_heads + 2*kv_heads) * head_dim]; ``key_cache``/
    ``value_cache`` are [max_block_num, kv_heads, block_size, head_dim].
    Two phases, per the reference semantics: prefill rows
    (seq_lens_encoder > 0) run causal self-attention over the prompt and
    write it into the blocks; decode rows (seq_lens_this_time == 1 with
    seq_lens_decoder > 0) append one token and attend over the paged
    history. Returns (out, qkv, key_cache, value_cache).

    Quantized-cache / rotary / smooth-quant extras raise (the
    masked_multihead_attention policy: reject, never silently ignore)."""
    from ....framework.core import Tensor
    from ....models import paged_kv as _pk

    for bad_name, bad in (
            ("cache_k_quant_scales", cache_k_quant_scales),
            ("cache_v_quant_scales", cache_v_quant_scales),
            ("cache_k_dequant_scales", cache_k_dequant_scales),
            ("cache_v_dequant_scales", cache_v_dequant_scales),
            ("qkv_out_scale", qkv_out_scale), ("out_shift", out_shift),
            ("out_smooth", out_smooth), ("rope_emb", rope_emb),
            ("pre_key_cache", pre_key_cache),
            ("pre_value_cache", pre_value_cache)):
        if bad is not None:
            raise NotImplementedError(
                f"block_multihead_attention: {bad_name} is not supported by "
                "this build (apply rotary in the model; use "
                "LlamaDecodeEngine(kv_cache_dtype='int8') for quantized KV)")
    if use_dynamic_cachekv_quant or out_scale != -1:
        raise NotImplementedError(
            "block_multihead_attention: cache-KV quantization paths are not "
            "supported here")
    if mask is not None or tgt_mask is not None:
        raise NotImplementedError(
            "block_multihead_attention: custom mask/tgt_mask are not "
            "supported; the paged path computes causal prefill and "
            "full-history decode masking only")
    if block_tables is None:
        raise ValueError("block_tables is required")

    def _v(x):
        return x.value if isinstance(x, Tensor) else jnp.asarray(x)

    qkv_v = _v(qkv)
    kc = _v(key_cache)
    vc = _v(value_cache)
    tables = _v(block_tables).astype(jnp.int32)
    enc = np.asarray(_v(seq_lens_encoder)).reshape(-1)
    dec = np.asarray(_v(seq_lens_decoder)).reshape(-1)
    this = np.asarray(_v(seq_lens_this_time)).reshape(-1)

    n_kv, bs, hd = kc.shape[1], kc.shape[2], kc.shape[3]
    n_q = qkv_v.shape[-1] // hd - 2 * n_kv
    if qkv_bias is not None:
        qkv_v = qkv_v + _v(qkv_bias).reshape(-1)

    # reference layout [nb, kv, bs, d] <-> pool layout [nb, bs, kv, d]
    kc_p = jnp.swapaxes(kc, 1, 2)
    vc_p = jnp.swapaxes(vc, 1, 2)

    is_prefill = enc.sum() > 0
    B = tables.shape[0]
    if is_prefill:
        if dec.sum() != 0:
            raise NotImplementedError(
                "block_multihead_attention: mixed prefill+decode batches "
                "are not supported; split the batch by phase")
        if not (this == enc).all():
            raise NotImplementedError(
                "block_multihead_attention: chunked prefill "
                "(seq_lens_this_time != seq_lens_encoder) is not supported "
                f"(this={this.tolist()}, encoder={enc.tolist()})")
        S = int(enc.max())
        # unpack varlen rows -> padded [B, S, ...] in ONE scatter (a
        # per-sequence .at[b, :L].set loop would copy the whole padded
        # array B times)
        row_b = np.repeat(np.arange(B), this)               # [token_num]
        row_t = np.concatenate([np.arange(int(L)) for L in this])
        rows_all = qkv_v.reshape(-1, n_q + 2 * n_kv, hd)
        q_pad = jnp.zeros((B, S, n_q, hd), qkv_v.dtype).at[
            row_b, row_t].set(rows_all[:, :n_q])
        k_pad = jnp.zeros((B, S, n_kv, hd), qkv_v.dtype).at[
            row_b, row_t].set(rows_all[:, n_q:n_q + n_kv])
        v_pad = jnp.zeros((B, S, n_kv, hd), qkv_v.dtype).at[
            row_b, row_t].set(rows_all[:, n_q + n_kv:])
        lens = jnp.asarray(enc, jnp.int32)
        kc_p, vc_p = _pk.paged_write_prefill(kc_p, vc_p, tables, lens,
                                             k_pad, v_pad)
        # causal self-attention over the prompt (fp32 softmax)
        groups = n_q // n_kv
        qg = q_pad.reshape(B, S, n_kv, groups, hd)
        logits = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                            k_pad.astype(jnp.float32)) / np.sqrt(hd)
        t_idx = jnp.arange(S)
        causal = t_idx[None, :] <= t_idx[:, None]           # [S, S]
        valid = t_idx[None, :] < lens[:, None]              # [B, S]
        m = causal[None, None, None] & valid[:, None, None, None, :]
        logits = jnp.where(m, logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        o = jnp.einsum("bhgst,bthd->bshgd", probs,
                       v_pad.astype(jnp.float32)).astype(qkv_v.dtype)
        # re-pack the padded output to varlen rows with one gather
        out = o.reshape(B, S, n_q * hd)[row_b, row_t]
    else:
        if not (this == 1).all():
            raise NotImplementedError(
                "block_multihead_attention decode phase expects one token "
                "per sequence (seq_lens_this_time == 1)")
        rows = qkv_v.reshape(B, n_q + 2 * n_kv, hd)
        q_new = rows[:, :n_q]
        k_new = rows[:, n_q:n_q + n_kv]
        v_new = rows[:, n_q + n_kv:]
        lens = jnp.asarray(dec, jnp.int32)
        kc_p, vc_p = _pk.paged_write_decode(kc_p, vc_p, tables, lens,
                                            k_new, v_new)
        o = _pk.paged_attention_decode(q_new, kc_p, vc_p, tables, lens)
        out = o.reshape(B, n_q * hd)

    kc_out = jnp.swapaxes(kc_p, 1, 2)
    vc_out = jnp.swapaxes(vc_p, 1, 2)
    # the returned qkv reflects the bias actually used for attention (the
    # reference kernel applies qkv_bias in place)
    return (Tensor(out), Tensor(qkv_v), Tensor(kc_out), Tensor(vc_out))
