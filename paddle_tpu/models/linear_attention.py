"""A gated delta-rule (linear-attention) layer's mixer, for the serving block.

The layer keeps no keys and values: per sequence it has a float32 STATE
``[heads, key_dim, value_dim]`` and the last ``conv_kernel - 1`` inputs of its
depthwise convolutions, both in the SLOT the cache manager gave the sequence
(``paged_kv.StateSlots``; pool entry ``(state, conv)``), the pool's last slot
the null slot. With ``x`` [T, hidden], one token a lane:

- ``q~ = x Wq``, ``k~ = x Wk`` (heads x key_dim), ``v~ = x Wv``, ``z = x Wz``
  (heads x value_dim), ``a = x Wa``, ``b = x Wb`` (heads);
- each channel of ``[q~ | k~ | v~]`` through its causal depthwise convolution
  over the SEQUENCE (taps ``conv`` [kernel, channels], the last tap on the
  token itself, no bias), then SiLU;
- per head ``q = l2norm(q) / sqrt(key_dim)``, ``k = l2norm(k)``;
  ``beta = sigmoid(b)``, doubled where the kind allows negative eigenvalues;
  ``g = -exp(A_log) softplus(a + dt_bias)``;
- the recurrence (``ops/pallas/gated_delta_rule.py``), float32;
- per head ``o = RMS(o) * w_o * silu(z)``, then ``Wo``.

A lane's predecessors are the lanes before it in its run and, before those,
what the slot holds; a run that starts at position 0 starts from zeros
whatever the slot holds, which is how a slot is reset for its next request.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.pallas import gated_delta_rule as gdr

_F32 = jnp.float32


def _conv_rows(conv, xin, width):
    """The rows the convolution reads, one buffer: the slots' kept inputs
    (slot ``s``'s ``j``-th at ``s * width + j``), then the lanes' own."""
    slots = conv.shape[0]
    return jnp.concatenate([conv.reshape(slots * width, -1), xin]), \
        np.int32(slots * width)


def _source(plan, lane, back, base, width):
    """Row of ``_conv_rows`` holding the input ``back`` tokens before lane
    ``lane``'s, and whether it is real (a fresh run has nothing before its
    first lane)."""
    off, row = plan["off"][lane], plan["rows"][lane]
    here = back <= off
    src = jnp.where(here, base + lane - back,
                    row * width + width - (back - off))
    return src, here | ~plan["fresh"][lane]


def causal_conv(xin, conv, taps, positions, plan):
    """``xin`` [T, channels] through the depthwise causal convolution, and the
    slots' kept inputs afterwards. ``conv`` [slots, kernel - 1, channels];
    ``taps`` [kernel, channels]."""
    T = xin.shape[0]
    K = taps.shape[0]
    w = taps.astype(_F32)
    if plan is None:                    # lane i is slot i's next token
        kept = conv[:T] * (positions != 0)[:, None, None].astype(conv.dtype)
        win = jnp.concatenate([kept, xin[:, None]], axis=1)
        y = jnp.einsum("tkc,kc->tc", win.astype(_F32), w)
        return y, conv.at[:T].set(win[:, 1:])
    rows, base = _conv_rows(conv, xin, K - 1)
    lanes = jnp.arange(T, dtype=jnp.int32)
    y = jnp.zeros(xin.shape, _F32)
    for back in range(K):
        src, real = _source(plan, lanes, np.int32(back), base, K - 1)
        y = y + w[K - 1 - back] * jnp.where(real[:, None], rows[src],
                                            0).astype(_F32)
    # a slot's kept inputs: the last kernel - 1 of its run, seen from the
    # run's last lane
    slots = conv.shape[0]
    null = np.int32(slots - 1)
    end = jnp.full((slots,), -1, jnp.int32).at[
        jnp.where(plan["last"], plan["rows"], null)].max(lanes)
    has = (end >= 0).at[null].set(False)
    end = jnp.maximum(end, 0)
    new = []
    for j in range(K - 1):
        src, real = _source(plan, end, np.int32(K - 2 - j), base, K - 1)
        new.append(jnp.where((has & real)[:, None], rows[src], 0))
    new = jnp.stack(new, axis=1).astype(conv.dtype)
    return y, jnp.where(has[:, None, None], new, conv)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + np.float32(1e-6))


def mixer(kind, p, x, pool, positions, plan, eps):
    """The layer's mixer for ``x`` [T, hidden], one token a lane; ``plan``
    None (lane ``i`` is slot ``i``'s next token) or
    ``gated_delta_rule.plan_runs``' of the lanes. Returns ``([T, heads *
    value_dim] in x's dtype, pool')``."""
    state, conv = pool
    T = x.shape[0]
    H, dk, dv = kind.num_heads, kind.key_dim, kind.value_dim
    xin = jnp.concatenate([x @ p["wq"], x @ p["wk"], x @ p["wv"]], axis=-1)
    y, conv = causal_conv(xin, conv, p["conv"], positions, plan)
    y = jax.nn.silu(y)
    q = _l2norm(y[:, :H * dk].reshape(T, H, dk)) * np.float32(dk ** -0.5)
    k = _l2norm(y[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = y[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid((x @ p["wb"]).astype(_F32))
    if kind.neg_eigval:
        beta = beta * np.float32(2.0)
    g = -jnp.exp(p["A_log"].astype(_F32)) * jax.nn.softplus(
        (x @ p["wa"]).astype(_F32) + p["dt_bias"].astype(_F32))
    o, state = gdr.gated_delta(q, k, v, g, beta, state, positions, plan)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + np.float32(eps))
    o = (o.astype(x.dtype) * p["o_norm"]).astype(_F32) * jax.nn.silu(
        (x @ p["wz"]).astype(_F32).reshape(T, H, dv))
    return o.astype(x.dtype).reshape(T, H * dv), (state, conv)
