"""An expert layer that is told which experts it holds.

Reference analog: the expert-parallel half of incubate/distributed/models/moe
(MoELayer with ``moe_group``: every rank routes over all experts and computes
its own). The training MoELayer here dispatches through a one-hot
``(tokens, experts, capacity)`` tensor and drops what overflows; serving wants
neither. This layer routes every token over ALL ``n_experts`` at the published
width (sigmoid scores, a selection bias that moves the choice and not the
weights, the ``top_k`` largest, weights normalized over all ``top_k`` chosen),
then computes ``w_e * expert_e(h)`` for the (token, expert) pairs that fall on
the experts it HOLDS, ``range(lo, lo + held)``, and adds them up per token.
What the absent experts would add is left out: that partial sum is what one
chip of an expert-parallel deployment contributes before the exchange, and no
code here stands in for the other chips or for the exchange.

Pairs are sorted by expert into a grouped product (``jax.lax.ragged_dot``), so
each held expert's three matrices are read at most once a call whatever the
routing, no token is dropped, and there is no capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["route_sigmoid_topk", "held_experts_mlp"]


def route_sigmoid_topk(h, router, bias, top_k):
    """``(experts [T, top_k] int32, weights [T, top_k] float32)``.

    Scores are ``sigmoid(h @ router)`` in float32 at the highest matmul
    precision whatever ``h``'s dtype: with random weights the eighth and
    ninth scores lie about as far apart as a bfloat16 dot product of
    ``hidden`` terms errs, and a flipped choice moves the layer's output by
    1 / top_k. ``bias`` [n_experts] is added for the CHOICE only
    (``noaux_tc``); the weights are the chosen scores over their sum."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return experts.astype(jnp.int32), chosen / chosen.sum(-1, keepdims=True)


@jax.named_scope("held_experts")
def held_experts_mlp(h, router, bias, w1, w3, w2, lo, top_k, valid=None):
    """``h`` [T, hidden]; ``router`` [hidden, n_experts]; ``bias``
    [n_experts]; ``w1`` / ``w3`` [held, hidden, width] and ``w2`` [held,
    width, hidden]: the SwiGLU experts ``lo .. lo + held - 1``. Returns
    ``(y [T, hidden], pairs [3] int32)``: the held experts' weighted sum per
    token, and how many (token, expert) pairs fell on held experts, how many
    were routed in all, and how many held experts got a pair, counted over
    ``valid`` tokens ([T] bool; all, when None). ``lo`` may be traced."""
    T, held = h.shape[0], w1.shape[0]
    experts, weights = route_sigmoid_topk(h, router, bias, top_k)
    local = experts.reshape(-1) - lo                       # [T * top_k]
    here = (local >= 0) & (local < held)
    # pairs on absent experts sort behind the last group and are never
    # multiplied: ragged_dot stops at the groups' total
    group = jnp.where(here, local, held).astype(jnp.int32)
    order = jnp.argsort(group, stable=True)
    token = order // top_k
    sizes = jnp.zeros(held + 1, jnp.int32).at[group].add(1)[:held]
    xs = h[token]                                          # [T * top_k, hidden]
    a = lax.ragged_dot(xs, w1, sizes)
    b = lax.ragged_dot(xs, w3, sizes)
    y = lax.ragged_dot((jax.nn.silu(a) * b).astype(h.dtype), w2, sizes)
    w = jnp.where(here, weights.reshape(-1), 0.0)[order]
    # (rows behind the groups hold whatever the product left there)
    y = jnp.where((w > 0)[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = jnp.zeros((T, h.shape[1]), jnp.float32).at[token].add(y)
    counted = here.reshape(T, top_k)
    if valid is not None:
        counted = counted & valid[:, None]
        routed = valid.sum() * top_k
    else:
        routed = T * top_k
    hit = jnp.zeros(held + 1, jnp.int32).at[
        jnp.where(counted.reshape(-1), group, held)].add(1)[:held] > 0
    pairs = jnp.stack([counted.sum(), routed, hit.sum()]).astype(jnp.int32)
    return out.astype(h.dtype), pairs
