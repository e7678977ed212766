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

Pairs are sorted by expert into a grouped product, so each held expert's
three matrices are read at most once a call whatever the routing, no token is
dropped, and there is no capacity. What multiplies is picked by a rule that
reads only the inputs (``_kernel_applies``): on a TPU, at bfloat16 or float32
and widths that fill whole 128-lane rows, the Pallas kernels of
``ops/pallas/grouped_matmul.py`` (``held_experts_gmm_up``: gate and up side by
side with the SwiGLU in float32 in VMEM; ``held_experts_gmm_down``), over rows
gathered with every expert's group padded to whole row tiles; everywhere else
(the CPU, narrow test widths, other dtypes) three ``jax.lax.ragged_dot`` calls
over the sorted rows as they are, which is the kernels' reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .....ops.pallas import grouped_matmul as gmm

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


def _kernel_applies(h, w1, w2):
    """Whether the grouped products run as the Pallas kernels
    (``ops.pallas.grouped_matmul``): on a TPU, activations and all the
    experts' matrices in ONE dtype, bfloat16 or float32, hidden size and
    expert width whole multiples of the 128-lane row (a weight panel is cut
    in 128s; a row tile is the dtype's sublane tile whatever the rows), and a
    panel of the whole contraction inside the kernels' VMEM. Everything read
    here is visible in the inputs: no flag picks the path."""
    hidden, width = w1.shape[1:]
    return (jax.devices()[0].platform == "tpu"
            and h.dtype in (jnp.bfloat16, jnp.float32)
            and w1.dtype == h.dtype and w2.dtype == h.dtype
            and hidden % 128 == 0 and width % 128 == 0
            and gmm.fits(hidden, width, h.dtype))


@jax.named_scope("held_experts")
def held_experts_mlp(h, router, bias, w1, w3, w2, lo, top_k, valid=None):
    """``h`` [T, hidden]; ``router`` [hidden, n_experts]; ``bias``
    [n_experts]; ``w1`` / ``w3`` [held, hidden, width] and ``w2`` [held,
    width, hidden]: the SwiGLU experts ``lo .. lo + held - 1``. Returns
    ``(y [T, hidden], pairs [4] int32)``: the held experts' weighted sum per
    token, and how many (token, expert) pairs fell on held experts, how many
    were routed in all, and how many held experts got a pair, counted over
    ``valid`` tokens ([T] bool; all, when None); last, the rows of all row
    tiles the grouped product visited, padding and every lane included
    (tiles of one row where ``ragged_dot`` ran: the pairs in its groups).
    ``lo`` may be traced."""
    T, held = h.shape[0], w1.shape[0]
    M = T * top_k
    experts, weights = route_sigmoid_topk(h, router, bias, top_k)
    local = experts.reshape(-1) - lo                       # [T * top_k]
    here = (local >= 0) & (local < held)
    # pairs on absent experts sort behind the last group and are never
    # multiplied: both products stop at the groups' total
    group = jnp.where(here, local, held).astype(jnp.int32)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros(held + 1, jnp.int32).at[group].add(1)[:held]
    # the rows as the product takes them: each group padded to whole row
    # tiles for the kernels, the sorted rows themselves (tiles of one row)
    # for ragged_dot
    kernel = _kernel_applies(h, w1, w2)
    tm = gmm.row_tile(h.dtype) if kernel else 1
    plan = gmm.plan_row_tiles(sizes, tm, M)
    at = jnp.arange(gmm.padded_rows(M, held, tm), dtype=jnp.int32)
    tile, k = at // tm, at % tm
    real = k < plan["n"][tile]
    pair = order[jnp.minimum(plan["row0"][tile] + k, M - 1)]
    token = pair // top_k
    xs = h[token]                                          # [rows, hidden]
    if kernel:
        y = gmm.gmm_down(gmm.gmm_up(xs, w1, w3, plan), w2, plan)
    else:
        a = lax.ragged_dot(xs, w1, sizes)
        b = lax.ragged_dot(xs, w3, sizes)
        y = lax.ragged_dot((jax.nn.silu(a) * b).astype(h.dtype), w2, sizes)
    w = jnp.where(real, weights.reshape(-1)[pair], 0.0)
    # (rows no group owns hold whatever the product left there)
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
    pairs = jnp.stack([counted.sum(), routed, hit.sum(),
                       plan["tiles"] * tm]).astype(jnp.int32)
    return out.astype(h.dtype), pairs
